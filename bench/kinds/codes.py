"""The LDPC code of a configuration, built once and cached on disk.

The code graph is part of the deployment, not of the data: a configuration
fixes ``(K, l, r, code_seed)`` and every run uses the same code.  Building
it (:func:`repro.core.ldpc.make_regular_ldpc`) takes about half a minute at
K = 8192, so the first run in a checkout saves its arrays under
``bench/cache/`` and later runs load them.  The file holds H's nonzeros
and G's parity block exactly as built (float64), so a loaded code equals a
fresh one bit for bit.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import NamedTuple

import numpy as np

__all__ = ["CACHE_DIR", "Code", "load_code"]

CACHE_DIR = Path(__file__).resolve().parents[1] / "cache"


def _path(cache_dir: Path, K: int, l: int, r: int, seed: int) -> Path:
    return Path(cache_dir) / f"ldpc-K{K}-l{l}-r{r}-seed{seed}.npz"


class Code(NamedTuple):
    code: object          # the program's LDPCCode
    hit: bool             # loaded from the cache
    h_cols: np.ndarray    # (p, r) each check's neighbour columns, ascending
    h_abs: np.ndarray     # (p, r) |H| at those columns


def load_code(K: int, l: int, r: int, code_seed: int,
              cache_dir: Path = CACHE_DIR) -> Code:
    """The ``make_regular_ldpc(K, l=l, r=r, seed=code_seed)`` code, from
    the cache where it is there, with its check structure (the graph
    decides which coordinates an erasure pattern leaves unresolved; the
    weights enter the error bound of a recovered value)."""
    from repro.core.ldpc import LDPCCode, make_regular_ldpc

    path = _path(cache_dir, K, l, r, code_seed)
    if path.exists():
        with np.load(path) as f:
            p, N = int(f["p"]), int(f["N"])
            H = np.zeros((p, N), np.float64)
            rows = np.repeat(np.arange(p), f["h_cols"].shape[1])
            H[rows, f["h_cols"].ravel()] = f["h_vals"].ravel()
            G = np.zeros((N, K))
            G[np.arange(K), np.arange(K)] = 1.0
            G[K:] = f["P"]
            code = LDPCCode(H=H, G=G, N=N, K=K, l=l, r=r, kind="ldpc",
                            seed=int(f["seed"]))
            return Code(code, True, f["h_cols"], np.abs(f["h_vals"]))
    code = make_regular_ldpc(K, l=l, r=r, seed=code_seed)
    mask = code.H != 0.0
    cols = np.stack([np.flatnonzero(row) for row in mask])   # (p, r)
    vals = np.take_along_axis(code.H, cols, axis=1)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp.npz")
    np.savez(tmp, p=code.p, N=code.N, seed=code.seed, h_cols=cols,
             h_vals=vals, P=code.G[K:])
    os.replace(tmp, path)
    return Code(code, False, cols, np.abs(vals))
