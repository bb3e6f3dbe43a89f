"""CPU rehearsal of every cell: set-up, window and comparison at a tiny
size through the harness's own ``run`` (its look for a chip skipped), the
control in a lower precision, and the faults the comparison must catch."""
import io
import json
import os
import subprocess
import sys
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.kinds import codes, reference

SMALL = {"lsq-k8192": {"k": 256, "m": 1024, "N": 512, "workers": 8},
         "gradagg-k20-b25m": {"dim": 4096}}
CELLS = ["lsq-k8192.sync-bern0.1", "lsq-k8192.sync-q0",
         "gradagg-k20-b25m.sync-bern0.1"]
SEED = 2**33 + 12345          # seeds may be wider than 32 bits


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("codes")


def _run(cell, cache, seed=SEED, trace=False, **kw):
    out, err = io.StringIO(), io.StringIO()
    config = cell.split(".")[0]
    rc = harness.run(cell, seed, 0.3, trace, t_start=time.perf_counter(),
                     require_chip=False, overrides=SMALL[config],
                     cell_kw={"cache_dir": cache, **kw},
                     peaks=harness.peaks_for("TPU v5 lite"), out=out,
                     err=err)
    assert rc == 0, err.getvalue()
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    return result, err.getvalue()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct(cell, cache):
    result, err = _run(cell, cache)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "compared"
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    # the numbers compared are the last lines of standard error
    tail = err.strip().splitlines()[-len(result["compared"]):]
    assert all(line.startswith("compared ") for line in tail)
    assert '"setup": "code_s"' in err


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(cell, cache):
    result, _ = _run(cell, cache, control="bf16")
    assert not result["correct"], result["compared"]


def test_traced_run_completes(cache):
    result, _ = _run("gradagg-k20-b25m.sync-bern0.1", cache, trace=True)
    assert result["correct"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert result["device"]["window_s"] > 0
    assert "breakdown" in result


# ------------------------------------------------------------- faults


def _lsq_fault(monkeypatch, fault):
    from repro.distributed.master import DistributedCodedGD

    orig = DistributedCodedGD.step

    def step(self, theta, mask):
        if fault == "half":       # half the workers' products left out
            mask = np.asarray(mask).copy()
            mask[: mask.size // 2] = True
        theta2, n, r, b = orig(self, theta, mask)
        if fault == "unchanged":
            theta2 = theta
        elif fault == "altered":  # one answer altered where it is made
            scale = self.scheme.lr * float(jnp.linalg.norm(self.scheme.b))
            theta2 = theta2.at[0].add(0.05 * scale / np.sqrt(theta.size))
        elif fault == "nan":
            theta2 = theta2.at[-1].set(jnp.nan)
        return theta2, n, r, b

    monkeypatch.setattr(DistributedCodedGD, "step", step)


def _gradagg_fault(monkeypatch, fault):
    from repro.distributed.master import DistributedCodedAggregator

    orig = DistributedCodedAggregator.aggregate
    last = {}

    def aggregate(self, partials, mask):
        if fault == "half":       # half the shards left out, mean of rest
            K = partials.shape[0]
            total, n = orig(self, partials.at[K // 2:].set(0.0), mask)
            return 2.0 * total, n
        total, n = orig(self, partials, mask)
        if fault == "unchanged":  # the previous call's answer again
            total, last["t"] = last.get("t", jnp.zeros_like(total)), total
        elif fault == "altered":
            total = total.at[0].add(1.0)
        elif fault == "nan":
            total = total.at[-1].set(jnp.nan)
        return total, n

    monkeypatch.setattr(DistributedCodedAggregator, "aggregate", aggregate)


# a NaN answer spreads NaN through the reference's bound: that warns
@pytest.mark.filterwarnings("ignore:invalid value:RuntimeWarning")
@pytest.mark.parametrize("fault", ["unchanged", "half", "altered", "nan"])
@pytest.mark.parametrize("cell", ["lsq-k8192.sync-bern0.1",
                                  "gradagg-k20-b25m.sync-bern0.1"])
def test_fault_is_not_correct(cell, fault, cache, monkeypatch):
    (_lsq_fault if cell.startswith("lsq") else _gradagg_fault)(
        monkeypatch, fault)
    result, _ = _run(cell, cache)
    assert not result["correct"], (fault, result["compared"])
    json.dumps(result, allow_nan=False)     # strict JSON, whatever failed


# -------------------------------------------------- the pieces it rests on


def test_no_tpu_exits_nonzero_without_a_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_code_cache_round_trip(tmp_path):
    from repro.core.ldpc import make_regular_ldpc

    fresh = make_regular_ldpc(64, l=3, r=6, seed=5)
    built = codes.load_code(64, 3, 6, 5, tmp_path)
    loaded = codes.load_code(64, 3, 6, 5, tmp_path)
    assert (built.hit, loaded.hit) == (False, True)
    for c in (built, loaded):
        assert np.array_equal(c.code.H, fresh.H)
        assert np.array_equal(c.code.G, fresh.G)
        assert c.code.seed == fresh.seed and c.code.N == fresh.N
        assert np.array_equal(c.h_cols, fresh.check_idx)
        assert np.array_equal(c.h_abs, np.abs(fresh.H[
            np.arange(fresh.p)[:, None], fresh.check_idx]))


@pytest.mark.parametrize("q", [0.1, 0.3, 0.5])
def test_reference_peeling_matches_the_program(q, cache):
    from repro.core.engine import CodedComputeEngine

    code, _, cols, _ = codes.load_code(128, 3, 6, 0, cache)
    eng = CodedComputeEngine(code, decode_iters=4, backend="sparse")
    rng = np.random.default_rng(7)
    for _ in range(8):
        erased = rng.random(code.N) < q
        _, unresolved = eng.recover(jnp.ones(code.N), jnp.asarray(erased))
        want = reference.peel_erased(cols, erased, 4)[:code.K]
        assert np.array_equal(np.asarray(unresolved), want)
