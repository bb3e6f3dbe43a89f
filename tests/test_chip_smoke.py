"""Rehearsal of ``chip_smoke.py`` on the CPU at a tiny size.

The chip smoke's phase functions run here unchanged — the same drivers,
the same per-step checks (exact unresolved counts against the host
schedule solve, zero-filled unresolved coordinates, resolved gradients
within the peel-chain bound, the f32 reference at q = 0, loss descent) —
at k = 256 with W = 8 logical workers, Pallas kernels in interpret mode.
The four-chip path runs in a subprocess on four virtual CPU devices.
``main`` itself must refuse to run without a TPU.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402
from repro.distributed.topology import make_worker_mesh  # noqa: E402

K, W, STEPS = 256, 8, 5


@pytest.fixture(scope="module")
def pb():
    return chip_smoke.build_problem(K, W, seed=0)


@pytest.fixture(scope="module")
def clock():
    return chip_smoke.CompileClock()


@pytest.fixture(scope="module")
def ref(pb):
    thetas = chip_smoke.reference_thetas(pb, STEPS)
    return thetas, chip_smoke.reference_tolerance(pb, thetas)


@pytest.mark.parametrize("q", [0.0, chip_smoke.STRAGGLER_Q])
@pytest.mark.parametrize("config",
                         ["sparse", "auto", "replay", "pallas_tiled"])
def test_phase_a_rehearsal(pb, clock, ref, config, q):
    rec = chip_smoke.run_sync(pb, config, q, STEPS, 0, make_worker_mesh(1),
                              clock, ref)
    json.dumps(rec)
    assert rec["ok"] and len(rec["unresolved"]) == STEPS
    if q == 0.0:
        assert rec["ref_dev_rel"] <= rec["rtol"]
    else:
        assert sum(rec["stragglers"]) > 0     # the masks erased something
    if config == "auto":
        assert rec["tpu_custom_call"] is False   # interpreted off-TPU


@pytest.mark.parametrize("delay", [False, True])
def test_phase_b_rehearsal(pb, clock, delay):
    rec = chip_smoke.run_pipeline(pb, STEPS, 0, make_worker_mesh(1), clock,
                                  delay)
    json.dumps(rec)
    assert rec["ok"] and rec["loss"][-1] < rec["loss"][0]
    if delay:
        assert min(rec["wait_for"]) < W       # the delay model cut workers


def test_check_step_rejects_wrong_results(pb):
    """The per-step check is not vacuous: a wrong count, a moved
    unresolved coordinate, or a resolved coordinate off its gradient by
    more than the bound each fail it."""
    from repro.distributed.master import DistributedCodedGD

    dist = DistributedCodedGD(pb.base, pb.topo, make_worker_mesh(1))
    theta = np.zeros(K, np.float32)
    mask = np.zeros(W, bool)
    mask[:5] = True                   # 5 of 8 workers: some stay erased
    theta2, n_unres, _, budget = dist.step(theta, mask)
    erased = np.repeat(mask, pb.topo.rows_per_worker)
    chip_smoke.check_step(pb, theta, theta2, erased, n_unres, budget)
    _, resolved, g_err = chip_smoke.gradient_error(pb, theta, erased)
    assert n_unres > 0 and resolved.any()
    with pytest.raises(chip_smoke.SmokeFailure, match="unresolved"):
        chip_smoke.check_step(pb, theta, theta2, erased, n_unres + 1,
                              budget)
    bad = np.array(theta2)
    bad[np.flatnonzero(~resolved)[0]] += 1e-3
    with pytest.raises(chip_smoke.SmokeFailure, match="zero-fill"):
        chip_smoke.check_step(pb, theta, bad, erased, n_unres, budget)
    bad = np.array(theta2)
    i = np.flatnonzero(resolved)[0]
    bad[i] += 10 * (pb.lr * g_err[i] + 1e-6 * abs(bad[i]) + 1e-30)
    with pytest.raises(chip_smoke.SmokeFailure, match="peel-chain"):
        chip_smoke.check_step(pb, theta, bad, erased, n_unres, budget)


def _env(**extra):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_four_chip_phase_on_virtual_devices():
    """The ``--chips 4`` path on four virtual CPU devices: worker shards
    on four distinct devices, single and sharded master decodes agreeing
    with the one-device reference."""
    code = ("import json, chip_smoke\n"
            f"pb = chip_smoke.build_problem({K}, {W}, 0)\n"
            "clock = chip_smoke.CompileClock()\n"
            "recs = list(chip_smoke.four_chip_phase(pb, 3, 0, clock))\n"
            "print(json.dumps(recs))\n")
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900, cwd=REPO,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert res.returncode == 0, res.stderr[-4000:]
    recs = json.loads(res.stdout.strip().splitlines()[-1])
    assert [(r["config"], r["q"]) for r in recs] == [
        ("auto", 0.0), ("sharded", 0.0),
        ("auto", chip_smoke.STRAGGLER_Q), ("sharded", chip_smoke.STRAGGLER_Q)]
    for r in recs:
        assert r["ok"] and r["devices"] == 4
        assert r["worker_shard_devices"] == [0, 1, 2, 3]
        assert len(r["peak_bytes"]) == 4
    assert all(r["check_table_shard_devices"] == [0, 1, 2, 3]
               for r in recs if r["config"] == "sharded")


def test_main_refuses_without_tpu():
    """No accelerator: exit non-zero before any work, no result line."""
    res = subprocess.run([sys.executable, "chip_smoke.py"],
                         capture_output=True, text=True, timeout=300,
                         cwd=REPO, env=_env())
    assert res.returncode == 2
    assert res.stdout.strip() == ""
    assert "no TPU" in res.stderr


def test_large_code_construction_path(monkeypatch):
    """Codes past the greedy-pivot size take the blocked-LU column
    selection (the chip smoke's k = 8192 code does); forced onto it at a
    small size, the code is still (3, 6)-regular and systematic with
    H·G = 0."""
    from repro.core import ldpc

    monkeypatch.setattr(ldpc, "_GREEDY_PIVOT_MAX_P", 0)
    code = ldpc.make_regular_ldpc(128, l=3, r=6, seed=1)
    H, G = np.asarray(code.H), np.asarray(code.G)
    assert (np.count_nonzero(H, axis=0) == 3).all()
    assert (np.count_nonzero(H, axis=1) == 6).all()
    np.testing.assert_array_equal(G[:128], np.eye(128))
    assert np.abs(H @ G).max() < 1e-8 * np.abs(H).max() * 128


@pytest.mark.parametrize("env_dir", [None, "set"])
def test_compile_cache_location(tmp_path, env_dir):
    """Entry points' compile cache: JAX's own reading of
    JAX_COMPILATION_CACHE_DIR when set (the helper sets nothing else),
    otherwise the fixed ``<checkout>/.jax_cache``.  Run in a child so the
    process-wide JAX setting stays out of this test process."""
    code = ("import jax\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, cwd=tmp_path, env=env)
    assert res.returncode == 0, res.stderr[-2000:]
    returned, configured = res.stdout.split()
    want = str(tmp_path / "cc") if env_dir else str(REPO / ".jax_cache")
    assert returned == configured == want
