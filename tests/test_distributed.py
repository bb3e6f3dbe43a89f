"""The sharded coded-worker runtime: master/worker parity with the
single-device Scheme2 (bit-for-bit, every decode backend), worker-granular
straggling, telemetry-driven budgets, and the distributed AOT step.

The in-process tests run on whatever mesh this process has (1 CPU device in
the tier-1 job; 8 fake devices in the CI distributed job) — logical workers
are decoupled from devices, so the full code path including ``shard_map``
runs either way.  The subprocess test forces the fake 8-device mesh
explicitly (the acceptance configuration).
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    BernoulliStragglers,
    DelayModel,
    FixedCountStragglers,
    Scheme2,
    make_regular_ldpc,
    run_pgd,
    second_moment,
)
from repro.data import make_linear_problem
from repro.distributed import (
    DistributedCodedGD,
    StragglerRateEstimator,
    WorkerStragglers,
    WorkerTopology,
    make_worker_mesh,
)
from repro.distributed.selfcheck import check_parity

REPO = Path(__file__).resolve().parents[1]

K = 64
CODE = make_regular_ldpc(K, l=3, r=6, seed=0)
PROB = make_linear_problem(m=4 * K, k=K, seed=0)
MOM = second_moment(PROB.X, PROB.y)


def _scheme(backend="sparse", decode_iters=8, **kw):
    return Scheme2.build(CODE, MOM, lr=PROB.lr, decode_iters=decode_iters,
                         decode_backend=backend, **kw)


# ------------------------------------------------------------------- parity


@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_bit_parity_with_single_device_scheme2(backend):
    """Same key, same per-worker erasures → bit-identical iterates."""
    assert check_parity(K=K, n_workers=8, steps=5, q0=0.25,
                        backend=backend) == 5


def test_bit_parity_pallas_backend():
    """The fused-kernel decode under the distributed master (interpret
    mode off-TPU — slow, so fewer steps)."""
    assert check_parity(K=K, n_workers=8, steps=2, q0=0.25,
                        backend="pallas") == 2


def test_parity_on_fake_8_device_mesh_subprocess():
    """The acceptance configuration: a REAL 8-device mesh (fake CPU
    devices), all three decode backends, bit-identical trajectories."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, "-m", "repro.distributed.selfcheck",
         "--workers", "8", "--steps", "4"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert res.returncode == 0, f"selfcheck failed:\n{res.stdout}\n{res.stderr}"
    assert res.stdout.count("parity OK") == 3      # dense, sparse, pallas
    assert "devices=8" in res.stdout


# ------------------------------------------------------ sharded master decode


def test_sharded_master_decode_bit_parity():
    """master_decode="sharded": the decode itself runs over the mesh (check
    tiles partitioned, one all-gather merge per round) and the trajectory
    stays bit-identical to the single-device sparse decode — the overwrite
    merge crosses shards as a select, never an f32 sum."""
    assert check_parity(K=K, n_workers=8, steps=5, q0=0.25,
                        backend="sparse", master_decode="sharded") == 5


def test_sharded_decode_matches_sparse_rounds():
    """The shard_map-ped decode function itself (ragged check padding over
    the mesh) against the single-device fixed-D sparse loop, bit for bit."""
    from repro.core.decoder import peel_fixed_sparse
    from repro.distributed.sharded_decode import (build_sharded_decode,
                                                  shard_check_tables)

    code = make_regular_ldpc(100, l=3, r=6, seed=1)   # p = 100: ragged
    mesh = make_worker_mesh()
    idx_sh, coeff_sh = shard_check_tables(code, mesh)
    rng = np.random.default_rng(0)
    cw = jnp.asarray(code.encode(rng.standard_normal((100, 2))), jnp.float32)
    dec = jax.jit(build_sharded_decode(mesh, iters=8))
    for seed in range(3):
        er = jnp.asarray(np.random.default_rng(seed).random(code.N) < 0.35)
        rx = jnp.where(er[:, None], 0.0, cw)
        ref_v, ref_e = peel_fixed_sparse(jnp.asarray(code.check_idx),
                                         jnp.asarray(code.check_coeff),
                                         rx, er, 8)
        v, e, r = dec(idx_sh, coeff_sh, rx, er, jnp.asarray([8], jnp.int32))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(ref_v))
        np.testing.assert_array_equal(np.asarray(e), np.asarray(ref_e))
        assert int(r) == 8


def test_sharded_parity_on_fake_8_device_mesh_subprocess():
    """Sharded master decode ≡ single-device decode on the fake 8-device
    mesh (the acceptance claim for the sharded decode)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, "-m", "repro.distributed.selfcheck",
         "--workers", "8", "--steps", "4", "--master-decode", "sharded"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert res.returncode == 0, f"selfcheck failed:\n{res.stdout}\n{res.stderr}"
    assert "parity OK" in res.stdout
    assert "master_decode=sharded" in res.stdout
    assert "devices=8" in res.stdout


def test_sharded_telemetry_budget_traced_and_respected():
    """Telemetry budgets flow into the sharded master program as the same
    traced (1,) operand: varying budgets reuse ONE compiled program, and
    rounds spent never exceed the granted budget."""
    scheme = _scheme(decode_iters=32)
    topo = WorkerTopology(8, CODE.N)
    dist = DistributedCodedGD(scheme, topo, budget_mode="telemetry",
                              master_decode="sharded", max_rounds=32)
    theta = jnp.zeros(K)
    budgets_seen = set()
    for t in range(6):
        mask = BernoulliStragglers(0.05 if t < 3 else 0.4).sample(
            jax.random.PRNGKey(t), 8)
        theta, _, rounds, budget = dist.step(theta, mask)
        budgets_seen.add(budget)
        assert rounds <= budget
    assert len(budgets_seen) > 1
    assert dist._master_program._cache_size() == 1


def test_sharded_master_decode_validation():
    with pytest.raises(ValueError):
        DistributedCodedGD(_scheme(), WorkerTopology(8, CODE.N),
                           master_decode="hologram")


def test_run_matches_run_pgd_trajectory():
    """The master driver's python loop reproduces run_pgd's scanned
    trajectory under the same lifted straggler stream (same key schedule);
    unresolved counts match exactly, errors to float tolerance."""
    scheme = _scheme()
    topo = WorkerTopology(8, CODE.N)
    stragglers = WorkerStragglers(BernoulliStragglers(0.2), topo)
    key = jax.random.PRNGKey(3)
    theta0 = jnp.zeros(K)
    ref = run_pgd(scheme, theta0, stragglers, 10, key=key,
                  theta_star=PROB.theta_star)
    dist = DistributedCodedGD(scheme, topo)
    got = dist.run(theta0, BernoulliStragglers(0.2), 10, key=key,
                   theta_star=PROB.theta_star)
    np.testing.assert_array_equal(got.unresolved, np.asarray(ref.unresolved))
    # run_pgd fuses the whole trajectory into one scanned program; the
    # master loop launches per-step programs — same math, different XLA
    # fusion, so float equality is approximate here (the bit-exact claim
    # against a per-step reference is test_bit_parity_* above); the
    # per-step rounding difference compounds over the 10 GD steps, so the
    # band is wider than a single decode's.
    np.testing.assert_allclose(got.errors, np.asarray(ref.errors),
                               rtol=5e-3, atol=1e-5)
    # per-coordinate drift accumulates over the 10 steps; the error norm
    # above pins the trajectory, coordinates get an absolute band
    np.testing.assert_allclose(np.asarray(got.theta), np.asarray(ref.theta),
                               rtol=5e-3, atol=5e-3)


# ------------------------------------------------- worker-granular straggling


def test_worker_straggler_lift_erases_whole_shards():
    topo = WorkerTopology(8, CODE.N)
    model = WorkerStragglers(FixedCountStragglers(3), topo)
    mask = model.sample(jax.random.PRNGKey(0), CODE.N)
    m = np.asarray(mask).reshape(8, topo.rows_per_worker)
    per_worker = m.any(axis=1)
    assert per_worker.sum() == 3                  # exactly s workers
    assert (m.all(axis=1) == per_worker).all()    # whole shards, never rows
    with pytest.raises(ValueError):
        model.sample(jax.random.PRNGKey(0), CODE.N + 1)


def test_distributed_validates_construction():
    scheme = _scheme()
    with pytest.raises(ValueError):               # N mismatch
        DistributedCodedGD(scheme, WorkerTopology(4, 2 * CODE.N))
    with pytest.raises(ValueError):               # unknown budget mode
        DistributedCodedGD(scheme, WorkerTopology(8, CODE.N),
                           budget_mode="psychic")
    dist = DistributedCodedGD(scheme, WorkerTopology(8, CODE.N))
    with pytest.raises(ValueError):               # wrong mask width
        dist.step(jnp.zeros(K), jnp.zeros(CODE.N, bool))


# ----------------------------------------------------------- telemetry loop


def test_telemetry_budgets_track_climate_and_save_rounds():
    """Online telemetry: budgets rise with the straggler climate, mean
    decode rounds land far under the fixed worst-case budget, and the
    adaptive decode still resolves what the fixed decode resolves."""
    max_rounds = 32
    scheme = _scheme(decode_iters=max_rounds)
    topo = WorkerTopology(8, CODE.N)
    dist = DistributedCodedGD(scheme, topo, budget_mode="telemetry",
                              estimator=StragglerRateEstimator(decay=0.7),
                              max_rounds=max_rounds)
    calm = dist.run(jnp.zeros(K), BernoulliStragglers(0.05), 12,
                    key=jax.random.PRNGKey(0))
    stormy_est = StragglerRateEstimator(decay=0.7)
    dist2 = DistributedCodedGD(scheme, topo, budget_mode="telemetry",
                               estimator=stormy_est, max_rounds=max_rounds)
    stormy = dist2.run(jnp.zeros(K), BernoulliStragglers(0.35), 12,
                       key=jax.random.PRNGKey(0))
    # budgets track the observed climate (tail steps, past the prior)
    assert calm.budgets[-5:].mean() < stormy.budgets[-5:].mean()
    assert calm.rates[-1] < stormy.rates[-1]
    # decode effort stays far under the worst-case fixed budget
    assert calm.rounds.mean() < max_rounds / 4
    assert (calm.rounds <= calm.budgets).all()
    assert (stormy.rounds <= stormy.budgets).all()


def test_telemetry_step_budget_is_traced_not_recompiled():
    """Varying per-step budgets must reuse ONE compiled master program."""
    scheme = _scheme(decode_iters=32)
    topo = WorkerTopology(8, CODE.N)
    dist = DistributedCodedGD(scheme, topo, budget_mode="telemetry",
                              max_rounds=32)
    theta = jnp.zeros(K)
    budgets_seen = set()
    for t in range(8):
        mask = BernoulliStragglers(0.05 if t < 4 else 0.4).sample(
            jax.random.PRNGKey(t), 8)
        theta, _, _, budget = dist.step(theta, mask)
        budgets_seen.add(budget)
    assert len(budgets_seen) > 1                  # budgets actually varied
    assert dist._master_program._cache_size() == 1


def test_delay_model_wait_for_semantics():
    """With a DelayModel the master waits for the telemetry-chosen fastest
    wait_for workers; the implied mask and simulated step time are
    consistent with the order statistics."""
    scheme = _scheme(decode_iters=16)
    topo = WorkerTopology(8, CODE.N)
    dist = DistributedCodedGD(scheme, topo, budget_mode="telemetry",
                              max_rounds=16)
    res = dist.run(jnp.zeros(K), None, 10, key=jax.random.PRNGKey(1),
                   delay_model=DelayModel(tau=1.0, mu=1.0))
    assert ((1 <= res.wait_for) & (res.wait_for <= 8)).all()
    assert (res.step_times >= 1.0).all()          # tau floor
    # waiting for fewer workers can only shorten the simulated step
    assert res.errors.shape == (10,)


# ------------------------------------------------------------- AOT step


def test_build_distributed_gd_step_lowers():
    """The production-scale master/worker step lowers + compiles on a
    reduced (devices, 1) workers x data mesh, both decode variants."""
    from repro.distributed.master import build_distributed_gd_step
    from repro.launch.mesh import make_mesh

    n_dev = jax.device_count()
    mesh = make_mesh((n_dev, 1), ("workers", "data"))
    for decode in ("dense", "sparse"):
        jitted, specs = build_distributed_gd_step(
            256, 128, 4, jnp.float32, mesh, decode=decode)
        compiled = jitted.lower(*specs).compile()
        assert compiled is not None
    with pytest.raises(ValueError):
        build_distributed_gd_step(256, 128, 4, jnp.float32, mesh,
                                  decode="pallas")


# ------------------------------------------------ seeded workers & grad-agg


def test_seeded_worker_encode_bit_parity():
    """worker_encode="seeded": workers hold only generator gather tables,
    fuse encode into the matvec — bit-identical to the single-device
    Scheme2.build_seeded trajectory under the lifted masks."""
    assert check_parity(K=K, n_workers=8, steps=5, q0=0.25,
                        backend="sparse", worker_encode="seeded") == 5


def test_seeded_worker_encode_validates_scheme():
    """A materialized scheme cannot drive seeded workers (there are no
    gather tables to shard; C is the encoded operator, not M)."""
    topo = WorkerTopology(8, CODE.N)
    with pytest.raises(ValueError, match="build_seeded"):
        DistributedCodedGD(_scheme(), topo, make_worker_mesh(),
                           worker_encode="seeded")


def test_distributed_grad_agg_bit_parity():
    """DistributedCodedAggregator (2-D payload worker launch) vs the
    single-device CodedAggregator, several masks: exact unresolved counts,
    both sums within the peel-chain bound of the exact coded sum."""
    from repro.distributed.selfcheck import check_grad_agg_parity
    assert check_grad_agg_parity(n_shards=64, dim=17, n_workers=8,
                                 steps=4, q0=0.25) == 4


def test_seeded_and_grad_agg_parity_subprocess():
    """The two new selfcheck modes on the REAL fake-8-device mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("JAX_PLATFORMS", "cpu")
    res = subprocess.run(
        [sys.executable, "-m", "repro.distributed.selfcheck",
         "--workers", "8", "--steps", "4", "--backends", "sparse",
         "--worker-encode", "seeded"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert res.returncode == 0, f"selfcheck failed:\n{res.stdout}\n{res.stderr}"
    assert "parity OK" in res.stdout
    assert "worker_encode=seeded" in res.stdout
    assert "devices=8" in res.stdout
    res = subprocess.run(
        [sys.executable, "-m", "repro.distributed.selfcheck",
         "--workers", "8", "--steps", "4", "--backends", "sparse",
         "--grad-agg"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO)
    assert res.returncode == 0, f"selfcheck failed:\n{res.stdout}\n{res.stderr}"
    assert "parity OK: grad-agg" in res.stdout
    assert "devices=8" in res.stdout


def test_selfcheck_json_mode_in_process(capsys, tmp_path):
    """--json puts one machine-readable object on stdout (the obs status
    line goes to stderr, keeping it parseable) and exports --obs-out."""
    import json

    from repro.distributed.selfcheck import main

    obs = tmp_path / "sc.jsonl"
    rc = main(["--K", "32", "--workers", "8", "--steps", "2",
               "--backends", "dense", "--json", "--obs-out", str(obs)])
    cap = capsys.readouterr()
    assert rc == 0
    doc = json.loads(cap.out)                  # stdout is pure JSON
    assert doc["ok"] is True and doc["workers"] == 8
    assert doc["checks"] == [{
        "kind": "gd-step", "backend": "dense", "master_decode": "single",
        "worker_encode": "materialized", "ok": True, "steps": 2}]
    assert "[obs]" in cap.err
    assert obs.exists() and obs.with_suffix(".trace.json").exists()
