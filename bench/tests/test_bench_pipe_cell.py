"""CPU rehearsal of the pipelined least-squares cell at a small size
through the harness's own ``run``: correct on the program, not correct on
the control (the reference with bfloat16 operands) and under each fault
the comparison must catch; the delay process it draws its traffic from."""
import io
import json
import time

import numpy as np
import pytest

from bench import harness

CELL = "lsq-k8192.pipe-delay"
SMALL = {"k": 256, "m": 1024, "N": 512, "workers": 8}
SEED = 2**33 + 777


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("codes")


def _run(cache, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(CELL, SEED, 0.3, False, t_start=time.perf_counter(),
                     require_chip=False, overrides=SMALL,
                     cell_kw={"cache_dir": cache, **kw},
                     peaks=harness.peaks_for("TPU v5 lite"), out=out,
                     err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_cell_is_correct_and_folds(cache, monkeypatch):
    from repro.distributed.pipeline import AsyncDistributedCodedGD

    orig = AsyncDistributedCodedGD.run
    late = []

    def run(self, *a, **kw):
        res = orig(self, *a, **kw)
        late.append(int(res.resolved_late.sum()))
        return res

    monkeypatch.setattr(AsyncDistributedCodedGD, "run", run)
    result = _run(cache)
    assert result["correct"], result["compared"]
    assert set(result["compared"]) == {"unresolved_gap", "err_over_bound"}
    assert sum(late) > 0           # late workers did fold back in


def test_bf16_control_is_not_correct(cache):
    result = _run(cache, control="bf16")
    assert not result["correct"], result["compared"]


def _fault(monkeypatch, fault):
    import repro.distributed.pipeline as pipeline

    if fault == "half":            # half the workers cut, whatever arrived
        orig_cut = pipeline.delay_step_control

        def cut(delays, wait, factor):
            mask, cutoff, observed = orig_cut(delays, wait, factor)
            mask = mask.copy()
            mask[: mask.size // 2] = True
            return mask, cutoff, observed

        monkeypatch.setattr(pipeline, "delay_step_control", cut)
        return
    orig = pipeline.AsyncDistributedCodedGD.run

    def run(self, theta0, *a, **kw):
        res = orig(self, theta0, *a, **kw)
        th = np.array(res.thetas)
        if fault == "unchanged":
            th[:] = np.asarray(theta0)
        elif fault == "altered":   # the first step's answer altered
            scale = self.scheme.lr * float(np.linalg.norm(self.scheme.b))
            th[0, 0] += 0.05 * scale / np.sqrt(th.shape[1])
        elif fault == "nan":
            th[0, -1] = np.nan
        return res._replace(thetas=th)

    monkeypatch.setattr(pipeline.AsyncDistributedCodedGD, "run", run)


@pytest.mark.filterwarnings("ignore:invalid value:RuntimeWarning")
@pytest.mark.parametrize("fault", ["half", "unchanged", "altered", "nan"])
def test_fault_is_not_correct(fault, cache, monkeypatch):
    _fault(monkeypatch, fault)
    result = _run(cache)
    assert not result["correct"], (fault, result["compared"])
    json.dumps(result, allow_nan=False)


def test_delays_follow_the_seed():
    process = harness.load_part("stragglers", "delay")
    spec = {"tau": 1.0, "mu": 1.0, "chunk": 200}
    seed = 2**33 + 7
    a = process.worker_masks(spec, 32, 8192, seed)
    assert a.shape == (process.POOL, 200, 32) and a.dtype == np.float32
    assert np.array_equal(a, process.worker_masks(spec, 32, 8192, seed))
    assert not np.array_equal(a, process.worker_masks(spec, 32, 8192,
                                                      seed + 1))
    assert a.min() >= 1.0
    assert abs(float(a.mean()) - 2.0) < 0.02     # tau + 1/mu
    with pytest.raises(ValueError):
        process.worker_masks({**spec, "mu": 0.0}, 32, 4, seed)


def test_reference_cut_matches_the_program():
    from repro.core.straggler import DelayModel
    from repro.distributed.master import delay_step_control

    from bench.kinds import pipe_reference

    rng = np.random.default_rng(3)
    d = (1.0 + rng.exponential(1.0, (50, 32))).astype(np.float32)
    wait = rng.integers(16, 33, 50)
    cut, lags = pipe_reference.cuts(d, wait)
    for t in range(50):
        mask, cutoff, _ = delay_step_control(d[t], int(wait[t]), 2.0)
        assert np.array_equal(cut[t], mask)
        assert np.array_equal(lags[t], DelayModel.arrival_lags(d[t], cutoff))
