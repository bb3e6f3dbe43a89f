"""CPU rehearsal of the coded-training cell at small widths through the
harness's own ``run``: correct on the program, not correct on the control
(the reference at an 8-bit mantissa) and under each fault the comparison
must catch (workers erased, a shard dropped, the sum altered, a NaN, the
parameters left unchanged, AdamW at the wrong step size); its work counts
at the configuration's size."""
import io
import json
import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.kinds import train

CELL = "dsv2lite-ep8-k8.sync-bern0.1"
SMALL = {"hidden_size": 64, "num_attention_heads": 2,
         "num_key_value_heads": 2, "kv_lora_rank": 16,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "intermediate_size": 128, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "vocab_size": 256, "seq_len": 32,
         "published": {"num_hidden_layers": 27, "n_routed_experts": 16,
                       "vocab_size": 2048},
         "n_routed_experts": 4, "expert_offset": 4, "bucket_elems": 4096,
         "attn_chunk": 16, "batch_pool": 4}
SEED = 2**33 + 4321


def _run(trace=False, **kw):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(CELL, SEED, 0.3, trace, t_start=time.perf_counter(),
                     require_chip=False, overrides=SMALL, cell_kw=kw,
                     peaks=harness.peaks_for("TPU v5 lite"), out=out,
                     err=err)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1]), err.getvalue()


def test_cell_is_correct():
    result, err = _run()
    assert result["correct"], result["compared"]
    assert set(result["compared"]) == {"unresolved_gap", "grad_err",
                                       "grad_err_tree", "update_err",
                                       "loss_err"}
    assert all(result["compared"][k]["value"] < 1e-4
               for k in ("grad_err", "grad_err_tree", "update_err"))
    assert set(result["metrics"]) == {"step_ms", "setup_s"}
    assert '"setup": "build_s"' in err


def test_traced_run_completes():
    result, _ = _run(trace=True)
    assert result["correct"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    labels = {name for name, _ in result["breakdown"]["idle_gaps"]}
    assert {"train/reduce", "train/fetch"} <= labels


def test_fp8_control_is_not_correct():
    result, _ = _run(control="fp8")
    assert not result["correct"], result["compared"]
    assert result["compared"]["grad_err"]["value"] > \
        result["compared"]["grad_err"]["limit"]


def _fault(monkeypatch, fault):
    from repro.core.grad_agg import CodedAggregator
    from repro.train.coded_step import CodedTrainStep

    if fault in ("unchanged", "lr"):
        orig_opt = CodedTrainStep._opt_fn

        def opt_fn(self):
            if fault == "lr":         # AdamW at twice its step size
                self.opt = self.opt._replace(lr=2 * self.opt.lr)
                return orig_opt(self)

            def train_opt_program(params, opt_state, sums):
                return params, opt_state     # the state left unchanged
            return train_opt_program

        monkeypatch.setattr(CodedTrainStep, "_opt_fn", opt_fn)
        return
    if fault == "half":           # half the workers' symbols left out
        orig_step = CodedTrainStep.__call__

        def call(self, params, opt_state, batch, mask):
            mask = np.asarray(mask).copy()
            mask[: mask.size // 2] = True
            return orig_step(self, params, opt_state, batch, mask)

        monkeypatch.setattr(CodedTrainStep, "__call__", call)
        return
    orig = CodedAggregator.aggregate

    def aggregate(self, partials, mask):
        if fault == "dropped":    # shard 0's gradient never arrives
            return orig(self, partials.at[0].set(0.0), mask)
        total, n = orig(self, partials, mask)
        if fault == "altered":
            total = total.at[0].add(jnp.linalg.norm(total))
        elif fault == "nan":
            total = total.at[-1].set(jnp.nan)
        return total, n

    monkeypatch.setattr(CodedAggregator, "aggregate", aggregate)


@pytest.mark.parametrize("fault", ["half", "dropped", "altered", "nan",
                                   "unchanged", "lr"])
def test_fault_is_not_correct(fault, monkeypatch):
    _fault(monkeypatch, fault)
    result, _ = _run()
    assert not result["correct"], (fault, result["compared"])
    json.dumps(result, allow_nan=False)


def _cfg():
    return json.loads((harness.BENCH / "configs" / "dsv2lite-ep8-k8.json")
                      .read_text())


def test_counts_from_shapes():
    cfg = _cfg()
    assert train.param_count(cfg) == 535_060_992
    w = train.work_counts(cfg)
    tokens = 8 * 4096
    # 258 M multiply-adds per token of weights plus causal attention
    assert 2.5e8 < train.matmul_macs(cfg) - 5 * 16 * 2048.5 * 320 < 2.6e8
    assert w["model"]["flops"] == w["uncoded"]["flops"] == pytest.approx(
        6 * train.matmul_macs(cfg) * tokens)
    assert 6.0e13 < w["model"]["flops"] < 6.2e13
    P = 535_060_992
    assert w["reduce"] == {"bytes": 2 * (8 + 12) * P * 4,
                           "flops": 2 * 4 * 8 * P}
    assert w["uncoded"]["bytes"] == 10 * P * 4
    assert w["model"]["bytes"] == 17 * P * 4
    assert train.capacity(cfg) == 488     # 4096·6·1.25/64 + 1, to 8


def test_program_counts_its_parameters_alike():
    """The work counts' parameter count is the program's own layout."""
    import jax
    from repro.models import Model

    drv = harness.load_part("drivers", "train.sync")
    cfg = {**_cfg(), **SMALL}
    shapes = jax.eval_shape(Model(drv.model_config(cfg)).init,
                            jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == \
        train.param_count(cfg)


def test_metric_readers_read_the_programs():
    from bench.trace_reduce import Summary

    summ = Summary(window_s=2.0, busy_s=1.9, n_calls=2,
                   module_s={"train_fwd_program": 0.2,
                             "train_grad_program": 1.2,
                             "train_opt_program": 0.1,
                             "reduce_program": 0.3},
                   top_ops=[], idle_by_label=[])
    r = harness.Reading(summ, train.work_counts(_cfg()),
                        harness.peaks_for("TPU v5 lite"))
    read = {m: harness.load_part("metrics", m).read(r) for m in
            ("train.model_ms", "train.model_roofline", "train.reduce_ms",
             "train.reduce_roofline", "train.mfu")}
    assert read["train.model_ms"] == pytest.approx(750.0)
    assert read["train.reduce_ms"] == pytest.approx(150.0)
    assert read["train.model_roofline"] == pytest.approx(
        100 * r.least_ms("model") / 750.0)
    assert read["train.mfu"] == pytest.approx(100 * r.least_ms("uncoded")
                                              / 1000.0)
    shares = ("train.model_roofline", "train.reduce_roofline", "train.mfu")
    assert all(0 < read[m] <= 100 for m in shares)
    # a trace without the training programs (the parent's) reads nothing
    empty = harness.Reading(Summary(1.0, 0.5, 1, {"worker_program": 0.1},
                                    [], []), r.work, r.peaks)
    assert all(harness.load_part("metrics", m).read(empty) is None
               for m in read)
