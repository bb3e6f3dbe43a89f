"""Work counts, peaks and the manifest's own consistency."""
import json

import numpy as np
import pytest

from bench import harness
from bench.kinds import gradagg, lsq

MANIFEST = json.loads((harness.ROOT / "BENCHMARK.json").read_text())


def _cfg(name):
    return json.loads((harness.BENCH / "configs" / f"{name}.json")
                      .read_text())


def test_lsq_counts_from_shapes():
    w = lsq.work_counts(_cfg("lsq-k8192"))
    k, N = 8192, 16384
    assert w["worker"] == {"bytes": 4 * k * k, "flops": 2 * N * k}
    assert w["master"] == {"bytes": 4 * (N + k), "flops": 0}
    assert w["uncoded"] == {"bytes": 4 * k * k, "flops": 2 * k * k}


def test_gradagg_counts_from_shapes():
    w = gradagg.work_counts(_cfg("gradagg-k20-b25m"))
    K, N, dim = 20, 40, 6553600
    assert w["worker"] == {"bytes": 4 * (K + N) * dim,
                           "flops": 2 * (N - K) * K * dim}
    assert w["master"] == {"bytes": 4 * (N + K) * dim, "flops": 0}
    assert w["uncoded"] == {"bytes": 4 * (K + 1) * dim, "flops": K * dim}


@pytest.mark.parametrize("kind, name", [(lsq, "lsq-k8192"),
                                        (gradagg, "gradagg-k20-b25m")])
@pytest.mark.parametrize("backend", ["auto", "sparse", "pallas",
                                     "pallas_tiled", "dense"])
@pytest.mark.parametrize("encode", ["materialized", "seeded",
                                    "seeded-fused"])
def test_counts_ignore_the_implementation(kind, name, backend, encode):
    cfg = _cfg(name)
    base = kind.work_counts(cfg)
    assert kind.work_counts({**cfg, "decode_backend": backend,
                             "worker_encode": encode}) == base


def test_least_time_and_peaks():
    peaks = harness.peaks_for("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    # bandwidth-bound: 819 MB at 819 GB/s
    assert harness.least_seconds({"bytes": 819e6, "flops": 1e9},
                                 peaks) == pytest.approx(1e-3)
    # compute-bound
    assert harness.least_seconds({"bytes": 0, "flops": 197e12},
                                 peaks) == pytest.approx(1.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")


def test_manifest_names_files_that_exist():
    for cell in MANIFEST["workloads"]:
        entry, e2e, per_layer, cfg, traffic = harness.load_cell(
            cell["name"])
        assert entry["chips"] == 1
        assert cfg["kind"] in ("lsq", "gradagg")
        # the driver and the straggler process are files found by name
        driver = harness.load_part("drivers",
                                   f"{cfg['kind']}.{traffic['driver']}")
        assert hasattr(driver, "Cell")
        process = harness.load_part("stragglers",
                                    traffic["stragglers"]["process"])
        assert hasattr(process, "worker_masks")
        assert {m["name"] for m in e2e} >= {"step_ms", "setup_s"}
        assert per_layer
    for c in MANIFEST["configs"]:
        assert (harness.ROOT / c["file"]).exists()
        assert _cfg(c["name"])["source"] == c["source"]
        assert _cfg(c["name"])["reduced"] == c["reduced"]
    for m in MANIFEST["per_layer"]:
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
        assert m["moves"] == "step_ms"


def test_end_to_end_metrics_are_all_computed():
    # every end-to-end metric of the manifest is one the harness times
    names = {m["name"] for m in MANIFEST["end_to_end"]}
    assert names == {"step_ms", "setup_s"}


def test_a_missing_part_is_an_error():
    with pytest.raises(KeyError):
        harness.load_part("drivers", "lsq.no-such-driver")


@pytest.mark.parametrize("q", [0.0, 0.1, 0.5])
def test_bernoulli_masks_follow_the_seed(q):
    process = harness.load_part("stragglers", "bernoulli")
    seed = 2**33 + 7
    a = process.worker_masks({"q": q}, 40, 4096, seed)
    assert a.shape == (4096, 40) and a.dtype == bool
    assert np.array_equal(a, process.worker_masks({"q": q}, 40, 4096, seed))
    assert abs(a.mean() - q) < 0.01
    if q > 0:
        assert not np.array_equal(
            a, process.worker_masks({"q": q}, 40, 4096, seed + 1))
