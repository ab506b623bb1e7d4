"""Work counts, the table of peaks, and the roofline shares built on them."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import run as bench  # noqa: E402
import trace_reduce as tr  # noqa: E402

V5E = "TPU v5 lite"


def _config(name):
    return (json.loads((BENCH / "configs" / f"{name}.json").read_text()),
            bench.load_module(BENCH / "configs" / f"{name}.py"))


def test_kmeans_work_at_a_small_shape():
    cfg, mod = _config("kmeans")
    w = mod.work({**cfg, "features": 16, "clusters": 32}, {"points": 1024}, 1)
    # 3 flops per feature per centroid per point, 1 per feature per point;
    # each float32 point read once.
    assert w["kernel"] == {"flops": 3 * 1024 * 32 * 16 + 1024 * 16,
                           "bytes": 4 * 1024 * 16}
    assert w["step"] == w["kernel"]


def test_hotspot_work_at_a_small_shape():
    cfg, mod = _config("hotspot")
    for chips in (1, 4):
        w = mod.work({**cfg, "rows_per_chip": 8, "cols": 128}, {}, chips)
        # 15 flops per cell; temp and power read, out written, per chip.
        assert w["kernel"] == {"flops": 15 * 1024, "bytes": 3 * 4 * 1024}


def test_hotspot_constants_are_rodinias_and_stable():
    cfg, mod = _config("hotspot")
    c = mod.constants(cfg)
    # hotspot.c for a 16 mm chip on a 1024 grid: step/Cap, 1/Rx, 1/Rz.
    assert c["sdc"] == pytest.approx(1.3653333, rel=1e-6)
    assert c["rx"] == c["ry"] == pytest.approx(0.1)
    assert c["rz"] == pytest.approx(1 / 20480)
    # An explicit step is stable when the centre's weight stays positive.
    assert c["sdc"] * (2 * c["rx"] + 2 * c["ry"] + c["rz"]) < 1


def test_peaks_are_the_published_v5e_numbers():
    p = bench.peaks_for(V5E)
    assert p["flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_has_no_peaks():
    with pytest.raises(KeyError, match="TPU v9"):
        bench.peaks_for("TPU v9")


def _run_at(kernel_s: float, iter_s: float, kernel="kmeans", chips=1):
    cfg, mod = _config(kernel)
    traffic = {"points": 1 << 26}
    work = mod.work(cfg, traffic, chips)
    iters = 10
    trace = tr.Reduced(window_s=iter_s * iters, busy_s=[kernel_s * iters],
                       op_s={}, kind_s=[{"kernel": kernel_s * iters}],
                       idle_s={})
    return bench.Run(kernel=kernel, chips=chips, iters=iters,
                     window_s=iter_s * iters, work=work,
                     peak=bench.peaks_for(V5E), spans=[], trace=trace)


@pytest.mark.parametrize("kernel", ["kmeans", "hotspot"])
def test_shares_reach_100_only_at_the_roof(kernel):
    roof = _run_at(1.0, 1.0, kernel).roof_s("kernel")
    at_roof = _run_at(roof, roof, kernel)
    assert at_roof.kernel_roofline(kernel) == pytest.approx(100.0)
    assert 100.0 * at_roof.roof_s("step") / at_roof.iter_s == pytest.approx(
        100.0)
    for slower in (1.01, 2.0, 50.0):
        r = _run_at(roof * slower, roof * slower, kernel)
        assert r.kernel_roofline(kernel) < 100.0
        assert 100.0 * r.roof_s("step") / r.iter_s < 100.0


def test_kmeans_is_bound_by_bytes_on_v5e():
    r = _run_at(1.0, 1.0)
    w = r.work["kernel"]
    assert r.roof_s("kernel") == pytest.approx(w["bytes"] / 819e9)
    assert w["flops"] / 197e12 < w["bytes"] / 819e9


def test_a_roofline_of_another_kernel_is_silent():
    r = _run_at(1.0, 1.0, "kmeans")
    assert r.kernel_roofline("hotspot") is None
    r.trace.kind_s = [{}]
    assert r.kernel_roofline("kmeans") is None
