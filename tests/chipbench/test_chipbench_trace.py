"""The trace reduction (chipbench/trace_reduce.py), on a trace recorded on a
TPU v5e and on small hand-made ones."""

from __future__ import annotations

import glob
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[2] / "chipbench"
sys.path.insert(0, str(BENCH))

import trace_reduce as tr  # noqa: E402


@pytest.fixture(scope="module")
def chip_trace():
    with open(BENCH / "testdata" / "trace_v5e_1chip.json") as f:
        return json.load(f)


def _covered_ns(intervals, lo, hi):
    """Covered length by brute force on a 1 ns grid (independent of
    trace_reduce.union)."""
    grid = np.zeros(int(hi - lo) + 1, bool)
    for s, e in intervals:
        a, b = int(round(max(s, lo) - lo)), int(round(min(e, hi) - lo))
        if b > a:
            grid[a:b] = True
    return int(grid.sum())


def test_chip_trace_busy_union(chip_trace):
    r = tr.reduce(chip_trace)
    lo, hi = chip_trace["window"]
    ops = [(s, s + d) for _, s, d in chip_trace["devices"][0]]
    assert len(r.busy_s) == 1
    assert r.window_s == pytest.approx((hi - lo) * 1e-9)
    assert r.busy_s[0] * 1e9 == pytest.approx(_covered_ns(ops, lo, hi),
                                              abs=len(ops))
    assert 0 < r.busy_s[0] < r.window_s


def test_chip_trace_op_times_by_stable_name(chip_trace):
    r = tr.reduce(chip_trace)
    want: dict[str, float] = {}
    for text, _, dur in chip_trace["devices"][0]:
        name = text.split(" = ")[0].lstrip("%").rsplit(".", 1)[0]
        want[name] = want.get(name, 0.0) + dur * 1e-9
    got = {k.split(":")[0]: v for k, v in r.op_s.items()}
    assert got.keys() == want.keys()
    for k in want:
        assert got[k] == pytest.approx(want[k])
    # Three k-means launches (34.6 ms each) and ten stencil steps (4.9 ms).
    assert r.op_s["kmeans_pallas:custom-call"] == pytest.approx(0.1039,
                                                               rel=1e-3)
    assert r.op_s["hotspot_pallas:custom-call"] == pytest.approx(0.0490,
                                                                rel=1e-3)
    assert r.kind_s[0]["kernel"] == pytest.approx(
        r.op_s["kmeans_pallas:custom-call"]
        + r.op_s["hotspot_pallas:custom-call"])
    assert "collective" not in r.kind_s[0]
    assert [n for n, _ in r.top_ops(2)] == ["kmeans_pallas:custom-call",
                                            "hotspot_pallas:custom-call"]


def test_chip_trace_idle_gaps_labelled_by_host_span(chip_trace):
    r = tr.reduce(chip_trace)
    assert sum(r.idle_s.values()) == pytest.approx(
        r.window_s - r.busy_s[0], rel=1e-9)
    assert set(r.idle_s) <= set(tr.HOST_SPANS) | {tr.IDLE_NO_SPAN}
    # The device waits longest while the host blocks at the window's end.
    assert r.top_idle(1)[0][0] == "sync"


def test_overlapping_ops_count_once_and_gaps_take_the_innermost_span():
    raw = {"window": [0.0, 100.0],
           "host": [["launch", 0.0, 60.0], ["update", 40.0, 10.0],
                    ["sync", 80.0, 20.0]],
           "devices": [[["%a.1 = f32[8] fusion(f32[8] %x)", 0.0, 30.0],
                        ["%b.2 = f32[8] fusion(f32[8] %x)", 10.0, 10.0],
                        ["%a.3 = f32[8] fusion(f32[8] %x)", 25.0, 20.0]]]}
    r = tr.reduce(raw)
    assert r.busy_s == [pytest.approx(45e-9)]
    assert r.op_s == {"a:fusion": pytest.approx(50e-9),
                      "b:fusion": pytest.approx(10e-9)}
    # Idle: 45-60 (update until 50, then launch), 60-80 none, 80-100 sync.
    assert r.idle_s == {"update": pytest.approx(5e-9),
                        "launch": pytest.approx(10e-9),
                        tr.IDLE_NO_SPAN: pytest.approx(20e-9),
                        "sync": pytest.approx(20e-9)}


def test_ops_are_clipped_to_the_window_and_chips_averaged():
    raw = {"window": [10.0, 20.0], "host": [],
           "devices": [[["%k.1 = f32[8] custom-call(f32[8] %x), "
                         'custom_call_target="tpu_custom_call"', 0.0, 15.0]],
                       [["%cp.1 = f32[8] collective-permute(f32[8] %x)",
                         12.0, 4.0]]]}
    r = tr.reduce(raw)
    assert r.busy_s == [pytest.approx(5e-9), pytest.approx(4e-9)]
    assert r.kind_seconds("kernel", device=0) == pytest.approx(5e-9)
    assert r.kind_seconds("collective", device=1) == pytest.approx(4e-9)
    assert r.kind_seconds("kernel") == pytest.approx(2.5e-9)
    assert r.idle_s == {tr.IDLE_NO_SPAN: pytest.approx((5e-9 + 6e-9) / 2)}


@pytest.mark.parametrize("text,name,kind", [
    ("%kmeans_pallas.1 = (f32[8192,32,16]{2,1,0}, f32[8192,32,1]{2,1,0}) "
     "custom-call(f32[16,67108864]{1,0} %bitcast), "
     'custom_call_target="tpu_custom_call"', "kmeans_pallas:custom-call",
     "kernel"),
    ("%reduce_sum.14 = f32[32,16]{1,0} reduce(f32[8192,32,16]{2,1,0} %p, "
     "f32[] %c), dimensions={0}", "reduce_sum:reduce", "xla"),
    ("%collective-permute-start.2 = (f32[1,16384], f32[1,16384]) "
     "collective-permute-start(f32[1,16384] %x)",
     "collective-permute-start:collective-permute-start", "collective"),
    ("%all-reduce.5 = f32[32,16]{1,0} all-reduce(f32[32,16]{1,0} %s)",
     "all-reduce:all-reduce", "collective"),
])
def test_op_names_and_kinds(text, name, kind):
    assert tr.op_name(text) == name
    assert tr.op_kind(text) == kind


def test_load_puts_the_harness_spans_on_the_trace_clock(tmp_path):
    import jax
    import jax.numpy as jnp

    import spans

    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # for the profiler's own copy of the span
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    spans.RECORDED.clear()
    with spans.span("window"), jax.profiler.TraceAnnotation("window"):
        with spans.span("launch"):
            y = f(x)
        with spans.span("sync"):
            y.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    raw = tr.load(path, spans.RECORDED)
    assert raw["window"][1] > raw["window"][0]
    assert [h[0] for h in raw["host"]] == ["launch", "sync"]
    assert raw["devices"] == []  # no TPU planes off the chip
    profiler_window = [
        e for p in jax.profiler.ProfileData.from_file(path).planes
        for line in p.lines for e in line.events if e.name == "window"][0]
    # The two clocks agree to well under a millisecond.
    assert abs(profiler_window.start_ns - raw["window"][0]) < 1e6
