"""The readers of the Context's per-launch compile counts
(``metrics/compile_ms.py`` and ``metrics/programs_per_launch.py``) on
synthetic runs."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
sys.path.insert(0, str(BENCH))

import run as bench  # noqa: E402


def _span(name, dur, **args):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": dur, "pid": 0,
            "stream": "driver", "cat": "compute", "args": args}


def _run(spans):
    return bench.Run(kernel="hotspot", chips=4, iters=len(spans),
                     window_s=1.0, work={}, peak={}, spans=spans, trace=None)


LAUNCHES = [
    _span("plan:hotspot", 2e-4, launch=1),
    _span("launch:hotspot", 0.8, launch=1, programs=34, cache_loads=34,
          traces=44, compile_s=0.7),
    _span("execute:hotspot", 0.79, launch=1),
    _span("jax:compile", 0.02, launch=1, fun="concatenate"),
    _span("launch:hotspot", 0.9, launch=2, programs=30, cache_loads=30,
          traces=40, compile_s=0.5),
]


@pytest.mark.parametrize("name,want", [
    ("compile_ms", 600.0), ("compile_ms.mesh", 600.0),
    ("programs_per_launch", 32.0), ("programs_per_launch.mesh", 32.0),
])
def test_reads_the_mean_over_launch_spans(name, want):
    assert bench.reader(name)(_run(LAUNCHES)) == pytest.approx(want)


@pytest.mark.parametrize("name", ["compile_ms.mesh",
                                  "programs_per_launch.mesh"])
def test_mesh_name_resolves_to_its_quantitys_reader(name):
    assert bench.quantity(name) != name
    assert not (BENCH / "metrics" / f"{name}.py").exists()
    assert bench.reader(name).__module__ == bench.load_module(
        BENCH / "metrics" / f"{bench.quantity(name)}.py").__name__


@pytest.mark.parametrize("name", ["compile_ms", "programs_per_launch"])
@pytest.mark.parametrize("spans", [
    [],  # a cell that does not launch through Context
    [_span("plan:hotspot", 2e-4), _span("sync", 0.1)],
    # a program whose launch spans carry no compile counts
    [_span("launch:hotspot", 0.8, grid=[64, 256], devices=4)],
], ids=["none", "no-launch", "no-counts"])
def test_silent_without_counted_launch_spans(name, spans):
    assert bench.reader(name)(_run(spans)) is None


def test_a_launch_that_compiles_nothing_reads_zero():
    quiet = [_span("launch:kmeans", 7e-4, programs=0, cache_loads=0,
                   traces=0, compile_s=0.0)] * 3
    assert bench.reader("programs_per_launch")(_run(quiet)) == 0
    assert bench.reader("compile_ms")(_run(quiet)) == 0
