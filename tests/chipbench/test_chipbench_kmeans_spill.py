"""CPU rehearsal of ``kmeans.spill``: at a small size, with the device given
a budget under the points' bytes, the points stay on the host tier and
every Lloyd pass streams them in several superblocks; the run matches its
reference traced and untraced, reports ``h2d_gbps`` when traced, and comes
out not correct with a fault planted in the program."""

from __future__ import annotations

import functools
import sys
from pathlib import Path

import jax
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _small as small  # noqa: E402

import repro.core  # noqa: E402
import repro.core.launch as launch  # noqa: E402
import repro.kernels  # noqa: E402

#: Under the small cell's 2.2 MB of points: 32 superblocks of 512 points.
BUDGET = 1 << 21
CHUNK = 512


@pytest.fixture
def streamed(monkeypatch):
    """Contexts built with ``BUDGET``; per-chunk programs compiled afresh
    before and after, so that a planted fault is traced and left behind."""
    monkeypatch.setattr(repro.core, "Context", functools.partial(
        repro.core.Context, device_budget=BUDGET))
    step = launch._stream_step
    step.clear_cache()
    yield small.small_cell("kmeans.spill")
    step.clear_cache()


def test_spill_cell_streams_and_matches_its_reference(streamed):
    res = small.run_on_cpu(streamed, 2**31 + 11)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"iter_s", "setup_s"}


def test_traced_spill_run_reports_h2d_gbps(streamed):
    res = small.run_on_cpu(streamed, 17, trace=True)
    assert res["correct"], res["checks"]
    # Off the chip only the host-side readers find something.
    assert set(res["metrics"]) == {"plan_ms", "dispatch_ms", "step_mfu",
                                   "h2d_gbps"}
    assert res["metrics"]["h2d_gbps"]["value"] > 0


def test_points_are_streamed_in_several_superblocks(streamed):
    prog = streamed.config.Program(streamed.cfg, streamed.workload["traffic"],
                                   3, jax.devices()[:1])
    assert prog.args["points"].tier == "host"
    prog.step()
    (record,) = prog.ctx.records
    assert record.plan.num_superblocks == (
        streamed.workload["traffic"]["points"] // CHUNK)


def _skip_one(step):
    def fake(acc, offset, *a, **kw):
        return acc if int(offset[0]) == CHUNK else step(acc, offset, *a, **kw)
    return fake


def _count_one_twice(step):
    def fake(acc, offset, *a, **kw):
        if int(offset[0]) == CHUNK:
            acc = step(acc, offset, *a, **kw)
        return step(acc, offset, *a, **kw)
    return fake


def _kernel_fault(kind):
    orig = repro.kernels.kmeans_assign_reduce

    def fake(points, centroids, **kw):
        if kind == "half":  # half of every superblock's points left out
            return orig(points[:points.shape[0] // 2], centroids, **kw)
        _, counts = orig(points, centroids, **kw)  # centroids left in place
        return centroids * counts[:, None], counts

    return fake


@pytest.mark.parametrize("fault", ["skipped", "twice", "stale", "half"])
def test_spill_fault_comes_out_not_correct(streamed, monkeypatch, fault):
    if fault == "skipped":
        monkeypatch.setattr(launch, "_stream_step",
                            _skip_one(launch._stream_step))
    elif fault == "twice":
        monkeypatch.setattr(launch, "_stream_step",
                            _count_one_twice(launch._stream_step))
    else:
        monkeypatch.setattr(repro.kernels, "kmeans_assign_reduce",
                            _kernel_fault(fault))
    res = small.run_on_cpu(streamed, 17)
    assert not res["correct"], res["checks"]
