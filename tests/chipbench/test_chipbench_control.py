"""The correctness check fails where it must: the control (the reference at
the next lower precision, in the program's place) and runs with the timed
path broken underneath come out not correct, at a size the CPU holds."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

import _small as small  # noqa: E402
import control  # noqa: E402  (chipbench/control.py, on the path via _small)

import repro.kernels  # noqa: E402


@pytest.mark.parametrize("name,points", [("kmeans.hbm", 1 << 20),
                                         ("hotspot.hbm", None)])
def test_control_comes_out_not_correct(name, points):
    cell = small.small_cell(name)
    if points:
        cell.workload["traffic"].update(points=points, block_rows=1 << 16)
    lines = []
    summary = control.collect(cell, [], [101, 102, 2**31 + 103],
                              jax.devices()[:1], out=lines.append)
    assert len(lines) == 4
    for line in lines[:3]:
        assert json.loads(line)["correct"] is False
    assert summary["upper"]


# -- faults planted in the program under the harness ------------------------


def _kmeans_fault(kind):
    orig = repro.kernels.kmeans_assign_reduce

    def fake(points, centroids, **kw):
        n, k = points.shape[0], centroids.shape[0]
        if kind == "unchanged":  # sums and counts that rebuild the input
            m = jnp.full((k,), n / k, jnp.float32)
            return centroids * m[:, None], m
        if kind == "half":  # half the points left out
            return orig(points[:n // 2], centroids, **kw)
        if kind == "stale":  # the counts right, the centroids left in place
            _, counts = orig(points, centroids, **kw)
            return centroids * counts[:, None], counts
        sums, counts = orig(points, centroids, **kw)  # one point twice
        return sums.at[0].add(points[0]), counts.at[0].add(1.0)

    return fake


def _hotspot_fault(kind):
    orig = repro.kernels.hotspot_step

    def fake(temp, power, **kw):
        if kind == "unchanged":
            return temp
        return orig(temp, power, **kw).at[3, 5].add(0.1)  # one cell altered

    return fake


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered", "stale"])
def test_kmeans_fault_comes_out_not_correct(monkeypatch, fault):
    monkeypatch.setattr(repro.kernels, "kmeans_assign_reduce",
                        _kmeans_fault(fault))
    res = small.run_on_cpu(small.small_cell("kmeans.hbm"), 17)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["unchanged", "altered"])
def test_hotspot_fault_comes_out_not_correct(monkeypatch, fault):
    monkeypatch.setattr(repro.kernels, "hotspot_step", _hotspot_fault(fault))
    res = small.run_on_cpu(small.small_cell("hotspot.hbm"), 17)
    assert not res["correct"], res["checks"]


def test_mesh_without_the_halo_exchange_comes_out_not_correct():
    code = """
import json
import jax.numpy as jnp
import repro.core.launch as launch

def no_exchange(x, halo, axes, mesh):
    zero = jnp.zeros_like(x[:1])
    return jnp.concatenate([zero, x, zero])

cell = small.small_cell('hotspot.mesh4')
print(json.dumps(small.run_on_cpu(cell, 23)))
launch._halo_exchange = no_exchange
print(json.dumps(small.run_on_cpu(cell, 23)))
"""
    sound, broken = small.run_with_four_devices(code)
    assert sound["correct"], sound["checks"]
    assert not broken["correct"], broken["checks"]


def test_faults_planted_in_the_reference_read_not_correct():
    cell = small.small_cell("kmeans.hbm")
    lines = []
    summary = control.collect(cell, [], [], jax.devices()[:1],
                              out=lines.append, fault_seeds=[31, 2**31 + 5])
    verdicts = {}
    for line in map(json.loads, lines[:-1]):
        verdicts.setdefault(line["side"], []).append(line["correct"])
    assert verdicts["fault:stale"] == [False, False]
    assert verdicts["fault:half"] == [False, False]
    # Centroids left where they were read 1 on the update's measure.
    assert summary["faults"]["stale"]["update_err"] == pytest.approx(1.0)
    assert summary["faults"]["stale"]["moved_share"] == 0.0
