#!/usr/bin/env python3
"""Chip benchmark of Lightning: one run of one cell.

    python3 chipbench/run.py --workload kmeans.hbm --seed 7 --seconds 30 --trace 0

Everything is found by name, so a cell, a configuration or a metric is added
by adding files:

* ``chipbench/workloads/<cell>.json``: the configuration it runs, the
  traffic (the sizes it runs at), the chips it needs, why it exists, and the
  limit of each number its correctness check compares;
* ``chipbench/configs/<config>.json`` and ``<config>.py``: the sizes, and the
  data made from the seed, the iteration through the program's own entry
  point, a plain reference, the control, and the work one iteration needs;
* ``chipbench/metrics/<metric>.py``: one reader per per-layer metric named in
  ``BENCHMARK.json``, or per quantity (``dispatch_ms`` also reads
  ``dispatch_ms.mesh``); a reader that finds nothing to read returns None.

Which metrics a cell reports, ``BENCHMARK.json`` says (``cell_metrics``).

A run builds the cell from ``--seed``, runs the first iterations whose
results are checked (they also compile every program the window uses),
then dispatches iterations for ``--seconds``, at most ``IN_FLIGHT`` ahead of
the device, and waits for the last.  ``iter_s`` is the window over the
iterations (``iter_s.mesh`` in cells across chips); ``setup_s`` runs from
the start of the process to the first timed iteration.  With ``--trace 1`` the window runs under the profiler and
the per-layer metrics are reported instead.  After the window the program's
state is freed and the checked iterations are compared with the reference.
The last line of standard output is one JSON object; the compared numbers
and their limits are the last lines of standard error.  With no TPU, or
fewer chips than the cell needs, the run exits 2 and prints no result.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

from spans import RECORDED, span  # noqa: E402
#: JAX's persistent compilation cache: a fixed path inside the checkout.
CACHE_DIR = ROOT / ".jax_cache"
#: Iterations dispatched ahead of the device: enough that the host's
#: dispatch overlaps the device's work, few enough that the window ends
#: with the device, not with a queue of work still to run.
IN_FLIGHT = 2


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell needs."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    cfg: dict
    config: object  # the configuration's module


def load_cell(name: str) -> Cell:
    workload = load_json(HERE / "workloads" / f"{name}.json")
    cfg_name = workload["config"]
    return Cell(name, workload, load_json(HERE / "configs" / f"{cfg_name}.json"),
                load_module(HERE / "configs" / f"{cfg_name}.py"))


def quantity(name: str) -> str:
    """What a metric measures: its name before the first dot.  A quantity
    split by the end-to-end metric it moves (``dispatch_ms.mesh`` beside
    ``dispatch_ms``) is computed and read alike."""
    return name.split(".", 1)[0]


def cell_metrics(cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics of BENCHMARK.json that ``cell``
    reports.  A metric with a ``workloads`` key is reported in the cells it
    names; an end-to-end metric without one in every cell, and a per-layer
    metric without one in every cell that reports the metric it moves."""
    bench = load_json(ROOT / "BENCHMARK.json")

    def named(m, default):
        return cell in m["workloads"] if "workloads" in m else default(m)

    e2e = [m for m in bench["end_to_end"] if named(m, lambda m: True)]
    moved = {m["name"] for m in e2e}
    return e2e, [m for m in bench["per_layer"]
                 if named(m, lambda m: m["moves"] in moved)]


def reader(name: str):
    """The reader of a per-layer metric: ``metrics/<name>.py``, or the one
    of its quantity."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        path = HERE / "metrics" / f"{quantity(name)}.py"
    return load_module(path).read


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = load_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def setup_jax() -> None:
    """Point JAX's persistent cache into the checkout and let it keep every
    program, however quick to compile, so that only a cell's first run in a
    checkout compiles."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_devices(chips: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devices[0].platform!r}, "
                     "not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


# JAX's compile and trace events, counted so the window can show it has none.
_EVENTS = collections.Counter()
_LISTENING = []


def _count(event: str, *_, **__) -> None:
    _EVENTS[event.rsplit("/", 1)[-1]] += 1


def _listen() -> None:
    if not _LISTENING:
        import jax

        jax.monitoring.register_event_duration_secs_listener(_count)
        _LISTENING.append(True)


@dataclasses.dataclass
class Run:
    """What a per-layer metric's reader may read."""

    kernel: str  # the configuration's kernel, as its metrics name it
    chips: int
    iters: int
    window_s: float
    work: dict  # per chip and iteration: {"kernel"|"step": {flops, bytes}}
    peak: dict  # flops_per_s, hbm_bytes_per_s
    spans: list  # the Context's own spans inside the window
    trace: object  # trace_reduce.Reduced, or None

    @property
    def iter_s(self) -> float:
        return self.window_s / self.iters

    def roof_s(self, part: str) -> float:
        """Least time one chip needs for ``part`` of an iteration: its
        FLOPs at peak or its bytes at HBM bandwidth, whichever is more."""
        w = self.work[part]
        return max(w["flops"] / self.peak["flops_per_s"],
                   w["bytes"] / self.peak["hbm_bytes_per_s"])

    def kernel_roofline(self, kernel: str) -> float | None:
        """Percent of its roofline that ``kernel`` reaches, where it is this
        cell's kernel and ran inside the traced window."""
        if self.kernel != kernel or self.trace is None:
            return None
        seconds = self.trace.kind_seconds("kernel")
        if seconds <= 0:
            return None
        return 100.0 * self.roof_s("kernel") * self.iters / seconds


def _window(prog, seconds: float) -> tuple[int, float, float]:
    """Dispatch iterations for ``seconds``, at most ``IN_FLIGHT`` ahead of
    the device, then wait for the last.  Returns (iterations, seconds,
    start on the perf_counter clock)."""
    import jax

    pending: collections.deque = collections.deque()
    t0 = time.perf_counter()
    iters = 0
    while True:
        pending.append(prog.step())
        iters += 1
        if len(pending) > IN_FLIGHT:
            with span("sync"):
                jax.block_until_ready(pending.popleft())
        if time.perf_counter() - t0 >= seconds:
            break
    with span("sync"):
        jax.block_until_ready(list(pending))
    return iters, time.perf_counter() - t0, t0


def _traced_window(prog, seconds):
    """The window under the profiler; returns its numbers and the raw trace."""
    import jax

    from trace_reduce import load

    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0  # host spans come from spans.py
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            RECORDED.clear()
            with span("window"):
                out = _window(prog, seconds)
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        return out, load(paths[0], RECORDED)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run(cell: Cell, seed: int, seconds: float, trace: bool) -> dict:
    """One run of ``cell``; returns the result line's object."""
    import jax

    import trace_reduce

    setup_jax()
    _listen()
    e2e, per_layer = cell_metrics(cell.name)
    chips = int(cell.workload["chips"])
    devices = find_devices(chips)
    dev0 = devices[0]
    traffic = cell.workload["traffic"]
    tracer = None
    if trace:
        from repro.obs.trace import Tracer

        tracer = Tracer(clock=time.perf_counter)
    precision = cell.cfg.get("matmul_precision", "default")
    with jax.default_matmul_precision(precision):
        prog = cell.config.Program(cell.cfg, traffic, seed, devices, tracer)
        got = prog.check(int(cell.cfg["check_steps"]))
        setup_s = time.perf_counter() - _T0
        before = dict(_EVENTS)
        if trace:
            (iters, window_s, t0), raw = _traced_window(prog, seconds)
        else:
            iters, window_s, t0 = _window(prog, seconds)
            raw = None
    in_window = {k: v - before.get(k, 0) for k, v in _EVENTS.items()
                 if v != before.get(k, 0)}
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices)
    print(f"window: iterations={iters} seconds={window_s} "
          f"compiles={in_window.get('backend_compile_duration', 0)} "
          f"traces={in_window.get('jaxpr_trace_duration', 0)} "
          f"events={json.dumps(in_window, sort_keys=True)}", file=sys.stderr)
    print(f"peak_bytes_in_use={peak_bytes}", file=sys.stderr)
    spans = [] if tracer is None else [
        e for e in tracer.events
        if e["ph"] == "X" and t0 <= e["ts"] <= t0 + window_s]
    prog.free()
    del prog
    gc.collect()

    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    if trace:
        reduced = trace_reduce.reduce(raw)
        device["busy_s"] = reduced.mean_busy_s()
        device["window_s"] = reduced.window_s
        info = Run(kernel=cell.config.KERNEL, chips=chips, iters=iters,
                   window_s=window_s,
                   work=cell.config.work(cell.cfg, traffic, chips),
                   peak=peaks_for(dev0.device_kind), spans=spans,
                   trace=reduced)
        metrics = {}
        for m in per_layer:
            value = reader(m["name"])(info)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        value = {"iter_s": window_s / iters, "setup_s": setup_s}
        metrics = {m["name"]: {"value": value[quantity(m["name"])],
                               "unit": m["unit"]} for m in e2e}

    readings = cell.config.readings(cell.cfg, traffic, seed, devices, got)
    print(f"readings: {json.dumps(readings, sort_keys=True)}", file=sys.stderr)
    checks, within = verdict(readings, cell.workload["limits"])
    correct = iters > 0 and within
    result = {"correct": correct, "attempted": iters, "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced.top_ops(10),
                               "idle_gaps": reduced.top_idle(10)}
    result["checks"] = checks
    return result


def verdict(readings: dict, limits: dict) -> tuple[dict, bool]:
    """Each number compared beside its limit, and whether every one lies
    within it.  A missing or non-finite reading fails, and shows as null
    (JSON has no infinity)."""
    checks = {k: {"value": readings[k]
                  if math.isfinite(readings.get(k, math.nan)) else None,
                  "limit": lim} for k, lim in limits.items()}
    return checks, all(c["value"] is not None and c["value"] <= c["limit"]
                       for c in checks.values())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    cell = load_cell(args.workload)
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
