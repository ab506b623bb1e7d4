"""The one reduction from a profiler trace to the benchmark's numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
what the metrics need, as plain lists:

* ``window``: start and end (ns) of the host span ``window`` that the
  harness opens around the timed iterations;
* ``host``: the harness's own host spans (``HOST_SPANS``, recorded by
  spans.py) as ``[name, start_ns, dur_ns]``;
* ``devices``: for each TPU in order, its ``XLA Ops`` events as
  ``[hlo_text, start_ns, dur_ns]``.

Device events and the host spans then share one clock.  On a TPU the name of
an ``XLA Ops`` event is the HLO instruction's text; it carries no name stack,
so ``op_kind`` tells a Pallas kernel from an XLA op by its custom-call
target, and a collective by its opcode.

``Reduced`` then gives, inside the window: each device's busy time (the
union of its op intervals, so that overlapping ops count once), device time
per stable op name and per kind, and the idle gaps labelled by the
innermost host span open at the time.
"""

from __future__ import annotations

import collections
import dataclasses
import re

HOST_SPANS = ("window", "launch", "update", "sync")
IDLE_NO_SPAN = "no_span"

_COLLECTIVES = ("all-reduce", "all-gather", "collective-permute",
                "reduce-scatter", "all-to-all", "send", "recv")
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
# "%name.12 = <shape> opcode(operands), attrs" -- the shape may be a tuple.
_HLO = re.compile(r"^%?([\w\-]+?)(?:\.\d+)?\s*=\s*.*?\s([a-z][\w\-]*)\(")


def load(path: str, spans) -> dict:
    """The raw events of one ``.xplane.pb``, as plain lists, with the
    harness's host ``spans`` (``[name, start_ns, dur_ns]`` on the wall
    clock, see spans.py) moved onto the trace's clock."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    start = None
    devices: dict[int, list[list]] = {}
    for plane in data.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                devices.setdefault(int(m.group(1)), []).extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events)
    if start is None:
        raise ValueError("the trace has no profile_start_time")
    host = [[n, s - start, d] for n, s, d in spans if n in HOST_SPANS]
    windows = [h for h in host if h[0] == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span, found {len(windows)}")
    _, start, dur = windows[0]
    return {"window": [start, start + dur],
            "host": sorted((h for h in host if h[0] != "window"),
                           key=lambda h: h[1]),
            "devices": [devices[i] for i in sorted(devices)]}


def op_name(text: str) -> str:
    """A stable name for an HLO instruction: its name without the numeric
    suffix, and its opcode (``kmeans_pallas:custom-call``)."""
    m = _HLO.match(text)
    return f"{m.group(1)}:{m.group(2)}" if m else text.split(" ", 1)[0]


def op_kind(text: str) -> str:
    """``kernel`` for a Pallas (Mosaic) kernel, ``collective`` for an
    exchange between chips, ``xla`` for any other op."""
    if 'custom_call_target="tpu_custom_call"' in text:
        return "kernel"
    m = _HLO.match(text)
    opcode = m.group(2) if m else ""
    if opcode.startswith(_COLLECTIVES):
        return "collective"
    return "xla"


def union(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, end = 0.0, float("-inf")
    for s, e in sorted(intervals):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def host_segments(spans, lo: float, hi: float) -> list[tuple[float, float, str]]:
    """``[lo, hi]`` cut into pieces, each labelled with the innermost
    (latest-started) host span open over it, or ``IDLE_NO_SPAN``."""
    edges = []
    for i, (_, s, d) in enumerate(spans):
        edges.append((s, 1, i))
        edges.append((s + d, 0, i))  # at a tie, a span ends before the next
    edges.sort()
    out, open_, at = [], [], lo
    for t, is_start, i in edges:
        t = min(max(t, lo), hi)
        if t > at:
            out.append((at, t, spans[open_[-1]][0] if open_ else IDLE_NO_SPAN))
            at = t
        if is_start:
            open_.append(i)
        elif i in open_:
            open_.remove(i)
    if at < hi:
        out.append((at, hi, spans[open_[-1]][0] if open_ else IDLE_NO_SPAN))
    return out


def label_gaps(gap_list, segments) -> dict[str, float]:
    """Idle time per host label: the overlap of sorted, disjoint gaps with
    sorted, disjoint labelled segments, in one merge."""
    out: dict[str, float] = collections.defaultdict(float)
    j = 0
    for g_lo, g_hi in gap_list:
        while j < len(segments) and segments[j][1] <= g_lo:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < g_hi:
            a, b, label = segments[k]
            out[label] += min(b, g_hi) - max(a, g_lo)
            k += 1
    return out


@dataclasses.dataclass
class Reduced:
    """A traced window reduced to seconds."""

    window_s: float
    busy_s: list[float]  # per device
    op_s: dict[str, float]  # stable op name -> seconds, all devices
    kind_s: list[dict[str, float]]  # per device: kind -> seconds
    idle_s: dict[str, float]  # host span -> idle seconds, mean over devices

    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0

    def kind_seconds(self, kind: str, device: int | None = None) -> float:
        """Seconds of ``kind`` ops on ``device``, or the mean over all."""
        per = [k.get(kind, 0.0) for k in self.kind_s]
        if device is not None:
            return per[device] if device < len(per) else 0.0
        return sum(per) / len(per) if per else 0.0

    def top_ops(self, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(self.op_s.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def top_idle(self, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(self.idle_s.items(),
                                          key=lambda kv: -kv[1])[:n]]


def reduce(raw: dict) -> Reduced:
    lo, hi = raw["window"]
    segments = host_segments(raw["host"], lo, hi)
    busy, kinds = [], []
    ops: dict[str, float] = collections.defaultdict(float)
    idle: dict[str, float] = collections.defaultdict(float)
    for events in raw["devices"]:
        spans, kind_s = [], collections.defaultdict(float)
        for text, start, dur in events:
            s, e = max(start, lo), min(start + dur, hi)
            if e <= s:
                continue
            spans.append((s, e))
            ops[op_name(text)] += (e - s) * 1e-9
            kind_s[op_kind(text)] += (e - s) * 1e-9
        busy.append(union(spans) * 1e-9)
        kinds.append(dict(kind_s))
        for label, ns in label_gaps(gaps(spans, lo, hi), segments).items():
            idle[label] += ns * 1e-9 / len(raw["devices"])
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy, op_s=dict(ops),
                   kind_s=kinds, idle_s=dict(idle))
