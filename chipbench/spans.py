"""The harness's host spans, on the wall clock (``time.time_ns``) that the
profiler lays its trace on.

The profiler's own host tracing is left off: on a TPU it records millions
of runtime events per gigabyte streamed, enough to exhaust the host's
memory in the out-of-core cell.  ``trace_reduce.load`` moves these spans
onto the trace's clock by the trace's ``profile_start_time``.
"""

from __future__ import annotations

import contextlib
import time

#: Closed spans as ``[name, start_ns, dur_ns]``, in order of closing.
RECORDED: list[list] = []


@contextlib.contextmanager
def span(name: str):
    start = time.time_ns()
    try:
        yield
    finally:
        RECORDED.append([name, start, time.time_ns() - start])
