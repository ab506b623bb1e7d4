"""JAX's compile pipeline, seen from inside the program.

JAX reports each stage of turning a function into a device program through
``jax.monitoring``, with its start and end: tracing to a jaxpr, lowering to
an MLIR module, and the backend compile, which also covers a load from the
persistent compilation cache.  A :class:`CompileWatch` collects the events
that fire on the calling thread while it is open.  ``Context.launch`` opens
one around each launch, so what a launch compiled is counted where the work
happens, with the tracer on or off.

One process-wide listener, registered when the first watch opens, routes
every event to the watch open on the thread that fired it and ignores events
outside one.  With a tracer on, each event also becomes a ``jax:<stage>``
span on the ``driver`` stream, a child of the innermost span open on that
thread.  JAX is imported only then, so ``repro.obs`` stays importable
without it.
"""

from __future__ import annotations

import threading

from .trace import profiler_clock

#: ``jax.monitoring`` time-span events, by the stage each one names.
STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
#: Fired once per program found in the persistent compilation cache.
CACHE_HIT = "/jax/compilation_cache/cache_hits"

_local = threading.local()  # .watch: the watch open on this thread
_install_lock = threading.Lock()
_installed = False


def _install() -> None:
    global _installed
    with _install_lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_time_span_listener(_on_time_span)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    watch = getattr(_local, "watch", None)
    stage = STAGES.get(event)
    if watch is not None and stage is not None:
        watch._record(stage, start, end, kw.get("fun_name", ""))


def _on_event(event: str, **kw) -> None:
    watch = getattr(_local, "watch", None)
    if watch is not None and event == CACHE_HIT:
        watch.cache_loads += 1


class CompileWatch:
    """What JAX traced, lowered and compiled on this thread while open.

    ``programs`` counts backend compiles (cache loads included),
    ``cache_loads`` the programs found in the persistent cache, ``traces``
    the functions traced to a jaxpr, and ``compile_s`` the seconds covered
    by all three stages, nested events counted once.  With ``tracer``
    enabled each event is recorded as a ``jax:<stage>`` span, with ``fun``
    and, when given, ``launch`` in its args.
    """

    __slots__ = ("tracer", "launch", "intervals", "programs", "cache_loads",
                 "traces", "_outer")

    def __init__(self, tracer, launch: int | None = None):
        self.tracer = tracer
        self.launch = launch
        self.intervals: list[tuple[float, float]] = []
        self.programs = 0
        self.cache_loads = 0
        self.traces = 0
        self._outer = None

    def __enter__(self) -> "CompileWatch":
        if not _installed:
            _install()
        self._outer = getattr(_local, "watch", None)
        _local.watch = self
        return self

    def __exit__(self, *exc) -> bool:
        _local.watch = self._outer
        return False

    @property
    def compile_s(self) -> float:
        """Seconds covered by the events' intervals (their union)."""
        total, end = 0.0, float("-inf")
        for s, e in sorted(self.intervals):
            if e > end:
                total += e - max(s, end)
                end = e
        return total

    def _record(self, stage: str, start: float, end: float, fun: str) -> None:
        self.intervals.append((start, end))
        if stage == "compile":
            self.programs += 1
        elif stage == "trace":
            self.traces += 1
        tracer = self.tracer
        if not tracer.enabled:
            return
        # JAX stamps events on the profiler's wall clock; on any other
        # clock the span ends now, when the event's callback fires.
        dur = end - start
        ts = start if tracer.clock is profiler_clock else tracer.now() - dur
        args = {"fun": fun}
        if self.launch is not None:
            args["launch"] = self.launch
        tracer.complete(f"jax:{stage}", ts, dur, stream="driver",
                        cat="compile", args=args, span_id=tracer.new_id(),
                        parent=tracer.current())


__all__ = ["CACHE_HIT", "STAGES", "CompileWatch"]
