"""Observability layer (repro.obs): tracer, metrics registry, overlap
analyzer, and their integration with the scheduler / launch / serve /
train layers.

The load-bearing properties:

* trace export is **byte-identical** across two identical runs (logical
  clock, sorted keys, stable ordering) — traces are diffable artifacts;
* the disabled path allocates nothing (one shared null-span singleton);
* ``SimResult.stats`` is now a registry snapshot diff but keeps its
  historical dict shape;
* the overlap analyzer reproduces an exactly-computable synthetic case
  and produces sane per-device reports for real multi-worker plans.
"""

import json
import time

import numpy as np
import pytest

from repro.core import (
    ArrayMeta,
    BlockDist,
    FaultInjector,
    HardwareModel,
    MemoryManager,
    Planner,
    EvenWork,
    Simulator,
    Tier,
    Topology,
    fail_task,
    parse,
)
from repro.core.memory import MEM_STAT_KEYS
from repro.obs import (
    NULL_TRACER,
    MetricsRegistry,
    Tracer,
    analyze,
    default_registry,
    use_registry,
    validate_chrome_trace,
)
from repro.obs.trace import _NULL_SPAN


def small_hw(**kw):
    defaults = dict(
        device_capacity=1e6, host_capacity=1e9, disk_capacity=1e12,
        host_link_bw=1e9, disk_bw=1e8, task_overhead=1e-6,
        alloc_cost=1e-6, staging_throttle=1e6,
    )
    defaults.update(kw)
    return HardwareModel(**defaults)


def stencil_plan(n=2048, chunk=256, devices=4):
    ann = parse("global i => read inp[i-1:i+1], write out[i]")
    planner = Planner(Topology(devices, devices_per_node=2))
    arrays = {
        "inp": ArrayMeta("inp", (n,), 4, BlockDist(chunk)),
        "out": ArrayMeta("out", (n,), 4, BlockDist(chunk)),
    }
    return planner.plan_launch("stencil", ann, (n,), EvenWork(), arrays)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_monotonic(self):
        reg = MetricsRegistry()
        c = reg.counter("x")
        c.inc()
        c.inc(2.5)
        assert c.value() == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_labeled_children_aggregate_into_parent(self):
        reg = MetricsRegistry()
        c = reg.counter("tasks")
        c.labels(worker=0).inc(3)
        c.labels(worker=1).inc(4)
        assert c.labels(worker=0) is c.labels(worker=0)  # get-or-create
        assert c.labels(worker=0).value() == 3
        assert c.value() == 7  # parent = own + sum(children)

    def test_gauge(self):
        g = MetricsRegistry().gauge("depth")
        g.set(5)
        g.inc()
        g.dec(2)
        assert g.value() == 4

    def test_histogram_stats(self):
        h = MetricsRegistry().histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        assert h.count() == 4
        assert h.sum() == pytest.approx(6.05)
        assert h.mean() == pytest.approx(6.05 / 4)
        assert h.quantile(0.5) == 1.0  # bucket upper bound
        assert h.quantile(1.0) == 10.0

    def test_registry_get_or_create_and_kind_mismatch(self):
        reg = MetricsRegistry()
        assert reg.counter("a") is reg.counter("a")
        with pytest.raises(TypeError):
            reg.gauge("a")

    def test_snapshot_diff(self):
        reg = MetricsRegistry()
        reg.counter("c").labels(k="x").inc(2)
        before = reg.snapshot()
        reg.counter("c").labels(k="x").inc(3)
        reg.counter("c").labels(k="y").inc(1)
        delta = MetricsRegistry.diff(reg.snapshot(), before)
        assert delta["c"] == 4
        assert delta["c{k=x}"] == 3
        assert delta["c{k=y}"] == 1

    def test_merge_across_registries(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("n").labels(w=0).inc(1)
        b.counter("n").labels(w=0).inc(2)
        b.counter("n").labels(w=1).inc(5)
        b.histogram("h").observe(0.2)
        a.merge(b)
        snap = a.snapshot()
        assert snap["n"] == 8
        assert snap["n{w=0}"] == 3
        assert snap["n{w=1}"] == 5
        assert snap["h.count"] == 1

    def test_use_registry_swaps_default(self):
        outer = default_registry()
        with use_registry() as reg:
            assert default_registry() is reg
            default_registry().counter("tmp").inc()
            assert reg.counter("tmp").value() == 1
        assert default_registry() is outer


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


class TestTracer:
    def test_null_tracer_is_zero_cost(self):
        assert not NULL_TRACER.enabled
        # every span() answers the same shared singleton — no allocation
        assert NULL_TRACER.span("a") is NULL_TRACER.span("b")
        assert NULL_TRACER.span("a") is _NULL_SPAN
        with NULL_TRACER.span("a") as sp:
            sp.add(k=1)  # no-op sink

    def test_span_nesting_and_error_annotation(self):
        tr = Tracer()
        with tr.span("outer", stream="s"):
            with tr.span("inner", stream="s"):
                pass
        with pytest.raises(RuntimeError):
            with tr.span("bad", stream="s"):
                raise RuntimeError("boom")
        names = {e["name"]: e for e in tr.events}
        assert set(names) == {"outer", "inner", "bad"}
        # inner closed before outer; error spans carry the exception type
        assert names["inner"]["ts"] > names["outer"]["ts"]
        assert names["bad"]["args"]["error"] == "RuntimeError"

    def test_export_is_valid_chrome_trace(self):
        tr = Tracer()
        tr.complete("k", 0.0, 1e-3, worker=1, stream="compute",
                    cat="compute")
        tr.instant("f", ts=5e-4, worker=1, stream="sched", cat="fault")
        obj = tr.to_chrome()
        assert validate_chrome_trace(obj) == []
        # metadata names the process/threads for Perfetto's track labels
        metas = [e for e in obj["traceEvents"] if e["ph"] == "M"]
        assert {m["name"] for m in metas} == {"process_name", "thread_name"}

    def test_validator_flags_broken_traces(self):
        assert validate_chrome_trace({}) != []
        assert validate_chrome_trace({"traceEvents": []}) != []
        bad_key = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0}]}
        assert any("missing required key" in e
                   for e in validate_chrome_trace(bad_key))
        decreasing = {"traceEvents": [
            {"name": "a", "ph": "i", "ts": 5.0, "pid": 0, "tid": 0},
            {"name": "b", "ph": "i", "ts": 1.0, "pid": 0, "tid": 0},
        ]}
        assert any("non-decreasing" in e
                   for e in validate_chrome_trace(decreasing))

    def test_traced_sim_export_is_byte_identical(self):
        """Two identical seeded runs → byte-identical trace JSON (the
        acceptance bar: no wall-clock reads anywhere in the pipeline)."""

        def one_run() -> str:
            lp = stencil_plan()
            tr = Tracer()
            sim = Simulator(small_hw(), 4, tracer=tr)
            sim.run(lp.plan)
            return tr.to_json()

        j1, j2 = one_run(), one_run()
        assert j1 == j2
        assert validate_chrome_trace(json.loads(j1)) == []

    def test_spans_record_ids_and_their_parents(self):
        tr = Tracer()
        with tr.span("root"):
            with tr.span("child"):
                with tr.span("grandchild"):
                    pass
            with tr.span("sibling"):
                assert tr.current() is not None
        assert tr.current() is None
        tr.complete("sim", 0.0, 1e-3)
        tr.instant("mark", ts=0.0)
        ev = {e["name"]: e for e in tr.events}
        assert ev["root"]["parent"] is None
        assert ev["child"]["parent"] == ev["root"]["id"]
        assert ev["sibling"]["parent"] == ev["root"]["id"]
        assert ev["grandchild"]["parent"] == ev["child"]["id"]
        ids = [ev[n]["id"] for n in ("root", "child", "grandchild",
                                     "sibling")]
        assert len(set(ids)) == 4
        # complete() and instant() keep their old shape: no tree fields
        assert "id" not in ev["sim"] and "id" not in ev["mark"]
        chrome = {e["name"]: e for e in tr.to_chrome()["traceEvents"]}
        assert chrome["child"]["args"] == {"id": ev["child"]["id"],
                                           "parent": ev["root"]["id"]}
        assert "args" not in chrome["sim"]

    def test_span_parents_are_per_thread(self):
        """Threads opening spans at once on one tracer: every span's parent
        is the span its own thread had open, and no id repeats."""
        import sys
        import threading

        tr = Tracer()
        threads, rounds = 16, 200

        def work(t):
            for r in range(rounds):
                with tr.span(f"outer:{t}"):
                    with tr.span(f"inner:{t}"):
                        pass

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            with tr.span("main"):
                pool = [threading.Thread(target=work, args=(t,))
                        for t in range(threads)]
                for th in pool:
                    th.start()
                for th in pool:
                    th.join(timeout=60)
                assert not any(th.is_alive() for th in pool)
        finally:
            sys.setswitchinterval(interval)
        by_id = {e["id"]: e for e in tr.events}
        assert len(by_id) == len(tr.events) == 2 * threads * rounds + 1
        for e in tr.events:
            kind, _, t = e["name"].partition(":")
            if kind == "inner":
                assert by_id[e["parent"]]["name"] == f"outer:{t}"
            else:  # the main thread's span is no other thread's parent
                assert e["parent"] is None

    def test_profiler_clock_spans_land_in_the_device_trace(self, tmp_path):
        """A span on profiler_clock around a jitted call, moved by the
        trace's profile_start_time, lies inside the trace's window and
        around the call's XLA op events."""
        import jax
        import jax.numpy as jnp

        from repro.obs import profiler_clock

        f = jax.jit(lambda x: jnp.sin(x) @ x)
        x = jnp.ones((256, 256))
        f(x).block_until_ready()
        tr = Tracer(clock=profiler_clock)
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("call"):
                f(x).block_until_ready()
        (path,) = tmp_path.glob("**/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(str(path))
        stats = {p.name: dict(p.stats) for p in data.planes}
        start_ns = stats["Task Environment"]["profile_start_time"]
        stop_ns = stats["Task Environment"]["profile_stop_time"]
        (span,) = tr.events
        lo = span["ts"] * 1e9 - start_ns
        hi = lo + span["dur"] * 1e9
        assert 0 <= lo < hi <= stop_ns - start_ns
        ops = [(e.start_ns, e.start_ns + e.duration_ns)
               for p in data.planes for line in p.lines
               for e in line.events
               if e.name.startswith(("dot_general", "wrapped_sine"))]
        assert ops
        slack = 1e3  # ns: a float64 of epoch seconds keeps ~0.24 µs
        assert all(lo - slack <= s and e <= hi + slack for s, e in ops)

    def test_text_timeline_renders(self):
        lp = stencil_plan()
        tr = Tracer()
        Simulator(small_hw(), 4, tracer=tr).run(lp.plan)
        txt = tr.text_timeline()
        assert "lanes" in txt.splitlines()[0]
        assert any("compute" in line for line in txt.splitlines())


class TestCompileWatch:
    """The routing of JAX's compile events, fed synthetic events through
    the same callbacks ``jax.monitoring`` calls."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def test_counts_stages_and_unions_nested_intervals(self):
        from repro.obs import compiles

        with compiles.CompileWatch(NULL_TRACER) as w:
            compiles._on_time_span(self.TRACE, 10.0, 11.0, fun_name="f")
            compiles._on_time_span(self.TRACE, 10.2, 10.4, fun_name="g")
            compiles._on_time_span(self.LOWER, 11.0, 11.5, fun_name="f")
            compiles._on_time_span(self.COMPILE, 12.0, 12.25, fun_name="f")
            compiles._on_event(compiles.CACHE_HIT)
            compiles._on_time_span("/jax/other", 0.0, 100.0)
            compiles._on_event("/jax/other")
        assert (w.programs, w.traces, w.cache_loads) == (1, 2, 1)
        # nested (10.2–10.4 inside 10–11) and touching intervals count once
        assert w.compile_s == pytest.approx(1.75)

    def test_events_outside_a_watch_or_on_another_thread_are_ignored(self):
        import threading

        from repro.obs import compiles

        compiles._on_time_span(self.COMPILE, 0.0, 1.0, fun_name="f")
        with compiles.CompileWatch(NULL_TRACER) as w:
            t = threading.Thread(target=compiles._on_time_span,
                                 args=(self.COMPILE, 0.0, 1.0))
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        compiles._on_time_span(self.COMPILE, 0.0, 1.0, fun_name="f")
        assert w.programs == 0 and w.compile_s == 0.0

    def test_child_spans_on_the_profiler_clock_keep_jaxs_times(self):
        from repro.obs import compiles, profiler_clock

        tr = Tracer(clock=profiler_clock)
        now = profiler_clock()
        with tr.span("execute:k") as parent:
            with compiles.CompileWatch(tr, launch=7):
                compiles._on_time_span(self.LOWER, now - 0.5, now - 0.2,
                                       fun_name="f")
        (child,) = [e for e in tr.events if e["name"] == "jax:lower"]
        assert child["ts"] == now - 0.5
        assert child["dur"] == pytest.approx(0.3)
        assert child["parent"] == parent.id
        assert child["args"] == {"fun": "f", "launch": 7}

    def test_child_spans_on_another_clock_end_when_reported(self):
        from repro.obs import compiles

        tr = Tracer()  # logical clock: 1 µs per read
        with compiles.CompileWatch(tr):
            compiles._on_time_span(self.COMPILE, 100.0, 100.002,
                                   fun_name="f")
        (child,) = tr.events
        assert child["dur"] == pytest.approx(0.002)
        assert child["ts"] + child["dur"] == pytest.approx(tr.now() - 1e-6)
        assert child["parent"] is None and child["args"] == {"fun": "f"}


# ---------------------------------------------------------------------------
# Overlap analyzer
# ---------------------------------------------------------------------------


class TestOverlap:
    def test_exact_synthetic_case(self):
        tr = Tracer()
        tr.complete("k", 0.0, 10.0, worker=0, stream="compute",
                    cat="compute")
        tr.complete("x", 5.0, 10.0, worker=0, stream="h2d", cat="transfer")
        rep = analyze(tr)
        assert rep.wall == pytest.approx(15.0)
        d = rep.device(0)
        assert d.overlap == pytest.approx(5.0)
        assert d.overlap_fraction == pytest.approx(5.0 / 15.0)
        assert d.exposed_transfer == pytest.approx(5.0)

    def test_analyzes_exported_chrome_trace_too(self):
        tr = Tracer()
        tr.complete("k", 0.0, 10.0, worker=0, stream="compute",
                    cat="compute")
        tr.complete("x", 5.0, 10.0, worker=0, stream="h2d", cat="transfer")
        rep = analyze(json.loads(tr.to_json()))
        assert rep.device(0).overlap == pytest.approx(5.0)

    def test_multi_worker_plan_report(self):
        lp = stencil_plan()
        tr = Tracer()
        Simulator(small_hw(), 4, tracer=tr).run(lp.plan)
        rep = analyze(tr)
        assert len(rep.devices) == 4
        for d in rep.devices:
            assert 0.0 <= d.overlap_fraction <= 1.0
            assert d.busy["compute"] > 0.0
            assert d.busy["transfer"] > 0.0
        assert "overlap report" in rep.summary()


# ---------------------------------------------------------------------------
# Runtime integration
# ---------------------------------------------------------------------------


class TestRuntimeIntegration:
    def test_sim_stats_ride_the_registry(self):
        lp = stencil_plan()
        reg = MetricsRegistry()
        res = Simulator(small_hw(), 4, registry=reg).run(lp.plan)
        # compat view: same keys/shape as the old hand-summed dicts
        for k in ("stage_wait",) + tuple(MEM_STAT_KEYS):
            assert k in res.stats, k
        assert res.stats["h2d_bytes"] > 0
        snap = reg.snapshot()
        assert snap["mem.h2d_bytes"] == res.stats["h2d_bytes"]
        assert snap["sim.tasks_total"] == len(lp.plan.tasks)
        # per-worker children present under the parent totals
        per_worker = [v for k, v in snap.items()
                      if k.startswith("mem.h2d_bytes{")]
        assert sum(per_worker) == snap["mem.h2d_bytes"]

    def test_sim_stats_are_per_run_deltas(self):
        """A shared registry accumulates, but each SimResult.stats only
        reports its own run (snapshot diff)."""
        reg = MetricsRegistry()
        r1 = Simulator(small_hw(), 4, registry=reg).run(stencil_plan().plan)
        r2 = Simulator(small_hw(), 4, registry=reg).run(stencil_plan().plan)
        assert r1.stats["h2d_bytes"] == r2.stats["h2d_bytes"]
        assert reg.snapshot()["mem.h2d_bytes"] == pytest.approx(
            r1.stats["h2d_bytes"] + r2.stats["h2d_bytes"])

    def test_memory_manager_occupancy_gauges(self):
        reg = MetricsRegistry()
        mm = MemoryManager(small_hw(), worker=0, registry=reg)
        mm.register(("a", 0), 1000, Tier.HOST)
        mm.stage([("a", 0)])
        snap = reg.snapshot()
        assert snap["mem.tier_bytes{tier=DEVICE,worker=0}"] == 1000
        assert snap["mem.tier_bytes{tier=HOST,worker=0}"] == 0
        assert mm.stats["h2d_bytes"] == 1000

    def test_failed_tasks_counted_and_marked_in_trace(self):
        lp = stencil_plan()
        reg = MetricsRegistry()
        tr = Tracer()
        inj = FaultInjector([fail_task(at=0)], registry=reg)
        res = Simulator(small_hw(), 4, fault_injector=inj, registry=reg,
                        tracer=tr).run(lp.plan)
        assert res.stats["task_retries"] == 1
        assert res.stats["faults_injected"] >= 1
        assert reg.snapshot()["faults.injected{kind=task}"] == 1
        assert any(e["name"] == "fault:task_retries" for e in tr.events)
        assert any(e["name"].startswith("replay:") or
                   e["args"].get("attempt", 0) > 0
                   for e in tr.events if e["ph"] == "X")

    def test_launch_context_spans_and_counters(self):
        import jax.numpy as jnp

        from repro.core import Context, KernelDef

        reg = MetricsRegistry()
        tr = Tracer()
        ctx = Context(tracer=tr, registry=reg)
        k = KernelDef.define(
            "scale", lambda views, info: {"y": views["x"] * 2.0},
            "global i => read x[i], write y[i]",
        )
        x = ctx.array(jnp.ones(16), name="x")
        y = ctx.zeros((16,), name="y")
        out = ctx.launch(k, grid=(16,), args={"x": x, "y": y})
        assert float(out["y"].value[0]) == 2.0
        assert reg.snapshot()["launch.count{kernel=scale}"] == 1
        names = [e["name"] for e in tr.events]
        assert "plan:scale" in names and "launch:scale" in names

    def test_launch_spans_share_one_launch_id(self):
        import jax
        import jax.numpy as jnp

        from repro.core import Context, KernelDef

        tr = Tracer(clock=time.perf_counter)
        ctx = Context(tracer=tr, registry=MetricsRegistry())
        fresh = jax.jit(lambda x: x * 3.0 + 1.0)  # new: traced and compiled
        k = KernelDef.define(
            "affine", lambda views, info: {"y": fresh(views["x"])},
            "global i => read x[i], write y[i]",
        )
        x = ctx.array(jnp.ones(16), name="x")
        y = ctx.zeros((16,), name="y")
        ctx.launch(k, grid=(16,), args={"x": x, "y": y})
        ev = {}
        for e in tr.events:
            ev.setdefault(e["name"], []).append(e)
        (plan,), (launch,), (execute,) = (ev["plan:affine"],
                                          ev["launch:affine"],
                                          ev["execute:affine"])
        lid = launch["args"]["launch"]
        assert plan["args"]["launch"] == execute["args"]["launch"] == lid
        assert execute["parent"] == launch["id"]
        jax_spans = [e for e in tr.events if e["name"].startswith("jax:")]
        assert {e["name"] for e in jax_spans} >= {"jax:trace", "jax:lower",
                                                  "jax:compile"}
        for e in jax_spans:
            assert e["args"]["launch"] == lid
            assert e["parent"] == execute["id"]
            assert e["stream"] == "driver" and e["args"]["fun"]
            # a child lies inside its parent
            assert execute["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= execute["ts"] + execute["dur"]
        a = launch["args"]
        assert a["programs"] == len(ev["jax:compile"]) >= 1
        assert a["traces"] == len(ev["jax:trace"])
        assert 0 < a["compile_s"] <= launch["dur"]
        # only this launch's children: none outside the launch id
        assert all(e["args"].get("launch") == lid for e in tr.events)

    def test_null_tracer_launch_records_no_span_but_counts(self,
                                                           monkeypatch):
        import jax.numpy as jnp

        from repro.core import Context, KernelDef
        from repro.obs import trace as trace_mod

        def boom(*a, **k):
            raise AssertionError("a span or event was built")

        monkeypatch.setattr(trace_mod._Span, "__init__", boom)
        monkeypatch.setattr(trace_mod.Tracer, "complete", boom)
        reg = MetricsRegistry()
        ctx = Context(registry=reg)
        assert ctx.tracer is NULL_TRACER
        k = KernelDef.define(
            "halve", lambda views, info: {"y": views["x"] * 0.5},
            "global i => read x[i], write y[i]",
        )
        x = ctx.array(jnp.ones(8), name="x")
        y = ctx.zeros((8,), name="y")
        ctx.launch(k, grid=(8,), args={"x": x, "y": y})
        snap = reg.snapshot()
        assert snap["launch.count{kernel=halve}"] == 1
        assert "launch.programs{kernel=halve}" in snap
        assert "launch.compile_s{kernel=halve}" in snap

    def test_repeated_single_device_launch_compiles_nothing(self):
        import jax.numpy as jnp

        from repro.core import Context, KernelDef

        reg = MetricsRegistry()
        ctx = Context(tracer=Tracer(clock=time.perf_counter), registry=reg)
        k = KernelDef.define(
            "shift", lambda views, info: {"y": views["x"] + 7.0},
            "global i => read x[i], write y[i]",
        )
        x = ctx.array(jnp.ones(24), name="x")
        y = ctx.zeros((24,), name="y")
        ctx.launch(k, grid=(24,), args={"x": x, "y": y})
        first = reg.snapshot()
        for _ in range(3):
            ctx.launch(k, grid=(24,), args={"x": x, "y": y})
        delta = MetricsRegistry.diff(reg.snapshot(), first)
        assert delta["launch.count{kernel=shift}"] == 3
        assert delta["launch.programs{kernel=shift}"] == 0
        assert delta["launch.compile_s{kernel=shift}"] == 0
        spans = [e for e in ctx.tracer.events
                 if e["name"] == "launch:shift"][1:]
        assert [e["args"]["programs"] for e in spans] == [0, 0, 0]

    def test_repeated_mesh_launch_compiles_nothing(self):
        """On 4 virtual devices the first mesh launch builds and compiles
        its jitted ``shard_map``; the next three reuse it and compile
        nothing."""
        from _subproc import run_with_devices

        out = run_with_devices("""
import json, time
import jax, numpy as np
from repro.core import *
from repro.obs import MetricsRegistry, Tracer

reg = MetricsRegistry()
tr = Tracer(clock=time.perf_counter)
ctx = Context(mesh=jax.make_mesh((4,), ("data",)), tracer=tr, registry=reg)
k = KernelDef.define(
    "stencil", lambda v, i: {"output": (v["input"][:-2] + v["input"][2:]) / 2},
    "global i => read input[i-1:i+1], write output[i]")
n = 256
inp = ctx.array(np.arange(n, dtype=np.float32), dist=StencilDist(n // 4, 1),
                name="input")
out = ctx.zeros((n,), dist=BlockDist(n // 4), name="output")
ctx.launch(k, grid=(n,), args={"input": inp, "output": out})
first = reg.snapshot()
for _ in range(3):
    ctx.launch(k, grid=(n,), args={"input": inp, "output": out})
print(json.dumps({"first": first, "delta": MetricsRegistry.diff(reg.snapshot(), first),
                  "events": tr.events}))
""", n_devices=4)
        got = json.loads(out.strip().splitlines()[-1])
        first, delta = got["first"], got["delta"]
        assert first["launch.programs{kernel=stencil}"] >= 1
        assert first["launch.mesh_cache{kernel=stencil,result=miss}"] == 1
        assert delta["launch.count{kernel=stencil}"] == 3
        assert delta["launch.programs{kernel=stencil}"] == 0
        assert delta["launch.compile_s{kernel=stencil}"] == 0
        assert delta["launch.mesh_cache{kernel=stencil,result=hit}"] == 3
        assert delta.get("launch.mesh_cache{kernel=stencil,result=miss}",
                         0) == 0
        spans = {name: [e["args"] for e in got["events"] if e["name"] == name]
                 for name in ("launch:stencil", "execute:stencil")}
        assert [a["programs"] for a in spans["launch:stencil"]][1:] == [0] * 3
        assert [a["cached"] for a in spans["execute:stencil"]] == [
            False, True, True, True]

    def test_mesh_launch_counts_what_an_outside_listener_sees(self):
        """On 4 virtual devices, a mesh launch's ``programs`` equals the
        backend compiles an independent listener saw during it (equality,
        not a number: a launch that compiles nothing must read 0)."""
        from _subproc import run_with_devices

        out = run_with_devices("""
import json, time
import jax, numpy as np
from repro.core import *
from repro.obs import MetricsRegistry, Tracer

seen = []
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, d, **kw: seen.append(ev.endswith("backend_compile_duration")))
mesh = jax.make_mesh((4,), ("data",))
reg = MetricsRegistry()
tr = Tracer(clock=time.perf_counter)
ctx = Context(mesh=mesh, tracer=tr, registry=reg)
k = KernelDef.define(
    "stencil", lambda v, i: {"output": (v["input"][:-2] + v["input"][2:]) / 2},
    "global i => read input[i-1:i+1], write output[i]")
n = 256
inp = ctx.array(np.arange(n, dtype=np.float32), dist=StencilDist(n // 4, 1),
                name="input")
out = ctx.zeros((n,), dist=BlockDist(n // 4), name="output")
rows = []
for _ in range(2):
    before = sum(seen)
    ctx.launch(k, grid=(n,), args={"input": inp, "output": out})
    rows.append(sum(seen) - before)
launches = [e for e in tr.events if e["name"] == "launch:stencil"]
print(json.dumps({"seen": rows, "launches": launches, "events": tr.events,
                  "programs": reg.snapshot()["launch.programs{kernel=stencil}"]}))
""", n_devices=4)
        got = json.loads(out.strip().splitlines()[-1])
        launches = got["launches"]
        assert [e["args"]["programs"] for e in launches] == got["seen"]
        assert got["programs"] == sum(got["seen"])
        for launch in launches:
            assert launch["args"]["devices"] == 4
            assert 0 <= launch["args"]["compile_s"] <= launch["dur"]
            lid = launch["args"]["launch"]
            mine = [e for e in got["events"] if e["args"]["launch"] == lid]
            assert {e["name"] for e in mine} >= {"plan:stencil",
                                                 "launch:stencil",
                                                 "execute:stencil"}
            compiles = [e for e in mine if e["name"] == "jax:compile"]
            assert len(compiles) == launch["args"]["programs"]

    def test_serve_engine_metrics(self):
        import jax

        from repro.configs import get_smoke_config
        from repro.models import init_params
        from repro.serve.engine import Request, ServeEngine

        cfg = get_smoke_config("gemma-2b")
        params = init_params(jax.random.key(0), cfg)
        reg = MetricsRegistry()
        fake = iter(range(1000))
        engine = ServeEngine(params, cfg, slots=2, max_len=64,
                             registry=reg, clock=lambda: float(next(fake)))
        rng = np.random.default_rng(0)
        for rid in range(3):
            engine.submit(Request(
                rid=rid, prompt=rng.integers(0, cfg.vocab, 8, dtype=np.int64)
                .astype(np.int32), max_new_tokens=4,
            ))
        assert reg.snapshot()["serve.queue_depth"] == 3
        done = engine.run()
        assert len(done) == 3
        snap = reg.snapshot()
        assert snap["serve.requests{status=completed}"] == 3
        assert snap["serve.queue_depth"] == 0
        assert snap["serve.ttft_s.count"] == 3
        assert snap["serve.decode_step_s.count"] == engine.stats["steps"]

    def test_train_metrics(self, tmp_path):
        from repro.launch.train import run_training

        reg = MetricsRegistry()
        fake = iter(range(10000))
        res = run_training(
            "gemma-2b", smoke=True, steps=4, batch=2, seq=32,
            registry=reg, clock=lambda: float(next(fake)),
        )
        assert res["steps"] == 4
        snap = reg.snapshot()
        assert snap["train.steps"] == 4
        assert snap["train.step_s.count"] == 4
        assert snap["train.tokens_per_s"] > 0
