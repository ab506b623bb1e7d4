"""Distributed kernel launches (paper §2.1, §3) lowered to JAX.

The user-facing model mirrors the paper's host API (Fig. 9):

    ctx = Context(mesh)                           # driver
    k = KernelDef("stencil", body,
                  annotation="global i => read input[i-1:i+1], write output[i]")
    out = ctx.launch(k, grid=(n,), work_dist=..., args={...})

``Context`` plays the paper's *driver*: it owns array metadata, invokes the
planner for every launch, records the stitched task DAG (sequential
consistency via chunk-conflict edges), and dispatches execution:

* **single device** — the kernel body runs on full-array views (the planner
  still runs, so plans/DAGs are inspectable and the simulator can cost them);
* **out of core** — a single-device launch with an argument on the host
  tier (an array larger than the device can take, paper §3.4) runs the
  plan's EXECUTE tasks in order, one superblock at a time: each task's read
  region of the host array goes to the device while the body runs on the
  previous one, and ``reduce`` outputs are combined on the device;
* **mesh** — the launch lowers to one jitted ``shard_map``, built on the
  first launch of its signature (kernel, grid, argument shapes, dtypes and
  specs, planned patterns, scalars) and reused from the context's cache
  after that: each device executes its superblock; the planner's
  per-argument :class:`CommPattern` decides the collective that
  materializes each argument's access region:

    LOCAL       shard passed straight through (no communication)
    REPLICATED  full array everywhere (storage is replicated)
    GATHER      ``all_gather`` reassembles the full array
    HALO        ``ppermute`` edge exchange, concatenated onto the shard
    REDUCE      kernel emits partials; ``psum``/``pmin``/``pmax`` combines

This is the paper's wrapper-kernel machinery translated: block-index
virtualization becomes the shard_map program id; offset rebasing becomes the
local-coordinate views handed to the body.
"""

from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import time
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.compiles import CompileWatch
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.trace import _NULL_SPAN, NULL_TRACER

from . import annotations as ann_mod
from .annotations import READ, Annotation, REDUCE as MODE_REDUCE
from .dist_array import DEVICE, HOST, DistributedArray, full_array, make_array
from .distributions import Distribution, ReplicatedDist
from .faults import FaultInjector, RecoveryPolicy
from .ndrange import Region
from .plan_ir import CommPattern, ExecutionPlan, LaunchPlan, Task, TaskKind
from .planner import ArrayMeta, Planner, Topology
from .reductions import collective_reduce, combine, identity_for
from .superblock import BlockWork, EvenWork, WorkDistribution


@dataclasses.dataclass(frozen=True)
class KernelDef:
    """A Lightning kernel: a JAX-callable body plus its data annotation.

    ``body(views, info)`` receives ``views``: dict arg-name → jnp array
    covering that argument's access region for this superblock (local
    coordinates), and ``info``: a :class:`SuperblockInfo`.  It returns a dict
    arg-name → array for each *written* argument (for ``reduce`` arguments it
    returns the local partial over the full output region).

    The body may be a plain jnp function or a Pallas ``ops`` wrapper — both
    are traced inside the launch's jitted program on a mesh, and run as they
    are on one device.  A ``KernelDef`` is hashed and compared by its fields,
    so keeping one and launching it again reuses the mesh program.
    """

    name: str
    body: Callable[..., Mapping[str, jax.Array]]
    annotation: Annotation
    scalars: tuple[str, ...] = ()  # non-array parameters, passed through

    @staticmethod
    def define(
        name: str,
        body: Callable[..., Mapping[str, jax.Array]],
        annotation: str,
        scalars: Sequence[str] = (),
    ) -> "KernelDef":
        return KernelDef(name, body, ann_mod.parse(annotation), tuple(scalars))


@dataclasses.dataclass(frozen=True)
class SuperblockInfo:
    """Launch-local context handed to kernel bodies (the paper's
    ``virtBlockIdx`` + offset constants, in JAX clothing)."""

    grid: tuple[int, ...]  # full launch grid (threads)
    thread_offset: tuple[Any, ...]  # global index of this superblock's origin
    local_shape: tuple[int, ...]  # threads in this superblock
    device_index: Any  # flat device id (traced under shard_map)
    scalars: dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class LaunchRecord:
    """What the driver remembers about one launch (for tests/inspection)."""

    plan: LaunchPlan
    in_specs: dict[str, P]
    out_specs: dict[str, P]
    comm: dict[str, CommPattern]


class Context:
    """The driver: array registry + planner + launch execution."""

    def __init__(
        self,
        mesh: Mesh | None = None,
        mesh_axes: Sequence[str] | None = None,
        devices_per_node: int = 4,
        fault_injector: FaultInjector | None = None,
        recovery: RecoveryPolicy | None = None,
        tracer=None,
        registry: MetricsRegistry | None = None,
        plan_cache: bool = True,
        device_budget: int | None = None,
    ):
        self.mesh = mesh
        # Bytes the device may take, for devices that report no memory
        # limit (the CPU); otherwise read from the device (``device_budget``).
        # 0 keeps every numpy array on the host tier (``stream_kmeans``).
        self._device_budget = device_budget
        # The budget that sizes streamed superblocks, read once so every
        # launch of this context streams chunks of the same shape.
        self._stream_budget: int | None = None
        # Host-tier chunks on the device, oldest first, each with the output
        # that consumed it: at most two are kept (``_execute_streamed``).
        self._streaming: collections.deque = collections.deque()
        # Observability: launches emit plan/launch/execute spans (and JAX's
        # jax:* compile stages) on the ``driver`` stream and count launches,
        # retries, recoveries, compiled programs and compile seconds on the
        # registry (resolved lazily so ``use_registry`` redirects us too).
        self.tracer = tracer or NULL_TRACER
        self._registry = registry
        # Fault tolerance: with an injector threaded in, failed kernel
        # launches retry under `recovery` instead of propagating; every
        # failure/recovery is recorded in `fault_events`.
        self.fault_injector = fault_injector
        self.recovery = recovery or RecoveryPolicy()
        self.fault_events: list[dict] = []
        if mesh is not None:
            self.mesh_axes = tuple(mesh_axes or mesh.axis_names)
            num_devices = mesh.size
            self._device = mesh.devices.flat[0]
        else:
            self.mesh_axes = tuple(mesh_axes or ())
            num_devices = 1
            default = jax.config.jax_default_device
            self._device = (jax.devices(default)[0] if isinstance(default, str)
                            else default or jax.devices()[0])
        self.topology = Topology(num_devices, devices_per_node)
        # Plan caching (repeated launches skip re-planning) shares this
        # context's registry so hit/miss counters land with the launch ones.
        self.planner = Planner(self.topology, registry=registry,
                               cache_plans=plan_cache)
        self.records: list[LaunchRecord] = []
        # One shared plan across launches: the planner stitches consecutive
        # launches with chunk-conflict edges (sequential consistency).
        self.plan = ExecutionPlan(launch_name="driver")
        self._array_counter = 0
        # Jitted mesh programs by launch signature (see ``_execute_mesh``).
        self._mesh_programs: collections.OrderedDict = collections.OrderedDict()

    # -- array factory (paper: context.ones / zeros) ---------------------------

    @property
    def registry(self) -> MetricsRegistry:
        return self._registry if self._registry is not None \
            else default_registry()

    @property
    def num_devices(self) -> int:
        return self.topology.num_devices

    def _fresh_name(self, prefix: str) -> str:
        self._array_counter += 1
        return f"{prefix}_{self._array_counter}"

    @property
    def device(self) -> jax.Device:
        """The device of a one-device context: the mesh's, else JAX's
        default device when the context was made."""
        return self._device

    def device_budget(self) -> int | None:
        """Bytes the device can take now: the budget this context was given,
        else :attr:`device`'s ``bytes_limit`` less its ``bytes_in_use``;
        ``None`` where the device reports no limit."""
        if self._device_budget is not None:
            return self._device_budget
        stats = self.device.memory_stats() or {}
        if "bytes_limit" not in stats:
            return None
        return int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0))

    def array(
        self,
        value: jax.Array | np.ndarray,
        dist: Distribution | None = None,
        name: str | None = None,
    ) -> DistributedArray:
        """Register ``value``.  On one device, a numpy array larger than
        :meth:`device_budget` (its bytes in host memory; a narrow array's
        device layout may take more) stays in host memory (the ``HOST``
        tier) and its launches stream it; anything else is placed on the
        device."""
        dist = dist or ReplicatedDist()
        tier = DEVICE
        if isinstance(value, np.ndarray) and self.num_devices == 1:
            budget = self.device_budget()
            if budget is not None and value.nbytes > budget:
                tier = HOST
        return make_array(
            name or self._fresh_name("arr"),
            value,
            dist,
            mesh=self.mesh,
            mesh_axes=self.mesh_axes,
            tier=tier,
        )

    def zeros(self, shape, dtype=jnp.float32, dist=None, name=None):
        return self.full(shape, 0, dtype, dist, name)

    def ones(self, shape, dtype=jnp.float32, dist=None, name=None):
        return self.full(shape, 1, dtype, dist, name)

    def full(self, shape, fill, dtype=jnp.float32, dist=None, name=None):
        return full_array(
            name or self._fresh_name("arr"),
            shape,
            fill,
            dtype,
            dist or ReplicatedDist(),
            mesh=self.mesh,
            mesh_axes=self.mesh_axes,
        )

    # -- launch ------------------------------------------------------------------

    def launch(
        self,
        kernel: KernelDef,
        grid: Sequence[int],
        args: Mapping[str, DistributedArray],
        work_dist: WorkDistribution | None = None,
        work_axis: int = 0,
        scalars: Mapping[str, Any] | None = None,
        block_shape: Sequence[int] | None = None,
    ) -> dict[str, DistributedArray]:
        """Distributed kernel launch.  Returns new values for every written
        array (functional update — JAX arrays are immutable, so "writes"
        produce replacements; the Context rebinds names in its records)."""
        grid = tuple(int(g) for g in grid)
        streamed = [n for n, a in args.items() if a.tier == HOST]
        if streamed and work_dist is None:
            work_dist = BlockWork(self._stream_rows(grid[work_axis], [
                args[n] for n in streamed]), axis=work_axis)
        work_dist = work_dist or EvenWork(axis=work_axis)
        scalars = dict(scalars or {})
        arrays = {name: a.meta() for name, a in args.items()}

        tracer = self.tracer
        traced = tracer.enabled
        registry = self.registry
        # Every span of one launch carries its id as ``launch``.  With the
        # tracer off, no span, event or args dict is built.
        lid = tracer.new_id() if traced else None
        with (tracer.span(f"plan:{kernel.name}", stream="driver", cat="sched",
                          grid=list(grid), launch=lid)
              if traced else _NULL_SPAN):
            first_task = len(self.plan.tasks)
            plan = self.planner.plan_launch(
                kernel.name, kernel.annotation, grid, work_dist, arrays,
                block_shape=block_shape, plan=self.plan,
            )
        comm = {a.array: a.pattern for a in plan.args}
        registry.counter("launch.count").labels(kernel=kernel.name).inc()

        launch_span = (tracer.span(f"launch:{kernel.name}", stream="driver",
                                   cat="compute", grid=list(grid),
                                   devices=self.num_devices, launch=lid)
                       if traced else _NULL_SPAN)
        with launch_span:
            # JAX's trace/lower/compile events while the launch executes:
            # counted always, recorded as jax:* child spans when traced.
            with CompileWatch(tracer, launch=lid) as seen, (
                    tracer.span(f"execute:{kernel.name}", stream="driver",
                                cat="compute", launch=lid)
                    if traced else _NULL_SPAN) as execute_span:
                if self.mesh is None or self.mesh.size == 1:
                    if streamed:
                        tasks = [t for t in self.plan.tasks[first_task:]
                                 if t.kind is TaskKind.EXECUTE]
                        run = functools.partial(
                            self._execute_streamed, kernel, grid, args,
                            scalars, tasks, block_shape, lid)
                    else:
                        run = functools.partial(self._execute_single, kernel,
                                                grid, args, scalars)
                    outputs = self._with_recovery(kernel, run)
                    in_specs = {n: P() for n in args}
                    out_specs = {n: P() for n in outputs}
                else:
                    outputs, in_specs, out_specs, cached = self._with_recovery(
                        kernel, lambda: self._execute_mesh(kernel, grid, args,
                                                           scalars, plan,
                                                           work_dist)
                    )
                    if traced:
                        execute_span.add(cached=cached)
            compile_s = seen.compile_s
            registry.counter("launch.programs").labels(
                kernel=kernel.name).inc(seen.programs)
            registry.counter("launch.compile_s").labels(
                kernel=kernel.name).inc(compile_s)
            if traced:
                launch_span.add(programs=seen.programs,
                                cache_loads=seen.cache_loads,
                                traces=seen.traces, compile_s=compile_s)

        self.records.append(
            LaunchRecord(plan=plan, in_specs=in_specs, out_specs=out_specs,
                         comm=comm)
        )
        result: dict[str, DistributedArray] = {}
        for name, val in outputs.items():
            result[name] = args[name].replace_value(val)
        return result

    def _with_recovery(self, kernel: KernelDef, attempt_fn: Callable[[], Any]):
        """Run one launch attempt, retrying failed launches.

        With no injector this is a plain call (zero behavioral change).
        With one, injected ``launch`` probes — and any real exception the
        attempt raises — retry up to ``recovery.max_attempts`` times before
        propagating, mirroring the runtime-level retry the simulator's
        recovery engine models.  Launches are functional (inputs are
        immutable JAX arrays), so re-execution is always safe."""
        if self.fault_injector is None:
            return attempt_fn()
        attempt = 0
        while True:
            try:
                if self.fault_injector.probe(
                    "launch", task=len(self.records), site=kernel.name
                ):
                    raise RuntimeError(
                        f"injected launch failure: {kernel.name}"
                    )
                result = attempt_fn()
            except Exception as exc:  # noqa: BLE001 — retried, then re-raised
                attempt += 1
                self.fault_events.append({
                    "kind": "launch_failure", "launch": kernel.name,
                    "attempt": attempt, "error": repr(exc),
                })
                self.registry.counter("launch.retries").labels(
                    kernel=kernel.name).inc()
                if self.tracer.enabled:
                    self.tracer.instant(
                        f"launch_failure:{kernel.name}", ts=self.tracer.now(),
                        stream="driver", cat="fault",
                        args={"attempt": attempt},
                    )
                if attempt > self.recovery.max_attempts:
                    raise
                continue
            if attempt:
                self.fault_events.append({
                    "kind": "launch_recovered", "launch": kernel.name,
                    "attempt": attempt,
                })
                self.registry.counter("launch.recoveries").labels(
                    kernel=kernel.name).inc()
            return result

    @staticmethod
    def synchronize(*arrays: DistributedArray) -> None:
        """Block until dispatched work completes (paper Fig. 9 line 21).
        JAX dispatch is already asynchronous per-array; synchronizing simply
        blocks on the given arrays' buffers."""
        jax.block_until_ready([a.value for a in arrays])

    # -- single-device execution ---------------------------------------------------

    def _execute_single(
        self,
        kernel: KernelDef,
        grid: tuple[int, ...],
        args: Mapping[str, DistributedArray],
        scalars: dict[str, Any],
    ) -> dict[str, jax.Array]:
        views = {name: a.value for name, a in args.items()}
        info = SuperblockInfo(
            grid=grid,
            thread_offset=(0,) * len(grid),
            local_shape=grid,
            device_index=0,
            scalars=scalars,
        )
        outs = dict(kernel.body(views, info))
        # reduce() partials on one device are already the full reduction.
        return outs

    # -- out-of-core execution -------------------------------------------------------

    def _stream_rows(self, extent: int,
                     streamed: Sequence[DistributedArray]) -> int:
        """Superblock size, in steps of the work axis, of a launch that
        streams ``streamed``: the largest power of two at which two
        superblocks' host bytes take at most ``_STREAM_SHARE`` of the
        budget, read at the context's first streamed launch.  The rest of
        the budget is left for the device layout's padding of a chunk, the
        body's temporaries and the arrays already on the device."""
        if self._stream_budget is None:
            self._stream_budget = self.device_budget() or 0
        row_bytes = sum(a.nbytes for a in streamed) / max(extent, 1)
        fit = int(self._stream_budget * _STREAM_SHARE / (2 * row_bytes))
        rows = 1 << (max(fit, 1).bit_length() - 1)
        return max(1, min(rows, extent))

    def _execute_streamed(
        self,
        kernel: KernelDef,
        grid: tuple[int, ...],
        args: Mapping[str, DistributedArray],
        scalars: dict[str, Any],
        tasks: Sequence[Task],
        block_shape: Sequence[int] | None,
        lid: int | None,
    ) -> dict[str, jax.Array]:
        """Run the plan's EXECUTE ``tasks`` in order over the host-tier
        arguments (paper §3.4).  Each task's read region of a host array
        goes to the device with ``jax.device_put``, in pieces put
        concurrently (``_put_pieces``); the transfer for task i+1 is
        enqueued before the body runs on task i, after the output of task
        i-1 is ready, so at most two tasks' chunks are on the device.
        Device-tier arguments are passed whole.  ``reduce`` outputs start
        at the operator's identity and are combined on the device, task by
        task, in one jitted program per chunk shape (``_stream_step``).
        Host-tier arguments are read-only, one disjoint region per task;
        any other written argument must be a ``reduce`` of the whole array
        in every task."""
        ann = kernel.annotation
        host = {n: a for n, a in args.items() if a.tier == HOST}
        for stmt in ann.stmts:
            if stmt.array in host and stmt.mode != READ:
                raise NotImplementedError(
                    f"{kernel.name}: {stmt.mode} access to host-resident "
                    f"{stmt.array!r}; a streamed launch only reads the host "
                    "tier")
            if stmt.writes and stmt.mode != MODE_REDUCE:
                raise NotImplementedError(
                    f"{kernel.name}: {stmt.mode} of {stmt.array!r} in a "
                    "launch that streams a host-resident argument; only "
                    "reduce outputs are supported")
        envs = [ann.env_for_superblock(t.region, block_shape=block_shape)
                for t in tasks]
        regions = {}  # host-tier argument -> its read region, task by task
        for n, a in host.items():
            regions[n] = [ann.stmt_for(n).region(env, a.shape) for env in envs]
            for prev, region in zip(regions[n], regions[n][1:]):
                if prev.overlaps(region):
                    raise NotImplementedError(
                        f"{kernel.name}: superblocks read overlapping regions "
                        f"of host-resident {n!r} (a halo or whole-array "
                        "read); a streamed launch reads each region once")
        ops = tuple((s.array, s.reduce_op or "+") for s in ann.stmts
                    if s.mode == MODE_REDUCE)
        for n, _ in ops:
            whole = Region.from_shape(args[n].shape)
            if any(ann.stmt_for(n).region(env, args[n].shape) != whole
                   for env in envs):
                raise NotImplementedError(
                    f"{kernel.name}: reduce into a part of {n!r} that "
                    "follows the superblock; a streamed launch combines "
                    "whole-array reduce outputs only")
        acc = {n: jnp.full(args[n].shape, identity_for(op, args[n].dtype),
                           device=self.device) for n, op in ops}
        resident = {n: a.value for n, a in args.items() if n not in host}
        dynamic = {k: v for k, v in scalars.items() if isinstance(v, jax.Array)}
        static = tuple(sorted((k, v) for k, v in scalars.items()
                              if k not in dynamic))

        inflight = self._streaming
        wait_s, h2d_bytes = 0.0, 0

        def put(i: int, keep: int) -> dict[str, jax.Array]:
            nonlocal wait_s, h2d_bytes
            t0 = time.perf_counter()
            while len(inflight) > keep:  # the device holds two chunks at most
                chunk, done = inflight.popleft()
                jax.block_until_ready(done)
                for x in chunk:
                    x.delete()
            wait_s += time.perf_counter() - t0
            parts = {n: a.value[regions[n][i].to_slices()]
                     for n, a in host.items()}
            h2d_bytes += sum(p.nbytes for p in parts.values())
            return {n: _put_pieces(p, self.device) for n, p in parts.items()}

        traced = self.tracer.enabled
        with (self.tracer.span(f"stream:{kernel.name}", stream="driver",
                               cat="transfer", launch=lid)
              if traced else _NULL_SPAN) as stream_span:
            chunk = put(0, keep=1)
            for i, task in enumerate(tasks):
                nxt = put(i + 1, keep=0) if i + 1 < len(tasks) else None
                acc = _stream_step(
                    acc, np.asarray(task.region.starts, np.int32), resident,
                    chunk, dynamic, kernel=kernel, grid=grid,
                    local_shape=task.region.shape, ops=ops, static=static)
                inflight.append((sum(chunk.values(), ()), acc))
                chunk = nxt
            if traced:
                stream_span.add(chunks=len(tasks), bytes=h2d_bytes,
                                wait_s=wait_s)
        registry = self.registry
        registry.counter("launch.h2d_bytes").labels(
            kernel=kernel.name).inc(h2d_bytes)
        registry.counter("launch.chunks").labels(
            kernel=kernel.name).inc(len(tasks))
        return acc

    # -- mesh execution --------------------------------------------------------------

    def _execute_mesh(
        self,
        kernel: KernelDef,
        grid: tuple[int, ...],
        args: Mapping[str, DistributedArray],
        scalars: dict[str, Any],
        plan: LaunchPlan,
        work_dist: WorkDistribution,
    ) -> tuple[dict[str, jax.Array], dict[str, P], dict[str, P], bool]:
        """Run one launch as a jitted ``shard_map``, reusing a cached one.

        The program is built once per launch signature and kept on this
        context; the key is what the launch's input shows: the
        :class:`KernelDef`, the grid and split axis, the argument names in
        order with each one's shape and dtype, the in/out partition specs,
        each argument's planned ``(pattern, mode, reduce_op, halo_width)``
        and the scalars.  A hashable scalar is keyed by type and value and
        baked into the program, so a scalar that changes on every launch
        should be a ``jax.Array``, which is passed to the program as a
        replicated argument; any other scalar makes the launch build its
        program without caching it.  The program closes over names, specs
        and patterns only, never over a launch's arrays.  A caller that
        builds a new ``KernelDef`` for every launch misses every time and
        pays one compile of the whole program per launch; the least recently
        used of more than ``_MESH_PROGRAMS`` programs is dropped.

        Returns the written values, the in/out specs and whether the
        program came from the cache.
        """
        mesh = self.mesh
        assert mesh is not None
        ann = kernel.annotation

        # Which grid axis does the work distribution split?  (Our work
        # distributions split one axis; MeshWork may split several, in which
        # case grid axis i maps to mesh axis i.)
        split_axis = getattr(work_dist, "axis", 0)

        in_specs: dict[str, P] = {}
        out_specs: dict[str, P] = {}
        patterns = {a.array: a for a in plan.args}

        for name, arr in args.items():
            ap = patterns[name]
            if ap.pattern is CommPattern.REPLICATED:
                in_specs[name] = P()
            else:
                in_specs[name] = arr.partition_spec()
        written = tuple(s.array for s in ann.stmts if s.writes)
        for name in written:
            ap = patterns[name]
            if ap.pattern is CommPattern.REDUCE or ap.mode == MODE_REDUCE:
                out_specs[name] = P()  # fully reduced, replicated result
            elif ap.pattern is CommPattern.REPLICATED:
                out_specs[name] = P()
            else:
                out_specs[name] = args[name].partition_spec()

        names = tuple(args)
        arg_plans = tuple(
            (patterns[n].pattern, patterns[n].mode, patterns[n].reduce_op,
             patterns[n].halo_width) for n in names)
        replicated = NamedSharding(mesh, P())
        dynamic = {k: jax.device_put(v, replicated) for k, v in scalars.items()
                   if isinstance(v, jax.Array)}
        static = tuple(sorted((k, type(v), v) for k, v in scalars.items()
                              if k not in dynamic))
        key = (kernel, grid, split_axis, names,
               tuple((a.shape, a.dtype) for a in args.values()),
               tuple(in_specs.items()), tuple(out_specs.items()), arg_plans,
               static, tuple(dynamic))
        try:
            fn = self._mesh_programs.get(key)
        except TypeError:  # an unhashable scalar: build, run, don't keep
            key = fn = None
        cached = fn is not None
        if cached:
            self._mesh_programs.move_to_end(key)
        else:
            fn = _mesh_program(kernel, mesh, self.mesh_axes, grid, split_axis,
                               names, arg_plans, written, in_specs, out_specs,
                               {k: v for k, _, v in static}, tuple(dynamic))
        out_vals = fn(*[a.value for a in args.values()], *dynamic.values())
        if key is not None and not cached:
            self._mesh_programs[key] = fn
            if len(self._mesh_programs) > _MESH_PROGRAMS:
                self._mesh_programs.popitem(last=False)
        self.registry.counter("launch.mesh_cache").labels(
            kernel=kernel.name, result="hit" if cached else "miss").inc()
        return dict(zip(written, out_vals)), in_specs, out_specs, cached


#: Most jitted mesh programs one :class:`Context` keeps (least recently used
#: dropped first): a bound for callers that make a new kernel every launch.
_MESH_PROGRAMS = 64

#: Pieces a streamed chunk is cut into along its first axis, each at least
#: ``_PUT_MIN_BYTES``, and put on the device concurrently: one put of a
#: large narrow array is bound by one host thread's copy into the device's
#: layout, which the host's other load slows.
_PUT_SPLIT = 4
_PUT_MIN_BYTES = 32 << 20


@functools.cache
def _put_pool() -> concurrent.futures.ThreadPoolExecutor:
    return concurrent.futures.ThreadPoolExecutor(
        _PUT_SPLIT, thread_name_prefix="repro-h2d")


def _put_pieces(part: np.ndarray, device: jax.Device) -> tuple[jax.Array, ...]:
    """``part`` on ``device``, as up to ``_PUT_SPLIT`` pieces along its
    first axis put concurrently (``_stream_step`` joins them)."""
    k = max(1, min(_PUT_SPLIT, part.nbytes // _PUT_MIN_BYTES, len(part)))
    if k == 1:
        return (jax.device_put(part, device),)
    return tuple(_put_pool().map(lambda p: jax.device_put(p, device),
                                 np.array_split(part, k)))


#: Share of the device budget that the two chunks of a streamed launch may
#: take, counted in host bytes: 1/8 leaves room for a narrow array's padded
#: device layout and for the body's temporaries.  For 34-column float32
#: points on one 16 GB v5e it gives superblocks of 2^22 points (570 MB).
_STREAM_SHARE = 1 / 8


@functools.partial(jax.jit, static_argnames=(
    "kernel", "grid", "local_shape", "ops", "static"))
def _stream_step(acc, offset, resident, chunks, dynamic, *, kernel, grid,
                 local_shape, ops, static):
    """One task of a streamed launch: the body on one superblock's views,
    its ``reduce`` partials combined into ``acc``.  ``chunks`` are the
    host-tier arguments' read regions, each as the pieces it was put in
    (``_put_pieces``); ``resident`` the device-tier arguments.  ``offset``
    (the superblock's origin) and ``dynamic`` (the ``jax.Array`` scalars)
    are traced, so tasks of one shape share one program across launches
    and contexts; ``static`` holds the other
    scalars as ``(name, value)`` pairs, which must be hashable."""
    views = {**resident, **{n: p[0] if len(p) == 1 else jnp.concatenate(p)
                            for n, p in chunks.items()}}
    info = SuperblockInfo(
        grid=grid,
        thread_offset=tuple(offset[d] for d in range(len(grid))),
        local_shape=local_shape,
        device_index=0,
        scalars={**dict(static), **dynamic},
    )
    outs = kernel.body(views, info)
    return {n: combine(op, acc[n], outs[n].astype(acc[n].dtype))
            for n, op in ops}


def _mesh_program(
    kernel: KernelDef,
    mesh: Mesh,
    axes: tuple[str, ...],
    grid: tuple[int, ...],
    split_axis: int,
    names: tuple[str, ...],
    arg_plans: tuple[tuple, ...],
    written: tuple[str, ...],
    in_specs: dict[str, P],
    out_specs: dict[str, P],
    static_scalars: dict[str, Any],
    dynamic_scalars: tuple[str, ...],
) -> Callable[..., tuple[jax.Array, ...]]:
    """One launch signature's program: ``jax.jit`` of a ``shard_map`` whose
    body gives each device its superblock's views, runs the kernel body and
    combines ``reduce`` outputs.  It takes the arguments' values in
    ``names`` order, then the ``dynamic_scalars``, and returns the written
    values in ``written`` order."""
    ann = kernel.annotation
    plans = dict(zip(names, arg_plans))
    n_shards = mesh.size

    def shard_body(*vals):
        views: dict[str, jax.Array] = {}
        named = dict(zip(names, vals))
        scalars = dict(static_scalars)
        scalars.update(zip(dynamic_scalars, vals[len(names):]))
        # Device/superblock identity inside shard_map.
        idx = jax.lax.axis_index(axes[0])
        for ax in axes[1:]:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        sb_threads = grid[split_axis] // n_shards
        offset = [0] * len(grid)
        offset[split_axis] = idx * sb_threads
        local_shape = list(grid)
        local_shape[split_axis] = sb_threads

        for name, val in named.items():
            pattern, _, _, halo_width = plans[name]
            stmt = ann.stmt_for(name)
            if pattern is CommPattern.LOCAL or pattern is CommPattern.REPLICATED:
                views[name] = val
            elif pattern is CommPattern.GATHER and stmt.reads:
                full = val
                sharded_dims = [
                    d for d, s in enumerate(in_specs[name])
                    if s is not None
                ] if len(in_specs[name]) else []
                for d in sharded_dims:
                    spec_axes = in_specs[name][d]
                    spec_axes = (spec_axes,) if isinstance(spec_axes, str) else spec_axes
                    for a in spec_axes:
                        full = jax.lax.all_gather(full, a, axis=d, tiled=True)
                views[name] = full
            elif pattern is CommPattern.HALO:
                views[name] = _halo_exchange(val, halo_width or (1,), axes, mesh)
            elif pattern is CommPattern.REDUCE:
                views[name] = val  # partial buffer; body overwrites
            else:  # SCATTER etc.: gather fallback (correct, slower)
                full = val
                for d, s in enumerate(in_specs[name]):
                    if s is None:
                        continue
                    for a in ((s,) if isinstance(s, str) else s):
                        full = jax.lax.all_gather(full, a, axis=d, tiled=True)
                views[name] = full

        info = SuperblockInfo(
            grid=grid,
            thread_offset=tuple(offset),
            local_shape=tuple(local_shape),
            device_index=idx,
            scalars=scalars,
        )
        outs = dict(kernel.body(views, info))
        final = []
        for name in written:
            pattern, mode, reduce_op, _ = plans[name]
            o = outs[name]
            if pattern is CommPattern.REDUCE or mode == MODE_REDUCE:
                o = collective_reduce(reduce_op or "+", o, axes)
            final.append(o)
        return tuple(final)

    return jax.jit(jax.shard_map(
        shard_body,
        mesh=mesh,
        in_specs=(tuple(in_specs[n] for n in names)
                  + (P(),) * len(dynamic_scalars)),
        out_specs=tuple(out_specs[n] for n in written),
        check_vma=False,
    ))


def _halo_exchange(
    x: jax.Array,
    halo: tuple[int, ...],
    axes: Sequence[str],
    mesh: Mesh,
) -> jax.Array:
    """Exchange ``halo`` cells with ±1 neighbours along the first mesh axis
    and concatenate onto the shard (1-D decomposition, the paper's stencil
    distribution).  Boundary shards receive zeros (the kernels' bounds checks
    ignore them, matching CUDA-side guards)."""
    axis = axes[0]
    n = mesh.shape[axis]
    h = next((v for v in halo if v), 1)
    dim = next((i for i, v in enumerate(halo) if v), 0)

    def take(arr, start, size, d):
        idx = [slice(None)] * arr.ndim
        idx[d] = slice(start, start + size) if start >= 0 else slice(start, None)
        return arr[tuple(idx)]

    left_edge = take(x, 0, h, dim)  # my first h rows → right neighbour's halo
    right_edge = take(x, -h, h, dim)  # my last h rows → left neighbour's halo

    # send right_edge to the next shard (it becomes their "left" halo)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    bwd = [(i, (i - 1) % n) for i in range(n)]
    from_left = jax.lax.ppermute(right_edge, axis, fwd)
    from_right = jax.lax.ppermute(left_edge, axis, bwd)

    idx = jax.lax.axis_index(axis)
    zeros = jnp.zeros_like(from_left)
    from_left = jnp.where(idx == 0, zeros, from_left)
    from_right = jnp.where(idx == n - 1, zeros, from_right)
    return jnp.concatenate([from_left, x, from_right], axis=dim)
