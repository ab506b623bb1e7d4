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
import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs.compiles import CompileWatch
from repro.obs.metrics import MetricsRegistry, default_registry
from repro.obs.trace import _NULL_SPAN, NULL_TRACER

from . import annotations as ann_mod
from .annotations import Annotation, REDUCE as MODE_REDUCE
from .dist_array import DistributedArray, full_array, make_array
from .distributions import Distribution, ReplicatedDist
from .faults import FaultInjector, RecoveryPolicy
from .ndrange import Region
from .plan_ir import CommPattern, ExecutionPlan, LaunchPlan
from .planner import ArrayMeta, Planner, Topology
from .reductions import collective_reduce
from .superblock import EvenWork, WorkDistribution


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
    ):
        self.mesh = mesh
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
        else:
            self.mesh_axes = tuple(mesh_axes or ())
            num_devices = 1
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

    def array(
        self,
        value: jax.Array | np.ndarray,
        dist: Distribution | None = None,
        name: str | None = None,
    ) -> DistributedArray:
        dist = dist or ReplicatedDist()
        return make_array(
            name or self._fresh_name("arr"),
            value,
            dist,
            mesh=self.mesh,
            mesh_axes=self.mesh_axes,
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
                    outputs = self._with_recovery(
                        kernel, lambda: self._execute_single(kernel, grid,
                                                             args, scalars)
                    )
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
