"""repro.dist.sharding: planner bridge on non-matmul annotations,
tree_specs structure/rank properties, spec dedup and constrain gating."""

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    ShardingRules,
    constrain,
    derive_rules_from_plan,
    dp_rules,
    tp_rules,
    tree_specs,
)


class TestPlannerBridge:
    def test_stencil2d_halo_pattern(self):
        """The stencil2d kernel's 2-D halo read can never be point-sharded:
        both input dims are slice accesses (HALO lowering), while the point
        write stays sharded on both grid axes."""
        specs = derive_rules_from_plan(
            "global [i, j] => read inp[i-1:i+1, j-1:j+1], write out[i,j]",
            grid_axis_names=("y", "x"),
            grid_axis_mesh={"y": "data", "x": "model"},
            array_ranks={"inp": 2, "out": 2},
        )
        assert specs["inp"] == P(None, None)
        assert specs["out"] == P("data", "model")

    def test_reduction_output_sharded_on_point_dim(self):
        specs = derive_rules_from_plan(
            "global [i, j] => read A[i,j], reduce(+) s[j]",
            grid_axis_names=("batch", "heads"),
            grid_axis_mesh={"batch": "data", "heads": "model"},
            array_ranks={"A": 2, "s": 1},
        )
        assert specs["A"] == P("data", "model")
        assert specs["s"] == P("model")

    def test_offset_and_scaled_points_replicate(self):
        """A[i+1] / A[2*i] are point accesses but not chunk-aligned — the
        planner serves them with gathers, so the bridge replicates them."""
        specs = derive_rules_from_plan(
            "global i => read A[i+1], read B[2*i], write C[i]",
            grid_axis_names=("batch",),
            grid_axis_mesh={"batch": "data"},
            array_ranks={"A": 1, "B": 1, "C": 1},
        )
        assert specs["A"] == P(None)
        assert specs["B"] == P(None)
        assert specs["C"] == P("data")

    def test_repeated_grid_var_dedupes(self):
        specs = derive_rules_from_plan(
            "global i => write D[i,i]",
            grid_axis_names=("batch",),
            grid_axis_mesh={"batch": "data"},
            array_ranks={"D": 2},
        )
        assert specs["D"] == P("data", None)

    def test_unmapped_grid_axis_replicates(self):
        specs = derive_rules_from_plan(
            "global [i, j] => write C[i,j]",
            grid_axis_names=("batch", "heads"),
            grid_axis_mesh={"batch": "data", "heads": None},
            array_ranks={"C": 2},
        )
        assert specs["C"] == P("data", None)


_LOGICAL_NAMES = [
    "batch", "seq", "d_model", "heads", "kv_heads", "kv_seq",
    "d_ff", "vocab", "experts", "zero1", None,
]
_leaves = st.lists(
    st.lists(st.sampled_from(_LOGICAL_NAMES), min_size=0, max_size=4)
    .map(tuple),
    min_size=1,
    max_size=6,
)


def _is_axes_leaf(x):
    return isinstance(x, tuple)


def _is_spec_leaf(x):
    return isinstance(x, P)


class TestTreeSpecs:
    @given(leaves=_leaves, split=st.integers(0, 6), tp=st.booleans())
    @settings(max_examples=200, deadline=None)
    def test_structure_preserved_and_rank_matches(self, leaves, split, tp):
        """Property: tree_specs is structure-preserving and every emitted
        spec has exactly the rank of its logical-axes leaf, with each mesh
        axis used at most once."""
        rules = tp_rules() if tp else dp_rules()
        tree = {
            "nested": {f"k{i}": leaf for i, leaf in
                       enumerate(leaves[:split])},
            "flat": list(leaves[split:]),
        }
        specs = tree_specs(rules, tree)

        in_def = jax.tree.structure(tree, is_leaf=_is_axes_leaf)
        out_def = jax.tree.structure(specs, is_leaf=_is_spec_leaf)
        assert in_def == out_def

        in_leaves = jax.tree.leaves(tree, is_leaf=_is_axes_leaf)
        out_leaves = jax.tree.leaves(specs, is_leaf=_is_spec_leaf)
        for axes, spec in zip(in_leaves, out_leaves):
            assert isinstance(spec, P)
            assert len(spec) == len(axes), (axes, spec)
            flat = [
                a
                for entry in spec if entry is not None
                for a in (entry if isinstance(entry, tuple) else (entry,))
            ]
            assert len(flat) == len(set(flat)), (axes, spec)

    def test_empty_tuple_is_scalar_spec(self):
        assert tree_specs(tp_rules(), {"step": ()})["step"] == P()

    def test_none_leaf_passes_through(self):
        assert tree_specs(tp_rules(), {"x": None})["x"] is None


class TestSpecDedup:
    def test_tuple_rule_partial_overlap(self):
        r = ShardingRules.of(batch=("pod", "data"), zero1=("data", "model"))
        # batch consumes pod+data; zero1 keeps only the unused model axis.
        assert r.spec(("batch", "zero1")) == P(("pod", "data"), ("model",))
        assert r.spec(("zero1", "batch")) == P(("data", "model"), ("pod",))

    def test_fully_consumed_tuple_falls_back_to_none(self):
        r = ShardingRules.of(a=("data",), b=("data",))
        assert r.spec(("a", "b")) == P(("data",), None)


class TestConstrain:
    def test_noop_without_rules_or_mesh(self):
        x = jnp.ones((4, 4))
        assert constrain(x, None, ("batch", "d_model")) is x
        # Pure rule tables (no mesh attached) gate to a no-op too.
        assert constrain(x, tp_rules(), ("batch", "d_model")) is x

    def test_applies_constraint_with_mesh(self):
        mesh = jax.make_mesh(
            (1,), ("data",),
            axis_types=(jax.sharding.AxisType.Auto,),
        )
        rules = tp_rules(data=("data",)).with_mesh(mesh)

        @jax.jit
        def f(x):
            return constrain(x, rules, ("batch", "d_model")) * 2.0

        out = f(jnp.ones((4, 8)))
        assert out.shape == (4, 8)
        assert float(out[0, 0]) == 2.0


class TestCollectiveSpans:
    """S3 (ISSUE 10): collectives emit per-collective spans on a ``dist``
    stream through the module tracer installed with ``set_tracer``."""

    def test_ring_allreduce_emits_dist_span(self):
        from jax import shard_map
        from repro.dist import ring_allreduce, set_tracer
        from repro.obs.trace import Tracer

        mesh = jax.make_mesh((1,), ("data",))
        tracer = Tracer()
        prev = set_tracer(tracer)
        try:
            f = shard_map(lambda x: ring_allreduce(x, "data"), mesh=mesh,
                          in_specs=P("data"), out_specs=P("data"))
            out = f(jnp.arange(8.0))
        finally:
            set_tracer(prev)
        assert jnp.allclose(out, jnp.arange(8.0))  # n=1: identity
        spans = [e for e in tracer.events
                 if e["name"] == "collective:ring_allreduce"]
        assert spans
        e = spans[0]
        assert e["stream"] == "dist" and e["cat"] == "dist"
        assert e["args"]["axis"] == "data"
        assert e["args"]["n"] == 1 and e["args"]["size"] == 8

    def test_hierarchical_allreduce_span_and_default_null(self):
        from repro.dist import hierarchical_grad_allreduce, set_tracer
        from repro.dist import collectives
        from repro.obs.trace import NULL_TRACER, Tracer

        # default tracer is the no-op singleton
        assert collectives._TRACER is NULL_TRACER
        tracer = Tracer()
        prev = set_tracer(tracer)
        try:
            grads = {"w": jnp.ones((2,)), "b": jnp.zeros((3,))}
            out = hierarchical_grad_allreduce(grads, intra_axes=(),
                                              inter_axes=())
        finally:
            restored = set_tracer(prev)
        assert restored is tracer  # set_tracer returns the previous tracer
        assert collectives._TRACER is NULL_TRACER
        assert out["w"].shape == (2,)
        spans = [e for e in tracer.events
                 if e["name"] == "collective:hierarchical_grad_allreduce"]
        assert spans and spans[0]["args"]["leaves"] == 2

    def test_set_tracer_none_restores_null(self):
        from repro.dist import set_tracer
        from repro.dist import collectives
        from repro.obs.trace import NULL_TRACER, Tracer

        set_tracer(Tracer())
        set_tracer(None)
        assert collectives._TRACER is NULL_TRACER
