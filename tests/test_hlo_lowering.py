"""Lowering verification: the compiled program contains exactly the grouped
collectives the schedule (and the cost model) assume.

The cost model prices a stage as one grouped reduce-scatter/all-gather pair
riding the stage's axis (``flextree_tpu/planner/cost_model.py``); round 1
never verified that the XLA lowering actually produces that sequence.  These
tests pin it: per-stage op counts, per-stage ``replica_groups`` shapes, no
``all_to_all``, and — for the non-sum ring exchange — the per-hop message
size (the ``(w-1)/w``-of-the-tile traffic contract of the reference's
per-block path, ``mpi_mod.hpp:454-660``).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from flextree_tpu.parallel import tree_allreduce
from flextree_tpu.parallel.mesh import flat_mesh

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)

COUNT = 64  # elements per device; divisible by 8 so no tail collective


def _stablehlo(topo, op="sum", count=COUNT):
    mesh = flat_mesh(8, "ft")

    def f(row):
        return tree_allreduce(row[0], "ft", topo, op=op)[None]

    return (
        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft")))
        .lower(jnp.zeros((8, count), jnp.int32 if op != "sum" else jnp.float32))
        .as_text()
    )


def _group_shapes(ir: str, op_name: str) -> list[str]:
    """replica_groups tensor shapes (e.g. '2x4') for each ``op_name`` op."""
    shapes = []
    for m in re.finditer(rf'"stablehlo.{op_name}"\(.*?\n', ir):
        tail = ir[m.start() : m.start() + 2000]
        g = re.search(r"replica_groups = dense<.*?> : tensor<(\d+x\d+)xi64>", tail)
        if g:
            shapes.append(g.group(1))
    return shapes


@pytest.mark.parametrize(
    "topo,expect_stage_groups",
    [
        # (4,2): stage0 = 2 groups of 4, stage1 = 4 groups of 2
        ((4, 2), ["2x4", "4x2"]),
        # (2,2,2): every stage = 4 groups of 2
        ((2, 2, 2), ["4x2", "4x2", "4x2"]),
    ],
)
def test_sum_tree_lowers_to_grouped_rs_ag(topo, expect_stage_groups):
    ir = _stablehlo(topo)
    rs = _group_shapes(ir, "reduce_scatter")
    ag = _group_shapes(ir, "all_gather")
    assert rs == expect_stage_groups, f"reduce_scatter stages {rs} in:\n{ir[:500]}"
    # phase 2 unwinds in reverse
    assert ag == list(reversed(expect_stage_groups)), f"all_gather stages {ag}"
    assert "all_to_all" not in ir
    assert "stablehlo.all_reduce" not in ir  # not a degenerate flat fusion


def test_flat_sum_uses_ungrouped_pair():
    ir = _stablehlo((8,))
    assert ir.count("stablehlo.reduce_scatter") == 1
    assert ir.count('"stablehlo.all_gather"') == 1
    assert "all_to_all" not in ir


def test_generic_op_tree_uses_ring_exchange():
    """Non-sum stages must be the ppermute ring (one collective_permute per
    stage, iterated w-1 times) moving tile/w elements per hop — not the
    round-1 all_gather+fold that moved the whole group payload."""
    topo = (4, 2)
    ir = _stablehlo(topo, op="bor")
    n_cp = ir.count('"stablehlo.collective_permute"')
    assert n_cp == len(topo), f"expected {len(topo)} ring exchanges, got {n_cp}"
    # phase 1 must not all_gather; phase 2 has exactly one per stage
    assert len(_group_shapes(ir, "all_gather")) == len(topo)
    assert "reduce_scatter" not in ir  # sum-only primitive
    # traffic: per-hop message is tile/w elements.  stage0: 64/4=16 i32;
    # stage1 tile=16, w=2 -> 8 i32.  Both appear as collective_permute
    # operand types.
    # The attribute dict between the operand list and the result type itself
    # contains nested ``<...>`` (e.g. ``#stablehlo.channel_handle<handle = 1,
    # type = 1>``), so don't try to span it with a regex — grab each
    # collective_permute line and read the ``: (tensor<NxTY>)`` operand type
    # at its end instead.
    msgs = []
    for line in ir.splitlines():
        if '"stablehlo.collective_permute"' not in line:
            continue
        m = re.search(r":\s*\(tensor<(\d+)xi32>\)", line)
        assert m, f"collective_permute line without i32 operand type: {line}"
        msgs.append(m.group(1))
    assert sorted(int(m) for m in msgs) == [8, 16], msgs


# ------------------------------------------------- bucketed-sync guard


def _collective_counts(ir: str) -> dict:
    return {
        "rs": ir.count('"stablehlo.reduce_scatter"'),
        "ag": ir.count('"stablehlo.all_gather"'),
        "ar": ir.count('"stablehlo.all_reduce"'),
        "cp": ir.count('"stablehlo.collective_permute"'),
    }


@pytest.mark.parametrize(
    "bucket_bytes,widths",
    [
        (1 << 30, dict(d_model=32, d_ff=64)),
        # the derived plan (PR 29) at widths whose MLP matrices (1 MiB) go
        # alone, in their own shape, while everything else is packed:
        # still buckets x stages, with more buckets of one leaf
        (None, dict(d_model=64, d_ff=8192)),
    ],
    ids=["one_bucket_a_group", "derived_plan_large_leaves_alone"],
)
def test_bucketed_train_step_collectives_bounded_by_buckets(
    bucket_bytes, widths
):
    """Regression tripwire against silently falling back to per-leaf sync:
    the lowered bucketed train step's scheduled-collective count must be
    bounded by buckets x stages, not leaves x stages.

    The train step's forward/backward have their own collectives (tp
    psums, loss reductions), identical across sync strategies — so the
    ``grad_topo="psum"`` lowering (whose FlexTree rs/ag count is zero) is
    the subtraction baseline isolating the sync's contribution.
    """
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.parallel.bucketing import plan_buckets, replication_key
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        make_train_step,
        state_specs,
    )

    model_cfg = TransformerConfig(
        vocab_size=64, n_heads=4, n_layers=2, **widths
    )
    mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, model_cfg), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)

    def lower(train_cfg):
        step = make_train_step(mesh, model_cfg, train_cfg)
        return step.lower(state_sds, tok, tok).as_text()

    per_leaf = _collective_counts(lower(TrainConfig(bucket_bytes=0)))
    bucketed = _collective_counts(lower(TrainConfig(bucket_bytes=bucket_bytes)))
    native = _collective_counts(lower(TrainConfig(grad_topo="psum")))

    # the sync's own scheduled collectives, by subtraction
    sync_rs_leaf = per_leaf["rs"] - native["rs"]
    sync_rs_bucket = bucketed["rs"] - native["rs"]
    sync_ag_leaf = per_leaf["ag"] - native["ag"]
    sync_ag_bucket = bucketed["ag"] - native["ag"]

    # expected bucket plan: same grouping the sync runs (flat topo per
    # axis -> 1 stage, so rs count == sum over buckets of their axis count)
    pspecs = state_specs(model_cfg, "tp")["params"]
    flat_g, treedef = jax.tree.flatten(state_sds["params"])
    flat_s = treedef.flatten_up_to(pspecs)
    axis_sizes = {"dp": 2, "sp": 2, "tp": 2}

    def local(g, spec):
        # the sync plans what a device holds: the shard of a tp-sharded leaf
        shape = jax.sharding.NamedSharding(mesh, spec).shard_shape(g.shape)
        return jax.ShapeDtypeStruct(shape, g.dtype)

    from flextree_tpu.schedule.stages import Topology

    buckets = plan_buckets(
        [local(g, s) for g, s in zip(flat_g, flat_s)], flat_s,
        ("dp", "sp", "tp"),
        topos={ax: Topology.flat(2) for ax in axis_sizes},
        axis_sizes=axis_sizes, bucket_bytes=bucket_bytes,
    )
    if bucket_bytes is None:
        alone = [b for b in buckets if not b.packed and b.nbytes >= 1 << 19]
        assert len(alone) == 4 and any(b.packed for b in buckets)
    expected_bucket_rs = sum(len(b.axes) for b in buckets)
    n_synced_leaves = sum(
        1 for s in flat_s if replication_key(s, ("dp", "sp", "tp"))
    )

    assert sync_rs_bucket == expected_bucket_rs, (sync_rs_bucket, buckets)
    assert sync_ag_bucket == expected_bucket_rs
    # the tripwire: per-leaf scales with leaves; bucketed must not
    assert sync_rs_leaf >= n_synced_leaves > len(buckets)
    assert sync_rs_bucket < sync_rs_leaf
    assert sync_ag_bucket < sync_ag_leaf
    # fused tails: at most one dense collective per (bucket, axis), vs one
    # per (leaf, axis) on the per-leaf path
    assert bucketed["ar"] <= per_leaf["ar"]


def test_chunked_allreduce_keeps_stage_collective_count():
    """chunks=C multiplies scheduled collectives by C (one rs+ag pair per
    chunk per stage) — never more — and introduces no all_to_all."""
    topo = (4, 2)
    chunks = 4
    mesh = flat_mesh(8, "ft")

    def f(row):
        return tree_allreduce(row[0], "ft", topo, chunks=chunks)[None]

    ir = (
        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft")))
        .lower(jnp.zeros((8, COUNT), jnp.float32))
        .as_text()
    )
    counts = _collective_counts(ir)
    assert counts["rs"] == chunks * len(topo)
    assert counts["ag"] == chunks * len(topo)
    assert "all_to_all" not in ir


def test_ring_lowering_is_permute_loop():
    from flextree_tpu.parallel import ring_allreduce

    mesh = flat_mesh(8, "ft")

    def f(row):
        return ring_allreduce(row[0], "ft")[None]

    ir = (
        jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft")))
        .lower(jnp.zeros((8, COUNT), jnp.float32))
        .as_text()
    )
    # two fori_loops (reduce-scatter walk + allgather walk), each with one
    # neighbor permute of split_size elements
    assert ir.count('"stablehlo.collective_permute"') == 2
    assert "all_reduce" not in ir
