"""Gradient bucketing/fusion: grouping, planning, and bitwise identity.

The bucketed sync (``parallel/bucketing.py``) is the production default
train path; the per-leaf sync stays as the A/B oracle.  These tests pin the
contract that makes that safe:

- :func:`replication_key` / :func:`spec_axes` — the shared grouping helper
  used by the per-leaf sync, the bucketed sync, and ``global_grad_norm``;
- :func:`plan_buckets` — leaves fuse only within a (replication-axis-set,
  dtype) group, greedily capped at the bucket size;
- :func:`choose_bucket_bytes` — the planner-derived bucket size follows the
  alpha-beta tradeoff (launch-heavy fabric -> few big buckets,
  bandwidth-heavy -> many pipelined buckets);
- **bitwise identity**: bucketed ``sync_grads`` output equals per-leaf
  output bit-for-bit across dtype mixes (f32/bf16), flat/tree/ring/lonely
  topologies, non-divisible tail sizes, the native-psum sentinel, and the
  chunk-pipelined execution mode.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flextree_tpu.parallel.bucketing import (
    DEFAULT_MAX_BUCKET_BYTES,
    Bucket,
    bucketed_sync_grads,
    plan_buckets,
    replication_key,
    spec_axes,
)
from flextree_tpu.parallel.mesh import flat_mesh
from flextree_tpu.parallel.allreduce import tree_allreduce
from flextree_tpu.parallel.train import (
    global_grad_norm,
    make_mesh_nd,
    resolve_axis_topos,
    sync_grads,
)
from flextree_tpu.planner.choose import choose_bucket_bytes
from flextree_tpu.planner.cost_model import LinkParams, TpuCostParams
from flextree_tpu.schedule.stages import Topology

MESH_AXES = ("dp", "sp", "tp")


# ---------------------------------------------------------- grouping helper


def test_spec_axes_names_and_order():
    assert spec_axes(P()) == ()
    assert spec_axes(None) == ()
    assert spec_axes(P(None, "tp")) == ("tp",)
    # sorted, nested tuples flattened
    assert spec_axes(P("tp", ("dp", "sp"))) == ("dp", "sp", "tp")
    assert spec_axes(P(("sp",), None, "dp")) == ("dp", "sp")


def test_replication_key_is_complement_in_mesh_order():
    assert replication_key(P(), MESH_AXES) == MESH_AXES
    assert replication_key(None, MESH_AXES) == MESH_AXES
    assert replication_key(P(None, "tp"), MESH_AXES) == ("dp", "sp")
    assert replication_key(P(("dp", "sp"), "tp"), MESH_AXES) == ()
    # order is mesh order, not spec order
    assert replication_key(P("sp"), ("tp", "sp", "dp")) == ("tp", "dp")


def test_global_grad_norm_groups_via_shared_helper():
    """grad-norm's axis-set grouping and bucketing's must agree: both key
    off the axes a spec NAMES (spec_axes).  Single-device smoke: the norm
    math itself is pinned by test_train_features."""
    g = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.asarray([[12.0]])}
    s = {"a": P(), "b": P()}
    assert float(global_grad_norm(g, s)) == pytest.approx(13.0)


# ---------------------------------------------------------- plan_buckets


def _sds(shape, dtype="float32"):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def test_plan_buckets_groups_by_axes_and_dtype():
    leaves = [
        _sds((8,)), _sds((8,), "bfloat16"), _sds((8,)),
        _sds((4, 2), "bfloat16"),
    ]
    specs = [P(), P(), P(None, "tp"), P()]
    buckets = plan_buckets(leaves, specs, MESH_AXES, bucket_bytes=1 << 30)
    keyed = {(b.axes, b.dtype): b.indices for b in buckets}
    assert keyed[(MESH_AXES, "float32")] == (0,)
    assert keyed[(MESH_AXES, "bfloat16")] == (1, 3)
    assert keyed[(("dp", "sp"), "float32")] == (2,)


def test_plan_buckets_respects_cap_and_keeps_order():
    leaves = [_sds((256,)) for _ in range(5)]  # 1 KiB each
    specs = [P()] * 5
    buckets = plan_buckets(leaves, specs, MESH_AXES, bucket_bytes=2048)
    assert [b.indices for b in buckets] == [(0, 1), (2, 3), (4,)]
    assert all(b.nbytes <= 2048 for b in buckets)
    # a single leaf larger than the cap still gets (its own) bucket
    big = plan_buckets([_sds((4096,))], [P()], MESH_AXES, bucket_bytes=64)
    assert [b.indices for b in big] == [(0,)]


def test_plan_buckets_skips_fully_sharded_and_size1_axes():
    leaves = [_sds((8,)), _sds((8,))]
    specs = [P(("dp", "sp"), "tp"), P(None, "tp")]
    # axis sizes: tp=1 collapses, dp/sp real
    buckets = plan_buckets(
        leaves, specs, MESH_AXES,
        axis_sizes={"dp": 2, "sp": 2, "tp": 1},
        bucket_bytes=1 << 30,
    )
    # leaf 0 is sharded over dp+sp (tp dropped: size 1) -> no sync at all;
    # leaf 1 replicates over dp, sp only
    assert len(buckets) == 1
    assert buckets[0].axes == ("dp", "sp")
    assert buckets[0].indices == (1,)


@pytest.mark.parametrize(
    "leaf,want",
    [
        # 16 MiB each: past the size at which packing lowers the priced
        # cost (planner.choose_in_place_bytes), every leaf goes alone, in
        # its own shape
        ((1 << 22,), [(0,), (1,), (2,), (3,)]),
        # 4 KiB each: under it, the four are packed into one capped bucket
        ((1 << 10,), [(0, 1, 2, 3)]),
    ],
    ids=["large_leaves_go_alone", "small_leaves_are_packed"],
)
def test_plan_buckets_derived_plan(leaf, want):
    leaves = [_sds(leaf) for _ in range(4)]
    specs = [P()] * 4
    topos = {ax: Topology.flat(2) for ax in MESH_AXES}
    buckets = plan_buckets(
        leaves, specs, MESH_AXES, topos=topos,
        axis_sizes={ax: 2 for ax in MESH_AXES}, bucket_bytes=None,
    )
    assert [b.indices for b in buckets] == want
    assert [b.packed for b in buckets] == [len(w) > 1 for w in want]
    # a packed bucket stays under the memory cap; a lone leaf is its own size
    assert all(b.nbytes <= DEFAULT_MAX_BUCKET_BYTES for b in buckets)


# ---------------------------------------------------------- bucket chooser


def _params(launch_us, bw_GBps=45.0):
    return TpuCostParams(
        ici=LinkParams(bandwidth_GBps=bw_GBps, latency_us=1.0),
        launch_us=launch_us,
    )


def test_choose_bucket_bytes_launch_heavy_fuses_everything():
    topo = Topology.flat(8)
    nbytes = 1 << 20
    # per-collective overhead huge vs byte time -> one bucket
    assert choose_bucket_bytes(
        nbytes, topo, n_leaves=64, params=_params(launch_us=1e6)
    ) == nbytes


def test_choose_bucket_bytes_bandwidth_heavy_pipelines():
    topo = Topology.flat(8)
    nbytes = 64 << 20
    # negligible fixed cost, slow fabric -> argmin lands on max buckets
    cap = choose_bucket_bytes(
        nbytes, topo, n_leaves=8, params=_params(launch_us=1e-9, bw_GBps=0.001)
    )
    assert cap == -(-nbytes // 8)  # k = n_leaves bound
    # bucket size shrinks (k grows) as launch overhead falls
    big = choose_bucket_bytes(nbytes, topo, n_leaves=8, params=_params(1e6))
    assert cap < big


def test_choose_bucket_bytes_validation():
    topo = Topology.flat(8)
    assert choose_bucket_bytes(0, topo, params=_params(1.0)) == 1
    with pytest.raises(ValueError, match="nbytes"):
        choose_bucket_bytes(-1, topo, params=_params(1.0))
    with pytest.raises(ValueError, match="topology"):
        choose_bucket_bytes(1024, [], params=_params(1.0))


# ---------------------------------------------------------- bitwise identity


def _rng_tree(seed, shapes_dtypes):
    rng = np.random.default_rng(seed)
    tree = {}
    for i, (shape, dtype) in enumerate(shapes_dtypes):
        x = rng.standard_normal(shape).astype(np.float32)
        tree[f"leaf{i}"] = jnp.asarray(x, dtype=jnp.dtype(dtype))
    return tree


def _run_sync(mesh, mesh_axes, tree, specs, grad_topo, bucket_bytes, chunks=1):
    topos = resolve_axis_topos(mesh, mesh_axes, grad_topo)

    def f(t):
        return sync_grads(
            t, specs, mesh_axes, topos, bucket_bytes=bucket_bytes, chunks=chunks
        )

    fn = jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(specs,), out_specs=specs, check_vma=False
        )
    )
    return fn(tree)


def _assert_bitwise(a_tree, b_tree):
    flat_a, td_a = jax.tree.flatten(a_tree)
    flat_b, td_b = jax.tree.flatten(b_tree)
    assert td_a == td_b
    for a, b in zip(flat_a, flat_b):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes(), "bucketed sync is not bitwise-identical"


# the dtype-mixed, tail-heavy leaf set: odd sizes force per-leaf tails on
# every topology, scalars force pure-tail leaves, bf16 forces a second group
_LEAVES_1D = [
    ((17,), "float32"),
    ((3, 3), "float32"),
    ((16,), "float32"),
    ((5,), "bfloat16"),
    ((1,), "float32"),
    ((2, 2), "bfloat16"),
    ((31,), "bfloat16"),
]


@pytest.mark.parametrize("topo", [None, "4,2", "2,2,2", "1"],
                         ids=["flat", "tree42", "tree222", "ring"])
@pytest.mark.parametrize("bucket_bytes", [None, 64, 1 << 30],
                         ids=["planner", "cap64B", "one-bucket"])
def test_bucketed_sync_bitwise_identical_1axis(topo, bucket_bytes):
    mesh = flat_mesh(8, "dp")
    tree = _rng_tree(0, _LEAVES_1D)
    specs = {k: P() for k in tree}
    per_leaf = _run_sync(mesh, ("dp",), tree, specs, topo, bucket_bytes=0)
    fused = _run_sync(mesh, ("dp",), tree, specs, topo, bucket_bytes=bucket_bytes)
    _assert_bitwise(per_leaf, fused)


@pytest.mark.parametrize("bucket_bytes", [None, 1 << 30],
                         ids=["planner", "one-bucket"])
def test_bucketed_sync_bitwise_identical_lonely_fallback(bucket_bytes):
    # bucket_bytes=None also covers the planner-derived sizing pricing a
    # LonelyTopology (choose_bucket_bytes routes it via lonely_allreduce_cost)
    mesh = make_mesh_nd(5, (5,), ("dp",))
    tree = _rng_tree(1, _LEAVES_1D)
    specs = {k: P() for k in tree}
    per_leaf = _run_sync(mesh, ("dp",), tree, specs, "4+1", bucket_bytes=0)
    fused = _run_sync(mesh, ("dp",), tree, specs, "4+1", bucket_bytes=bucket_bytes)
    _assert_bitwise(per_leaf, fused)


def test_choose_bucket_bytes_lonely_topology():
    t = Topology.resolve(5, "4+1")
    assert choose_bucket_bytes(1 << 20, t, n_leaves=8, params=_params(1e6)) == 1 << 20


@pytest.mark.parametrize("chunks", [2, 3], ids=["c2", "c3"])
def test_bucketed_sync_bitwise_identical_chunked(chunks):
    mesh = flat_mesh(8, "dp")
    tree = _rng_tree(2, _LEAVES_1D)
    specs = {k: P() for k in tree}
    per_leaf = _run_sync(mesh, ("dp",), tree, specs, "4,2", bucket_bytes=0)
    fused = _run_sync(
        mesh, ("dp",), tree, specs, "4,2", bucket_bytes=1 << 30, chunks=chunks
    )
    _assert_bitwise(per_leaf, fused)


def test_bucketed_sync_bitwise_identical_3axis_mixed_specs():
    """(2,2,2) mesh, sharded + replicated leaves, FlexTree on dp, native
    psum sentinel on sp, flat on tp — every sync strategy in one tree."""
    mesh = make_mesh_nd(8, (2, 2, 2), MESH_AXES)
    tree = _rng_tree(3, [
        ((16,), "float32"),          # replicated: syncs over dp, sp, tp
        ((4, 2), "float32"),         # tp-sharded: syncs over dp, sp
        ((4, 2), "float32"),         # fully sharded: no sync
        ((6,), "bfloat16"),          # replicated, second dtype group
        ((7,), "float32"),           # replicated, tail on every axis
    ])
    specs = {
        "leaf0": P(), "leaf1": P(None, "tp"), "leaf2": P(("dp", "sp"), "tp"),
        "leaf3": P(), "leaf4": P(),
    }
    grad_topo = {"dp": "2", "sp": "psum", "tp": None}
    per_leaf = _run_sync(mesh, MESH_AXES, tree, specs, grad_topo, bucket_bytes=0)
    fused = _run_sync(mesh, MESH_AXES, tree, specs, grad_topo, bucket_bytes=None)
    _assert_bitwise(per_leaf, fused)


@pytest.mark.parametrize("grad_chunks", [1, 2])
def test_fused_train_step_bitwise_identical_to_per_leaf(grad_chunks):
    """The sync identity carried through the whole step: forward, backward,
    the planner-derived bucketed sync (plain and chunk-pipelined) and AdamW
    leave every parameter with exactly the per-leaf step's bits."""
    from conftest import own_copy
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_train_step,
    )

    model = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    mesh = make_mesh_nd(8, (8, 1, 1), MESH_AXES)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
    state = init_train_state(jax.random.PRNGKey(0), model)
    per_leaf, _ = make_train_step(
        mesh, model, TrainConfig(grad_topo="4,2", bucket_bytes=0)
    )(own_copy(state), toks, toks)
    fused, _ = make_train_step(
        mesh, model, TrainConfig(grad_topo="4,2", grad_chunks=grad_chunks)
    )(state, toks, toks)
    _assert_bitwise(per_leaf["params"], fused["params"])


def test_single_leaf_bucket_compiles_identically():
    """The single-large-tensor regression guard, structurally: with one
    leaf there is nothing to fuse, and the bucketed sync must compile to
    the SAME program as per-leaf (modulo op-name metadata from the
    comm_span scopes) — so a fused-vs-per-leaf delta measured in that
    regime is noise, not a fusion cost."""
    from conftest import strip_hlo_debug

    mesh = flat_mesh(8, "dp")
    topos = resolve_axis_topos(mesh, ("dp",), None)
    tree = {"g": jnp.zeros((8, 4096), jnp.float32)}
    io_spec = {"g": P("dp")}

    def make(bucket_bytes):
        def f(t):
            rows = {k: v[0] for k, v in t.items()}
            out = sync_grads(
                rows, {"g": P()}, ("dp",), topos, bucket_bytes=bucket_bytes
            )
            return {k: v[None] for k, v in out.items()}

        return jax.jit(
            jax.shard_map(
                f, mesh=mesh, in_specs=(io_spec,), out_specs=io_spec,
                check_vma=False,
            )
        )

    per_leaf = strip_hlo_debug(make(0).lower(tree).compile().as_text())
    fused = strip_hlo_debug(make(None).lower(tree).compile().as_text())
    assert per_leaf == fused


# ---------------------------------------------------------- chunked allreduce


@pytest.mark.parametrize("topo", ["8", "4,2", "2,2,2"])
@pytest.mark.parametrize("count,chunks", [(64, 3), (67, 2), (24, 8), (7, 4)])
def test_chunked_tree_allreduce_bitwise(topo, count, chunks):
    """chunks > 1 must be a pure execution-schedule change: chunk
    boundaries sit at multiples of N and every stage collective is
    elementwise, so the result is bit-identical to the unchunked tree."""
    mesh = flat_mesh(8, "ft")
    rng = np.random.default_rng(count * chunks)
    data = jnp.asarray(rng.standard_normal((8, count)).astype(np.float32))

    def run(c):
        def f(row):
            return tree_allreduce(row[0], "ft", topo, chunks=c)[None]

        return jax.jit(
            jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"))
        )(data)

    a, b = np.asarray(run(1)), np.asarray(run(chunks))
    assert a.tobytes() == b.tobytes()


def test_chunk_sizes_balanced_multiples():
    from flextree_tpu.parallel.allreduce import _chunk_sizes

    assert _chunk_sizes(64, 8, 3) == [24, 24, 16]
    assert sum(_chunk_sizes(64, 8, 3)) == 64
    assert _chunk_sizes(24, 8, 8) == [8, 8, 8]  # capped at blocks
    assert _chunk_sizes(8, 8, 4) == [8]
    assert all(s % 8 == 0 for s in _chunk_sizes(72, 8, 4))


# ---------------------------------------------------------- observability


def test_comm_span_names_scope_and_feeds_the_ledger():
    from flextree_tpu.utils.profiling import comm_span, span_ledger

    with span_ledger() as ledger:
        with comm_span("ft_bucket0_dp_3leaves_128B"):
            pass
    assert ledger.names == ("ft_bucket0_dp_3leaves_128B",)
    assert ledger.total_bytes("ft_bucket") == 128

    # and it must be traceable: the named scope lands on the operation
    @jax.jit
    def f(x):
        with comm_span("ft_bucket_test"):
            return x * 2

    assert float(f(jnp.float32(2.0))) == 4.0
    assert "ft_bucket_test" in f.lower(jnp.float32(2.0)).as_text(debug_info=True)
