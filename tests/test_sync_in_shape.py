"""The gradient sync keeps a leaf in its own shape wherever it can.

A tree stage is elementwise across ranks and tiles dimension 0, so an N-D
leaf whose leading dimension divides by the axis size runs the identical
stages with no ``reshape(-1)`` in and no ``reshape(shape)`` out
(``parallel.allreduce._tree_keeps_shape``): on the TPU each of those is a
copy through HBM.  The planner gives such a leaf a bucket of its own once
packing it stops lowering the cost it prices
(``planner.choose_in_place_bytes``).  Pinned here:

- the in-shape path is **bitwise** the flattened path, for flat and
  multi-stage trees, 2-D and 3-D leaves, f32 and bf16, through ``allreduce``
  (the IR route) and ``tree_allreduce`` (the legacy executor) alike;
- where it cannot engage (leading dimension does not divide, the ring, a
  lonely shape, chunk-pipelining) the flat path runs and is still bitwise
  equal;
- the plan: which leaves go alone, what is packed, the counts and the
  provenance that says so;
- the lowered and compiled dp4-shaped train step holds no flat copy of a
  leaf that went in place.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flextree_tpu.parallel.allreduce import (
    _tree_keeps_shape,
    allreduce,
    tree_allreduce,
)
from flextree_tpu.parallel.bucketing import (
    bucketed_sync_grads,
    plan_buckets,
    plan_counts,
)
from flextree_tpu.parallel.mesh import flat_mesh
from flextree_tpu.parallel.train import (
    make_mesh_nd,
    resolve_axis_topos,
    sync_grads,
)
from flextree_tpu.planner.choose import choose_in_place_bytes
from flextree_tpu.planner.cost_model import LinkParams, TpuCostParams
from flextree_tpu.schedule.stages import Topology

# a fabric so slow that a leaf of a few hundred bytes is worth a collective
# of its own (75-384 bytes over the topologies below; 566 KB by the default
# constants): lets test leaves of a kilobyte go in place, beside packed ones
SLOW_WIRE = TpuCostParams(ici=LinkParams(bandwidth_GBps=0.008, latency_us=1.0))


def _collective(fn, n, x):
    """``fn`` of each rank's row of ``x`` (n, *shape), and its StableHLO."""
    mesh = flat_mesh(n, "ft")
    f = jax.jit(
        jax.shard_map(
            lambda r: fn(r[0])[None], mesh=mesh, in_specs=P("ft"),
            out_specs=P("ft"), check_vma=False,
        )
    )
    return np.asarray(f(x)), f.lower(x).as_text()


def _rows(n, shape, dtype="float32", seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.standard_normal((n,) + shape).astype(np.float32),
        dtype=jnp.dtype(dtype),
    )


def _reduce_scatters(ir):
    """(operand type, result type) of every reduce_scatter in StableHLO
    text (the op carries a region, so its types close it lines later)."""
    return re.findall(
        r'"stablehlo\.reduce_scatter"(?:.|\n)*?\}\) : \(tensor<([^>]*)>\) -> '
        r"tensor<([^>]*)>", ir,
    )


def _flat_path(topo, entry):
    return lambda v: entry(v.reshape(-1), "ft", topo).reshape(v.shape)


# ------------------------------------------------------------ the in-shape path

TREES = [(4, "4"), (4, "2,2"), (8, "8"), (8, "4,2"), (8, "2,2,2")]


@pytest.mark.parametrize("entry", [allreduce, tree_allreduce],
                         ids=["ir_route", "legacy"])
@pytest.mark.parametrize("shape", [(16, 6), (8, 3, 5)], ids=["2d", "3d"])
@pytest.mark.parametrize("n,topo", TREES, ids=[t for _, t in TREES])
def test_in_shape_is_bitwise_the_flattened_path(n, topo, shape, entry):
    x = _rows(n, shape, seed=n + len(shape))
    got, ir = _collective(lambda v: entry(v, "ft", topo), n, x)
    want, _ = _collective(_flat_path(topo, entry), n, x)
    assert got.tobytes() == want.tobytes()
    # the first stage scatters the leaf itself: its operand has the leaf's
    # shape, not the flat view's
    dims = "x".join(str(d) for d in shape)
    assert _reduce_scatters(ir)[0][0] == f"{dims}xf32", ir


@pytest.mark.parametrize("n,topo", TREES, ids=[t for _, t in TREES])
def test_in_shape_bf16_is_bitwise_the_flattened_path(n, topo):
    x = _rows(n, (16, 4), "bfloat16", seed=3)
    got, _ = _collective(lambda v: allreduce(v, "ft", topo), n, x)
    want, _ = _collective(_flat_path(topo, allreduce), n, x)
    assert got.tobytes() == want.tobytes()


def test_in_shape_non_sum_op_runs_the_ring_stages_on_the_leaf():
    x = jnp.asarray(
        np.random.default_rng(5).integers(0, 255, size=(8, 16, 3)), jnp.int32
    )
    got, _ = _collective(lambda v: allreduce(v, "ft", "4,2", op="bor"), 8, x)
    want, _ = _collective(
        lambda v: allreduce(v.reshape(-1), "ft", "4,2", op="bor").reshape(
            v.shape
        ),
        8, x,
    )
    assert got.tobytes() == want.tobytes()


def test_tree_keeps_shape_rule():
    def sds(*s):
        return jax.ShapeDtypeStruct(s, jnp.float32)

    assert _tree_keeps_shape(sds(8, 3), 4)
    assert _tree_keeps_shape(sds(16, 2, 2), 8)
    assert not _tree_keeps_shape(sds(6, 4), 4)     # leading dim does not divide
    assert not _tree_keeps_shape(sds(16), 4)       # 1-D is its own flat view
    assert not _tree_keeps_shape(sds(4, 0), 4)     # nothing to move
    assert not _tree_keeps_shape(sds(16, 4), 4, chunks=2)  # chunks slice flat
    assert _tree_keeps_shape(sds(4, 1), 4, chunks=2)  # one block: one chunk


# ------------------------------------------------------------------ fallbacks


@pytest.mark.parametrize(
    "n,topo,shape,chunks",
    [
        (4, "4", (6, 5), 1),        # leading dimension does not divide
        (8, "4,2", (12, 3), 1),     # divides the first width, not the product
        (8, "1", (16, 4), 1),       # the ring
        (4, "3+1", (9, 4), 1),      # a lonely shape
        (8, "3,2+2", (12, 3), 1),
        (8, "4,2", (16, 6), 3),     # chunk-pipelined
    ],
    ids=["nodiv", "nodiv_product", "ring", "lonely31", "lonely322", "chunked"],
)
def test_fallback_is_bitwise_the_flattened_path(n, topo, shape, chunks):
    x = _rows(n, shape, seed=7)
    got, ir = _collective(
        lambda v: allreduce(v, "ft", topo, chunks=chunks), n, x
    )
    want, _ = _collective(
        lambda v: allreduce(
            v.reshape(-1), "ft", topo, chunks=chunks
        ).reshape(v.shape),
        n, x,
    )
    assert got.tobytes() == want.tobytes()
    # nothing collective runs on the N-D leaf itself
    dims = "x".join(str(d) for d in shape)
    assert all(src != f"{dims}xf32" for src, _ in _reduce_scatters(ir)), ir
    for line in ir.splitlines():
        if "collective_permute" in line:
            assert f"(tensor<{dims}xf32>)" not in line, line


# -------------------------------------------------- the sync over a whole tree


def _sync(mesh, axes, tree, specs, grad_topo, **kw):
    topos = resolve_axis_topos(mesh, axes, grad_topo)

    def f(t):
        if "params" in kw:  # only the bucketed entry takes cost constants
            return bucketed_sync_grads(t, specs, axes, topos, **kw)
        return sync_grads(t, specs, axes, topos, **kw)

    return jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(specs,), out_specs=specs, check_vma=False
        )
    )(tree)


def _tree(seed, shapes_dtypes):
    rng = np.random.default_rng(seed)
    return {
        f"leaf{i}": jnp.asarray(
            rng.standard_normal(s).astype(np.float32), dtype=jnp.dtype(d)
        )
        for i, (s, d) in enumerate(shapes_dtypes)
    }


# small leaves around large ones, as a layer's norm scales lie round its
# matrices; one large leaf whose leading dimension does not divide by 8
_MIXED = [
    ((8,), "float32"), ((16, 24), "float32"), ((8,), "float32"),
    ((24, 16), "float32"), ((3,), "float32"), ((8, 4, 6), "float32"),
    ((16, 8), "bfloat16"), ((5,), "bfloat16"), ((12, 17), "float32"),
]


@pytest.mark.parametrize("topo", [None, "4,2", "2,2,2", "1", "psum"],
                         ids=["flat", "tree42", "tree222", "ring", "psum"])
def test_default_plan_with_leaves_in_place_is_bitwise_per_leaf(topo):
    mesh = flat_mesh(8, "dp")
    tree = _tree(11, _MIXED)
    specs = {k: P() for k in tree}
    per_leaf = _sync(mesh, ("dp",), tree, specs, topo, bucket_bytes=0)
    planned = _sync(
        mesh, ("dp",), tree, specs, topo, bucket_bytes=None, params=SLOW_WIRE
    )
    for k in tree:
        a, b = np.asarray(per_leaf[k]), np.asarray(planned[k])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), k


def test_default_plan_with_leaves_in_place_is_bitwise_per_leaf_lonely():
    mesh = make_mesh_nd(5, (5,), ("dp",))
    tree = _tree(12, _MIXED)
    specs = {k: P() for k in tree}
    per_leaf = _sync(mesh, ("dp",), tree, specs, "4+1", bucket_bytes=0)
    planned = _sync(
        mesh, ("dp",), tree, specs, "4+1", bucket_bytes=None, params=SLOW_WIRE
    )
    for k in tree:
        assert np.asarray(per_leaf[k]).tobytes() == np.asarray(planned[k]).tobytes()


# --------------------------------------------------------------------- the plan


def _sds(shape, dtype="float32"):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def test_choose_in_place_bytes_is_where_two_buckets_stop_costing_more():
    """T(k) = k * fixed + byte * (k + 1) / (2k): at the size returned, two
    such leaves cost the same packed (k=1) as apart (k=2)."""
    from flextree_tpu.planner.cost_model import allreduce_cost

    for topo in (Topology.flat(4), Topology(8, (4, 2)), Topology.ring(8)):
        p = TpuCostParams()
        b = choose_in_place_bytes(topo, params=p)
        fixed = allreduce_cost(topo, 0, p).total_us
        byte = allreduce_cost(topo, 2 * b, p).total_us - fixed
        assert fixed + byte == pytest.approx(2 * fixed + 0.75 * byte, rel=1e-3)
    # a dearer launch packs more, a slower wire packs less
    base = choose_in_place_bytes(Topology.flat(4), params=TpuCostParams())
    assert choose_in_place_bytes(
        Topology.flat(4), params=TpuCostParams(launch_us=20.0)
    ) > base
    assert choose_in_place_bytes(Topology.flat(4), params=SLOW_WIRE) < base
    # one size for a leaf synced over two axes: both launches are saved
    assert choose_in_place_bytes(
        [Topology.flat(4), Topology.flat(2)], params=TpuCostParams()
    ) > 0
    with pytest.raises(ValueError, match="topology"):
        choose_in_place_bytes([], params=TpuCostParams())


def test_large_leaf_between_small_ones_no_longer_closes_their_bucket():
    big = choose_in_place_bytes(Topology.flat(4), params=TpuCostParams())
    n_big = -(-big // 4)
    leaves = [_sds((8,)), _sds((n_big,)), _sds((8,)), _sds((n_big, 2)), _sds((8,))]
    buckets = plan_buckets(
        leaves, [P()] * 5, ("dp",), topos={"dp": Topology.flat(4)},
        axis_sizes={"dp": 4}, bucket_bytes=None,
    )
    assert [(b.indices, b.packed) for b in buckets] == [
        ((0, 2, 4), True), ((1,), False), ((3,), False),
    ]
    assert plan_counts(buckets) == {
        "in_place_leaves": 2, "packed_leaves": 3,
        "in_place_bytes": 4 * n_big * 3, "packed_bytes": 96,
    }
    # one byte under the size, the leaf is packed like any other
    small = plan_buckets(
        [_sds((8,)), _sds((n_big - 1,)), _sds((8,))], [P()] * 3, ("dp",),
        topos={"dp": Topology.flat(4)}, axis_sizes={"dp": 4},
        max_bucket_bytes=1 << 30,
    )
    assert [b.indices for b in small] == [(0, 1, 2)]


@pytest.mark.parametrize(
    "kw,want",
    [
        (dict(bucket_bytes=1 << 30), [(0, 1, 2)]),
        (dict(bucket_bytes=64), [(0,), (1,), (2,)]),
        # the derived cap (16 MiB in two or more buckets): the large leaf
        # closes the first small one's bucket, as every plan did before
        (dict(sharded=True), [(0,), (1,), (2,)]),
        (dict(codec="int8"), [(0,), (1,), (2,)]),
    ],
    ids=["explicit_cap", "explicit_small_cap", "sharded", "lossy_codec"],
)
def test_explicit_cap_sharded_and_lossy_plans_pack_as_before(kw, want):
    """Only the derived plan of the exact replicated sync sets large leaves
    apart and packs the small ones across them; an explicit cap, the ZeRO
    plan and a lossy codec's plan stay consecutive and greedy."""
    from flextree_tpu.ops.quantize import get_codec

    if "codec" in kw:
        kw = dict(codec=get_codec(kw["codec"]))
    leaves = [_sds((8,)), _sds((1 << 20, 4)), _sds((8,))]
    args = (leaves, [P()] * 3, ("dp",))
    common = dict(
        topos={"dp": Topology.flat(4)}, axis_sizes={"dp": 4},
        max_bucket_bytes=1 << 30,
    )
    buckets = plan_buckets(*args, **common, **kw)
    assert [b.indices for b in buckets] == want
    derived = plan_buckets(*args, **common)
    assert [b.indices for b in derived] == [(0, 2), (1,)]


def test_benchmark_configuration_plan_is_43_in_place_and_15_packed():
    """``pythia-1.4b`` at the benchmark's depth 7 on mesh (4,1,1): every
    matrix and the embedding go alone (43 leaves, 1.82 GB), the 15 norm
    scales (8 KB each) share one bucket."""
    import json
    import pathlib

    from flextree_tpu.models.transformer import (
        TransformerConfig,
        init_params,
        param_specs,
    )

    c = json.loads(
        (pathlib.Path(__file__).parent.parent
         / "benchmarks/configs/pythia-1.4b.json").read_text()
    )
    cfg = TransformerConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_layers=c["num_hidden_layers"],
        d_ff=c["intermediate_size"],
    )
    assert cfg.n_layers == 7
    shapes = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    flat, treedef = jax.tree.flatten(shapes)
    axes = ("dp", "sp", "tp")
    sizes = {"dp": 4, "sp": 1, "tp": 1}
    for cap in (None, 64 << 20):  # the CPU's cap here, the TPU's there
        buckets = plan_buckets(
            flat, treedef.flatten_up_to(param_specs(cfg, "tp")), axes,
            topos={ax: Topology.flat(n) for ax, n in sizes.items()},
            axis_sizes=sizes, max_bucket_bytes=cap,
        )
        assert plan_counts(buckets) == {
            "in_place_leaves": 43, "packed_leaves": 15,
            "in_place_bytes": 1_821_376_512, "packed_bytes": 15 * 8192,
        }
        assert len(buckets) == 44
        assert sorted(len(b.indices) for b in buckets) == [1] * 43 + [15]
        assert all(
            flat[b.indices[0]].shape[0] % 4 == 0 and flat[b.indices[0]].ndim == 2
            for b in buckets if not b.packed
        )


# ------------------------------------------------------------ what is recorded


def test_provenance_says_packed_and_the_plan_counts_its_leaves():
    from flextree_tpu.obs import flight_recorder

    mesh = flat_mesh(4, "dp")
    tree = _tree(13, _MIXED[:6])
    specs = {k: P() for k in tree}
    with flight_recorder(None) as rec:
        _sync(
            mesh, ("dp",), tree, specs, None, bucket_bytes=None,
            params=SLOW_WIRE,
        )
        events = list(rec.events)
    planned = [e for e in events if e["kind"] == "bucket_planned"]
    assert [e["packed"] for e in planned] == [e["n_leaves"] > 1 for e in planned]
    assert sum(not e["packed"] for e in planned) == 3
    (plan,) = [e for e in events if e["kind"] == "bucket_plan"]
    assert {k: plan[k] for k in (
        "in_place_leaves", "packed_leaves", "in_place_bytes", "packed_bytes",
        "n_buckets",
    )} == {
        "in_place_leaves": 3, "packed_leaves": 3,
        "in_place_bytes": 4 * (16 * 24 + 24 * 16 + 8 * 4 * 6),
        "packed_bytes": 4 * (8 + 8 + 3), "n_buckets": 4,
    }


def test_trainer_start_up_line_prints_the_four_counts(capsys):
    from flextree_tpu import trainer

    assert trainer.main([
        "--devices", "4", "--mesh", "4,1,1", "--vocab", "64", "--d-model", "64",
        "--n-heads", "2", "--n-layers", "1", "--d-ff", "4096", "--steps", "1",
        "--batch", "4", "--seq-len", "8", "--corpus-tokens", "2000",
    ]) == 0
    out = capsys.readouterr().out
    # w1 and w2 (1 MiB each) go alone; wq/wk/wv/wo and the embedding
    # (16 KiB each) and the three norm scales share one bucket
    assert (
        "planner constants: built-in defaults (not calibrated on this "
        "fabric); gradient sync: 2 leaves / 2097152 bytes in place, "
        "8 leaves / 82688 bytes packed\n"
    ) in out


# ------------------------------------------------------- the step's own program


def _dp4_step():
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_train_step,
    )

    cfg = TransformerConfig(
        vocab_size=640, d_model=256, n_heads=2, n_layers=2, d_ff=1024
    )
    mesh = make_mesh_nd(4, (4, 1, 1), ("dp", "sp", "tp"))
    state = jax.eval_shape(
        lambda k: init_train_state(k, cfg), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((4, 16), jnp.int32)
    step = make_train_step(mesh, cfg, TrainConfig())
    return step.lower(state, tok, tok), state["params"]


def test_dp4_step_syncs_each_in_place_leaf_in_its_own_shape():
    """mesh (4,1,1), widths at which the planner's default constants send
    the MLP's matrices and the embedding alone (1 MiB and 640 KiB): per
    leaf that goes alone ONE reduce-scatter + all-gather pair in the
    leaf's shape, and nowhere a flat array of its size — not in what JAX
    lowers, not in what XLA compiles.  (On the CPU the packed buckets are
    capped at 128 KiB, so the 256 KiB attention matrices go alone as well;
    the plan itself is the oracle for which.)"""
    from flextree_tpu.models.transformer import TransformerConfig, param_specs

    lowered, params = _dp4_step()
    leaves, treedef = jax.tree.flatten(params)
    cfg = TransformerConfig(
        vocab_size=640, d_model=256, n_heads=2, n_layers=2, d_ff=1024
    )
    sizes = {"dp": 4, "sp": 1, "tp": 1}
    buckets = plan_buckets(
        leaves, treedef.flatten_up_to(param_specs(cfg, "tp")),
        ("dp", "sp", "tp"),
        topos={ax: Topology.flat(n) for ax, n in sizes.items()},
        axis_sizes=sizes,
    )
    alone = [leaves[b.indices[0]].shape for b in buckets if not b.packed]
    alone = [s for s in alone if len(s) == 2]  # a lone norm scale is 1-D
    assert {(256, 1024), (1024, 256), (640, 256)} <= set(alone)
    assert any(b.packed for b in buckets)

    ir = lowered.as_text()
    scatters = _reduce_scatters(ir)
    # collectives = buckets x stages: the flat tree has one stage
    assert len(scatters) == len(buckets)
    for rows, cols in set(alone):
        n = alone.count((rows, cols))
        ins, outs = f"{rows}x{cols}xf32", f"{rows // 4}x{cols}xf32"
        assert scatters.count((ins, outs)) == n, (rows, cols)
        assert len(re.findall(
            rf'"stablehlo.all_gather"\(%\w+\).*\(tensor<{outs}>\)'
            rf" -> tensor<{ins}>", ir,
        )) == n, (rows, cols)
        assert f"tensor<{rows * cols}xf32>" not in ir, (rows, cols)

    hlo = lowered.compile().as_text()
    for rows, cols in set(alone):
        assert f"f32[{rows * cols}]" not in hlo, (rows, cols)
        assert re.search(
            rf"f32\[{rows},{cols}\]\S* (all-reduce|all-gather)(-start|-done)?\(",
            hlo,
        ), (rows, cols)
