"""The grouped matmul of a decode round's expert products
(``ops/grouped_matmul.py``) under the interpreter, against
``lax.ragged_dot``; ``moe.dropless_experts`` through both lowerings; the
rule that chooses one, and the counter made from it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from flextree_tpu.models.configs import BLOCKS, config_from_dict
from flextree_tpu.models.moe import dropless_experts, expert_kernel_layers
from flextree_tpu.ops.grouped_matmul import (
    ROW_TILE, group_visits, grouped_kernel_admits, grouped_matmul,
    runs_grouped_kernel,
)
from flextree_tpu.utils import backend

# (sizes a group, rows M): K = 128 throughout
GROUPS = {
    "even": ([16, 16, 16, 16], 64),
    "experts-with-no-row": ([5, 0, 30, 0, 0, 7], 64),
    "every-row-on-one-expert": ([0, 64, 0, 0], 64),
    "no-local-row-at-all": ([0, 0, 0, 0], 64),
    "sum-short-of-the-rows": ([3, 2, 1, 4], 96),
    "a-group-straddles-three-tiles": ([9, 70, 3], 96),
    "one-row-each": ([1] * 20, 32),
}


def _operands(sizes, m, n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    g, k = len(sizes), 128
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa: E731
    return (mk(m, k), mk(g, k, n) / 8, mk(g, k, n) / 8,
            jnp.asarray(sizes, jnp.int32))


def _reached(got, want, sizes):
    """``got`` is ``want`` on the groups' rows and zero on the rest of the
    last tile a group reaches (with no row at all, of the first tile);
    further tiles are nobody's."""
    total = int(np.sum(sizes))
    edge = max(-(-total // ROW_TILE) * ROW_TILE, ROW_TILE)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got[:total], want[:total], atol=1e-4, rtol=1e-2)
    assert not got[total:edge].any()


@pytest.mark.parametrize("n,tn", [(256, None), (256, 128), (384, None), (384, 128)],
                         ids=["n256", "n256-slabs", "n384", "n384-slabs"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_the_kernel_is_ragged_dot_on_the_groups_rows(case, n, tn):
    """Row tiles of 32 against XLA's grouped product, the weight block
    whole or in slabs of N (384 is the state cell's 18 lane tiles scaled
    down: 3, no power of two)."""
    sizes, m = GROUPS[case]
    xs, w, _, sz = _operands(sizes, m, n, jnp.float32)
    got = grouped_matmul(xs, w, sz, tn=tn)
    assert got.dtype == jnp.float32 and got.shape == (m, n)
    want = lax.ragged_dot(xs, w, sz, preferred_element_type=jnp.float32,
                          precision=lax.Precision.HIGHEST)
    _reached(got, want, sizes)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(GROUPS))
def test_the_gated_product_keeps_the_activation_in_the_held_type(case, dtype):
    """``silu(x W_gate) * (x W_up)`` in one call: both products in f32,
    the result in the operands' type."""
    sizes, m = GROUPS[case]
    dtype = jnp.dtype(dtype)
    xs, w, wg, sz = _operands(sizes, m, 256, dtype, seed=1)
    got = grouped_matmul(xs, w, sz, wg)
    assert got.dtype == dtype and got.shape == (m, 256)
    f32 = dict(preferred_element_type=jnp.float32, precision=lax.Precision.HIGHEST)
    want = (
        jax.nn.silu(lax.ragged_dot(xs, wg, sz, **f32)) * lax.ragged_dot(xs, w, sz, **f32)
    ).astype(dtype)
    _reached(got, want, sizes)


@pytest.mark.parametrize("case", list(GROUPS))
def test_the_visits_name_every_pair_of_tile_and_group_that_holds_a_row(case):
    sizes, m = GROUPS[case]
    group, tile, offsets, count = jax.tree.map(
        np.asarray, group_visits(jnp.asarray(sizes, jnp.int32), m))
    assert group.shape == tile.shape == (m // ROW_TILE + len(sizes) - 1,)
    assert list(offsets) == [0, *np.cumsum(sizes)]
    want = [
        (t, g) for t in range(m // ROW_TILE) for g, n in enumerate(sizes)
        if n and offsets[g] < (t + 1) * ROW_TILE and offsets[g + 1] > t * ROW_TILE
    ]
    if not want:  # one visit of an empty group, which zeroes a tile
        assert count == 1 and offsets[group[0]] == offsets[group[0] + 1]
        return
    assert count == len(want)
    assert list(zip(tile[:count], group[:count])) == want


def test_shapes_that_are_no_whole_tiles_are_refused_by_name():
    xs, w, _, sz = _operands([4, 4], 24, 256, jnp.float32)
    with pytest.raises(ValueError, match="whole row tiles"):
        grouped_matmul(xs, w, sz)
    xs, w, _, sz = _operands([4, 4], 32, 192, jnp.float32)
    with pytest.raises(ValueError, match="whole lane tiles"):
        grouped_matmul(xs, w, sz)


# ------------------------------------- dropless_experts, both lowerings


def _layer(n, d, f, held, n_experts, k, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.standard_normal(s), dtype)  # noqa: E731
    lo, hi = held
    experts = {
        "w_gate": mk(hi - lo, d, f) / 8, "w_up": mk(hi - lo, d, f) / 8,
        "w_down": mk(hi - lo, f, d) / 8,
    }
    picks = np.stack([rng.permutation(n_experts)[:k] for _ in range(n)])
    weights = jnp.asarray(rng.random((n, k)), jnp.float32)
    return mk(n, d), jnp.asarray(picks, jnp.int32), weights, experts


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [None, "some", "none"])
@pytest.mark.parametrize("held", [(0, 8), (4, 12), (0, 32)],
                         ids=["first-8", "middle-8", "all-32"])
def test_dropless_experts_is_one_result_through_both_lowerings(held, rows, dtype):
    """The same picks through ``lax.ragged_dot`` and through the kernel:
    every local pick computed, ``sizes`` alike, the sums equal within what
    two orders of f32 accumulation differ by."""
    dtype = jnp.dtype(dtype)
    n = 24
    h, picks, weights, experts = _layer(n, 128, 256, held, 32, 4, seed=3, dtype=dtype)
    mask = {None: None, "some": jnp.arange(n) % 3 != 0,
            "none": jnp.zeros((n,), bool)}[rows]
    run = lambda impl: jax.jit(  # noqa: E731
        lambda *a: dropless_experts(*a, held, mask, impl=impl)
    )(h, picks, weights, experts)
    (want, want_sizes), (got, got_sizes) = run("ragged"), run("pallas")
    assert list(np.asarray(got_sizes)) == list(np.asarray(want_sizes))
    local = (np.asarray(picks) >= held[0]) & (np.asarray(picks) < held[1])
    if mask is not None:
        local &= np.asarray(mask)[:, None]
    assert int(got_sizes.sum()) == int(local.sum())
    assert bool(jnp.isfinite(got).all())
    scale = float(jnp.abs(want).max()) or 1.0
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want),
        atol=(2e-5 if dtype == jnp.float32 else 2e-2) * scale)


def test_an_unknown_lowering_is_refused():
    h, picks, weights, experts = _layer(8, 128, 128, (0, 4), 8, 2, seed=0)
    with pytest.raises(ValueError, match="unknown grouped-product impl"):
        dropless_experts(h, picks, weights, experts, (0, 4), impl="mosaic")


# ----------------------------------------------- the rule and the counter


def _shapes(rows, groups, k, n, dtype="bfloat16", w_dtype=None):
    return (jax.ShapeDtypeStruct((rows, k), jnp.dtype(dtype)),
            jax.ShapeDtypeStruct((groups, k, n), jnp.dtype(w_dtype or dtype)))


@pytest.mark.parametrize("shape,admitted,runs", [
    ((1024, 32, 2304, 1024), True, True),    # the state cell's round
    ((640, 128, 3072, 1024), True, True),    # Laguna's
    ((256, 16, 7680, 2048), True, True),     # the latent cell's
    ((4096, 32, 2304, 1024), True, False),   # a 512-token prefill there
    ((32768, 32, 2304, 1024), True, False),  # a 4,096-token one
    ((96, 16, 64, 32), False, False),        # the tests' blocks
    ((1000, 32, 2304, 1024), False, False),  # no whole row tiles
], ids=["state", "laguna", "latent", "prefill-512", "prefill-4096", "tiny", "ragged-rows"])
def test_the_kernel_runs_on_a_tpu_where_an_expert_gets_a_handful_of_rows(
    monkeypatch, shape, admitted, runs
):
    xs, w = _shapes(*shape)
    assert grouped_kernel_admits(xs, w) == admitted
    assert not runs_grouped_kernel(xs, w)  # the CPU: lax.ragged_dot, whatever
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    assert runs_grouped_kernel(xs, w) == runs


def test_operands_of_two_types_keep_ragged_dot(monkeypatch):
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    assert not runs_grouped_kernel(*_shapes(1024, 32, 2304, 1024, "float32", "bfloat16"))
    assert runs_grouped_kernel(*_shapes(1024, 32, 2304, 1024, "float32"))


def _cell(name):
    import json
    import os

    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
    with open(os.path.join(root, "configs", name + ".json")) as f:
        return config_from_dict(json.load(f))


@pytest.mark.parametrize("name,block,slots,layers", [
    ("kimi-linear-48b-a3b", "kimi_linear", 128, 12),
    ("laguna-s-2.1", "laguna", 64, 4),
    ("openpangu-ultra-moe-718b", "pangu_ultra_moe", 32, 4),
])
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_the_block_says_which_expert_layers_run_the_kernel(
    monkeypatch, platform, name, block, slots, layers
):
    """``Block.expert_kernel_layers`` at the three cells' slots: every
    sparse layer on a TPU, none on the CPU; (0, 0) for the dense block."""
    monkeypatch.setattr(backend, "kernel_platform", lambda: platform)
    cfg = _cell(name)
    took = layers if platform == "tpu" else 0
    assert BLOCKS[block].expert_kernel_layers(cfg, slots) == (layers, took)
    assert expert_kernel_layers(cfg, slots) == (layers, took)
    # a prefill's rows at the same widths keep lax.ragged_dot
    assert expert_kernel_layers(cfg, 4096)[1] == 0
    assert BLOCKS["gpt_neox"].expert_kernel_layers(None, slots) == (0, 0)


@pytest.mark.parametrize("stated,want", [
    ({"expert_layers": 12, "expert_kernel_layers": 12}, 100.0),  # the cell on a TPU
    ({"expert_layers": 12, "expert_kernel_layers": 0}, 0.0),  # lax.ragged_dot, counted
    ({"expert_layers": 0, "expert_kernel_layers": 0}, None),  # the dense block
    ({"state_layers": 10}, None),  # a parent commit's span: nothing to read
], ids=["all", "none", "no-experts", "parent"])
def test_the_kernel_share_is_the_expert_layers_that_run_the_kernel(stated, want):
    """``kernels.moe_kernel_share`` is data alone: an accepted reader over
    the two counts on ``ft.engine.decode_dispatch``."""
    import os
    import types

    from benchmarks.lib import harness, xplane as X
    from benchmarks.readers import spans as S

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    meta = harness._read_json(os.path.join(
        root, "benchmarks", "metrics", "kernels.moe_kernel_share.json"))
    assert meta["reader"] == "spans:count_ratio_p50"
    host = [X.Event("bench_window", 0, 1000)] + [
        X.Event("ft.engine.decode_dispatch", 100 * i, 50, dict(stated))
        for i in range(3)
    ]
    trace = X.Trace([X.Plane("/host:CPU", [X.Line("python3", host)])])
    ctx = harness.ReaderContext(
        types.SimpleNamespace(name="toy"),
        harness.Run(True, 0, 0, {}, {}, 0.0, None), {}, trace, (0.0, 1000.0))
    got = S.count_ratio_p50(ctx, **meta["args"])
    assert got == (want if want is None else pytest.approx(want))
    entry, = [m for m in harness.load_benchmark()["per_layer"]
              if m["name"] == "kernels.moe_kernel_share"]
    assert entry["source"] == "program_counter" and entry["workloads"] == [
        "laguna-s-2.1.chat-closed-c64", "openpangu-ultra-moe-718b.doc-closed-c32",
        "kimi-linear-48b-a3b.gen-closed-c128",
        "sdar-30b-a3b-chat.gen-block4-closed-c32"]
