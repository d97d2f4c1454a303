"""The openPangu-Ultra-MoE block through the one ``ServingEngine`` against
its plain reference (``benchmarks/reference/pangu_ultra_moe_decoder.py``),
at a small shape that keeps every ratio: hidden 64, 4 heads of 16 + 8
(queries and keys) and 16 (values), queries compressed to 24 and the
cached row to 16 + 8, 16 sigmoid-routed experts top-4 with 4 held and one
shared, a dense first layer, sandwich norms, an untied head.
"""

import copy
import dataclasses
import json
import math
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import counts_pangu as C, harness, serve_closed_latent as driver
from benchmarks.reference import pangu_ultra_moe_decoder as ref
from flextree_tpu.models import pangu_ultra_moe as pangu
from flextree_tpu.models.configs import BLOCKS, config_from_dict, pool_layout
from flextree_tpu.models.moe import MOE_COUNTS, gated_ffn
from flextree_tpu.obs import flight_recorder
from flextree_tpu.ops.paged_attention import (
    paged_attention_latent, paged_attention_latent_gather,
)
from flextree_tpu.serving import (
    BatcherConfig, PagedCacheConfig, Request, ServingEngine, costs,
)
from flextree_tpu.serving.kv_cache import (
    export_blocks, gather_seq, init_pools, write_imported, write_swapped,
)
from flextree_tpu.serving.migration import MigrationError, pack_kv, unpack_kv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "openpangu-ultra-moe-718b.doc-closed-c32"
PUBLISHED = harness._read_json(os.path.join(
    REPO, "benchmarks", "configs", "openpangu-ultra-moe-718b.json"))


def tiny(dtype="float32", **over) -> dict:
    c = copy.deepcopy(PUBLISHED)
    c.update(
        vocab_size=128, hidden_size=64, intermediate_size=160,
        q_lora_rank=24, kv_lora_rank=16, num_attention_heads=4,
        num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_intermediate_size=32, n_routed_experts=4,
        num_experts_per_tok=4, published={"n_routed_experts": 16},
        experts_held=[4, 8], compute_dtype=dtype, param_dtype=dtype,
    )
    c.update(over)
    return c


PCFG = PagedCacheConfig(num_blocks=40, block_size=4, blocks_per_seq=8)


def engine(config, seed=3, slots=3, **bcfg):
    return ServingEngine.from_config(
        config, PCFG, BatcherConfig(slots=slots, **bcfg), seed=seed)


# ------------------------------------------- engine against the reference

# bf16 at these toy widths against the float32 reference: rounding of the
# residual stream after each of ten residual adds and of the cached rows
# moves logits by about a hundredth of the largest (seen: 0.010 to 0.016
# over seeds); the cell's own limits (4e-2 on logits, 5e-2 on scores) leave
# over twice that, and a wrong mechanism moves them by O(1)
BF16_LOGITS_TOL = 3e-2


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_absorbed_decode_through_the_latent_pool_equals_one_reference_forward(dtype):
    """The engine's own programs (expanded prefill of 20 tokens, the pool
    write, 6 ABSORBED decode steps through the paged latent pool) against
    ONE expanded reference forward: in f32 to 1e-4 with no pick differing,
    in bf16 under a stated tolerance."""
    config = tiny(dtype)
    got = driver.check_against_reference(engine(config), config, 5, 20, 6, 7)
    if dtype == "float32":
        assert got["prefill_rel_err"] < 1e-4 and got["decode_rel_err_max"] < 1e-4
        assert got["score_rel_err"] < 1e-4 and got["picks_differing"] == 0
    else:
        assert max(got["prefill_rel_err"], got["decode_rel_err_max"]) < BF16_LOGITS_TOL
    assert got["ok"] and got["pool_ok"], got
    assert got["picks"] == 4 * 26 * 4


def _mutated(name, config, params):
    """A reference that differs from the program by one mechanism."""
    config, params = copy.deepcopy(config), jax.tree.map(lambda a: a, params)
    heads, nope, rope = 4, 16, 8
    if name == "softmax_for_sigmoid":
        config["scoring_func"] = "softmax"
    elif name == "no_routed_scale":
        config["routed_scaling_factor"] = 1.0
    elif name == "no_sandwich_norm":  # plain pre-norm: the post norms gone
        config["sandwich_norm"] = False
    elif name == "rotary_on_the_wrong_columns":
        # the queries' rotary block and their first `rope` columns change
        # places, a head at a time: the reference rotates other columns
        for layer in params["layers"]:
            w = layer["wq_b"].reshape(-1, heads, nope + rope)
            w = jnp.concatenate(
                [w[..., nope:], w[..., rope:nope], w[..., :rope]], axis=-1)
            layer["wq_b"] = w.reshape(layer["wq_b"].shape)
    elif name == "scale_of_the_nope_width":
        # q * sqrt(24/16) under 1/sqrt(24) is q under 1/sqrt(16)
        for layer in params["layers"]:
            layer["wq_b"] = layer["wq_b"] * math.sqrt((nope + rope) / nope)
    elif name == "no_inner_norm":  # ln_kv as the identity scale is not none
        for layer in params["layers"]:
            layer["wkv_b"] = layer["wkv_b"] * 1.5
    return config, params


@pytest.mark.parametrize("name", [
    "softmax_for_sigmoid", "no_routed_scale", "no_sandwich_norm",
    "rotary_on_the_wrong_columns", "scale_of_the_nope_width", "no_inner_norm",
])
def test_one_wrong_mechanism_fails_the_comparison(name):
    config = tiny()
    eng = engine(config)
    ref_config, ref_params = _mutated(name, config, eng.params)
    got = driver.check_against_reference(
        eng, config, 5, 20, 6, 7, reference_params=ref_params,
        reference_config=ref_config)
    assert not got["ok"], got


def test_a_float32_pool_fails_the_pool_check_and_nothing_else():
    config = tiny("bfloat16")
    eng = engine(config)
    eng.pools = jax.tree.map(lambda a: a.astype(jnp.float32), eng.pools)
    assert eng.pools["ckv"][0].shape == (40, 4, 24)  # one row, no heads axis
    got = driver.check_against_reference(eng, config, 5, 20, 6, 7)
    # every numeric limit passes (a wider pool is closer to the reference)
    assert got["decode_rel_err_max"] < driver.LOGITS_REL_TOL
    assert not got["pool_ok"] and not got["ok"]


@pytest.mark.parametrize("name", ["expanded", "two_parts", "heads_axis", "short"])
def test_a_pool_that_is_not_one_latent_row_fails_the_pool_check(name):
    config = tiny("bfloat16")
    good = init_pools(config_from_dict(config), PCFG)
    assert driver.pool_ok(good, config)
    layers = good["ckv"]
    bad = {
        # K and V of every head, as the other blocks' pools hold them
        "expanded": {"k": [jnp.zeros((40, 4, 4, 24), jnp.bfloat16)] * 5,
                     "v": [jnp.zeros((40, 4, 4, 16), jnp.bfloat16)] * 5},
        "two_parts": {"ckv": layers, "kr": layers},
        "heads_axis": {"ckv": [a[:, :, None, :].repeat(4, 2) for a in layers]},
        "short": {"ckv": layers[:4]},
    }[name]
    assert not driver.pool_ok(bad, config)


def test_requests_through_the_engine_follow_the_reference_greedily():
    """Whole requests through step(): every token the engine emits is the
    reference's argmax at that position, given the tokens before it."""
    config = tiny()
    eng = engine(config)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, (n,)).astype(np.int32) for n in (5, 9, 13)]
    for i, p in enumerate(prompts):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=7))
    eng.run_until_idle()
    for i, p in enumerate(prompts):
        tokens = np.asarray(eng.completed[i].tokens)
        seq = np.concatenate([p, tokens])
        want = ref.forward(eng.params, jnp.asarray(seq), config)["logits"]
        greedy = np.asarray(want).argmax(-1)[len(p) - 1 : -1]
        assert np.array_equal(tokens, greedy)


# ------------------------------------------------ prefill attention in blocks


@pytest.mark.parametrize("q_block,kv_group", [(4, 8), (8, 8), (2, 24), (4, 16)])
def test_blocked_prefill_attention_equals_unblocked(q_block, kv_group):
    """24 positions: query blocks of 2 to 8 inside groups of 8 to 24 (a
    group that the block does not divide goes whole) against one block
    that is everything."""
    rng = np.random.default_rng(4)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    args = (arr(2, 24, 4, 16), arr(2, 24, 4, 8), arr(2, 24, 4, 16),
            arr(2, 24, 8), arr(2, 24, 4, 16))
    whole = pangu.blocked_causal_attention(*args, 0.2, 24, 24)
    blocked = pangu.blocked_causal_attention(*args, 0.2, q_block, kv_group)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(whole), atol=2e-6)
    # and the whole is the plain causal softmax over expanded keys
    qn, qr, kn, kr, v = (np.asarray(a, np.float64) for a in args)
    s = np.einsum("bqhd,bkhd->bhqk", qn, kn) + np.einsum("bqhr,bkr->bhqk", qr, kr)
    s = np.where(np.tril(np.ones((24, 24), bool)), s * 0.2, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", p / p.sum(-1, keepdims=True), v)
    np.testing.assert_allclose(np.asarray(whole), want, atol=2e-6)


def test_a_prefill_in_row_blocks_equals_one_pass():
    """The FFN a block of token rows at a time (and attention in query
    blocks) gives the prefill of one pass."""
    cfg = config_from_dict(tiny())
    params = pangu.init_params(jax.random.PRNGKey(1), cfg)
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 128, (1, 24)))
    one = pangu.prefill(params, tokens, dataclasses.replace(
        cfg, q_block=24, kv_group=24, ffn_rows=24), 32)
    blocks = pangu.prefill(params, tokens, dataclasses.replace(
        cfg, q_block=4, kv_group=8, ffn_rows=8), 32)
    for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(blocks)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=2e-5)


def test_the_largest_score_block_of_an_8192_token_prefill_is_under_1_gb():
    cfg = config_from_dict(PUBLISHED)
    assert 8192 % cfg.kv_group == 0 and cfg.kv_group % cfg.q_block == 0
    # 128 heads x 128 queries x 8,192 keys in f32: half a GB (the whole
    # (H, T, T) array would be 34 GB)
    assert cfg.n_heads * cfg.q_block * 8192 * 4 == 2 ** 29


# ----------------------------------------------------- the latent decode walk


@pytest.mark.parametrize("chunk", [1, 2, 3, 8])
def test_the_latent_walk_equals_the_gather_oracle(chunk):
    """Ragged lengths (an empty slot, one at a block's edge, one a
    position short of the table's capacity), poisoned pool rows past
    every length, four heads over one 24-wide row whose first 16 numbers
    are the values."""
    rng = np.random.default_rng(7)
    pool = jnp.asarray(rng.standard_normal((20, 4, 24)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((4, 4, 24)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((4, 24)), jnp.float32)
    tables = np.zeros((4, 6), np.int32)
    tables[1, :2], tables[2, :3], tables[3, :6] = [1, 2], [3, 4, 5], range(6, 12)
    lengths = jnp.asarray([0, 8, 9, 23], jnp.int32)
    pool = pool.at[0].set(1e4).at[5, 2:].set(-1e4)  # past slot 2's 9
    kw = dict(value_dim=16, scale=0.2)
    want = paged_attention_latent_gather(q, new, pool, tables, lengths, **kw)
    got = paged_attention_latent(
        q, new, pool, tables, lengths, impl="jnp", block_chunk=chunk, **kw)
    assert got.shape == (4, 4, 16) and bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    # the empty slot attends to its own new row alone
    np.testing.assert_allclose(
        np.asarray(got[0]), np.broadcast_to(np.asarray(new[0, :16]), (4, 16)),
        atol=1e-6)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_the_latent_kernel_equals_the_gather_oracle(dtype, tol):
    """The Pallas kernel under the interpreter at blocks of 16 (what its
    tiling admits): an empty slot, a slot that ends inside a block, one a
    position short of the table's capacity; 8 heads over a 192-wide row
    whose first 128 numbers are the values."""
    from flextree_tpu.ops.paged_attention import latent_kernel_admits

    rng = np.random.default_rng(8)
    dt = jnp.dtype(dtype)
    pool = jnp.asarray(rng.standard_normal((14, 16, 192)), dt)
    q = jnp.asarray(rng.standard_normal((4, 8, 192)), dt)
    new = jnp.asarray(rng.standard_normal((4, 192)), dt)
    tables = np.zeros((4, 6), np.int32)
    tables[1, :2], tables[2, :3], tables[3, :6] = [1, 2], [3, 4, 5], range(6, 12)
    lengths = jnp.asarray([0, 32, 37, 95], jnp.int32)
    assert latent_kernel_admits(q, pool, 128)
    assert not latent_kernel_admits(q, pool[:, :12], 128)  # 12-row blocks
    assert not latent_kernel_admits(q, pool, 96)  # values of part of a tile
    kw = dict(value_dim=128, scale=0.07)
    want = paged_attention_latent_gather(q, new, pool, tables, lengths, **kw)
    got = paged_attention_latent(
        q, new, pool, tables, lengths, impl="pallas", **kw)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol)


def test_the_flash_forward_with_narrow_values_equals_the_blocked_attention():
    """What the prefill runs on a TPU (the ``kvgrid`` flash forward over
    keys with the rotary part repeated a head, values narrower than keys)
    against what it runs elsewhere, under the interpreter."""
    from flextree_tpu.ops.pallas_attention import flash_attention

    rng = np.random.default_rng(5)
    arr = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    qn, qr, kn, kr, v = (arr(1, 40, 4, 16), arr(1, 40, 4, 8), arr(1, 40, 4, 16),
                         arr(1, 40, 8), arr(1, 40, 4, 16))
    want = pangu.blocked_causal_attention(qn, qr, kn, kr, v, 0.2, 8, 16)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr[:, :, None], qr.shape)], -1)
    got = flash_attention(
        jnp.concatenate([qn, qr], -1), k, v, scale=0.2, variant="kvgrid",
        block_q=16, block_k=16)
    assert got.shape == (1, 40, 4, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    with pytest.raises(ValueError, match="kvgrid"):
        flash_attention(jnp.concatenate([qn, qr], -1), k, v, variant="loop")


def test_the_latent_walk_refuses_a_pool_with_a_heads_axis():
    with pytest.raises(ValueError, match="pool of rows"):
        paged_attention_latent(
            jnp.zeros((2, 4, 24)), jnp.zeros((2, 24)), jnp.zeros((8, 4, 4, 24)),
            jnp.zeros((2, 3), jnp.int32), jnp.zeros((2,), jnp.int32),
            value_dim=16, scale=1.0)


# ----------------------------------------------------------------- the share


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11 and 12-15 on four chips: the four routed
    parts, with the shared expert counted once (attention, the norms and
    the router are computed alike on all), equal the uncut reference
    layer; and a share alone is the reference given the same share."""
    config = tiny(n_routed_experts=16, experts_held=[0, 16])
    cfg = config_from_dict(config)
    layer = pangu.init_params(jax.random.PRNGKey(4), cfg)["layers"][2]
    m = jax.random.normal(jax.random.PRNGKey(5), (23, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(m @ layer["router"])
        top, picks = jax.lax.top_k(scores, 4)
        w = top / top.sum(-1, keepdims=True) * 2.5
        whole = ref.routed_experts(layer["experts"], m, picks, w, (0, 16)) \
            + ref._gated(layer["shared"], m)
    shared = np.asarray(gated_ffn(layer["shared"], m))
    total = -3 * shared  # four shares count the shared expert four times
    for lo in range(0, 16, 4):
        held = (lo, lo + 4)
        share = dict(layer, experts={
            k: v[lo : lo + 4] for k, v in layer["experts"].items()})
        y, moe = pangu.expert_layer(
            share, m, top_k=4, scale=2.5, normalize=True, held=held,
            score="sigmoid")
        assert int(moe["sizes"].sum()) == int(
            ((picks >= lo) & (picks < lo + 4)).sum())
        alone = ref.routed_experts(share["experts"], m, picks, w, held)
        np.testing.assert_allclose(
            np.asarray(y) - shared, np.asarray(alone), atol=2e-5)
        total = total + np.asarray(y)
    np.testing.assert_allclose(total, np.asarray(whole), atol=5e-5)


@pytest.mark.parametrize("skew", [False, True])
def test_a_chip_that_holds_few_experts_drops_no_pick(skew):
    """2 of 64 experts held, 512 tokens x 4 picks: a thirty-second of the
    picks are local under a router that spreads, and over a quarter where
    a skewed one sends every token here; every local pick is computed and
    the result is the reference's either way."""
    from flextree_tpu.models.moe import dropless_experts

    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((512, 32)), jnp.float32)
    experts = {
        "w_gate": jnp.asarray(rng.standard_normal((2, 32, 16)), jnp.float32),
        "w_up": jnp.asarray(rng.standard_normal((2, 32, 16)), jnp.float32),
        "w_down": jnp.asarray(rng.standard_normal((2, 16, 32)), jnp.float32),
    }
    picks = rng.integers(0, 64, (512, 4)).astype(np.int32)
    if skew:
        picks[:, 0] = 10  # every token picks a held expert: 512+ local picks
    weights = jnp.asarray(rng.random((512, 4)), jnp.float32)
    got, sizes = jax.jit(
        lambda *a: dropless_experts(*a, (10, 12))
    )(h, jnp.asarray(picks), weights, experts)
    n_local = int(((picks >= 10) & (picks < 12)).sum())
    assert int(sizes.sum()) == n_local and (n_local > 256) == skew
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(experts, h, jnp.asarray(picks), weights, (10, 12))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)


# ------------------------------------------- the pool functions, any layout


def _filled_pools(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        init_pools(cfg, PCFG))


def _bits(tree):
    return [np.asarray(a).tobytes() for a in jax.tree.leaves(tree)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_swap_out_and_in_round_trips_latent_rows_bit_for_bit(dtype):
    cfg = config_from_dict(tiny(dtype))
    pools = _filled_pools(cfg)
    saved = jax.tree.map(np.asarray, gather_seq(pools, [3, 7, 2], length=10))
    assert set(saved) == {"ckv"} and saved["ckv"][0].shape == (10, 24)

    def pad(a):
        full = np.zeros((12, *a.shape[1:]), a.dtype)
        full[:10] = a
        return jnp.asarray(full)

    back = write_swapped(
        init_pools(cfg, PCFG), jax.tree.map(pad, saved),
        np.asarray([9, 1, 4], np.int32))
    assert _bits(gather_seq(back, [9, 1, 4], length=10)) == _bits(saved)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_export_and_import_round_trips_latent_blocks_bit_for_bit(dtype):
    """Through the wire format too: the f32 codec moves a bf16 pool's
    numbers exactly, and the meta states the layout."""
    cfg = config_from_dict(tiny(dtype))
    pools = _filled_pools(cfg, 1)
    blocks = jax.tree.map(np.asarray, export_blocks(pools, [5, 6, 11]))
    meta, blob = pack_kv(blocks)
    assert meta["layout"] == {"ckv": [24]} and meta["n_layers"] == 5
    assert len(meta["tensors"]) == 5  # one tensor a layer, no second part
    landed = write_imported(
        init_pools(cfg, PCFG),
        jax.tree.map(lambda a: jnp.asarray(a, cfg.dtype), unpack_kv(meta, blob)),
        np.asarray([2, 3, 8], np.int32))
    assert _bits(export_blocks(landed, [2, 3, 8])) == _bits(export_blocks(pools, [5, 6, 11]))


def test_a_migration_lands_on_a_replica_of_the_same_layout_only():
    config = tiny()
    prompt = np.arange(3, 12, dtype=np.int32)
    sender = engine(config)
    out = sender.prefill_for_migration(
        Request(rid=0, prompt=prompt, max_new_tokens=5, arrival_s=1.0))
    assert out["meta"]["layout"] == {"ckv": [24]}
    receiver = engine(config)
    req = Request(rid=0, prompt=prompt, max_new_tokens=5, arrival_s=1.0)
    assert receiver.admit_migrated(
        req, out["first_token"], out["meta"], out["blob"]) is not None
    receiver.run_until_idle()
    alone = engine(config)
    alone.submit(req)
    alone.run_until_idle()
    assert np.array_equal(receiver.completed[0].tokens, alone.completed[0].tokens)
    # a replica of the dense block holds K and V of (heads, head_dim)
    from flextree_tpu.models.transformer import TransformerConfig, init_params

    dense_cfg = TransformerConfig(
        vocab_size=128, d_model=24, n_heads=1, n_layers=5, d_ff=32)
    dense = ServingEngine(
        init_params(jax.random.PRNGKey(0), dense_cfg), dense_cfg, PCFG,
        BatcherConfig(slots=2))
    with pytest.raises(MigrationError, match="layout"):
        dense.admit_migrated(req, out["first_token"], out["meta"], out["blob"])


def test_preemption_by_swap_resumes_a_latent_sequence_token_for_token():
    """A pool too small for the traffic: sequences are swapped out and
    back in, and every request still returns what it returns alone."""
    config = tiny()
    small = PagedCacheConfig(num_blocks=10, block_size=4, blocks_per_seq=8)
    eng = ServingEngine.from_config(
        config, small,
        BatcherConfig(slots=4, admission="ondemand", preempt="swap"), seed=3)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 128, (9,)).astype(np.int32) for _ in range(4)]
    for i, p in enumerate(prompts):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=14))
    eng.run_until_idle()
    counters = eng.report()["counters"]
    assert counters["serve.preempts"] >= 1 and counters["serve.swap_outs"] >= 1
    for i, p in enumerate(prompts):
        alone = engine(config, slots=1)
        alone.submit(Request(rid=0, prompt=p, max_new_tokens=14))
        alone.run_until_idle()
        assert np.array_equal(eng.completed[i].tokens, alone.completed[0].tokens)


# ------------------------------------- bytes a position, from the pool layout


def _laguna_published():
    return harness._read_json(os.path.join(
        REPO, "benchmarks", "configs", "laguna-s-2.1.json"))


@pytest.mark.parametrize("name,want", [
    ("pangu", 5 * 576 * 2),  # 5,760: one bf16 row of 576 a layer
    ("laguna", 5 * 2 * 8 * 128 * 2),  # 20 KiB, as PR 28 stated it
    ("dense", 8 * 2 * 32 * 128 * 2),  # pythia-6.9b in bf16: K and V of 32 heads
])
def test_costs_state_the_cache_bytes_a_position_from_the_pool_layout(name, want):
    if name == "dense":
        c = harness._read_json(os.path.join(
            REPO, "benchmarks", "configs", "pythia-6.9b.json"))
        c = dict(c, model_type="gpt_neox")
    else:
        c = PUBLISHED if name == "pangu" else _laguna_published()
    cfg = config_from_dict(c)
    assert costs.cache_bytes_per_position(cfg) == want
    # and it is what init_pools allocates a position
    pcfg = PagedCacheConfig(num_blocks=3, block_size=2, blocks_per_seq=2)
    shapes = jax.eval_shape(lambda: init_pools(cfg, pcfg))
    held = sum(
        math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    assert held == want * 6
    # a round's streamed bytes are priced at it, the dense block's as before
    assert costs.decode_round_bytes(cfg, pcfg, 3, 2) == 3 * 2 * 2 * want
    if name == "dense":
        assert costs.kv_migration_elems(cfg, pcfg, 3) == [2 * 2 * 4096] * 2
    if name == "pangu":
        assert costs.kv_migration_elems(cfg, pcfg, 3) == [2 * 2 * 576]
        assert C.cache_bytes_per_position(c) == want


@pytest.mark.parametrize("name", ["dense", "laguna", "pangu"])
def test_an_engine_reports_its_cache_bytes_a_position(name):
    if name == "dense":
        from flextree_tpu.models.transformer import TransformerConfig, init_params

        cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
        eng = ServingEngine(init_params(jax.random.PRNGKey(0), cfg), cfg, PCFG,
                            BatcherConfig(slots=2))
        want = 2 * 2 * 32 * 4  # K and V, two layers, f32
    elif name == "laguna":
        from tests.test_laguna import tiny as laguna_tiny

        eng = engine(laguna_tiny())
        want = 5 * 2 * 2 * 16 * 4  # five layers, K and V of 2 heads of 16
    else:
        eng = engine(tiny("bfloat16"))
        want = 5 * 24 * 2
    assert eng.report()["cache_bytes_per_position"] == want
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.pools))
    assert pool_bytes == want * PCFG.num_blocks * PCFG.block_size
    assert eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                              max_new_tokens=3))
    with flight_recorder(None) as rec:
        eng.step()
    dispatch = [e for e in rec.events
                if e.get("name") == "ft.engine.decode_dispatch"][0]
    assert dispatch["cache_bytes_per_position"] == want
    assert dispatch["attn_layers"] == eng.cfg.n_layers


# ------------------------------------------- the configuration, as published

# the catalog's row (architectures.jsonl, openPangu-Ultra-MoE-718B)
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
    "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
    "moe_intermediate_size": 2048, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_theta": 25600000,
    "routed_scaling_factor": 2.5, "sandwich_norm": True,
    "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600,
}


def test_the_configuration_file_keeps_every_published_width():
    c = PUBLISHED
    changed = {k for k, v in CATALOG.items() if c[k] != v}
    assert changed == set(c["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert all(c["published"][k] == CATALOG[k] for k in c["reduced"])
    cfg = config_from_dict(c)
    assert (cfg.d_model, cfg.q_rank, cfg.kv_rank, cfg.n_heads) == (7680, 1536, 512, 128)
    assert (cfg.d_nope, cfg.d_rope, cfg.d_v, cfg.pool_row) == (128, 64, 128, 576)
    assert (cfg.d_ff, cfg.d_expert, cfg.d_shared) == (18432, 2048, 2048)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (256, (0, 16), 8)
    assert (cfg.n_layers, cfg.n_dense, cfg.routed_scale) == (5, 1, 2.5)
    assert cfg.vocab_size == 19200 == CATALOG["vocab_size"] // 8
    assert cfg.rope_theta == 25.6e6 and cfg.rms_eps == 1e-5
    assert len(c["assumed"]) == 5 and "16 chips" in c["deployment"]
    assert c["source"].endswith("openPangu-Ultra-MoE-718B/blob/main/config.json")
    # the bytes the cut states: 4,919 M parameters in bf16, leaf by leaf
    shapes = jax.eval_shape(
        lambda k: pangu.init_params(k, cfg), jax.random.PRNGKey(0))
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert 4.918e9 < count < 4.920e9
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(shapes))
    assert pool_layout(cfg) == (
        {"position": {"ckv": (576,)}, "slot": {}},) * cfg.n_layers
    # 1,699 M of them a decoded token multiplies with, with 0.5 local picks
    active = C.other_params(c) + 4 * C.expected_local_picks(c) * C.expert_params(c)
    assert C.expected_local_picks(c) == 0.5
    assert active - 7680 * 19200 == pytest.approx(1699e6, rel=1e-3)


@pytest.mark.parametrize("key,value,match", [
    ("num_nextn_predict_layers", 1, "second token"),
    ("scoring_func", "softmax", "sigmoid router"),
    ("sandwich_norm", False, "sandwich"),
    ("experts_held", [0, 3], "experts_held"),
])
def test_what_the_block_does_not_implement_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        config_from_dict(tiny(**{key: value}))


def test_the_table_of_blocks_names_every_model_type():
    assert set(BLOCKS) == {
        "gpt_neox", "laguna", "pangu_ultra_moe", "kimi_linear", "olmo_hybrid",
        "sdar_moe"}
    assert BLOCKS["pangu_ultra_moe"].config_type is pangu.PanguConfig
    with pytest.raises(ValueError, match="model_type"):
        config_from_dict({"model_type": "no_such_block"})


def test_the_prefix_cache_is_refused_for_this_block():
    with pytest.raises(NotImplementedError, match="prefix cache"):
        engine(tiny(), prefix_cache=True)


def test_the_cli_serves_a_configuration_file(tmp_path):
    from flextree_tpu.serving.__main__ import parse_args, serve

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(tiny()))
    eng, reqs, report = serve(parse_args([
        "--cpu", "--config", str(path), "--requests", "5", "--blocks", "40",
        "--max-new", "6"]))
    assert isinstance(eng.cfg, pangu.PanguConfig)
    assert report["completed"] == report["submitted"] == 5
    assert report["counters"]["serve.moe_picks"] > 0
    assert report["cache_bytes_per_position"] == 5 * 24 * 4
    assert report["attn_kernel_layers"] == 0  # the loop, said honestly


# ------------------------------------------------ spans, ids and counters


def test_a_round_carries_what_its_routers_counted():
    eng = engine(tiny(), slots=4)
    rng = np.random.default_rng(1)
    for i in range(3):
        assert eng.submit(Request(
            rid=i, prompt=rng.integers(0, 128, (6,)).astype(np.int32),
            max_new_tokens=5))
    with flight_recorder(None) as rec:
        eng.step()
        eng.step()
    books = [e for e in rec.events
             if e["kind"] == "span" and e["name"] == "ft.engine.bookkeeping"]
    assert len(books) == 2
    for book in books:
        assert set(MOE_COUNTS) <= set(book)
        # 3 active slots x 4 picks x 4 sparse layers; 4 held experts a layer
        assert book["picks"] == 3 * 4 * 4 and book["experts_held"] == 4 * 4
        assert 0 <= book["local_picks"] <= book["picks"]
        assert book["experts_hit"] <= min(book["experts_held"], book["local_picks"])
    counters = eng.report()["counters"]
    assert counters["serve.moe_picks"] == 2 * 48
    assert counters["serve.moe_local_picks"] == sum(b["local_picks"] for b in books)


NEW_SCOPES = ["ft_mla_proj", "ft_mla_core"]


@pytest.fixture(scope="module")
def program_paths():
    """The ``op_name`` path of every operation of the lowered decode and
    prefill programs."""
    eng = engine(tiny())
    texts = [
        eng._decode.lower(
            eng.params, eng.pools, np.zeros((3, 8), np.int32),
            np.zeros((3,), np.int32), np.zeros((3,), np.int32),
        ).as_text(debug_info=True),
        eng._prefill.lower(
            eng.params, np.zeros((1, 12), np.int32)
        ).as_text(debug_info=True),
    ]
    return [re.findall(r'loc\("([^"]*)"', t) for t in texts]


@pytest.mark.parametrize("scope", NEW_SCOPES + [
    "ft_moe_router", "ft_moe_experts", "ft_moe_shared", "ft_mlp", "ft_head",
    "ft_norm", "ft_embed"])
def test_the_served_programs_hold_the_scope(program_paths, scope):
    for paths in program_paths:
        assert any(re.search(rf"\b{scope}\b", p) for p in paths), scope


def test_the_new_scopes_never_nest_and_are_whole_names(program_paths):
    from benchmarks.readers import spans as S

    for paths in program_paths:
        for p in paths:
            found = S._SCOPE.findall(p)
            assert len(set(found)) <= 1, p
            # the reader's pattern takes the whole name: the latent
            # layer's operations never count under ft_attn or a bare ft_mla
            assert not {"ft_attn", "ft_mla", "ft_moe"} & set(found)


def test_the_decode_program_expands_no_cached_position():
    """The absorbed form: no array of the decode program has a cached
    position's axis (the table's 8 blocks of 4) beside the heads' expanded
    widths; the prefill, in the expanded form, has."""
    eng = engine(tiny())
    decode = eng._decode.lower(
        eng.params, eng.pools, np.zeros((3, 8), np.int32),
        np.zeros((3,), np.int32), np.zeros((3,), np.int32)).as_text()
    # (slots, positions, heads, nope) or (.., heads, v): what an expanded
    # key or value of the gathered rows would be shaped
    assert not re.search(r"tensor<3x(4|32)x4x(16|24)x", decode)
    assert re.search(r"tensor<3x4x24x", decode)  # the rows, as cached
    prefill = eng._prefill.lower(
        eng.params, np.zeros((1, 12), np.int32)).as_text()
    assert re.search(r"tensor<1x12x4x16x", prefill)


# ------------------------------------------------------------ the benchmark


def _cells():
    return [w["name"] for w in harness.load_benchmark()["workloads"]]


def test_load_cell_finds_the_new_cell():
    assert CELL in _cells()
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_closed_latent"
    assert cell.config["model_type"] == "pangu_ultra_moe"
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"serve_tokens_per_s", "serve_ttft_p50_ms",
                        "serve_gap_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    assert {"attn.mla_proj_share", "attn.mla_core_share",
            "kernels.mla_decode_roofline", "kernels.mla_prefill_roofline",
            "engine.prefill_time_share", "kernels.paged_kernel_share",
            "moe.experts_share", "moe.router_share", "moe.local_pick_share",
            "moe.experts_hit_share", "device.idle_share.serve"} <= per_layer
    assert not {"kernels.decode_roofline", "kernels.moe_decode_roofline",
                "attn.window_share", "attn.full_share"} & per_layer
    t = cell.traffic
    assert (t["clients"], t["slots"], t["deck"]) == (32, 32, 100)
    assert t["prompt_lens"] == [2048, 4096, 8192] and t["max_new"] == [128, 256, 512]
    assert t["prompt_weights"] == t["max_new_weights"] == [0.3, 0.4, 0.3]
    # every slot's worst case fits: admission never waits on memory
    assert t["num_blocks"] == t["slots"] * t["blocks_per_seq"] + 1
    assert t["block_size"] * t["blocks_per_seq"] == 8704 == max(t["prompt_lens"]) + max(t["max_new"])
    # a block size that is no multiple of the 128 lanes: XLA:TPU then keeps
    # the (N, bs, 576) pool row-major, as the kernel reads it
    assert t["block_size"] % 128 and t["block_size"] % 16 == 0
    assert t["check_blocks"] * t["block_size"] >= t["check_prompt"] + t["check_steps"]
    # the deck is whole numbers of each pair
    from benchmarks.lib import traffic as T

    cards = T.request_deck(t, 1)["cards"]
    counts = sorted(cards.count(pair) for pair in set(cards))
    assert counts == [9, 9, 9, 9, 12, 12, 12, 12, 16] and len(cards) == 100
    # the new cell joins the old lists at their end and nowhere else
    bench = harness.load_benchmark()
    assert bench["workloads"][4]["name"] == CELL
    assert bench["configs"][3]["name"] == "openpangu-ultra-moe-718b"


@pytest.mark.parametrize("seed", [1, 2147483999, 3100000932])
def test_the_deck_is_shuffled_by_the_seed_and_by_nothing_else(seed):
    """The cell's deck is ``request_deck``'s, as every other cell's: the
    same 100 cards whatever the seed, in an order the seed alone decides
    (seeds past 32 signed bits among them)."""
    from benchmarks.lib import traffic as T

    t = harness.load_cell(CELL).traffic
    cards = T.request_deck(t, seed)["cards"]
    assert cards == T.request_deck(t, seed)["cards"]
    other = T.request_deck(t, seed + 1)["cards"]
    assert sorted(other) == sorted(cards) and other != cards
    assert [p for p, _ in cards].count(8192) == 30


def test_the_loop_deals_the_plain_shuffle():
    """The driver's loop is ``serve_closed_model``'s own ``ModelLoop``
    over ``request_deck``'s order: the driver brings no deal of its own,
    and its ``run`` swaps the comparison alone."""
    import inspect

    from benchmarks.lib import serve_closed_model as base
    from benchmarks.lib import traffic as T

    assert driver.ModelLoop is base.ModelLoop
    eng = engine(tiny(), slots=2)
    t = dict(harness.load_cell(CELL).traffic)
    loop = driver.ModelLoop(eng, t, 5, 128, 1 << 30)
    assert loop.deck == T.request_deck(t, 5)
    assert inspect.getsource(driver.run).count("mock.patch.object") == 1


def test_the_parent_stops_at_once_on_the_new_cell():
    """A benchmark without the cell (the parent's) refuses its name
    before anything is imported or built."""
    bench = harness.load_benchmark()
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(CELL, bench)


def test_run_py_rehearses_the_new_cell():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", CELL, "--rehearsal", "--trace", "1", "--seed",
         "2147483999"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert all(m["value"] is None for m in line["metrics"].values())
    assert {"moe.local_pick_share", "moe.experts_hit_share",
            "kernels.paged_kernel_share", "engine.prefill_time_share",
            } <= set(line["metrics"])


# ------------------------------------------------ the counts, worked by hand


def test_the_counts_are_what_the_algorithm_must_do():
    c = PUBLISHED
    assert C.attention_params(c) == 196_575_232
    assert C.expert_params(c) == 47_185_920 and C.expert_bytes(c) == 94_371_840
    assert C.other_weight_bytes(c) == pytest.approx(3.50e9, rel=2e-3)
    assert C.cache_bytes_per_position(c) == 5760
    # a round: 10 experts hit a layer, 157 k live rows, 32 slots, 16 local
    # picks a sparse layer
    got = C.decode_round_bytes(c, 40, 157_000)
    assert got == C.other_weight_bytes(c) + 40 * 94_371_840 + 157_000 * 5760
    flops = C.decode_round_flops(c, 32, 64, 157_000)
    assert flops == 2 * (C.other_params(c) * 32 + 47_185_920 * 64) \
        + 157_000 * 5 * 128 * (576 + 512) * 2
    # the absorbed product sits at the chip's ridge: 242 FLOP a byte read
    assert 5 * 128 * 1088 * 2 / 5760 == pytest.approx(242, abs=1)
    # a prompt: 3.40 GFLOP a token in the matrices outside the head, the
    # causal core in the expanded form, the head once
    for t, tflop in ((2048, 7.8), (4096, 17.4), (8192, 41.6)):
        assert C.prefill_flops(c, t) == pytest.approx(tflop * 1e12, rel=0.01)
    per_token = (C.prefill_flops(c, 2) - C.prefill_flops(c, 1)) \
        - 5 * 128 * 320 * 2 * 2
    assert per_token == pytest.approx(3.40e9, rel=2e-3)


# ------------------------------------------- the new readers, worked by hand


def _metric(name):
    return harness._read_json(
        os.path.join(REPO, "benchmarks", "metrics", f"{name}.json"))


def _trace_ctx(decode_ns=(0.0, 0.0), prefill_ns=0.0, with_counts=True,
               window=1e9):
    """A made-up window: two decode rounds of 32 slots over 157,000 live
    rows whose program ran ``decode_ns`` each, one prefill of 4,096 and
    one of 2,048 tokens whose programs ran ``prefill_ns`` in all, under
    spans of 0.15 and 0.05 of the window."""
    from benchmarks.lib import xplane as X
    from benchmarks.lib.harness import ReaderContext, Run
    from benchmarks.lib.peaks import Peaks

    E = X.Event
    counts = {"experts_hit": 40, "local_picks": 64, "picks": 1024} \
        if with_counts else {}
    host = [
        E("bench_window", 0, window),
        E("ft.engine.prefill", 0.10 * window, 0.15 * window, {"prompt_len": 4096}),
        E("ft.engine.prefill", 0.50 * window, 0.05 * window, {"prompt_len": 2048}),
        E("ft.engine.bookkeeping", 0.30 * window, 10, dict(counts)),
        E("ft.engine.bookkeeping", 0.80 * window, 10, dict(counts)),
    ]
    modules = [
        E("jit_prefill_program(5)", 0.10 * window, prefill_ns * 2 / 3),
        E("jit_prefill_program(6)", 0.50 * window, prefill_ns / 3),
        E("jit__unknown(7)", 0.30 * window, decode_ns[0]),
        E("jit__unknown(7)", 0.80 * window, decode_ns[1]),
    ]
    ops = [E("%fusion.1 = bf16[32,128,576]{2,1,0} fusion(%x)", 0.3 * window, 100,
             {"tf_op": "jit(f)/ft_mla_core/while/body/dot_general"}),
           E("%fusion.2 = bf16[32,7680]{1,0} fusion(%y)", 0.3 * window + 100, 300,
             {"tf_op": "jit(f)/ft_mla_proj/dot_general"})]
    planes = [
        X.Plane("/host:CPU", [X.Line("python3", host)]),
        X.Plane("/device:TPU:0", [X.Line("XLA Ops", ops),
                                  X.Line("XLA Modules", modules)]),
    ]
    rounds = [(0.0, 0.0, 32, 32, 157_000), (0.0, 0.0, 32, 32, 157_000)]
    run = Run(True, 0, 0, {}, {"rounds": rounds}, 0.0, None)
    cell = types.SimpleNamespace(name="toy", config=PUBLISHED)
    peaks = Peaks(197e12, 819e9, 16e9, "test")
    return ReaderContext(cell, run, {}, X.Trace(planes), (0.0, window), peaks=peaks)


def test_the_decode_roofline_is_least_time_over_traced_time():
    from benchmarks.readers import mla as M

    meta = _metric("kernels.mla_decode_roofline")
    assert meta["reader"] == "mla:decode_roofline"
    by_bytes = C.decode_round_bytes(PUBLISHED, 40, 157_000) / 819e9
    by_flops = C.decode_round_flops(PUBLISHED, 32, 64, 157_000) / 197e12
    assert by_bytes > by_flops  # the reads bind a round, about 10 ms
    assert by_bytes == pytest.approx(10.0e-3, rel=0.05)
    # a program that took twice the least time reads 50; one AT the least
    # time, which no program can beat with these counts, reads 100
    least_ns = by_bytes * 1e9
    got = M.decode_roofline(_trace_ctx((2 * least_ns, 2 * least_ns)), **meta["args"])
    assert got == pytest.approx(50.0)
    at_peak = M.decode_roofline(_trace_ctx((least_ns, least_ns)), **meta["args"])
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    # a parent commit's rounds carry no count: nothing to read, no error
    assert M.decode_roofline(
        _trace_ctx((1e6, 1e6), with_counts=False), **meta["args"]) is None


def test_the_prefill_roofline_is_the_prompts_flops_over_traced_time():
    from benchmarks.readers import mla as M

    meta = _metric("kernels.mla_prefill_roofline")
    assert meta["reader"] == "mla:prefill_roofline"
    least_ns = (C.prefill_flops(PUBLISHED, 4096)
                + C.prefill_flops(PUBLISHED, 2048)) / 197e12 * 1e9
    assert least_ns == pytest.approx(128e6, rel=0.02)  # 25.2 TFLOP at the peak
    got = M.prefill_roofline(_trace_ctx(prefill_ns=2 * least_ns), **meta["args"])
    assert got == pytest.approx(50.0)
    at_peak = M.prefill_roofline(_trace_ctx(prefill_ns=least_ns), **meta["args"])
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    # no prefill program in the window (a parent, another model's name)
    assert M.prefill_roofline(_trace_ctx(prefill_ns=0.0), **meta["args"]) is None


def test_prefill_time_share_is_the_prefill_spans_over_the_window():
    from benchmarks.readers import mla as M, spans as S

    meta = _metric("engine.prefill_time_share")
    assert meta["reader"] == "mla:span_time_share"
    ctx = _trace_ctx()
    assert M.span_time_share(ctx, **meta["args"]) == pytest.approx(20.0)
    assert M.span_time_share(ctx, name="ft.engine.no_such_span") is None
    # and the two scope shares read the latent layer's operations apart
    assert S.scope_share(ctx, **_metric("attn.mla_core_share")["args"]) == pytest.approx(25.0)
    assert S.scope_share(ctx, **_metric("attn.mla_proj_share")["args"]) == pytest.approx(75.0)
