"""The SDAR-MoE block, which generates by diffusion over blocks, through the
one ``ServingEngine`` against its plain reference
(``benchmarks/reference/sdar_moe_decoder.py``), at a small shape that keeps
what the published one forces: grouped queries (4 over 2 K/V heads) with a
per-head q/k norm and rotary, an expert layer in every layer with no shared
expert, a block-causal mask over blocks of 4, a round that is ONE pass of 4
query positions a slot (denoising passes, then a commit pass a block).
"""

import copy
import json
import os
import re
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import counts_sdar as C, harness, serve_closed_block as driver
from benchmarks.readers import block as R
from benchmarks.reference import sdar_moe_decoder as ref
from flextree_tpu.models import TransformerConfig, init_params, moe
from flextree_tpu.models.configs import BLOCKS, config_from_dict
from flextree_tpu.obs import flight_recorder
from flextree_tpu.ops import paged_attention as pa
from flextree_tpu.ops.pallas_attention import flash_attention
from flextree_tpu.serving import (
    BatcherConfig, PagedCacheConfig, Request, ServingEngine, costs,
)
from flextree_tpu.serving.batcher import BlockDiffusion
from flextree_tpu.serving.engine import unmask_greedy_ids
from flextree_tpu.serving.migration import MigrationError
from flextree_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "sdar-30b-a3b-chat.gen-block4-closed-c32"
PUBLISHED = harness._read_json(os.path.join(
    REPO, "benchmarks", "configs", "sdar-30b-a3b-chat.json"))
MASK = 90


def tiny(dtype="float32", **over) -> dict:
    c = copy.deepcopy(PUBLISHED)
    c.update(
        vocab_size=96, hidden_size=32, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=16, num_hidden_layers=2,
        mask_token_id=MASK, compute_dtype=dtype, param_dtype=dtype,
    )
    c.update(over)
    return c


PCFG = PagedCacheConfig(num_blocks=24, block_size=8, blocks_per_seq=6)


def engine(config, seed=3, slots=3, pcfg=PCFG, **bcfg):
    return ServingEngine.from_config(
        config, pcfg, BatcherConfig(slots=slots, **bcfg), seed=seed)


def _prompt(length, seed=3):
    return np.random.default_rng(seed).integers(0, MASK, (length,)).astype(np.int32)


# ------------------------------------------- the engine against the reference


@pytest.mark.parametrize("prompt_len", [12, 10], ids=["whole-blocks", "tail-2"])
@pytest.mark.parametrize("steps", [1, 2, 4])
def test_prefill_and_every_pass_equal_the_reference_forward(steps, prompt_len):
    """The engine's prefill of each slot's prompt's whole blocks, then three
    blocks generated in all three slots at once, at three phases
    (``steps`` denoising passes and a commit pass each, the reveal on the
    device): each denoising pass's logits at the places still masked, the
    router's scores and picks over every position, and the committed K/V,
    against the float32 reference's forwards over the sequence as it
    stood, in every slot; every round's reveal against numpy's."""
    config = tiny(denoising_steps=steps)
    eng = engine(config)
    got = driver.check_against_reference(eng, config, 5, prompt_len, 3, 3)
    assert got["ok"], got
    assert (got["slots_live"], got["slots_compared"]) == (3, 3)
    assert got["passes"] == 3 * 3 * (steps + 1)
    # slot s starts s % (steps + 1) rounds late
    assert got["rounds"] == 3 * (steps + 1) + min(steps, 2)
    assert got["rounds_mixed"] > 0
    assert got["reveal_wrong"] == 0 and got["reveal_ties"] == 0
    assert got["reveal_agrees"] == 3 * 3 * (steps + 1)
    assert got["logits_rel_err_max"] < 1e-4
    assert got["score_rel_err"] < 1e-4 and got["picks_differing"] == 0
    assert got["kv_rel_err"] < 1e-5 and got["kv_rel_err_masked"] > 0.05


def test_the_bf16_block_is_within_the_cells_limits():
    config = tiny("bfloat16")
    got = driver.check_against_reference(engine(config), config, 7, 12, 3, 3)
    assert got["ok"], got
    assert got["logits_rel_err_max"] < driver.LOGITS_REL_TOL / 2


def test_the_committed_rows_are_the_clean_blocks_and_not_the_last_masked_pass(
    monkeypatch
):
    """The pool predicate holds the commit pass to its word: where the pool
    keeps the K/V of the last block as its last denoising pass saw it
    (masks at its last revealed places) instead of the clean block's, the
    comparison fails on that predicate alone."""
    config = tiny()
    real = driver._run_programs

    def masked_rows_kept(eng, prompts, blocks, n_blocks, compared):
        got = real(eng, prompts, blocks, n_blocks, compared)
        last = got["slots"][0]["passes"][-1]
        b, slots = eng.block_len, eng.bcfg.slots
        table = np.zeros((slots, eng.pcfg.blocks_per_seq), np.int32)
        table[0, :n_blocks] = got["slots"][0]["blocks"]
        lengths = np.zeros((slots,), np.int32)
        tokens = np.zeros((slots, b), np.int32)
        active = np.zeros((slots,), bool)
        lengths[0] = len(last["seq"]) - b
        tokens[0], active[0] = last["seq"][-b:], True
        _, eng.pools, _ = eng._decode(
            eng.params, eng.pools, table, lengths, tokens, active)
        return got

    clean = driver.check_against_reference(engine(config), config, 5, 12, 2, 3)
    monkeypatch.setattr(driver, "_run_programs", masked_rows_kept)
    stale = driver.check_against_reference(engine(config), config, 5, 12, 2, 3)
    assert clean["ok"] and not stale["ok"]
    assert stale["kv_rel_err"] > driver.KV_REL_TOL
    assert stale["kv_rel_err_masked"] < 1e-5
    assert stale["logits_rel_err_max"] < 1e-4 and stale["pool_ok"]


@pytest.mark.parametrize("over", [
    {"block_length": 1}, {"rope_theta": 10_000.0}, {"norm_topk_prob": False},
], ids=["causal-mask", "rope-theta", "unnormed-picks"])
def test_one_wrong_mechanism_fails_the_comparison(over):
    config = tiny()
    got = driver.check_against_reference(
        engine(config), config, 5, 12, 2, 3,
        reference_config=dict(config, **over))
    assert not got["ok"] and got["logits_rel_err_max"] > 0.05


def test_parameters_cut_to_three_mantissa_bits_fail_the_comparison():
    config = tiny()
    eng = engine(config)
    cut = jax.tree.map(
        lambda a: jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3),
        eng.params)
    got = driver.check_against_reference(
        eng, config, 5, 12, 2, 3, reference_params=cut)
    assert not got["ok"]


def test_a_reveal_of_the_least_probable_places_fails_the_comparison():
    """The reveal is held to numpy's own from the same logits: a pick
    program that reveals the LEAST probable masked places fails the
    comparison on that alone (the reference follows the program's reveals,
    so every other reading stays clean)."""
    config = tiny()
    eng = engine(config)

    def least_probable(logits, masked, reveal):
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        conf = 1.0 / jnp.sum(
            jnp.exp(logits - logits.max(axis=-1, keepdims=True)), axis=-1)
        order = jnp.argsort(jnp.where(masked, conf, jnp.inf), axis=-1)
        return ids, masked & (jnp.argsort(order, axis=-1) < reveal[:, None])

    eng._unmask = jax.jit(least_probable)
    got = driver.check_against_reference(eng, config, 5, 12, 2, 3)
    assert not got["ok"] and got["reveal_wrong"] > 0
    assert got["logits_rel_err_max"] < 1e-4 and got["kv_rel_err"] < 1e-5


def test_a_fault_in_the_last_slot_alone_fails_the_comparison(monkeypatch):
    """The slots after the first are held to the reference too: the last
    slot's first committed block zeroed in the pool, and nothing else
    changed, fails the comparison on the committed K/V."""
    config = tiny()
    real = driver._run_programs

    def last_slot_spoiled(eng, prompts, blocks, n_blocks, compared):
        got = real(eng, prompts, blocks, n_blocks, compared)
        lost = got["slots"][compared[-1]]["blocks"][-1]
        eng.pools = {part: [pool.at[lost].set(0) for pool in pools]
                     for part, pools in eng.pools.items()}
        return got

    monkeypatch.setattr(driver, "_run_programs", last_slot_spoiled)
    got = driver.check_against_reference(engine(config), config, 5, 12, 2, 3)
    assert not got["ok"] and got["kv_rel_err"] > driver.KV_REL_TOL
    assert got["logits_rel_err_max"] < 1e-4 and got["reveal_wrong"] == 0


def test_reveal_verdicts_take_numpys_reveal_and_forgive_only_a_near_tie():
    """0: the program's ids and reveal are numpy's (an exact tie to the
    lower place); 1: the reveal swaps two places within ``TIE_REL`` of
    each other; 2: anything else."""
    logits = np.zeros((6, 4, 5), np.float32)
    logits[:, :, 1] = [0.1, 0.5, 0.3, 0.5]  # places 1 and 3 tie at the top
    logits[3:5, 1, 1] = 0.5 - 1e-5  # place 3 a hair ahead of place 1
    masked = np.ones((6, 4), bool)
    reveal = np.array([2, 2, 2, 1, 1, 1], np.int32)
    ids = np.ones((6, 4), np.int32)
    ids[2, 0] = 0  # not the first index of the maximum
    revealed = np.array([
        [False, True, False, True],  # numpy's
        [False, True, True, False],  # 2 for 3: far from a tie
        [False, True, False, True],  # the ids differ
        [False, True, False, False],  # 1 for 3: a near tie
        [False, False, True, False],  # 2 for 3: far
        [False, True, False, False],  # the exact tie to the lower place
    ])
    got = driver.reveal_verdicts(logits, masked, reveal, ids, revealed)
    assert list(got) == [0, 2, 2, 1, 2, 0]


def _reference_generate(params, config, prompt, max_new):
    """Greedy generation by diffusion over blocks on the host, every pass a
    forward of the reference over the sequence as it stands, the reveal by
    the rule of ``unmask_greedy_ids`` (most probable masked places, ties to
    the lower)."""
    b, mask = int(config["block_length"]), int(config["mask_token_id"])
    diff = BlockDiffusion(b, int(config["denoising_steps"]), mask)
    whole = len(prompt) // b * b
    seq, tail, out = [int(t) for t in prompt[:whole]], [int(t) for t in prompt[whole:]], []
    fwd = jax.jit(lambda p, tok: ref.forward(p, tok, config)["logits"])
    while len(out) < max_new:
        block = tail + [mask] * (b - len(tail))
        masked = [False] * len(tail) + [True] * (b - len(tail))
        for count in diff.reveals(b - len(tail)):
            logits = np.asarray(fwd(params, jnp.asarray(seq + block)))[len(seq):]
            top = logits.max(-1, keepdims=True)
            conf = 1.0 / np.exp(logits - top).sum(-1)
            order = sorted((j for j in range(b) if masked[j]),
                           key=lambda j: (-conf[j], j))[:count]
            for j in order:
                block[j], masked[j] = int(logits[j].argmax()), False
        out += block[len(tail):]
        seq, tail = seq + block, []
    return out[:max_new]


def test_requests_through_the_engine_follow_the_reference_greedily():
    """Three requests in three slots, at different phases in every round
    (prompts of 12, 10 and 3 tokens: whole blocks, a tail of 2, no whole
    block at all), return the tokens the reference's own generation
    gives."""
    config = tiny()
    eng = engine(config)
    asks = [(_prompt(12, 1), 8), (_prompt(10, 2), 12), (_prompt(3, 4), 4)]
    for rid, (p, m) in enumerate(asks):
        assert eng.submit(Request(rid=rid, prompt=p, max_new_tokens=m))
    eng.run_until_idle()
    for rid, (p, m) in enumerate(asks):
        want = _reference_generate(eng.params, config, p, m)
        assert list(eng.completed[rid].tokens) == want, rid


# ----------------------------------------------------- the two attentions


def _block_causal(q, k, v, b):
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    pos = jnp.arange(t) // b
    s = jnp.where(pos[None, :] <= pos[:, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("b", [2, 4, 16])
def test_the_block_causal_flash_forward_is_the_masked_attention(b):
    """The ``kvgrid`` forward under the block-causal mask (interpreted), at
    tiles that a block straddles and tiles it skips, against the jnp
    attention under ``j // B <= i // B``."""
    rng = np.random.default_rng(b)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 64, 2, 128)), jnp.float32)
               for _ in range(3))
    got = flash_attention(q, k, v, causal=True, variant="kvgrid", block_len=b,
                          block_q=8, block_k=16, interpret=True)
    np.testing.assert_allclose(got, _block_causal(q, k, v, b), atol=2e-5)


def test_a_block_of_one_is_todays_causal_forward():
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 48, 2, 128)), jnp.float32)
               for _ in range(3))
    kw = dict(causal=True, variant="kvgrid", block_q=16, block_k=16,
              interpret=True)
    one = flash_attention(q, k, v, block_len=1, **kw)
    assert np.array_equal(one, flash_attention(q, k, v, **kw))
    np.testing.assert_allclose(one, _block_causal(q, k, v, 1), atol=2e-5)


def test_a_block_causal_mask_is_refused_where_it_is_not_written():
    q = jnp.zeros((1, 8, 1, 128))
    for kw in ({"variant": "loop"}, {"variant": "kvgrid", "causal": False},
               {"variant": "kvgrid", "block_len": 3}):
        with pytest.raises(ValueError, match="block-causal"):
            flash_attention(q, q, q, **{"block_len": 4, **kw})


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_the_folded_block_queries_walk_the_pool_as_the_gather_oracle(impl):
    """B positions a slot folded into the query-head axis, head-major (a
    K/V head's group of query heads times B together), over a pool whose
    rows the open block has just written: the walk over ``lengths + B - 1``
    positions plus the block's last row is the gather oracle's attention."""
    rng = np.random.default_rng(0)
    s, h, hkv, b, d, bs = 3, 4, 2, 4, 128, 8
    kp, vp = (jnp.asarray(rng.standard_normal((12, bs, hkv, d)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((s, h * b, d)), jnp.float32)
    kn, vn = (jnp.asarray(rng.standard_normal((s, hkv, d)), jnp.float32)
              for _ in range(2))
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]], jnp.int32)
    seen = jnp.asarray([8 + b - 1, 4 + b - 1, 20 + b - 1], jnp.int32)
    want = pa.paged_attention_gather(q, kn, vn, kp, vp, tables, seen)
    got = pa.paged_attention(q, kn, vn, kp, vp, tables, seen, impl=impl)
    np.testing.assert_allclose(got, want, atol=pa.FUSED_DECODE_ATOL)


@pytest.mark.parametrize("hkv,relayouts", [
    (1, False), (2, False), (4, False), (8, False), (16, False),
    (3, True), (6, True), (30, True),
])
def test_a_pool_is_read_as_the_tile_its_head_count_takes(
    monkeypatch, hkv, relayouts
):
    """On a TPU a K/V head count whose rows fill a tile of their own (a
    multiple of 8, or 1, 2, 4) is held as indexed and read (position,
    head); any other is held with another axis next to the lanes and read
    head-major."""
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    pool = jax.ShapeDtypeStruct((9, 128, hkv, 128), jnp.bfloat16)
    assert pa.pool_relayouts(pool) is relayouts
    monkeypatch.setattr(backend, "kernel_platform", lambda: "cpu")
    assert pa.pool_relayouts(pool) is False


@pytest.mark.parametrize("bs,admitted", [(128, True), (256, True), (16, False)])
def test_the_kernel_admits_the_cells_four_heads_row_major(bs, admitted):
    """Four heads of 128 at a block of 128: 512 rows a block, two blocks a
    1,024-row chunk, 128 folded query rows; at 16 positions a block's 64
    rows fill half the lanes of the scores."""
    q = jax.ShapeDtypeStruct((32, 128, 128), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((1025, bs, 4, 128), jnp.bfloat16)
    assert pa.kernel_admits(q, pool) is admitted


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_the_block_says_which_kernels_its_pass_runs(monkeypatch, platform):
    monkeypatch.setattr(backend, "kernel_platform", lambda: platform)
    took = platform == "tpu"
    cfg = config_from_dict(PUBLISHED)
    block = BLOCKS["sdar_moe"]
    assert block.block_len(cfg) == 4
    assert block.kernel_layers(cfg, PagedCacheConfig(1025, 128, 32)) == (6, 6 * took)
    assert block.kernel_layers(cfg, PagedCacheConfig(1025, 16, 256)) == (6, 0)
    # 32 slots x 4 positions x 8 picks = 1,024 sorted rows over 128 experts
    assert block.expert_kernel_layers(cfg, 32) == (6, 6 * took)
    assert BLOCKS["laguna"].block_len(None) == 1


# ---------------------------------------------------------- the expert layer


def test_an_expert_layer_without_a_shared_expert_adds_nothing_more():
    """A layer whose parameters hold no ``shared`` is the routed part alone,
    and its program opens no ``ft_moe_shared`` scope; a layer with one
    still adds it under that scope."""
    rng = np.random.default_rng(0)
    d, f, e = 16, 8, 4
    w = lambda *s: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32)  # noqa: E731
    layer = {"router": w(d, e), "experts": {
        "w_gate": w(e, d, f), "w_up": w(e, d, f), "w_down": w(e, f, d)}}
    x = w(5, d)
    kw = dict(top_k=2, scale=1.0, normalize=True, held=(0, e))
    y, got = moe.expert_layer(layer, x, **kw)
    _, choices, weights = moe.route_topk_normalized(x, layer["router"], 2)
    routed, _ = moe.dropless_experts(x, choices, weights, layer["experts"], (0, e))
    np.testing.assert_allclose(y, routed, atol=1e-6)
    text = jax.jit(lambda a: moe.expert_layer(layer, a, **kw)[0]).lower(
        x).as_text(debug_info=True)
    assert "ft_moe_experts" in text and "ft_moe_shared" not in text
    shared = dict(layer, shared={"w_gate": w(d, f), "w_up": w(d, f),
                                 "w_down": w(f, d)})
    y2, _ = moe.expert_layer(shared, x, **kw)
    np.testing.assert_allclose(
        y2, routed + moe.gated_ffn(shared["shared"], x), atol=1e-5)
    assert "ft_moe_shared" in jax.jit(
        lambda a: moe.expert_layer(shared, a, **kw)[0]).lower(x).as_text(
        debug_info=True)


# ----------------------------------------------------- the round's accounting


def test_the_reveal_takes_the_most_probable_masked_places_ties_to_the_lower():
    logits = jnp.asarray(np.log(np.asarray([[
        [0.1, 0.9], [0.5, 0.5], [0.3, 0.7], [0.05, 0.95],  # slot 0
    ], [
        [0.6, 0.4], [0.6, 0.4], [0.6, 0.4], [0.6, 0.4],  # slot 1: all tie
    ]])), jnp.float32)
    masked = jnp.asarray([[True, True, True, False], [False, True, True, True]])
    ids, revealed = unmask_greedy_ids(logits, masked, jnp.asarray([2, 2]))
    assert np.asarray(ids).tolist() == [[1, 0, 1, 1], [0, 0, 0, 0]]
    assert np.asarray(revealed).tolist() == [
        [True, False, True, False], [False, True, True, False]]
    _, none = unmask_greedy_ids(logits, masked, jnp.asarray([0, 0]))
    assert not np.asarray(none).any()


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_a_block_is_delivered_whole_at_one_stamp_after_its_passes(steps):
    """One request, a prompt of 8 and 12 new tokens: each block is
    ``steps`` denoising passes that reveal 4 / steps places each and
    deliver nothing but the last, which delivers the block's 4 tokens at
    one stamp; then a commit pass (none after the last block) that reveals
    nothing and advances the committed length by 4.  The first token is
    the first block's, and the request returns exactly 12 tokens."""
    eng = engine(tiny(denoising_steps=steps), slots=1)
    assert eng.submit(Request(rid=0, prompt=_prompt(8), max_new_tokens=12))
    with flight_recorder(None) as rec:
        outs = []
        while not eng.idle:
            outs.append(eng.step())
    per_block = [0] * (steps - 1) + [4]
    assert [o["emitted"] for o in outs] == per_block + [0] + per_block + [0] + per_block
    books = [e for e in rec.events
             if e["kind"] == "span" and e["name"] == "ft.engine.bookkeeping"]
    revealed = [4 // steps] * steps
    assert [e["revealed"] for e in books] == revealed + [0] + revealed + [0] + revealed
    assert [e["committed"] for e in books] == ([0] * steps + [1]) * 2 + [0] * steps
    assert [e["emitted"] for e in books] == [o["emitted"] for o in outs]
    done = eng.completed[0]
    assert done.n_tokens == 12
    stamps = done.token_times
    assert len(set(stamps[:4])) == len(set(stamps[4:8])) == len(set(stamps[8:])) == 1
    assert stamps[0] < stamps[4] < stamps[8] and done.first_token_s == stamps[0]
    counters = eng.report()["counters"]
    assert counters["serve.tokens_revealed"] == 12
    assert counters["serve.commit_passes"] == 2
    assert counters["serve.blocks_committed"] == 3
    assert counters["serve.decode_tokens"] == 12


def test_a_prompt_tail_fills_the_first_block_and_the_answer_is_exact():
    """A prompt of 10 (two whole blocks and a tail of 2) and 8 new tokens:
    the first block delivers its 2 generated places, the next its 4, the
    last 2 of its 4 (the answer is full); the prefill took 8 positions."""
    eng = engine(tiny(), slots=1)
    assert eng.submit(Request(rid=0, prompt=_prompt(10), max_new_tokens=8))
    with flight_recorder(None) as rec:
        outs = []
        while not eng.idle:
            outs.append(eng.step())
    assert [o["emitted"] for o in outs if o["emitted"]] == [2, 4, 2]
    assert eng.completed[0].n_tokens == 8
    prefills = [e for e in rec.events
                if e["kind"] == "span" and e["name"] == "ft.engine.prefill"]
    assert [e["prompt_len"] for e in prefills] == [10]


def test_spans_and_the_report_carry_the_blocks_numbers():
    eng = engine(tiny(), slots=2)
    for rid in range(2):
        assert eng.submit(Request(rid=rid, prompt=_prompt(8, rid), max_new_tokens=4))
    with flight_recorder(None) as rec:
        eng.step()
    dispatched = [e for e in rec.events
                  if e["kind"] == "span" and e["name"] == "ft.engine.decode_dispatch"]
    assert dispatched
    for e in dispatched:
        assert (e["block_len"], e["query_rows"], e["denoise_steps"]) == (4, 8, 2)
        assert e["attn_layers"] == 2 and e["expert_layers"] == 2
    report = eng.report()
    assert (report["block_len"], report["query_rows"], report["denoise_steps"]) == (4, 8, 2)
    assert report["cache_bytes_per_position"] == costs.cache_bytes_per_position(eng.cfg) \
        == 2 * 2 * 2 * 16 * 4
    dense = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64)
    one_token = ServingEngine(
        init_params(jax.random.PRNGKey(0), dense), dense, PCFG, BatcherConfig(slots=1))
    assert (one_token.report()["block_len"], one_token.report()["denoise_steps"]) == (1, 1)


# ------------------------------------------------------------- what is refused


@pytest.mark.parametrize("ask,reason", [
    (dict(max_new_tokens=6), "no multiple of the block length 4"),
    (dict(max_new_tokens=8, temperature=0.7, seed=1), "greedily"),
])
def test_a_request_the_block_cannot_serve_is_refused_at_submit(ask, reason):
    eng = engine(tiny())
    assert not eng.submit(Request(rid=0, prompt=_prompt(8), **ask))
    assert reason in eng.batcher.rejected[-1][1]


@pytest.mark.parametrize("bcfg,match", [
    (dict(admission="ondemand"), "preemption of a slot with an open diffusion block"),
    (dict(prefix_cache=True), "prefix cache is not implemented"),
])
def test_what_the_engine_does_not_implement_for_an_open_block_is_refused(bcfg, match):
    with pytest.raises(NotImplementedError, match=match):
        engine(tiny(), **bcfg)


def test_a_pool_block_holding_no_whole_diffusion_blocks_is_refused():
    with pytest.raises(ValueError, match="no whole number of diffusion blocks"):
        engine(tiny(), pcfg=PagedCacheConfig(num_blocks=24, block_size=6,
                                             blocks_per_seq=8))


def test_migration_of_an_open_block_is_refused():
    eng = engine(tiny())
    req = Request(rid=0, prompt=_prompt(8), max_new_tokens=4)
    with pytest.raises(MigrationError, match="open diffusion block"):
        eng.prefill_for_migration(req)
    with pytest.raises(MigrationError, match="open diffusion block"):
        eng.admit_migrated(req, 1, {}, b"")


@pytest.mark.parametrize("over,match", [
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"block_length": 3}, "power of"),
    ({"denoising_steps": 5}, "denoising passes"),
    ({"use_sliding_window": True}, "window layers"),
], ids=["dense-layers", "sparse-step", "block-3", "steps-5", "window"])
def test_what_the_block_does_not_implement_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        config_from_dict(tiny(**over))


# ------------------------------------------------------ scopes and programs


@pytest.fixture(scope="module")
def program_paths():
    """The ``op_name`` path of every operation of the lowered pass,
    prefill and reveal programs."""
    eng = engine(tiny())
    s, b = eng.bcfg.slots, eng.block_len
    pass_text = eng._decode.lower(
        eng.params, eng.pools, np.zeros((s, 6), np.int32),
        np.zeros((s,), np.int32), np.zeros((s, b), np.int32),
        np.zeros((s,), bool)).as_text(debug_info=True)
    prefill_text = eng._prefill.lower(
        eng.params, np.zeros((1, 8), np.int32)).as_text(debug_info=True)
    unmask_text = eng._unmask.lower(
        jnp.zeros((s, b, 96)), np.zeros((s, b), bool),
        np.zeros((s,), np.int32)).as_text(debug_info=True)
    return {name: re.findall(r'loc\("([^"]*)"', t) for name, t in (
        ("pass", pass_text), ("prefill", prefill_text), ("unmask", unmask_text))}


@pytest.mark.parametrize("program,scope", [
    ("pass", "ft_attn_block"), ("pass", "ft_moe_router"),
    ("pass", "ft_moe_experts"), ("pass", "ft_head"), ("pass", "ft_embed"),
    ("pass", "ft_norm"), ("prefill", "ft_attn_block"),
    ("prefill", "ft_moe_experts"), ("unmask", "ft_unmask"),
])
def test_the_served_programs_hold_the_scope(program_paths, program, scope):
    assert any(re.search(rf"\b{scope}\b", p) for p in program_paths[program])


def test_the_scopes_never_nest_and_no_shared_expert_or_head_is_left(program_paths):
    from benchmarks.readers import spans as S

    for name, paths in program_paths.items():
        for p in paths:
            found = S._SCOPE.findall(p)
            assert len(set(found)) <= 1, p
            assert "ft_moe_shared" not in found
    assert not any("ft_head" in p for p in program_paths["prefill"])


def test_the_programs_are_named_for_the_benchmark_to_find():
    from benchmarks.readers import chain

    eng = engine(tiny())
    s, b = eng.bcfg.slots, eng.block_len
    text = eng._decode.lower(
        eng.params, eng.pools, np.zeros((s, 6), np.int32),
        np.zeros((s,), np.int32), np.zeros((s, b), np.int32),
        np.zeros((s,), bool)).as_text()
    name = re.search(r"module @(\S+)", text)[1]
    assert chain.DECODE_PROGRAM.search(name), name
    assert re.search(_metric("kernels.block_decode_roofline")["args"]["match"], name)
    assert chain.PICK_PROGRAM.search("jit_" + eng._unmask.__name__)
    assert eng._prefill.__name__ == "prefill_program"


# ------------------------------------------------------------- the benchmark


def _metric(name):
    return harness._read_json(os.path.join(
        REPO, "benchmarks", "metrics", f"{name}.json"))


def test_load_cell_finds_the_new_cell_and_its_entries_are_additions():
    bench = harness.load_benchmark()
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "sdar-30b-a3b-chat"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_closed_block"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "serve_ttft_p50_ms", "serve_gap_p95_ms", "setup_s"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("engine.tokens_per_slot_pass")
    new = bench["per_layer"][first:first + 5]
    assert [m["name"] for m in new] == [
        "engine.tokens_per_slot_pass", "attn.block_share",
        "engine.unmask_share", "kernels.block_decode_roofline",
        "kernels.block_prefill_roofline"]
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        _metric(m["name"])
    per_layer = {m["name"] for m in cell.per_layer}
    assert {"kernels.paged_kernel_share", "kernels.moe_kernel_share",
            "moe.experts_hit_share", "engine.gap_device_ms_p50",
            "entry.compiles_in_window.serve"} <= per_layer
    assert not {"kv_cache.state_bytes_share", "kernels.moe_decode_roofline",
                "attn.full_share"} & per_layer
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])


def test_the_traffic_fits_the_pool_and_every_answer_is_whole_blocks():
    t = harness.load_cell(CELL).traffic
    assert all(m % PUBLISHED["block_length"] == 0 for m in t["max_new"])
    assert t["num_blocks"] == t["slots"] * t["blocks_per_seq"] + 1
    assert max(t["prompt_lens"]) + max(t["max_new"]) == t["block_size"] * t["blocks_per_seq"]
    assert t["block_size"] % PUBLISHED["block_length"] == 0
    assert t["num_blocks"] * t["block_size"] * C.cache_bytes_per_position(PUBLISHED) \
        == pytest.approx(1.61e9, rel=0.002)
    assert t["check_blocks"] * t["block_size"] >= (
        t["check_prompt"] + t["check_steps"] * PUBLISHED["block_length"])
    # the check holds every slot live, each in blocks of its own
    assert t["slots"] * t["check_blocks"] + 1 <= t["num_blocks"]


def test_the_loop_issues_one_order_and_the_seed_makes_the_tokens():
    """The block loop issues the deck in the ONE order ``spread_deck``
    deals for ``DEAL_SEED``, whatever the run's seed; the seed still makes
    every prompt's token ids, all below the mask id."""
    from benchmarks.lib import traffic as T

    t = harness.load_cell(CELL).traffic
    sent = {5: [], 6: []}
    for seed in sent:
        eng = types.SimpleNamespace(
            submit=lambda req, seed=seed: sent[seed].append(req) or True,
            batcher=None, cfg=types.SimpleNamespace(mask_id=MASK))
        loop = driver.BlockLoop(eng, t, seed, 96, 1 << 30)
        for _ in range(30):
            loop.issue()
        dealt = driver.spread_deck(t, driver.DEAL_SEED)
        assert [loop.sizes[i] for i in range(30)] == [
            T.request_size(dealt, i) for i in range(30)]
        assert max(int(r.prompt.max()) for r in sent[seed]) < MASK
    assert [len(r.prompt) for r in sent[5]] == [len(r.prompt) for r in sent[6]]
    assert not np.array_equal(sent[5][0].prompt, sent[6][0].prompt)


#: the catalog row's ``config`` (JetLM/SDAR-30B-A3B-Chat's config.json)
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}


def test_the_configuration_file_keeps_every_published_key_but_the_depth():
    assert PUBLISHED["reduced"] == ["num_hidden_layers"]
    for key, value in CATALOG.items():
        if key == "num_hidden_layers":
            assert PUBLISHED["published"][key] == value
            assert PUBLISHED[key] == 6
        else:
            assert PUBLISHED[key] == value, key
    assert set(PUBLISHED["assumed"]) >= {
        "block_length", "denoising_steps", "mask_token_id", "qk_norm",
        "embedding_scale", "commit_pass", "logits_position"}
    cfg = config_from_dict(PUBLISHED)
    assert (cfg.block_len, cfg.denoise_steps, cfg.mask_id) == (4, 2, 151669)
    assert cfg.n_heads * cfg.head_dim == 4096 and cfg.n_experts == 128


def test_the_cut_is_the_files_arithmetic():
    """``why_reduced``'s numbers, from the counts the rooflines use."""
    c = PUBLISHED
    outside = C.attention_params(c) + 2 * 2048 + 2 * 128
    assert outside == 19_140_864
    assert 128 * C.expert_params(c) == 603_979_776
    layer = outside + 128 * C.expert_params(c)
    vocab = 2 * 151_936 * 2048
    assert 6 * layer + vocab == pytest.approx(4_361e6, rel=1e-3)
    assert 48 * layer + vocab == pytest.approx(30.53e9, rel=1e-3)
    assert C.cache_bytes_per_position(c) == 12_288
    assert C.expert_bytes(c) == pytest.approx(9.44e6, rel=1e-3)
    # a prompt position: 0.683 GFLOP outside the attention's triangle
    assert C.prefill_flops(c, 4) - C.block_causal_pairs(4, 4) * 6 * 32 * 512 \
        == pytest.approx(4 * 0.683e9, rel=1e-3)


def test_the_block_causal_pairs_are_counted_by_hand():
    assert C.block_causal_pairs(8, 4) == 4 * 4 + 4 * 8
    assert C.block_causal_pairs(5, 1) == 15
    assert C.prefill_flops(PUBLISHED, 10) == C.prefill_flops(PUBLISHED, 8)


def test_the_tokens_a_slot_pass_is_revealed_over_passes():
    spans = [types.SimpleNamespace(name="ft.engine.bookkeeping", start_ns=s,
                                   stats=st)
             for s, st in ((10.0, {"revealed": 64, "decoded": 32}),
                           (20.0, {"revealed": 0, "decoded": 32}),
                           (30.0, {"revealed": 64, "decoded": 32}),
                           (40.0, {"decoded": 32}),
                           (99.0, {"revealed": 64, "decoded": 32}))]
    ctx = types.SimpleNamespace(window=(0.0, 50.0))
    ctx._program_spans = spans
    assert R.sum_ratio(ctx, "ft.engine.bookkeeping", "revealed", "decoded") \
        == pytest.approx(128 / 96)
    assert R.sum_ratio(ctx, "ft.engine.bookkeeping", "missing", "decoded") is None


def test_the_parent_stops_at_once_on_the_new_cell():
    with pytest.raises(ValueError, match="model_type 'sdar_moe2' is not implemented"):
        config_from_dict(dict(PUBLISHED, model_type="sdar_moe2"))
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
         "4100000999"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert all(m["value"] is None for m in line["metrics"].values())
    assert {"engine.tokens_per_slot_pass", "kernels.paged_kernel_share",
            "kernels.moe_kernel_share", "entry.compiles_in_window.serve",
            "engine.prefill_time_share"} <= set(line["metrics"])
