"""The Kimi-Linear block through the one ``ServingEngine`` against its
plain reference (``benchmarks/reference/kimi_linear_decoder.py``), at a
small shape that keeps every ratio: hidden 64, 8 layers in two periods
K,K,K,M (6 KDA layers of 4 heads of 16 with a state a slot, 2 MLA layers of
4 heads of 16 + 8 over a cached row of 16 + 8), a dense first layer, 16
sigmoid-routed experts top-4 with 4 held and one shared, an untied head.
"""

import copy
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

from benchmarks.lib import counts_kimi as C, harness, serve_closed_state as driver
from benchmarks.reference import kimi_linear_decoder as ref
from flextree_tpu.models import kimi_linear as kimi, pangu_ultra_moe as pangu
from flextree_tpu.models.configs import (
    BLOCKS, config_from_dict, pool_layout, position_parts, slot_parts,
)
from flextree_tpu.models.moe import gated_ffn
from flextree_tpu.obs import flight_recorder
from flextree_tpu.ops.linear_attention import (
    _heads_a_step, _step_pallas, causal_conv, delta_rule_chunked,
    delta_rule_step, runs_step_kernel, step_kernel_admits,
)
from flextree_tpu.serving import (
    BatcherConfig, PagedCacheConfig, Request, ServingEngine, costs,
)
from flextree_tpu.serving.kv_cache import init_pools, init_state
from flextree_tpu.serving.migration import (
    MigrationError, pack_kv, unpack_kv, unpack_state,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "kimi-linear-48b-a3b.gen-closed-c128"
PUBLISHED = harness._read_json(os.path.join(
    REPO, "benchmarks", "configs", "kimi-linear-48b-a3b.json"))


def tiny(dtype="float32", **over) -> dict:
    c = copy.deepcopy(PUBLISHED)
    c.update(
        vocab_size=128, hidden_size=64, intermediate_size=160, head_dim=16,
        kv_lora_rank=16, num_attention_heads=4, num_key_value_heads=4,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        moe_intermediate_size=32, num_experts=4, num_experts_per_token=4,
        num_hidden_layers=8, published={"num_experts": 16},
        experts_held=[4, 8], compute_dtype=dtype, param_dtype=dtype,
        linear_attn_config={
            "full_attn_layers": [4, 8], "head_dim": 16,
            "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 4,
            "short_conv_kernel_size": 4,
        },
    )
    c.update(over)
    return c


PCFG = PagedCacheConfig(num_blocks=40, block_size=4, blocks_per_seq=8)


def engine(config, seed=3, slots=3, pcfg=PCFG, **bcfg):
    return ServingEngine.from_config(
        config, pcfg, BatcherConfig(slots=slots, **bcfg), seed=seed)


def _alone(config, prompt, max_new, seed=3):
    eng = engine(config, seed=seed, slots=1)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=max_new))
    eng.run_until_idle()
    return eng.completed[0].tokens


def _prompts(n, length=9, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 128, (length,)).astype(np.int32) for _ in range(n)]


# ----------------------------- (a) the chunked scan against the recurrence


def _recurrence_inputs(t, gate, seed=0, h=3, dk=8, dv=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (t, h, dk)))
    v = jax.random.normal(ks[2], (t, h, dv))
    g = -gate * jax.random.uniform(ks[3], (t, h, dk), minval=0.8, maxval=1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)))
    s0 = jax.random.normal(ks[5], (h, dk, dv))
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("gate", [0.0, 0.05, 5.0], ids=["none", "weak", "strong"])
@pytest.mark.parametrize("t", [5, 64, 100, 131])
@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_scan_equals_the_token_by_token_recurrence(chunk, t, gate):
    """Chunks of 16 and 64, lengths that are no multiple of either, and
    gates from no decay at all to ``g`` near -5 a token (a chunk's decay
    then passes ``exp(-300)``: the factorisation that overflows)."""
    q, k, v, g, beta, s0 = _recurrence_inputs(t, gate)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = ref.delta_rule(q, k, v, g, beta, s0)
        o, s = delta_rule_chunked(
            q[None], k[None], v[None], g[None], beta[None], s0[None],
            chunk=chunk, sub=16)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    np.testing.assert_allclose(o[0], want_o, atol=2e-5 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(s[0], want_s, atol=2e-5 * float(jnp.abs(want_s).max()) + 1e-30)


def test_a_decay_that_underflows_float32_gives_zeros_not_nans():
    q, k, v, g, beta, s0 = _recurrence_inputs(70, 40.0)
    o, s = delta_rule_chunked(
        q[None], k[None], v[None], g[None], beta[None], s0[None], chunk=64)
    want_o, want_s = ref.delta_rule(q, k, v, g, beta, s0)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    np.testing.assert_allclose(o[0], want_o, atol=1e-5)
    np.testing.assert_allclose(s[0], want_s, atol=1e-5)


def test_the_one_token_update_is_the_recurrence_and_skips_inactive_slots():
    q, k, v, g, beta, s0 = _recurrence_inputs(4, 0.3)
    state = jnp.stack([s0, 2 * s0])
    for t in range(4):
        row = lambda x: jnp.stack([x[t], x[t]])  # noqa: E731
        o, new = delta_rule_step(
            row(q), row(k), row(v), row(g), row(beta), state,
            jnp.asarray([True, False]))
        assert np.asarray(new[1]).tobytes() == np.asarray(state[1]).tobytes()
        state = new
    want_o, want_s = ref.delta_rule(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o[0], want_o[-1], atol=1e-5)
    np.testing.assert_allclose(state[0], want_s, atol=1e-5)


def _step_inputs(slots, h, dk=128, dv=128, gate=0.3, seed=0):
    """One token a slot at a head size the update's kernel admits."""
    q, k, v, g, beta, s0 = _recurrence_inputs(slots, gate, seed, h, dk, dv)
    state = s0[None] * jnp.arange(1.0, slots + 1.0)[:, None, None, None]
    return tuple(x.astype(jnp.float32) for x in (q, k, v, g, beta, state))


@pytest.mark.parametrize("h,heads", [
    (8, None), (16, 8), (24, None), (16, None), (24, 24),
], ids=["8", "16-by-8", "24-by-8", "16", "24"])
def test_the_update_kernel_is_the_jnp_body(h, heads):
    """The Pallas kernel under the interpreter against the ``jnp`` body,
    heads of 128 x 128: head counts the 1 MB block limit takes whole (8,
    16), one it must split (24: 8 a step, the only multiple of 8 that
    divides it and fits), and forced groups (16 by 8, 24 whole).  An
    inactive slot's state comes back bit for bit."""
    assert _heads_a_step(h, 128, 128) == {8: 8, 16: 16, 24: 8}[h]
    assert _heads_a_step(32, 128, 128) == 16 and _heads_a_step(8, 256, 256) == 8
    q, k, v, g, beta, state = _step_inputs(3, h)
    active = jnp.asarray([True, False, True])
    want_o, want_s = delta_rule_step(q, k, v, g, beta, state, active, impl="jnp")
    o, s = _step_pallas(
        q, k, v, g, beta, state, active, heads=heads, interpret=True)
    np.testing.assert_allclose(o, want_o, atol=2e-6 * float(jnp.abs(want_o).max()))
    np.testing.assert_allclose(s, want_s, atol=2e-6 * float(jnp.abs(want_s).max()))
    assert np.asarray(s[1]).tobytes() == np.asarray(state[1]).tobytes()
    assert np.asarray(s[0]).tobytes() != np.asarray(state[0]).tobytes()


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_a_one_token_decay_that_underflows_gives_zeros_not_nans(impl):
    """``g`` near -200 a channel: ``exp(g)`` is 0 in float32, the old
    state is gone, and what is left is the token's own write."""
    q, k, v, g, beta, state = _step_inputs(2, 8, gate=250.0)
    o, s = delta_rule_step(q, k, v, g, beta, state, impl=impl)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    u = v * beta[..., None]
    np.testing.assert_allclose(s, k[..., None] * u[..., None, :], atol=1e-6)
    np.testing.assert_allclose(
        o, (k * q).sum(-1, keepdims=True) * u, atol=1e-6)


@pytest.mark.parametrize("shape,dtype,admitted", [
    ((128, 32, 128, 128), "float32", True),   # the benchmark's cell
    ((3, 8, 128, 256), "float32", True),
    ((3, 4, 16, 16), "float32", False),       # the tests' block
    ((3, 8, 8, 8), "float32", False),
    ((3, 8, 128, 64), "float32", False),
    ((3, 4, 128, 128), "float32", False),     # no whole sublane tile of heads
    ((3, 8, 128, 128), "bfloat16", False),    # the state is float32
])
def test_the_update_kernel_runs_on_a_tpu_at_shapes_its_tiling_admits(
    monkeypatch, shape, dtype, admitted
):
    from flextree_tpu.utils import backend

    state = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    assert step_kernel_admits(state) == admitted
    assert not runs_step_kernel(state)  # the CPU: the jnp body, whatever
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    assert runs_step_kernel(state) == admitted


def test_a_shape_the_update_kernel_refuses_walks_the_jnp_body_without_a_raise(
    monkeypatch
):
    """8-wide heads where the chip would run the kernel: no Mosaic
    lowering is tried (on this CPU it would raise), the result is the
    ``jnp`` body's; and an unknown ``impl`` is refused."""
    from flextree_tpu.utils import backend

    q, k, v, g, beta, state = _step_inputs(2, 3, dk=8, dv=8)
    want = delta_rule_step(q, k, v, g, beta, state, impl="jnp")
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    got = delta_rule_step(q, k, v, g, beta, state)
    for a, b in zip(got, want):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    with pytest.raises(ValueError, match="impl"):
        delta_rule_step(q, k, v, g, beta, state, impl="mosaic")


def test_the_convolution_carries_its_last_inputs():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 11, 6))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
    whole, tail = causal_conv(x, w)
    first, mid = causal_conv(x[:, :7], w)
    rest, last = causal_conv(x[:, 7:], w, mid)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), whole, atol=1e-6)
    assert np.array_equal(tail, x[:, -3:]) and np.array_equal(last, tail)
    # position 0 sees zeros before it
    np.testing.assert_allclose(whole[:, 0], x[:, 0] * w[3], atol=1e-6)


# ------------------------------------- (b) engine against the reference

# bf16 at these toy widths against the float32 reference, as
# tests/test_pangu_ultra_moe.py reads it: rounding of the residual stream
# after each of 16 residual adds moves logits by a few hundredths of the
# largest; a wrong mechanism moves them by O(1)
BF16_LOGITS_TOL = 6e-2


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_write_and_decode_through_the_engine_equal_one_reference_forward(dtype):
    """The engine's own programs (chunked prefill of 70 tokens: two chunks
    of 64, the write of rows and state, 6 decode rounds through the latent
    pool and the state) against ONE reference forward that runs the
    recurrence token by token."""
    config = tiny(dtype)
    eng = engine(config, pcfg=PagedCacheConfig(40, 4, 24))
    got = driver.check_against_reference(eng, config, 5, 70, 6, 19)
    if dtype == "float32":
        assert got["prefill_rel_err"] < 1e-4 and got["decode_rel_err_max"] < 1e-4
        assert got["score_rel_err"] < 1e-4 and got["picks_differing"] == 0
        assert got["ok"], got
    else:
        assert max(got["prefill_rel_err"], got["decode_rel_err_max"]) < BF16_LOGITS_TOL
    assert got["pool_ok"], got
    assert got["picks"] == 7 * 76 * 4


def _mutated(name, config, params):
    """A reference that differs from the program by one mechanism."""
    config, params = copy.deepcopy(config), jax.tree.map(lambda a: a, params)
    layers = params["layers"]
    if name == "no_decay":  # a_log -> -inf: g = 0
        for i in (0, 1, 2, 4, 5, 6):
            layers[i] = dict(layers[i], a_log=jnp.full_like(layers[i]["a_log"], -30.0))
    elif name == "no_convolution":  # only the newest tap
        for i in (0, 1, 2, 4, 5, 6):
            conv = layers[i]["conv"]
            layers[i] = dict(layers[i], conv=conv.at[:3].set(0.0))
    elif name == "no_gate_bias":
        for i in (0, 1, 2, 4, 5, 6):
            layers[i] = dict(layers[i], b_gb=jnp.zeros_like(layers[i]["b_gb"]))
    elif name == "no_output_norm_scale":
        for i in (0, 1, 2, 4, 5, 6):
            layers[i] = dict(layers[i], ln_o=jnp.ones_like(layers[i]["ln_o"]))
    elif name == "no_shared_key":  # the row's last 8 numbers left out
        for i in (3, 7):
            layers[i] = dict(
                layers[i], wkv_a=layers[i]["wkv_a"].at[:, 16:].set(0.0))
    elif name == "kinds_swapped":
        config["linear_attn_config"] = dict(
            config["linear_attn_config"], kda_layers=[1, 2, 3, 5, 6, 7])
        config["routed_scaling_factor"] = 1.0
    return config, params


@pytest.mark.parametrize("name", [
    "no_decay", "no_convolution", "no_gate_bias", "no_output_norm_scale",
    "no_shared_key", "kinds_swapped",
])
def test_one_wrong_mechanism_fails_the_comparison(name):
    config = tiny()
    eng = engine(config, pcfg=PagedCacheConfig(40, 4, 24))
    wrong_config, wrong_params = _mutated(name, config, eng.params)
    got = driver.check_against_reference(
        eng, config, 5, 70, 4, 19, reference_params=wrong_params,
        reference_config=wrong_config)
    assert not got["ok"] and got["pool_ok"], got


@pytest.mark.parametrize("name", [
    "rows_under_kda", "state_in_bf16", "state_a_position", "no_tail",
])
def test_what_is_not_a_state_a_slot_and_a_row_a_position_fails_the_pool_check(name):
    config = tiny()
    eng = engine(config)
    assert driver.pool_ok(eng, config)
    held = types.SimpleNamespace(
        bcfg=eng.bcfg, pools=dict(eng.pools), state=dict(eng.state))
    if name == "rows_under_kda":  # a paged part in every layer
        held.pools["ckv"] = eng.pools["ckv"] * 4
    elif name == "state_in_bf16":
        held.state["s"] = [a.astype(jnp.bfloat16) for a in eng.state["s"]]
    elif name == "state_a_position":  # a state kept a position, in blocks
        held.state["s"] = [jnp.zeros((40, 4, 4, 16, 16))] * 6
    elif name == "no_tail":
        del held.state["conv"]
    assert not driver.pool_ok(held, config)


def test_requests_through_the_engine_follow_the_reference_greedily():
    """Whole requests: every emitted token is the reference's argmax given
    the tokens before it (float32, where no pick is a near-tie)."""
    config = tiny()
    eng = engine(config, slots=2)
    prompts = _prompts(3, length=11)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    eng.run_until_idle()
    for i, p in enumerate(prompts):
        tokens = eng.completed[i].tokens
        assert len(tokens) == 6
        seq = np.concatenate([p, tokens[:-1]])
        want = ref.forward(eng.params, jnp.asarray(seq), config,
                           logits_from=len(p) - 1)["logits"]
        assert np.array_equal(np.argmax(np.asarray(want), -1), tokens)


# ------------------------------------------------------- (c) the shares


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-1, 2-3, ... 14-15 on EIGHT chips: the eight routed parts,
    with the shared expert counted once, equal the uncut reference layer;
    and a share alone is the reference given the same share."""
    config = tiny(num_experts=16, experts_held=[0, 16])
    cfg = config_from_dict(config)
    layer = kimi.init_params(jax.random.PRNGKey(4), cfg)["layers"][2]
    m = jax.random.normal(jax.random.PRNGKey(5), (23, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(m @ layer["router"])
        top, picks = jax.lax.top_k(scores, 4)
        w = top / top.sum(-1, keepdims=True) * 2.446
        whole = ref.routed_experts(layer["experts"], m, picks, w, (0, 16)) \
            + ref._gated(layer["shared"], m)
    shared = np.asarray(gated_ffn(layer["shared"], m))
    total = -7 * shared  # eight shares count the shared expert eight times
    for lo in range(0, 16, 2):
        held = (lo, lo + 2)
        share = dict(layer, experts={
            k: v[lo : lo + 2] for k, v in layer["experts"].items()})
        y, moe = pangu.ffn_layer(
            share, m, types.SimpleNamespace(
                is_dense=lambda i: False, top_k=4, routed_scale=2.446,
                norm_topk=True, experts_held=held, ffn_rows=4096), 2,
            rows=jnp.ones((23,), bool))
        assert int(moe["sizes"].sum()) == int(
            ((picks >= lo) & (picks < lo + 2)).sum())
        alone = ref.routed_experts(share["experts"], m, picks, w, held)
        np.testing.assert_allclose(
            np.asarray(y) - shared, np.asarray(alone), atol=2e-5)
        total = total + np.asarray(y)
    np.testing.assert_allclose(total, np.asarray(whole), atol=1e-4)


# ----------------------------------------- (d) a slot's state, admission


def test_a_reused_slot_starts_from_a_zero_state():
    """One slot, two requests one after the other: the second's tokens are
    a fresh engine's, bit for bit, whatever the first left in the slot."""
    config = tiny()
    first, second = _prompts(2, length=13)
    eng = engine(config, slots=1)
    eng.submit(Request(rid=0, prompt=first, max_new_tokens=7))
    eng.submit(Request(rid=1, prompt=second, max_new_tokens=7))
    eng.run_until_idle()
    assert any(float(jnp.abs(a).max()) > 0 for a in eng.state["s"])
    assert np.array_equal(eng.completed[1].tokens, _alone(config, second, 7))
    assert eng.report()["counters"]["serve.state_resets"] == 2


def test_a_round_leaves_an_inactive_slots_state_alone():
    config = tiny()
    eng = engine(config, slots=3)
    p = _prompts(1)[0]
    eng.submit(Request(rid=0, prompt=p, max_new_tokens=6))
    marked = jax.tree.map(lambda a: a.at[1:].set(0.37), eng.state)
    eng.state = marked
    before = jax.tree.map(lambda a: np.asarray(a[1:]).tobytes(), marked)
    eng.run_until_idle()
    after = jax.tree.map(lambda a: np.asarray(a[1:]).tobytes(), eng.state)
    assert before == after  # slots 1 and 2 never held a sequence
    assert np.array_equal(eng.completed[0].tokens, _alone(config, p, 6))


# ------------------------------ (e) preemption and migration carry the state


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_preemption_resumes_a_sequence_token_for_token(mode):
    """A pool too small for the traffic: sequences are evicted and resumed
    (their state swapped whole, or replayed), and every request still
    returns what it returns alone."""
    config = tiny()
    small = PagedCacheConfig(num_blocks=10, block_size=4, blocks_per_seq=8)
    eng = engine(config, slots=4, pcfg=small, admission="ondemand", preempt=mode)
    prompts = _prompts(4)
    for i, p in enumerate(prompts):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=14))
    eng.run_until_idle()
    counters = eng.report()["counters"]
    assert counters["serve.preempts"] >= 1 and counters["serve.resumes"] >= 1
    per_slot = costs.state_bytes_per_slot(eng.cfg)
    if mode == "swap":
        assert counters["serve.state_swap_bytes"] == \
            counters["serve.swap_outs"] * per_slot
        assert counters["serve.swap_out_bytes"] > counters["serve.state_swap_bytes"]
    else:
        assert counters.get("serve.state_swap_bytes", 0) == 0
        assert counters["serve.state_resets"] == 4 + counters["serve.resumes"]
    for i, p in enumerate(prompts):
        assert np.array_equal(eng.completed[i].tokens, _alone(config, p, 14))


@pytest.mark.parametrize("codec", ["f32", "int8"])
def test_a_migration_ships_the_state_and_its_metadata_states_it(codec):
    config = tiny()
    prompt = np.arange(3, 12, dtype=np.int32)
    req = Request(rid=0, prompt=prompt, max_new_tokens=5, arrival_s=1.0)
    sender = engine(config)
    out = sender.prefill_for_migration(req, codec=codec)
    meta = out["meta"]
    assert meta["layout"] == {"ckv": [24]} and meta["n_layers"] == 2
    assert meta["state"]["layout"] == {"s": [4, 16, 16], "conv": [3, 192]}
    assert meta["state"]["n_layers"] == 6 and len(meta["state"]["tensors"]) == 12
    carried = unpack_state(meta, out["blob"])
    assert [a.shape for a in carried["s"]] == [(4, 16, 16)] * 6
    assert len(unpack_kv(meta, out["blob"])["ckv"]) == 2
    if codec == "f32":
        assert meta["state"]["nbytes"] == costs.state_bytes_per_slot(sender.cfg)
        receiver = engine(config)
        assert receiver.admit_migrated(
            req, out["first_token"], meta, out["blob"]) is not None
        receiver.run_until_idle()
        assert np.array_equal(
            receiver.completed[0].tokens, _alone(config, prompt, 5))


def test_a_migration_lands_on_a_replica_of_the_same_layout_only():
    config = tiny()
    req = Request(rid=0, prompt=np.arange(3, 12, dtype=np.int32),
                  max_new_tokens=5, arrival_s=1.0)
    out = engine(config).prefill_for_migration(req)
    # a payload whose state is cut away, or torn, is refused
    meta = dict(out["meta"])
    del meta["state"]
    with pytest.raises(MigrationError):
        engine(config).admit_migrated(req, out["first_token"], meta, out["blob"])
    torn = bytearray(out["blob"])
    torn[-5] ^= 0x40  # a byte of the state
    with pytest.raises(MigrationError):
        unpack_state(out["meta"], bytes(torn))
    # another period: three MLA layers, five KDA layers
    other = tiny(linear_attn_config=dict(
        tiny()["linear_attn_config"], kda_layers=[1, 2, 3, 5, 6],
        full_attn_layers=[4, 7, 8]))
    with pytest.raises(MigrationError, match="layout"):
        engine(other).admit_migrated(
            req, out["first_token"], out["meta"], out["blob"])
    # and a block that keeps nothing a slot ships no state at all
    rows = {"k": [np.zeros((2, 4, 1, 8), np.float32)]}
    plain, blob = pack_kv(rows)
    assert "state" not in plain and unpack_state(plain, blob) == {}


# ------------------------------------------------- (f) the prefix cache


def test_the_prefix_cache_is_refused_with_the_reason():
    with pytest.raises(NotImplementedError, match="snapshot"):
        engine(tiny(), prefix_cache=True)


# ----------------------------------------------------- (g) the layout


def test_no_paged_part_lies_under_a_kda_layer():
    cfg = config_from_dict(tiny())
    layout = pool_layout(cfg)
    assert len(layout) == 8
    for i, layer in enumerate(layout):
        if cfg.kda[i]:
            assert layer["position"] == {} and set(layer["slot"]) == {"s", "conv"}
            assert layer["slot"]["s"] == ((4, 16, 16), "float32")
        else:
            assert layer == {"position": {"ckv": (24,)}, "slot": {}}
    assert position_parts(cfg) == {"ckv": ((24,), 2)}
    assert slot_parts(cfg) == {
        "s": (((4, 16, 16), "float32"), 6), "conv": (((3, 192), "float32"), 6)}
    pools, state = init_pools(cfg, PCFG), init_state(cfg, 3)
    assert [p.shape for p in pools["ckv"]] == [(40, 4, 24)] * 2
    assert [a.shape for a in state["s"]] == [(3, 4, 16, 16)] * 6


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_costs_state_both_numbers_from_the_layout(dtype):
    cfg = config_from_dict(tiny(dtype))
    pools, state = init_pools(cfg, PCFG), init_state(cfg, 3)
    per_position = sum(
        p.nbytes for p in jax.tree.leaves(pools)) // (40 * 4)
    per_slot = sum(a.nbytes for a in jax.tree.leaves(state)) // 3
    assert costs.cache_bytes_per_position(cfg) == per_position
    assert costs.state_bytes_per_slot(cfg) == per_slot
    eng = engine(tiny(dtype))
    report = eng.report()
    assert report["cache_bytes_per_position"] == per_position
    assert report["state_bytes_per_slot"] == per_slot
    assert report["state_layers"] == 6 and report["attn_layers"] == 2


def test_the_published_sizes_give_the_published_bytes():
    cfg = config_from_dict(PUBLISHED)
    assert costs.cache_bytes_per_position(cfg) == 3456 == C.cache_bytes_per_position(PUBLISHED)
    assert costs.state_bytes_per_slot(cfg) == 21_708_800 == C.state_bytes_per_slot(PUBLISHED)
    assert sum(cfg.kda) == 10 and cfg.kda[3] is False and cfg.kda[12] is True
    # the three blocks before this one keep nothing a slot
    for name in ("gpt_neox", "laguna", "pangu_ultra_moe"):
        assert name in BLOCKS
    dense = BLOCKS["gpt_neox"].from_dict({
        "vocab_size": 64, "hidden_size": 32, "num_attention_heads": 2,
        "num_hidden_layers": 3, "intermediate_size": 64})
    assert slot_parts(dense) == {} and costs.state_bytes_per_slot(dense) == 0
    assert init_state(dense, 4) == {}


# -------------------------- (h) ONE latent attention for both blocks


def test_latent_attention_without_rotary_and_compression_is_openpangus_own():
    """Rotary off and uncompressed queries, against openPangu's own path
    fed zero angles (every position 0) and an identity compression (``W_qa``
    the identity, ``ln_q`` ones, inputs of unit mean square)."""
    cfg = config_from_dict(tiny())
    layer = kimi.init_params(jax.random.PRNGKey(2), cfg)["layers"][3]
    a = jax.random.normal(jax.random.PRNGKey(3), (1, 9, 64), jnp.float32)
    a = a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True))
    theirs = types.SimpleNamespace(
        n_heads=4, q_rank=64, kv_rank=16, d_nope=16, d_rope=8, d_v=16,
        rms_eps=cfg.rms_eps, rope=True, rope_theta=10000.0,
        softmax_scale=cfg.softmax_scale, q_block=128, kv_group=1024)
    as_pangu = dict(
        layer, wq_a=jnp.eye(64, dtype=jnp.float32),
        ln_q=jnp.ones((64,), jnp.float32), wq_b=layer["wq"])
    with jax.default_matmul_precision("highest"):
        mine, row = pangu.latent_prefill(layer, a, jnp.arange(9), cfg, 12)
        want, want_row = pangu.latent_prefill(
            as_pangu, a, jnp.zeros((9,), jnp.int32), theirs, 12)
    np.testing.assert_allclose(mine, want, atol=2e-5)
    np.testing.assert_allclose(row, want_row, atol=2e-5)
    # and the absorbed decode over a pool, likewise
    pool = jnp.zeros((5, 4, 24), jnp.float32).at[1:4].set(
        row[0].reshape(3, 4, 24))
    tables = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    lengths = jnp.asarray([9], jnp.int32)
    x = a[:, :1]
    with jax.default_matmul_precision("highest"):
        got, pool_a = pangu.latent_decode(
            layer, x, lengths[:, None], pool, tables, lengths, cfg, False)
        exp, pool_b = pangu.latent_decode(
            as_pangu, x, jnp.zeros((1, 1), jnp.int32), pool, tables, lengths,
            theirs, False)
    np.testing.assert_allclose(got, exp, atol=2e-5)
    np.testing.assert_allclose(pool_a, pool_b, atol=2e-5)


# ------------------------------------------------------ the configuration


def test_the_configuration_file_keeps_every_published_width():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    c = PUBLISHED
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in c["reduced"]:
                assert c[key] == value, key
        lin, pub = c["linear_attn_config"], row["config"]["linear_attn_config"]
        assert c["published"]["linear_attn_config"] == pub
        for key in ("head_dim", "num_heads", "short_conv_kernel_size"):
            assert lin[key] == pub[key]
        assert lin["kda_layers"] == [i for i in pub["kda_layers"] if i <= 13]
        assert lin["full_attn_layers"] == [4, 8, 12]
    assert c["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"]
    assert (c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"],
            c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"],
            c["moe_intermediate_size"], c["intermediate_size"],
            c["num_experts_per_token"], c["routed_scaling_factor"]) == (
        2304, 32, 512, 128, 64, 128, 1024, 9216, 8, 2.446)
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) == (13, 32, 20480)
    assert c["published"]["num_experts"] == 256 and c["experts_held"] == [0, 32]
    assert c["vocab_size"] * 8 == c["published"]["vocab_size"]
    for key in ("deployment", "why_reduced", "assumed"):
        assert c[key]
    cfg = config_from_dict(c)
    assert cfg.n_experts == 256 and cfg.experts_held == (0, 32) and cfg.top_k == 8
    shapes = jax.eval_shape(
        lambda k: kimi.init_params(k, cfg), jax.random.PRNGKey(0))
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert 3.450e9 < count < 3.452e9  # 6.90 GB at bf16
    matrices = C.other_params(c) + 12 * 32 * C.expert_params(c) \
        + c["hidden_size"] * c["vocab_size"]  # + the embedding
    assert 0 < count - matrices < 1.5e6  # norms, convolutions, biases
    assert C.expected_local_picks(c) == 1.0
    assert cfg.active_matmul_params == C.other_params(c) + 12 * 8 * C.expert_params(c)


@pytest.mark.parametrize("key,value,match", [
    ("q_lora_rank", 1536, "compressed queries"),
    ("mla_use_nope", False, "rotary"),
    ("moe_router_activation_func", "softmax", "sigmoid"),
    ("num_expert_group", 8, "expert group"),
    ("num_nextn_predict_layers", 1, "next-token-prediction"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("experts_held", [0, 3], "experts_held"),
    ("linear_attn_config", {
        "full_attn_layers": [4], "head_dim": 16, "kda_layers": [1, 2, 3],
        "num_heads": 4, "short_conv_kernel_size": 4}, "do not split"),
])
def test_what_the_block_does_not_implement_is_refused(key, value, match):
    with pytest.raises(ValueError, match=match):
        config_from_dict(tiny(**{key: value}))


def test_the_table_names_the_block():
    assert BLOCKS["kimi_linear"].config_type is kimi.KimiLinearConfig
    assert isinstance(config_from_dict(tiny()), kimi.KimiLinearConfig)


def test_the_cli_serves_the_configuration_file(tmp_path):
    from flextree_tpu.serving.__main__ import parse_args, serve

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(tiny()))
    eng, reqs, report = serve(parse_args([
        "--cpu", "--config", str(path), "--requests", "5", "--blocks", "40",
        "--block-size", "4", "--blocks-per-seq", "8", "--slots", "3",
        "--prompt-len", "9", "--max-new", "6",
    ]))
    assert isinstance(eng.cfg, kimi.KimiLinearConfig)
    assert len(eng.completed) == 5
    assert all(done.n_tokens == 6 for done in eng.completed.values())


# ------------------------------------------------------ spans and counters


def test_spans_and_the_report_carry_the_states_numbers():
    eng = engine(tiny(), slots=4)
    for i, p in enumerate(_prompts(3, length=6, seed=1)):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    with flight_recorder(None) as rec:
        eng.step()
        eng.step()
    spans = [e for e in rec.events if e["kind"] == "span"]
    named = lambda n: [e for e in spans if e["name"] == n]  # noqa: E731
    per_slot = costs.state_bytes_per_slot(eng.cfg)
    for e in named("ft.engine.decode_dispatch"):
        assert e["state_bytes_per_slot"] == per_slot and e["state_layers"] == 6
        # 16-wide heads on the CPU: the jnp body in every layer, and said so
        assert e["state_kernel_layers"] == 0 == eng.report()["state_kernel_layers"]
        assert e["cache_bytes_per_position"] == 2 * 24 * 4 and e["attn_layers"] == 2
        # and lax.ragged_dot in every expert layer, said likewise (PR 37)
        assert e["expert_layers"] == eng.cfg.n_sparse == eng.report()["expert_layers"]
        assert e["expert_kernel_layers"] == 0 == eng.report()["expert_kernel_layers"]
    assert [e["state_slots_live"] for e in named("ft.engine.bookkeeping")] == [3, 3]
    assert [e["state_bytes"] for e in named("ft.engine.prefill")] == [per_slot] * 3
    # a block that keeps no state says 0
    from flextree_tpu.models.transformer import TransformerConfig, init_params

    dense_cfg = TransformerConfig(
        vocab_size=128, d_model=24, n_heads=1, n_layers=2, d_ff=32)
    dense = ServingEngine(
        init_params(jax.random.PRNGKey(0), dense_cfg), dense_cfg, PCFG,
        BatcherConfig(slots=2))
    assert dense.report()["state_bytes_per_slot"] == 0 == dense.report()["state_layers"]
    assert dense.report()["state_kernel_layers"] == 0
    assert dense.report()["expert_layers"] == 0 == dense.report()["expert_kernel_layers"]
    assert dense.state == {}


@pytest.mark.parametrize("platform,dim,took", [
    ("tpu", 128, 6), ("tpu", 16, 0), ("cpu", 128, 0),
], ids=["tpu-128", "tpu-16", "cpu-128"])
def test_the_block_says_which_state_layers_run_the_kernel(
    monkeypatch, platform, dim, took
):
    """``Block.state_kernel_layers``: fixed by the backend and the shapes
    alone, (0, 0) for a block that holds no state."""
    from flextree_tpu.utils import backend

    monkeypatch.setattr(backend, "kernel_platform", lambda: platform)
    lin = dict(tiny()["linear_attn_config"], head_dim=dim, num_heads=8)
    cfg = config_from_dict(tiny(linear_attn_config=lin))
    assert BLOCKS["kimi_linear"].state_kernel_layers(cfg) == (6, took)
    for name in ("gpt_neox", "laguna", "pangu_ultra_moe"):
        assert BLOCKS[name].state_kernel_layers(None) == (0, 0)


NEW_SCOPES = ["ft_kda_proj", "ft_kda_core"]


@pytest.fixture(scope="module")
def program_paths():
    """The ``op_name`` path of every operation of the lowered decode and
    prefill programs."""
    eng = engine(tiny())
    texts = [
        eng._decode.lower(
            eng.params, eng.pools, np.zeros((3, 8), np.int32),
            np.zeros((3,), np.int32), np.zeros((3,), np.int32), eng.state,
        ).as_text(debug_info=True),
        eng._prefill.lower(
            eng.params, np.zeros((1, 12), np.int32)
        ).as_text(debug_info=True),
    ]
    return [re.findall(r'loc\("([^"]*)"', t) for t in texts]


@pytest.mark.parametrize("scope", NEW_SCOPES + [
    "ft_mla_proj", "ft_mla_core", "ft_moe_router", "ft_moe_experts",
    "ft_moe_shared", "ft_mlp", "ft_head", "ft_norm", "ft_embed"])
def test_the_served_programs_hold_the_scope(program_paths, scope):
    for paths in program_paths:
        assert any(re.search(rf"\b{scope}\b", p) for p in paths), scope


def test_the_new_scopes_never_nest_and_are_whole_names(program_paths):
    from benchmarks.readers import spans as S

    for paths in program_paths:
        for p in paths:
            found = S._SCOPE.findall(p)
            assert len(set(found)) <= 1, p
            assert not {"ft_attn", "ft_mla", "ft_kda", "ft_moe"} & set(found)


def test_the_decode_program_is_named_for_the_benchmark_to_find():
    eng = engine(tiny())
    text = eng._decode.lower(
        eng.params, eng.pools, np.zeros((3, 8), np.int32),
        np.zeros((3,), np.int32), np.zeros((3,), np.int32), eng.state,
    ).as_text()
    name = re.search(r"module @(\S+)", text)[1]
    meta = _metric("kernels.kda_decode_roofline")
    assert re.search(meta["args"]["match"], name), name


# ------------------------------------------------------------ the benchmark


def _cells():
    return {w["name"]: w for w in harness.load_benchmark()["workloads"]}


def test_load_cell_finds_the_new_cell():
    assert CELL in _cells()
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_closed_state"
    assert cell.config["model_type"] == "kimi_linear"
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"serve_tokens_per_s", "serve_ttft_p50_ms",
                        "serve_gap_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    assert {"attn.kda_proj_share", "attn.kda_core_share",
            "kernels.kda_decode_roofline", "kernels.kda_prefill_roofline",
            "kv_cache.state_bytes_share", "kernels.kda_kernel_share",
            "attn.mla_proj_share",
            "attn.mla_core_share", "engine.prefill_time_share",
            "kernels.paged_kernel_share", "moe.experts_share",
            "moe.router_share", "moe.local_pick_share",
            "moe.experts_hit_share", "device.idle_share.serve"} <= per_layer
    assert not {"kernels.decode_roofline", "kernels.moe_decode_roofline",
                "kernels.mla_decode_roofline", "kernels.mla_prefill_roofline",
                "attn.window_share", "attn.full_share"} & per_layer
    t = cell.traffic
    assert (t["clients"], t["slots"], t["deck"]) == (128, 128, 200)
    assert t["prompt_lens"] == [512, 1024, 4096] and t["max_new"] == [256, 512, 1024]
    assert t["prompt_weights"] == t["max_new_weights"] == [0.3, 0.4, 0.3]
    assert t["admission"] == "reserve" and t["fused_decode"] is True
    assert t["warmup_rounds"] == 300
    # every slot's worst case fits: admission never waits on memory
    assert t["num_blocks"] == t["slots"] * t["blocks_per_seq"] + 1
    worst = max(t["prompt_lens"]) + max(t["max_new"])
    assert worst == 5120 <= t["block_size"] * t["blocks_per_seq"] < worst + t["block_size"]
    # XLA:TPU gives a (N, bs, 576) pool the minor axis that pads least to
    # the 128 lanes; the kernel reads it row-major, so 576 -> 640 (11.1%)
    # has to be the least (PERF.md section 6, PR 34)
    pad = lambda n: (-n % 128) / n  # noqa: E731
    assert t["block_size"] % 16 == 0
    assert pad(576) < pad(t["block_size"]) and pad(576) < pad(t["num_blocks"])
    assert t["check_prompt"] == 4096 and t["check_steps"] == 8
    assert t["check_blocks"] * t["block_size"] >= t["check_prompt"] + t["check_steps"]
    from benchmarks.lib import traffic as T

    cards = T.request_deck(t, 1)["cards"]
    counts = sorted(cards.count(pair) for pair in set(cards))
    assert counts == [18, 18, 18, 18, 24, 24, 24, 24, 32] and len(cards) == 200
    # the new cell joins the old lists at their end and nowhere else
    bench = harness.load_benchmark()
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    assert bench["workloads"][5]["name"] == CELL
    assert bench["configs"][4]["name"] == "kimi-linear-48b-a3b"
    older = [w["name"] for w in bench["workloads"][:5]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            # after every older cell of the list; later cells come after it
            upto = m["workloads"][: m["workloads"].index(CELL)]
            assert upto == [w for w in older if w in m["workloads"]]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("attn.kda_proj_share")
    assert names[first:first + 6] == [
        "attn.kda_proj_share", "attn.kda_core_share",
        "kernels.kda_decode_roofline", "kernels.kda_prefill_roofline",
        "kv_cache.state_bytes_share", "kernels.kda_kernel_share"]  # PR 35's
    assert first == 49  # and later PRs append after them


@pytest.mark.parametrize("seed", [1, 2147483999, 3100000932])
def test_the_deck_is_spread_by_the_seed_and_holds_its_mix_everywhere(seed):
    """The generator's own multiset and opening, in an order the seed
    alone decides; every stretch of 50 cards holds each prompt length and
    each answer length within one card of the deck's share."""
    from benchmarks.lib import traffic as T

    t = harness.load_cell(CELL).traffic
    plain, dealt = T.request_deck(t, seed), driver.spread_deck(t, seed)
    assert dealt["opening"] == plain["opening"]
    assert sorted(dealt["cards"]) == sorted(plain["cards"])
    assert dealt == driver.spread_deck(t, seed)
    assert dealt["cards"] != driver.spread_deck(t, seed + 1)["cards"]
    twice = dealt["cards"] * 2
    for start in range(0, 200, 7):
        stretch = twice[start : start + 50]
        for axis, sizes in ((0, t["prompt_lens"]), (1, t["max_new"])):
            for size, weight in zip(sizes, (0.3, 0.4, 0.3)):
                held = sum(1 for card in stretch if card[axis] == size)
                assert abs(held - 50 * weight) <= 2, (start, size, held)


def test_the_loop_deals_the_spread_deck():
    """What ``run`` swaps in issues the spread deal, rid by rid."""
    from benchmarks.lib import traffic as T

    t = harness.load_cell(CELL).traffic
    eng = types.SimpleNamespace(submit=lambda req: True, batcher=None)
    loop = driver.StateLoop(eng, t, 5, 64, 1 << 30)
    for _ in range(30):
        loop.issue()
    dealt = driver.spread_deck(t, 5)
    assert [loop.sizes[i] for i in range(30)] == [
        T.request_size(dealt, i) for i in range(30)]


def test_the_parent_stops_at_once_on_the_new_cell():
    """A program without the block (the parent's) refuses the
    configuration before anything is built."""
    with pytest.raises(ValueError, match="model_type 'kimi_linear2' is not implemented"):
        config_from_dict(dict(PUBLISHED, model_type="kimi_linear2"))
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
         "3100000999"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert all(m["value"] is None for m in line["metrics"].values())
    assert {"moe.local_pick_share", "moe.experts_hit_share",
            "kernels.paged_kernel_share", "engine.prefill_time_share",
            "kv_cache.state_bytes_share",
            "kernels.kda_kernel_share"} <= set(line["metrics"])


# ------------------------------------------------ the counts, worked by hand


def test_the_counts_are_what_the_algorithm_must_do():
    c = PUBLISHED
    assert C.layers(c) == {"all": 13, "kda": 10, "mla": 3, "dense": 1, "sparse": 12}
    assert C.kda_params(c) == 2304 * 12288 + 4096 * 2304 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    assert C.mla_params(c) == 2304 * 6144 + 2304 * 576 + 512 * 8192 + 4096 * 2304
    assert C.expert_params(c) == 3 * 2304 * 1024 and C.expert_bytes(c) == 14_155_776
    # weights outside the routed experts, the head's slice among them: 1.37 GB
    assert C.other_weight_bytes(c) == pytest.approx(1.37e9, rel=0.01)
    # a full round: every expert hit, 128 slots, 330,000 live rows
    by_bytes = C.decode_round_bytes(c, 384, 330_000, 128)
    assert by_bytes == pytest.approx(
        1.37e9 + 384 * 14.16e6 + 330_000 * 3456 + 128 * 21.7e6 * 2, rel=0.01)
    state_share = 128 * C.state_bytes_per_slot(c) * 2 / by_bytes
    assert 0.38 < state_share < 0.44  # the state is the largest part
    by_flops = C.decode_round_flops(c, 128, 128 * 12, 330_000)
    assert by_bytes / 819e9 > by_flops / 197e12  # memory binds a round
    # a prompt: the chunked scan is a few hundredths of the matrices
    scan = C.kda_scan_flops_per_token(c) * 4096
    assert 0.01 < scan / C.prefill_flops(c, 4096) < 0.06
    assert C.prefill_flops(c, 4096) == pytest.approx(7.0e12, rel=0.1)


def _metric(name):
    return harness._read_json(
        os.path.join(REPO, "benchmarks", "metrics", f"{name}.json"))


def _trace_ctx(decode_ns=(0.0, 0.0), prefill_ns=0.0, with_counts=True,
               window=1e9):
    """A made-up window: two decode rounds of 128 slots over 330,000 live
    rows whose program ran ``decode_ns`` each, one prefill of 4,096 and
    one of 512 tokens whose programs ran ``prefill_ns`` in all."""
    from benchmarks.lib import xplane as X
    from benchmarks.lib.harness import ReaderContext, Run
    from benchmarks.lib.peaks import Peaks

    E = X.Event
    counts = {"experts_hit": 384, "local_picks": 1536, "picks": 12288} \
        if with_counts else {}
    stated = {"state_bytes_per_slot": 21_708_800,
              "cache_bytes_per_position": 3456} if with_counts else {}
    host = [
        E("bench_window", 0, window),
        E("ft.engine.prefill", 0.10 * window, 0.15 * window, {"prompt_len": 4096}),
        E("ft.engine.prefill", 0.50 * window, 0.05 * window, {"prompt_len": 512}),
        E("ft.engine.decode_dispatch", 0.29 * window, 10, dict(stated)),
        E("ft.engine.bookkeeping", 0.30 * window, 10, dict(counts)),
        E("ft.engine.decode_dispatch", 0.79 * window, 10, dict(stated)),
        E("ft.engine.bookkeeping", 0.80 * window, 10, dict(counts)),
    ]
    modules = [
        E("jit_prefill_program(5)", 0.10 * window, prefill_ns * 2 / 3),
        E("jit_prefill_program(6)", 0.50 * window, prefill_ns / 3),
        E("jit_paged_decode_step_with_state(7)", 0.30 * window, decode_ns[0]),
        E("jit_paged_decode_step_with_state(7)", 0.80 * window, decode_ns[1]),
    ]
    ops = [E("%fusion.1 = f32[128,32,128,128]{3,2,1,0} fusion(%x)", 0.3 * window, 100,
             {"tf_op": "jit(f)/ft_kda_core/mul"}),
           E("%fusion.2 = bf16[128,12288]{1,0} fusion(%y)", 0.3 * window + 100, 300,
             {"tf_op": "jit(f)/ft_kda_proj/dot_general"})]
    planes = [
        X.Plane("/host:CPU", [X.Line("python3", host)]),
        X.Plane("/device:TPU:0", [X.Line("XLA Ops", ops),
                                  X.Line("XLA Modules", modules)]),
    ]
    rounds = [(0.0, 0.0, 128, 128, 330_000), (0.0, 0.0, 128, 128, 330_000)]
    run = harness.Run(True, 0, 0, {}, {"rounds": rounds}, 0.0, None)
    cell = types.SimpleNamespace(name="toy", config=PUBLISHED)
    peaks = Peaks(197e12, 819e9, 16e9, "test")
    return ReaderContext(cell, run, {}, X.Trace(planes), (0.0, window), peaks=peaks)


def test_the_decode_roofline_is_least_time_over_traced_time():
    from benchmarks.readers import kda as K

    meta = _metric("kernels.kda_decode_roofline")
    assert meta["reader"] == "kda:decode_roofline"
    least_ns = C.decode_round_bytes(PUBLISHED, 384, 330_000, 128) / 819e9 * 1e9
    assert least_ns == pytest.approx(16.5e6, rel=0.05)
    got = K.decode_roofline(_trace_ctx((2 * least_ns, 2 * least_ns)), **meta["args"])
    assert got == pytest.approx(50.0)
    at_peak = K.decode_roofline(_trace_ctx((least_ns, least_ns)), **meta["args"])
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    # a parent commit's rounds carry no count: nothing to read, no error
    assert K.decode_roofline(
        _trace_ctx((1e6, 1e6), with_counts=False), **meta["args"]) is None


def test_the_prefill_roofline_is_the_prompts_flops_over_traced_time():
    from benchmarks.readers import kda as K

    meta = _metric("kernels.kda_prefill_roofline")
    assert meta["reader"] == "kda:prefill_roofline"
    least_ns = (C.prefill_flops(PUBLISHED, 4096)
                + C.prefill_flops(PUBLISHED, 512)) / 197e12 * 1e9
    got = K.prefill_roofline(_trace_ctx(prefill_ns=2 * least_ns), **meta["args"])
    assert got == pytest.approx(50.0)
    at_peak = K.prefill_roofline(_trace_ctx(prefill_ns=least_ns), **meta["args"])
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    assert K.prefill_roofline(_trace_ctx(prefill_ns=0.0), **meta["args"]) is None


@pytest.mark.parametrize("stated,want", [
    ({"state_layers": 10, "state_kernel_layers": 10}, 100.0),  # the cell on a TPU
    ({"state_layers": 10, "state_kernel_layers": 0}, 0.0),  # the jnp body, counted
    ({"state_layers": 0, "state_kernel_layers": 0}, None),  # a block with no state
    ({"state_layers": 10}, None),  # a parent commit's span: nothing to read
], ids=["all", "none", "no-state", "parent"])
def test_the_kernel_share_is_the_state_layers_that_run_the_kernel(stated, want):
    from benchmarks.lib import xplane as X
    from benchmarks.readers import spans as S

    meta = _metric("kernels.kda_kernel_share")
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
              if m["name"] == "kernels.kda_kernel_share"]
    # the first state cell's; a later state block's cell comes after it
    assert entry["workloads"][0] == CELL and entry["source"] == "program_counter"


def test_the_state_bytes_share_and_the_scope_shares_read_what_the_program_states():
    from benchmarks.readers import kda as K, spans as S

    meta = _metric("kv_cache.state_bytes_share")
    assert meta["reader"] == "kda:state_bytes_share"
    ctx = _trace_ctx()
    state, rows = 128 * 21_708_800, 330_000 * 3456
    assert K.state_bytes_share(ctx, **meta["args"]) == pytest.approx(
        100.0 * state / (state + rows))
    # a program that states neither number (a parent): nothing to read
    assert K.state_bytes_share(
        _trace_ctx(with_counts=False), **meta["args"]) is None
    assert S.scope_share(ctx, **_metric("attn.kda_core_share")["args"]) == pytest.approx(25.0)
    assert S.scope_share(ctx, **_metric("attn.kda_proj_share")["args"]) == pytest.approx(75.0)
