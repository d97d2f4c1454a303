"""The Olmo-Hybrid block through the one ``ServingEngine`` against its plain
reference (``benchmarks/reference/olmo_hybrid_decoder.py``), at a small
shape that keeps what the published one forces: hidden 48, 8 layers in two
periods L,L,L,F (6 linear layers of 3 heads, keys of 8 under values of 16,
a state a slot; 2 full layers of 3 heads of 16 that cache K and V), a head
count that is no multiple of 8, write strengths up to 2, the reordered
norm, an untied head.
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

from benchmarks.lib import counts_olmo as C, harness, serve_closed_hybrid as driver
from benchmarks.reference import olmo_hybrid_decoder as ref
from flextree_tpu.models import olmo_hybrid as olmo
from flextree_tpu.models.configs import (
    BLOCKS, config_from_dict, pool_layout, position_parts, slot_parts,
)
from flextree_tpu.obs import flight_recorder
from flextree_tpu.ops import paged_attention as pa
from flextree_tpu.ops.linear_attention import (
    _unit_lower_inverse_by_blocks, delta_rule_chunked, delta_rule_step,
    step_kernel_admits,
)
from flextree_tpu.serving import (
    BatcherConfig, PagedCacheConfig, Request, ServingEngine, costs,
)
from flextree_tpu.serving.kv_cache import init_pools, init_state
from flextree_tpu.serving.migration import MigrationError, unpack_kv, unpack_state
from flextree_tpu.utils import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "olmo-hybrid-7b.longdoc-closed-c8"
PUBLISHED = harness._read_json(os.path.join(
    REPO, "benchmarks", "configs", "olmo-hybrid-7b.json"))
L, F = "linear_attention", "full_attention"


def tiny(dtype="float32", **over) -> dict:
    c = copy.deepcopy(PUBLISHED)
    c.update(
        vocab_size=128, hidden_size=48, intermediate_size=96,
        num_attention_heads=3, num_key_value_heads=3, num_hidden_layers=8,
        layer_types=[L, L, L, F] * 2, linear_num_key_heads=3,
        linear_num_value_heads=3, linear_key_head_dim=8,
        linear_value_head_dim=16, compute_dtype=dtype, param_dtype=dtype,
    )
    c.update(over)
    return c


PCFG = PagedCacheConfig(num_blocks=40, block_size=4, blocks_per_seq=8)
LONG = PagedCacheConfig(num_blocks=40, block_size=4, blocks_per_seq=24)


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


# ------------------------- (a) the recurrence with a decay a head, both forms


def _recurrence_inputs(t, gate, seed=0, h=3, dk=8, dv=16, beta_scale=2.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (t, h, dk)))
    v = jax.random.normal(ks[2], (t, h, dv))
    g = -gate * jax.random.uniform(ks[3], (t, h), minval=0.8, maxval=1.0)
    beta = beta_scale * jax.nn.sigmoid(jax.random.normal(ks[4], (t, h)) + 1.0)
    s0 = jax.random.normal(ks[5], (h, dk, dv))
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("gate", [0.0, 0.05, 5.0, 120.0],
                         ids=["none", "weak", "strong", "underflows"])
@pytest.mark.parametrize("t", [5, 64, 131])
def test_the_chunked_scan_with_a_decay_a_head_equals_the_recurrence(t, gate):
    """Keys of 8 under values of 16, write strengths up to 2, against the
    reference's token-by-token recurrence from a random state; a decay of
    exp(-120) a token underflows float32 to the zero it is."""
    q, k, v, g, beta, s0 = _recurrence_inputs(t, gate)
    assert float(beta.max()) > 1.5
    want_o, want_s = ref.delta_rule(q, k, v, g, beta, s0)
    got_o, got_s = delta_rule_chunked(
        q[None], k[None], v[None], g[None], beta[None], s0[None], chunk=64)
    assert bool(jnp.isfinite(got_o).all() and jnp.isfinite(got_s).all())
    np.testing.assert_allclose(got_o[0], want_o, atol=2e-5)
    np.testing.assert_allclose(got_s[0], want_s, atol=2e-5)


@pytest.mark.parametrize("gate", [0.3, 120.0], ids=["weak", "underflows"])
def test_the_one_token_update_with_a_decay_a_head_is_the_recurrence(gate):
    """Token by token through ``delta_rule_step`` (the ``jnp`` body: heads
    of 8 x 16 are no lane tiles), an inactive slot left bit for bit."""
    q, k, v, g, beta, s0 = _recurrence_inputs(12, gate)
    want_o, want_s = ref.delta_rule(q, k, v, g, beta, s0)
    state = jnp.stack([s0, s0])
    active = jnp.asarray([True, False])
    outs = []
    for i in range(12):
        two = lambda a: jnp.stack([a[i], a[i]])  # noqa: E731
        o, state = delta_rule_step(
            two(q), two(k), two(v), two(g), two(beta), state, active)
        outs.append(o[0])
    np.testing.assert_allclose(jnp.stack(outs), want_o, atol=2e-5)
    np.testing.assert_allclose(state[0], want_s, atol=2e-5)
    assert np.asarray(state[1]).tobytes() == np.asarray(s0).tobytes()
    assert not step_kernel_admits(jax.ShapeDtypeStruct((8, 30, 96, 192), jnp.float32))


@pytest.mark.parametrize("rank", ["head", "head-1"])
def test_a_decay_a_head_is_the_channel_form_with_the_decay_repeated(rank):
    """The rank of ``g`` alone says which form runs: (.., H) or (.., H, 1)
    a head's, (.., H, d_k) a channel's; both forms and both lowerings of
    the step agree where the channels' decays are one number repeated
    (``beta <= 1``: the channel form's inverse sums powers)."""
    q, k, v, g, beta, s0 = _recurrence_inputs(100, 0.4, beta_scale=1.0)
    wide = jnp.broadcast_to(g[..., None], k.shape)
    mine = g if rank == "head" else g[..., None]
    one = delta_rule_chunked(q[None], k[None], v[None], mine[None], beta[None], s0[None])
    many = delta_rule_chunked(q[None], k[None], v[None], wide[None], beta[None], s0[None])
    for a, b in zip(one, many):
        np.testing.assert_allclose(a, b, atol=2e-5)
    one = delta_rule_step(q, k, v, mine, beta, jnp.stack([s0] * 100))
    many = delta_rule_step(q, k, v, wide, beta, jnp.stack([s0] * 100))
    for a, b in zip(one, many):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    with pytest.raises(ValueError, match="neither a head's nor a channel's"):
        delta_rule_chunked(q[None], k[None], v[None], wide[None, ..., :3],
                           beta[None], s0[None])


def test_the_update_kernel_takes_a_decay_a_head_broadcast():
    """At heads the kernel admits (8 of 128 x 128) a head's decay is
    broadcast to the channels the kernel reads: the ``jnp`` body's result."""
    q, k, v, g, beta, s0 = (
        x.astype(jnp.float32)
        for x in _recurrence_inputs(2, 0.3, h=8, dk=128, dv=128))
    state = jnp.stack([s0, s0])
    got = delta_rule_step(q, k, v, g, beta, state, impl="pallas")
    want = delta_rule_step(q, k, v, g, beta, state, impl="jnp")
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_the_inverse_by_blocks_holds_where_the_power_series_does_not():
    """Close keys under write strengths of 2: ``(I + Diag(beta) A)^-1`` has
    entries of order one, the powers of ``Diag(beta) A`` entries of 2^j
    times a binomial that float32 cannot cancel."""
    from flextree_tpu.ops.linear_attention import _unit_lower_inverse

    c = 64
    rng = np.random.default_rng(0)
    keys = np.ones((c, 8)) + 0.05 * rng.standard_normal((c, 8))
    keys /= np.linalg.norm(keys, axis=-1, keepdims=True)
    a = np.tril(2.0 * keys @ keys.T, -1)
    want = np.linalg.inv(np.eye(c) + a)
    got = np.asarray(_unit_lower_inverse_by_blocks(jnp.asarray(a, jnp.float32)))
    assert np.abs(got - want).max() < 1e-3 * np.abs(want).max()
    series = np.asarray(_unit_lower_inverse(jnp.asarray(-a, jnp.float32)))
    assert not np.abs(series - want).max() < np.abs(want).max()
    # a chunk that is no power of two
    odd = np.tril(rng.standard_normal((24, 24)) * 0.3, -1)
    np.testing.assert_allclose(
        _unit_lower_inverse_by_blocks(jnp.asarray(odd, jnp.float32)),
        np.linalg.inv(np.eye(24) + odd), atol=1e-4)


# -------------------- (b) the engine's programs against ONE reference forward

# bf16 at hidden 48: rounding alone reads a few hundredths of the largest
# logit (the chip's limit is read at 3,840: benchmarks/lib/serve_closed_
# hybrid.py); a wrong mechanism moves them by O(1)
BF16_LOGITS_TOL = 0.15


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_write_and_decode_through_the_engine_equal_one_reference_forward(dtype):
    """The engine's own programs (prefill of 70 tokens: two chunks of 64 in
    the linear layers, the blocked attention in the full ones; the write of
    K and V rows and of the state; 6 decode rounds through pools and state)
    against ONE reference forward that runs the recurrence token by token."""
    config = tiny(dtype)
    eng = engine(config, pcfg=LONG)
    got = driver.check_against_reference(eng, config, 5, 70, 6, 19)
    if dtype == "float32":
        assert got["prefill_rel_err"] < 1e-4 and got["decode_rel_err_max"] < 1e-4
        assert got["ok"], got
    else:
        assert max(got["prefill_rel_err"], got["decode_rel_err_max"]) < BF16_LOGITS_TOL
    assert got["pool_ok"], got
    assert set(got) == {"ok", "prefill_rel_err", "decode_rel_err_max", "pool_ok"}


def test_a_prompt_in_segments_is_the_prompt_whole():
    """A long prompt's linear layers go ``gdn_segment`` tokens a pass, each
    from the state and the convolution inputs the last one left."""
    cfg = config_from_dict(tiny())
    params = olmo.init_params(jax.random.PRNGKey(1), cfg)
    tokens = jnp.asarray(_prompts(1, length=96)[0])[None]
    cfg = dataclasses.replace(cfg, gdn_chunk=16)
    whole, cache = olmo.prefill(params, tokens, cfg, 96)
    cut = dataclasses.replace(cfg, gdn_segment=32)
    parts, cache_cut = olmo.prefill(params, tokens, cut, 96)
    def close(a, b):  # float32's own noise, as a share of the largest
        assert float(jnp.abs(a - b).max()) <= 1e-4 * max(1.0, float(jnp.abs(b).max()))

    close(parts, whole)
    for a, b in zip(jax.tree.leaves(cache_cut), jax.tree.leaves(cache)):
        close(a, b)
    # a length the segment does not divide goes whole, and says the same
    odd, _ = olmo.prefill(params, tokens[:, :90], cut, 96)
    ref_odd, _ = olmo.prefill(params, tokens[:, :90], cfg, 96)
    close(odd, ref_odd)


def _mutated(name, config, params):
    """A reference that differs from the program by one mechanism."""
    config, params = copy.deepcopy(config), jax.tree.map(lambda a: a, params)
    layers = params["layers"]
    lin, full = (0, 1, 2, 4, 5, 6), (3, 7)

    def each(which, **leaves):
        for i in which:
            layers[i] = dict(layers[i], **{
                name: fn(layers[i][name]) for name, fn in leaves.items()})

    if name == "no_decay":  # a_log -> -inf: g = 0
        each(lin, a_log=lambda a: jnp.full_like(a, -30.0))
    elif name == "no_convolution":  # only the newest tap
        each(lin, conv=lambda a: a.at[:3].set(0.0))
    elif name == "beta_at_most_one":
        config["linear_allow_neg_eigval"] = False
    elif name == "no_output_norm_scale":
        each(lin, ln_o=jnp.ones_like)
    elif name == "no_qk_norm_scale":
        each(full, ln_q=jnp.ones_like, ln_k=jnp.ones_like)
    elif name == "no_post_norm_scale":  # the reordered norm's own scales
        each(lin + full, ln_attn=jnp.ones_like, ln_mlp=jnp.ones_like)
    elif name == "kinds_swapped":
        config["layer_types"] = [L, L, L, F, L, L, F, L]
        layers[6], layers[7] = layers[7], layers[6]
    return config, params


@pytest.mark.parametrize("name", [
    "no_decay", "no_convolution", "beta_at_most_one", "no_output_norm_scale",
    "no_qk_norm_scale", "no_post_norm_scale", "kinds_swapped",
])
def test_one_wrong_mechanism_fails_the_comparison(name):
    config = tiny()
    eng = engine(config, pcfg=LONG)
    wrong_config, wrong_params = _mutated(name, config, eng.params)
    got = driver.check_against_reference(
        eng, config, 5, 70, 4, 19, reference_params=wrong_params,
        reference_config=wrong_config)
    assert not got["ok"] and got["pool_ok"], got


@pytest.mark.parametrize("name", [
    "rows_under_linear", "one_pool", "state_in_bf16", "state_a_position",
    "no_tail", "pool_in_f64",
])
def test_what_is_not_a_state_a_slot_beside_k_and_v_rows_fails_the_pool_check(name):
    config = tiny()
    eng = engine(config)
    assert driver.pool_ok(eng, config)
    held = types.SimpleNamespace(
        bcfg=eng.bcfg, pcfg=eng.pcfg, pools=dict(eng.pools),
        state=dict(eng.state))
    if name == "rows_under_linear":  # K and V in every layer
        held.pools["k"] = eng.pools["k"] * 4
        held.pools["v"] = eng.pools["v"] * 4
    elif name == "one_pool":
        del held.pools["v"]
    elif name == "state_in_bf16":
        held.state["s"] = [a.astype(jnp.bfloat16) for a in eng.state["s"]]
    elif name == "state_a_position":  # a state kept a position, in blocks
        held.state["s"] = [jnp.zeros((40, 4, 3, 8, 16))] * 6
    elif name == "no_tail":
        del held.state["conv"]
    elif name == "pool_in_f64":
        held.pools["k"] = [p.astype(jnp.float64) for p in eng.pools["k"]]
    assert not driver.pool_ok(held, config)


def test_requests_through_the_engine_follow_the_reference_greedily():
    """Whole requests: every emitted token is the reference's argmax given
    the tokens before it (float32)."""
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


def test_the_full_layers_decode_through_the_kernel_head_major(monkeypatch):
    """What the cell's decode program runs on a TPU, here under the
    interpreter: both full layers walk their pools in the paged kernel,
    which reads the 3 K/V heads' blocks (head, position), as the v5e holds
    a pool whose heads fill no sublane tile, and the round's rows go in by
    ``put_rows``' slices; every request returns the tokens the loop
    returns."""
    from functools import partial

    config = tiny()
    prompts = _prompts(3, length=11)
    want = [_alone(config, p, 6) for p in prompts]
    monkeypatch.setattr(pa, "pool_relayouts", lambda pool: pool.ndim == 4)
    monkeypatch.setattr(
        olmo, "paged_attention", partial(pa.paged_attention, impl="pallas"))
    eng = engine(config, slots=3)
    for i, p in enumerate(prompts):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    eng.run_until_idle()
    for i, tokens in enumerate(want):
        assert np.array_equal(eng.completed[i].tokens, tokens)


# ----------------------------------------- (c) a slot's state, admission


def test_a_reused_slot_starts_from_a_zero_state_and_an_idle_one_is_left_alone():
    config = tiny()
    first, second = _prompts(2, length=13)
    eng = engine(config, slots=2)
    marked = jax.tree.map(lambda a: a.at[1:].set(0.37), eng.state)
    eng.state = marked
    before = jax.tree.map(lambda a: np.asarray(a[1:]).tobytes(), marked)
    eng.submit(Request(rid=0, prompt=first, max_new_tokens=7))
    eng.run_until_idle()
    eng.submit(Request(rid=1, prompt=second, max_new_tokens=7))
    eng.run_until_idle()
    assert np.array_equal(eng.completed[1].tokens, _alone(config, second, 7))
    assert eng.report()["counters"]["serve.state_resets"] == 2
    after = jax.tree.map(lambda a: np.asarray(a[1:]).tobytes(), eng.state)
    assert before == after  # slot 1 never held a sequence


# ------------------- (d) preemption and migration carry K/V rows AND state


@pytest.mark.parametrize("mode", ["swap", "recompute"])
def test_preemption_resumes_a_sequence_token_for_token(mode):
    """A pool too small for the traffic: sequences are evicted and resumed
    (their rows and their state swapped whole, or replayed), and every
    request still returns what it returns alone."""
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
def test_a_migration_ships_rows_and_state_and_its_metadata_states_both(codec):
    config = tiny()
    prompt = np.arange(3, 12, dtype=np.int32)
    req = Request(rid=0, prompt=prompt, max_new_tokens=5, arrival_s=1.0)
    sender = engine(config)
    out = sender.prefill_for_migration(req, codec=codec)
    meta = out["meta"]
    assert meta["layout"] == {"k": [3, 16], "v": [3, 16]} and meta["n_layers"] == 2
    assert meta["state"]["layout"] == {"s": [3, 8, 16], "conv": [3, 96]}
    assert meta["state"]["n_layers"] == 6 and len(meta["state"]["tensors"]) == 12
    carried = unpack_state(meta, out["blob"])
    assert [a.shape for a in carried["s"]] == [(3, 8, 16)] * 6
    rows = unpack_kv(meta, out["blob"])
    assert len(rows["k"]) == len(rows["v"]) == 2
    # and the planner prices what was packed: rows of the TWO layers that
    # cache them, and the state of the six that hold one
    priced = costs.predict_migration_us(sender.cfg, PCFG, len(prompt), codec)
    assert priced["bytes_on_wire"] == len(out["blob"])
    if codec == "f32":
        assert meta["state"]["nbytes"] == costs.state_bytes_per_slot(sender.cfg)
        receiver = engine(config)
        assert receiver.admit_migrated(
            req, out["first_token"], meta, out["blob"]) is not None
        receiver.run_until_idle()
        assert np.array_equal(
            receiver.completed[0].tokens, _alone(config, prompt, 5))
        # a replica of another period is refused
        other = tiny(layer_types=[L, L, F, F, L, L, L, F])
        with pytest.raises(MigrationError, match="layout"):
            engine(other).admit_migrated(
                req, out["first_token"], meta, out["blob"])


def test_the_prefix_cache_is_refused_with_the_reason():
    with pytest.raises(NotImplementedError, match="snapshot"):
        engine(tiny(), prefix_cache=True)


# ----------------------------------------------------- (e) the layout


def test_no_paged_part_lies_under_a_linear_layer():
    cfg = config_from_dict(tiny())
    layout = pool_layout(cfg)
    assert len(layout) == 8
    for i, layer in enumerate(layout):
        if cfg.linear[i]:
            assert layer["position"] == {} and set(layer["slot"]) == {"s", "conv"}
            assert layer["slot"]["s"] == ((3, 8, 16), "float32")
        else:
            assert layer == {"position": {"k": (3, 16), "v": (3, 16)}, "slot": {}}
    assert position_parts(cfg) == {"k": ((3, 16), 2), "v": ((3, 16), 2)}
    assert slot_parts(cfg) == {
        "s": (((3, 8, 16), "float32"), 6), "conv": (((3, 96), "float32"), 6)}
    pools, state = init_pools(cfg, PCFG), init_state(cfg, 3)
    assert [p.shape for p in pools["k"]] == [(40, 4, 3, 16)] * 2
    assert [a.shape for a in state["s"]] == [(3, 3, 8, 16)] * 6
    assert [a.shape for a in state["conv"]] == [(3, 3, 96)] * 6


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_costs_state_both_numbers_from_the_layout(dtype):
    cfg = config_from_dict(tiny(dtype))
    pools, state = init_pools(cfg, PCFG), init_state(cfg, 3)
    per_position = sum(p.nbytes for p in jax.tree.leaves(pools)) // (40 * 4)
    per_slot = sum(a.nbytes for a in jax.tree.leaves(state)) // 3
    assert costs.cache_bytes_per_position(cfg) == per_position
    assert costs.state_bytes_per_slot(cfg) == per_slot
    report = engine(tiny(dtype)).report()
    assert report["cache_bytes_per_position"] == per_position
    assert report["state_bytes_per_slot"] == per_slot
    assert report["state_layers"] == 6 and report["attn_layers"] == 2


def test_the_published_sizes_give_the_published_bytes():
    cfg = config_from_dict(PUBLISHED)
    assert costs.cache_bytes_per_position(cfg) == 30_720 == C.cache_bytes_per_position(PUBLISHED)
    assert costs.state_bytes_per_slot(cfg) == 13_685_760 == C.state_bytes_per_slot(PUBLISHED)
    assert cfg.linear == (True, True, True, False) * 2
    assert (cfg.gdn_heads, cfg.gdn_dk, cfg.gdn_dv, cfg.beta_scale) == (30, 96, 192, 2.0)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (30, 30, 128)


@pytest.mark.parametrize("forced", [False, True], ids=["gather", "slices"])
def test_a_pool_the_device_would_relayout_is_read_and_written_as_it_lies(
    monkeypatch, forced
):
    """``take_blocks`` / ``put_blocks`` / ``put_rows``: one gather or one
    scatter, or (on a TPU, a head count that is no multiple of 8) one
    dynamic slice a block or a row; the same values either way."""
    monkeypatch.setattr(
        backend, "kernel_platform", lambda: "tpu" if forced else "cpu")
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.standard_normal((9, 4, 3, 16)), jnp.float32)
    assert pa.pool_relayouts(pool) == forced
    assert not pa.pool_relayouts(jnp.zeros((9, 4, 8, 16)))
    idx = jnp.asarray([[5, 0], [2, 7]], jnp.int32)
    taken = jax.jit(pa.take_blocks)(pool, idx)
    assert np.array_equal(taken, np.asarray(pool)[np.asarray(idx)])
    blocks = jnp.asarray(rng.standard_normal((3, 4, 3, 16)), jnp.float32)
    ids = jnp.asarray([6, 1, 3], jnp.int32)
    put = jax.jit(pa.put_blocks)(pool, ids, blocks)
    assert np.array_equal(put, np.asarray(pool.at[ids].set(blocks)))
    rows = jnp.asarray(rng.standard_normal((3, 3, 16)), jnp.float32)
    blk, off = jnp.asarray([2, 8, 0], jnp.int32), jnp.asarray([1, 3, 0], jnp.int32)
    written = jax.jit(pa.put_rows)(pool, blk, off, rows)
    assert np.array_equal(written, np.asarray(pool.at[blk, off].set(rows)))
    # and the loop over a table reads the same through either
    q = jnp.asarray(rng.standard_normal((2, 3, 16)), jnp.float32)
    tables = jnp.asarray([[1, 2, 0], [3, 4, 5]], jnp.int32)
    lengths = jnp.asarray([6, 11], jnp.int32)
    out = pa.paged_attention(q, q, q, pool, pool, tables, lengths, impl="jnp")
    want = pa.paged_attention_gather(q, q, q, pool, pool, tables, lengths)
    assert float(jnp.abs(out - want).max()) <= pa.FUSED_DECODE_ATOL


# ------------------------------------------------------ the configuration


def test_the_configuration_file_keeps_every_published_width():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    c = PUBLISHED
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in c["reduced"]:
                assert c[key] == value, key
        assert c["published"]["layer_types"] == row["config"]["layer_types"]
        assert c["layer_types"] == row["config"]["layer_types"][:8]
    assert c["reduced"] == ["num_hidden_layers", "layer_types"]
    assert c["num_hidden_layers"] == 8 and c["published"]["num_hidden_layers"] == 32
    assert (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["linear_num_key_heads"], c["linear_num_value_heads"],
            c["linear_key_head_dim"], c["linear_value_head_dim"],
            c["linear_conv_kernel_dim"]) == (
        3840, 11008, 100352, 30, 30, 30, 30, 96, 192, 4)
    for key in ("deployment", "why_reduced", "assumed"):
        assert c[key]
    cfg = config_from_dict(c)
    shapes = jax.eval_shape(
        lambda k: olmo.init_params(k, cfg), jax.random.PRNGKey(0))
    count = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert 2.4355e9 < count < 2.4360e9  # 4.87 GB at bf16
    # the counts are the tree's own: every matrix, and the embedding
    matrices = C.weight_params(c) + c["hidden_size"] * c["vocab_size"]
    assert 0 < count - matrices < 0.4e6  # norm scales, convolutions, decays
    assert cfg.active_matmul_params == C.weight_params(c)
    assert C.weight_bytes(c) == pytest.approx(4.10e9, rel=0.005)


@pytest.mark.parametrize("over,match", [
    ({"rope_parameters": {"rope_theta": 500000.0}}, "no rotary path"),
    ({"linear_num_key_heads": 6}, "key heads and value heads differ"),
    ({"layer_types": [L, L, L, F]}, "4 entries for 8 layers"),
    ({"layer_types": [L, L, L, "sliding_attention"] * 2}, "layer type other"),
    ({"attention_bias": True}, "attention_bias"),
    ({"tie_word_embeddings": True}, "tied head"),
])
def test_what_the_block_does_not_implement_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        config_from_dict(tiny(**over))
    if "rope_parameters" in over:
        with pytest.raises(ValueError, match="no rotary"):
            ref.forward({}, jnp.zeros((2,), jnp.int32), tiny(**over))


def test_the_table_names_the_block_and_the_write_strength_follows_the_key():
    assert BLOCKS["olmo_hybrid"].config_type is olmo.OlmoHybridConfig
    assert isinstance(config_from_dict(tiny()), olmo.OlmoHybridConfig)
    assert len(BLOCKS) == 6
    assert config_from_dict(tiny()).beta_scale == 2.0
    assert config_from_dict(tiny(linear_allow_neg_eigval=False)).beta_scale == 1.0


def test_the_cli_serves_the_configuration_file(tmp_path):
    from flextree_tpu.serving.__main__ import parse_args, serve

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(tiny()))
    eng, reqs, report = serve(parse_args([
        "--cpu", "--config", str(path), "--requests", "5", "--blocks", "40",
        "--block-size", "4", "--blocks-per-seq", "8", "--slots", "3",
        "--prompt-len", "9", "--max-new", "6",
    ]))
    assert isinstance(eng.cfg, olmo.OlmoHybridConfig)
    assert len(eng.completed) == 5
    assert all(done.n_tokens == 6 for done in eng.completed.values())


# ------------------------------------------------------ spans and counters


def test_spans_and_the_report_carry_the_states_and_the_kernels_numbers():
    eng = engine(tiny(), slots=4)
    for i, p in enumerate(_prompts(3, length=6, seed=1)):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
    with flight_recorder(None) as rec:
        eng.step()
        eng.step()
    spans = [e for e in rec.events if e["kind"] == "span"]
    named = lambda n: [e for e in spans if e["name"] == n]  # noqa: E731
    per_slot = costs.state_bytes_per_slot(eng.cfg)
    assert per_slot == 6 * (3 * 8 * 16 * 4 + 3 * 96 * 4)
    dispatched = named("ft.engine.decode_dispatch")
    assert dispatched
    for e in dispatched:
        assert e["state_bytes_per_slot"] == per_slot and e["state_layers"] == 6
        assert e["state_kernel_layers"] == 0 == eng.report()["state_kernel_layers"]
        assert e["cache_bytes_per_position"] == 2 * 2 * 3 * 16 * 4
        assert e["attn_layers"] == 2 == eng.report()["attn_layers"]
        # this engine runs on the CPU, where every full layer walks the
        # loop (and heads of 16 fill no lanes): on a TPU at the published
        # heads and a block of 256 the same two keys say 2 of 2 (below)
        assert e["attn_kernel_layers"] == 0 == eng.report()["attn_kernel_layers"]
    assert [e["state_slots_live"] for e in named("ft.engine.bookkeeping")] == [3, 3]
    assert [e["state_bytes"] for e in named("ft.engine.prefill")] == [per_slot] * 3


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_the_block_says_which_kernel_admits_the_published_shapes(
    monkeypatch, platform
):
    """30 K/V heads fill no sublane tile, so on a TPU the paged kernel
    reads their pools (head, position), as the v5e holds them, a few
    heads of ONE block a step.  It admits a block whose head tile, ``bs``
    rows of 128, fills the lanes of the scores by itself and fits a chunk:
    a block size that is a multiple of 128 up to 1,024 (the cell's 256: 2
    of 2); at 16 or 32 positions a head's rows are an eighth or a quarter
    of the lanes, at 192 a tile and a half, at 2,048 two chunks: the loop,
    0 of 2, as everywhere on the CPU.  Heads of (96, 192) over 30 are no
    lane tiles: the state kernel 0 of 6 on both; a head count and widths
    both kernels admit are counted."""
    monkeypatch.setattr(backend, "kernel_platform", lambda: platform)
    took = platform == "tpu"
    cfg = config_from_dict(PUBLISHED)
    block = BLOCKS["olmo_hybrid"]
    for bs, admitted in ((16, False), (32, False), (128, True), (192, False),
                         (256, True), (1024, True), (2048, False)):
        pcfg = PagedCacheConfig(num_blocks=529, block_size=bs, blocks_per_seq=66)
        assert block.kernel_layers(cfg, pcfg) == (2, 2 * (admitted and took)), bs
    assert block.state_kernel_layers(cfg) == (6, 0)
    lanes = dataclasses.replace(
        cfg, gdn_heads=32, gdn_dk=128, gdn_dv=256, n_heads=32, n_kv_heads=32)
    assert block.state_kernel_layers(lanes) == (6, 6 * took)
    assert block.kernel_layers(
        lanes, PagedCacheConfig(529, 16, 66)) == (2, 2 * took)


NEW_SCOPES = ["ft_gdn_proj", "ft_gdn_core", "ft_attn_full"]


@pytest.fixture(scope="module")
def program_paths():
    """The ``op_name`` path of every operation of the lowered decode and
    prefill programs (a prompt of two segments)."""
    eng = engine(tiny())
    eng.cfg = dataclasses.replace(eng.cfg, gdn_segment=8, gdn_chunk=4)
    texts = [
        eng._decode.lower(
            eng.params, eng.pools, np.zeros((3, 8), np.int32),
            np.zeros((3,), np.int32), np.zeros((3,), np.int32), eng.state,
        ).as_text(debug_info=True),
        jax.jit(lambda p, tok: olmo.prefill(p, tok, eng.cfg, 32)).lower(
            eng.params, np.zeros((1, 16), np.int32)
        ).as_text(debug_info=True),
    ]
    return [re.findall(r'loc\("([^"]*)"', t) for t in texts]


@pytest.mark.parametrize("scope", NEW_SCOPES + [
    "ft_mlp", "ft_head", "ft_norm", "ft_embed"])
def test_the_served_programs_hold_the_scope(program_paths, scope):
    for paths in program_paths:
        assert any(re.search(rf"\b{scope}\b", p) for p in paths), scope


def test_the_new_scopes_never_nest_and_are_whole_names(program_paths):
    from benchmarks.readers import spans as S

    for paths in program_paths:
        for p in paths:
            found = S._SCOPE.findall(p)
            assert len(set(found)) <= 1, p
            assert not {"ft_attn", "ft_gdn", "ft_kda", "ft_mla"} & set(found)


def test_the_decode_program_is_named_for_the_benchmark_to_find():
    eng = engine(tiny())
    text = eng._decode.lower(
        eng.params, eng.pools, np.zeros((3, 8), np.int32),
        np.zeros((3,), np.int32), np.zeros((3,), np.int32), eng.state,
    ).as_text()
    name = re.search(r"module @(\S+)", text)[1]
    meta = _metric("kernels.gdn_decode_roofline")
    assert re.search(meta["args"]["match"], name), name
    assert eng._prefill.__name__ == "prefill_program"
    assert re.search(_metric("kernels.gdn_prefill_roofline")["args"]["match"],
                     "jit_prefill_program")


# ------------------------------------------------------------ the benchmark


def test_load_cell_finds_the_new_cell():
    bench = harness.load_benchmark()
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 6
    assert bench["configs"][5]["name"] == "olmo-hybrid-7b"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    cell = harness.load_cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_closed_hybrid"
    assert cell.config["model_type"] == "olmo_hybrid"
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"serve_tokens_per_s", "serve_ttft_p50_ms",
                        "serve_gap_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    assert {"attn.gdn_proj_share", "attn.gdn_core_share", "attn.full_share",
            "kernels.gdn_decode_roofline", "kernels.gdn_prefill_roofline",
            "kernels.kda_kernel_share", "kernels.paged_kernel_share",
            "kv_cache.state_bytes_share", "engine.prefill_time_share",
            "engine.gap_device_ms_p50", "engine.gap_host_ms_p50",
            "engine.gap_crossing_ms_p50", "engine.gap_outside_ms_p50",
            "entry.compiles_in_window.serve", "device.idle_share.serve",
            } <= per_layer
    assert not {n for n in per_layer if n.startswith(("moe.", "attn.mla",
                "attn.kda", "attn.window", "step.", "loop."))}
    assert not {"kernels.decode_roofline", "kernels.kda_decode_roofline",
                "kernels.moe_kernel_share"} & per_layer
    t = cell.traffic
    assert (t["clients"], t["slots"], t["deck"]) == (8, 8, 100)
    assert t["prompt_lens"] == [2048, 8192, 16384] and t["max_new"] == [128, 256, 512]
    assert t["prompt_weights"] == t["max_new_weights"] == [0.3, 0.4, 0.3]
    assert t["admission"] == "reserve" and t["fused_decode"] is True
    # every slot's worst case fits in whole blocks: admission never waits
    assert t["num_blocks"] == t["slots"] * t["blocks_per_seq"] + 1
    worst = max(t["prompt_lens"]) + max(t["max_new"])
    assert worst == 16_896 == t["block_size"] * t["blocks_per_seq"]
    # the device keeps the axis that pads least next to the lanes: the
    # block size (a multiple of 16: none) before 30 heads (-> 32) or the
    # blocks (529 -> 544), so a pool is held without a padded byte
    assert t["block_size"] % 16 == 0 and t["num_blocks"] % 16
    cfg = config_from_dict(cell.config)
    assert t["num_blocks"] * t["block_size"] * costs.cache_bytes_per_position(cfg) \
        == pytest.approx(4.16e9, rel=0.002)
    assert (t["check_prompt"], t["check_steps"], t["trace_seconds"]) == (8192, 8, 10)
    assert t["check_blocks"] * t["block_size"] >= t["check_prompt"] + t["check_steps"]
    # the new entries join the old lists at their end and nowhere else
    assert all(len(e["why"]) <= 200 for e in bench["workloads"] + bench["configs"])
    # (a later cell may follow it in a list, never come before it)
    later = [w["name"] for w in bench["workloads"][7:]]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            after = m["workloads"][m["workloads"].index(CELL) + 1:]
            assert all(w in later for w in after)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("attn.gdn_proj_share")
    assert names[first:first + 4] == [
        "attn.gdn_proj_share", "attn.gdn_core_share",
        "kernels.gdn_decode_roofline", "kernels.gdn_prefill_roofline"]
    for m in bench["per_layer"][first:first + 4]:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"


@pytest.mark.parametrize("seed", [1, 2147483999, 3100000932])
def test_the_deck_spreads_prompt_lengths_then_each_lengths_answers(seed):
    """The generator's own multiset and opening, in an order the seed alone
    decides; any 29 cards in a row (a window's worth) hold every prompt
    length within a card and a half of its share (the nine-pair spread: two
    and a half), so the median first token is an 8,192-token prefill in
    every window; each prompt length's answers come in its own mix."""
    from benchmarks.lib import traffic as T

    t = harness.load_cell(CELL).traffic
    plain, dealt = T.request_deck(t, seed), driver.nested_spread_deck(t, seed)
    counts = sorted(plain["cards"].count(pair) for pair in set(plain["cards"]))
    assert counts == [9, 9, 9, 9, 12, 12, 12, 12, 16] and len(plain["cards"]) == 100
    assert dealt["opening"] == plain["opening"]
    assert sorted(dealt["cards"]) == sorted(plain["cards"])
    assert dealt == driver.nested_spread_deck(t, seed)
    other = driver.nested_spread_deck(t, seed + 1)
    assert [p for p, _ in dealt["cards"]] != [p for p, _ in other["cards"]]
    twice = dealt["cards"] * 2
    for start in range(100):
        stretch = [p for p, _ in twice[start : start + 29]]
        for size, weight in zip(t["prompt_lens"], t["prompt_weights"]):
            assert abs(stretch.count(size) - 29 * weight) <= 1.5, (start, size)
    for prompt in t["prompt_lens"]:
        answers = [m for p, m in dealt["cards"] if p == prompt] * 2
        for start in range(0, len(answers) // 2, 3):
            for size, weight in zip(t["max_new"], t["max_new_weights"]):
                held = sum(1 for m in answers[start : start + 10] if m == size)
                assert abs(held - 10 * weight) <= 2, (prompt, start, size, held)


def test_the_loop_issues_one_order_and_the_seed_makes_the_tokens():
    """What ``run`` swaps in: this file's comparison, and a loop that issues
    the deck in the ONE order of ``DEAL_SEED`` whatever the run's seed (four
    seed-drawn deals read 2.8 to 4.8% in the rate on the chip); the seed
    still makes every prompt's token ids."""
    from benchmarks.lib import serve_closed_model as base, traffic as T

    t = harness.load_cell(CELL).traffic
    sent = {5: [], 6: []}
    for seed in sent:
        eng = types.SimpleNamespace(
            submit=lambda req, seed=seed: sent[seed].append(req) or True,
            batcher=None)
        loop = driver.HybridLoop(eng, t, seed, 64, 1 << 30)
        for _ in range(30):
            loop.issue()
        dealt = driver.nested_spread_deck(t, driver.DEAL_SEED)
        assert [loop.sizes[i] for i in range(30)] == [
            T.request_size(dealt, i) for i in range(30)]
    assert [len(r.prompt) for r in sent[5]] == [len(r.prompt) for r in sent[6]]
    assert not np.array_equal(sent[5][0].prompt, sent[6][0].prompt)
    seen = {}

    def run(cell, seed, seconds, trace_dir, t_start, counter):
        seen.update(loop=base.ModelLoop, check=base.check_against_reference)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(base, "run", run)
        driver.run(None, 0, 0.0, None, 0.0, None)
    assert seen["loop"] is driver.HybridLoop
    assert seen["check"] is driver.check_against_reference


def test_the_parent_stops_at_once_on_the_new_cell():
    """A program without the block (the parent's) refuses the configuration
    before anything is built, and a benchmark without the cell says so."""
    with pytest.raises(ValueError, match="model_type 'olmo_hybrid2' is not implemented"):
        config_from_dict(dict(PUBLISHED, model_type="olmo_hybrid2"))
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
    assert {"kernels.paged_kernel_share", "kernels.kda_kernel_share",
            "engine.prefill_time_share", "kv_cache.state_bytes_share",
            "entry.compiles_in_window.serve"} <= set(line["metrics"])


# ------------------------------------------------ the counts, worked by hand


def test_the_counts_are_what_the_algorithm_must_do():
    c = PUBLISHED
    assert C.layers(c) == {"all": 8, "linear": 6, "full": 2}
    assert C.linear_params(c) == 3840 * 11520 + 2 * 3840 * 5760 + 2 * 3840 * 30
    assert C.full_params(c) == 4 * 3840 * 3840
    assert C.ffn_params(c) == 3 * 3840 * 11008
    # the layers' and the head's weights: 4.10 GB
    assert C.weight_bytes(c) == 2 * (
        3840 * 100352 + 6 * C.linear_params(c) + 2 * C.full_params(c)
        + 8 * C.ffn_params(c))
    # a round of 8 slots over 70,000 live positions
    by_bytes = C.decode_round_bytes(c, 70_000, 8)
    assert by_bytes == C.weight_bytes(c) + 70_000 * 30_720 \
        + 8 * 6 * (2 * 2_211_840 + 2 * 69_120)
    assert by_bytes == pytest.approx(4.10e9 + 2.15e9 + 0.22e9, rel=0.01)
    by_flops = C.decode_round_flops(c, 8, 70_000)
    assert by_bytes / 819e9 > 20 * by_flops / 197e12  # memory binds a round
    # a prompt token: 3.33 GFLOP of matrices; the scan under a hundredth
    per_token = 2 * (C.weight_params(c) - 3840 * 100352)
    assert per_token == pytest.approx(3.33e9, rel=0.005)
    assert C.scan_flops_per_token(c) == 6 * 30 * (
        2 * 64 * 96 + 64 * (96 + 192) + 6 * 96 * 192 + 64 * 192)
    assert C.scan_flops_per_token(c) / per_token < 0.01
    whole = C.prefill_flops(c, 16384)
    attention = 2 * 30 * 2 * 128 * 2 * 16384 * 16385 / 2
    assert whole == pytest.approx(
        per_token * 16384 + 2 * 3840 * 100352 + attention
        + C.scan_flops_per_token(c) * 16384)
    assert 0.06 < attention / whole < 0.08


def _metric(name):
    return harness._read_json(
        os.path.join(REPO, "benchmarks", "metrics", f"{name}.json"))


def _trace_ctx(decode_ns=(0.0, 0.0), prefill_ns=0.0, window=1e9, device=True):
    """A made-up window: two decode rounds of 8 slots over 70,000 live
    positions whose program ran ``decode_ns`` each, one prefill of 16,384
    and one of 2,048 tokens whose programs ran ``prefill_ns`` in all; the
    rounds state what the cell's engine states on a TPU (both full layers
    in the paged kernel, no linear layer in the state kernel)."""
    from benchmarks.lib import xplane as X
    from benchmarks.lib.harness import ReaderContext
    from benchmarks.lib.peaks import Peaks

    E = X.Event
    stated = {"state_bytes_per_slot": 13_685_760, "cache_bytes_per_position": 30_720,
              "state_layers": 6, "state_kernel_layers": 0, "attn_layers": 2,
              "attn_kernel_layers": 2}
    host = [
        E("bench_window", 0, window),
        E("ft.engine.prefill", 0.10 * window, 0.15 * window, {"prompt_len": 16384}),
        E("ft.engine.prefill", 0.50 * window, 0.05 * window, {"prompt_len": 2048}),
        E("ft.engine.decode_dispatch", 0.29 * window, 10, dict(stated)),
        E("ft.engine.decode_dispatch", 0.79 * window, 10, dict(stated)),
    ]
    modules = [
        E("jit_prefill_program(5)", 0.10 * window, prefill_ns * 2 / 3),
        E("jit_prefill_program(6)", 0.50 * window, prefill_ns / 3),
        E("jit_paged_decode_step_with_state(7)", 0.30 * window, decode_ns[0]),
        E("jit_paged_decode_step_with_state(7)", 0.80 * window, decode_ns[1]),
    ]
    ops = [E("%fusion.1 = f32[8,30,96,192]{3,2,1,0} fusion(%x)", 0.3 * window, 100,
             {"tf_op": "jit(f)/ft_gdn_core/mul"}),
           E("%fusion.2 = bf16[8,11520]{1,0} fusion(%y)", 0.3 * window + 100, 200,
             {"tf_op": "jit(f)/ft_gdn_proj/dot_general"}),
           E("%fusion.3 = bf16[8,30,128]{2,1,0} fusion(%z)", 0.3 * window + 300, 100,
             {"tf_op": "jit(f)/ft_attn_full/while/body/dot_general"})]
    planes = [X.Plane("/host:CPU", [X.Line("python3", host)])]
    if device:
        planes.append(X.Plane("/device:TPU:0", [
            X.Line("XLA Ops", ops), X.Line("XLA Modules", modules)]))
    rounds = [(0.0, 0.0, 8, 8, 70_000), (0.0, 0.0, 8, 8, 70_000)]
    run = harness.Run(True, 0, 0, {}, {"rounds": rounds}, 0.0, None)
    cell = types.SimpleNamespace(name="toy", config=PUBLISHED)
    peaks = Peaks(197e12, 819e9, 16e9, "test")
    return ReaderContext(cell, run, {}, X.Trace(planes), (0.0, window), peaks=peaks)


def test_the_decode_roofline_is_least_time_over_traced_time():
    from benchmarks.readers import gdn as G

    meta = _metric("kernels.gdn_decode_roofline")
    assert meta["reader"] == "gdn:decode_roofline"
    least_ns = C.decode_round_bytes(PUBLISHED, 70_000, 8) / 819e9 * 1e9
    assert least_ns == pytest.approx(7.9e6, rel=0.02)
    got = G.decode_roofline(_trace_ctx((2 * least_ns, 2 * least_ns)), **meta["args"])
    assert got == pytest.approx(50.0)
    at_peak = G.decode_roofline(_trace_ctx((least_ns, least_ns)), **meta["args"])
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    # nothing to read (no device plane, no run of the program): no error
    assert G.decode_roofline(_trace_ctx((1e6, 1e6), device=False), **meta["args"]) is None
    assert G.decode_roofline(_trace_ctx((0.0, 0.0)), **meta["args"]) is None


def test_the_prefill_roofline_is_the_prompts_flops_over_traced_time():
    from benchmarks.readers import gdn as G

    meta = _metric("kernels.gdn_prefill_roofline")
    assert meta["reader"] == "gdn:prefill_roofline"
    least_ns = (C.prefill_flops(PUBLISHED, 16384)
                + C.prefill_flops(PUBLISHED, 2048)) / 197e12 * 1e9
    got = G.prefill_roofline(
        _trace_ctx(prefill_ns=2 * least_ns, window=1e10), **meta["args"])
    assert got == pytest.approx(50.0)
    at_peak = G.prefill_roofline(
        _trace_ctx(prefill_ns=least_ns, window=1e10), **meta["args"])
    assert at_peak == pytest.approx(100.0) and at_peak <= 100.0 + 1e-9
    assert G.prefill_roofline(_trace_ctx(prefill_ns=0.0), **meta["args"]) is None
    assert G.prefill_roofline(_trace_ctx(device=False), **meta["args"]) is None


def test_the_shares_read_what_the_program_states():
    from benchmarks.readers import kda as K, spans as S

    ctx = _trace_ctx()
    state, rows = 8 * 13_685_760, 70_000 * 30_720
    share = K.state_bytes_share(ctx, **_metric("kv_cache.state_bytes_share")["args"])
    assert share == pytest.approx(100.0 * state / (state + rows))
    assert 4.0 < share < 6.0  # a few percent: K and V fill the memory
    for name, want in (("attn.gdn_core_share", 25.0), ("attn.gdn_proj_share", 50.0),
                       ("attn.full_share", 25.0)):
        assert S.scope_share(ctx, **_metric(name)["args"]) == pytest.approx(want)
    for name, want in (("kernels.kda_kernel_share", 0.0),
                       ("kernels.paged_kernel_share", 100.0)):
        assert S.count_ratio_p50(ctx, **_metric(name)["args"]) == want
