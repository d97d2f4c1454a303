"""KV-cache generation vs the full-forward oracle.

The decisive property: decoding with the cache must produce exactly the
logits that re-running the whole forward over the growing sequence would —
teacher-forcing equivalence, checked position by position.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # multi-minute train-step tests (fast subset: -m 'not slow')

from flextree_tpu.models.generate import (
    decode_step,
    generate,
    init_kv_cache,
    prefill,
)
from flextree_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    base.update(kw)
    return TransformerConfig(**base)


def _setup(seed=0, b=2, t=12):
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    return cfg, params, tokens


def test_prefill_matches_forward_last_logits():
    cfg, params, tokens = _setup()
    logits, cache = prefill(params, tokens, cfg, max_len=32)
    ref = forward(params, tokens, cfg)
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref[:, -1]), atol=1e-4
    )
    np.testing.assert_array_equal(
        np.asarray(cache["length"]), np.full(tokens.shape[0], tokens.shape[1])
    )


def test_decode_matches_forward_teacher_forcing():
    """Feed the true next tokens; cached logits must equal full recompute."""
    cfg, params, tokens = _setup(t=12)
    prompt, rest = tokens[:, :4], tokens[:, 4:]
    logits, cache = prefill(params, prompt, cfg, max_len=16)
    for i in range(rest.shape[1]):
        seen = tokens[:, : 4 + i]
        ref = forward(params, seen, cfg)[:, -1]
        np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=1e-4)
        logits, cache = decode_step(params, cache, rest[:, i], cfg)
    ref = forward(params, tokens, cfg)[:, -1]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref), atol=1e-4)


def test_greedy_generate_matches_stepwise_argmax():
    cfg, params, tokens = _setup(t=6)
    out = generate(params, tokens, cfg, max_new_tokens=5)
    assert out.shape == (2, 5)

    # oracle: grow the sequence with full forwards + argmax
    seq = tokens
    want = []
    for _ in range(5):
        nxt = jnp.argmax(forward(params, seq, cfg)[:, -1], axis=-1).astype(jnp.int32)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(
        np.asarray(out), np.asarray(jnp.stack(want, axis=1))
    )


def test_generate_is_jittable():
    cfg, params, tokens = _setup(t=6)
    fn = jax.jit(
        lambda p, tok: generate(p, tok, cfg, max_new_tokens=4, max_len=10)
    )
    out = fn(params, tokens)
    ref = generate(params, tokens, cfg, max_new_tokens=4, max_len=10)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_sampled_generate_shape_and_determinism():
    cfg, params, tokens = _setup(t=4)
    k = jax.random.PRNGKey(7)
    a = generate(params, tokens, cfg, max_new_tokens=6, temperature=1.0, key=k)
    b = generate(params, tokens, cfg, max_new_tokens=6, temperature=1.0, key=k)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert a.shape == (2, 6)


def test_generate_validates_lengths():
    cfg, params, tokens = _setup(t=8)
    with pytest.raises(ValueError, match="exceeds"):
        generate(params, tokens, cfg, max_new_tokens=4, max_len=10)
    with pytest.raises(ValueError, match="exceeds"):
        prefill(params, tokens, cfg, max_len=4)


def test_kv_cache_shapes():
    cfg = _cfg()
    cache = init_kv_cache(cfg, batch=3, max_len=20)
    assert len(cache["k"]) == cfg.n_layers
    assert cache["k"][0].shape == (3, 20, cfg.n_heads, cfg.head_dim)
    # lengths are per-sequence so ragged batches share one cache
    assert cache["length"].shape == (3,)
    np.testing.assert_array_equal(np.asarray(cache["length"]), np.zeros(3))


def test_sampling_requires_key():
    cfg, params, tokens = _setup(t=4)
    with pytest.raises(ValueError, match="key"):
        generate(params, tokens, cfg, max_new_tokens=2, temperature=1.0)


def test_ragged_decode_matches_per_row_contiguous():
    """Rows at DIFFERENT cache lengths decode exactly as each would alone:
    build a ragged 2-row cache by hand (row 0 has seen 4 tokens, row 1 has
    seen 7), decode one shared step, and compare each row's logits with a
    single-row decode at that row's own length."""
    cfg, params, tokens = _setup(t=12)
    lens = [4, 7]
    # ragged cache: prefill each row alone, then splice into one batch
    caches, logits_rows = [], []
    for r, ln in enumerate(lens):
        lg, c = prefill(params, tokens[r : r + 1, :ln], cfg, max_len=16)
        caches.append(c)
        logits_rows.append(lg)
    ragged = {
        "k": [jnp.concatenate([c["k"][l] for c in caches]) for l in range(cfg.n_layers)],
        "v": [jnp.concatenate([c["v"][l] for c in caches]) for l in range(cfg.n_layers)],
        "length": jnp.asarray(lens, jnp.int32),
    }
    nxt = jnp.asarray(
        [tokens[0, lens[0]], tokens[1, lens[1]]], jnp.int32
    )
    got, ragged2 = decode_step(params, ragged, nxt, cfg)
    np.testing.assert_array_equal(np.asarray(ragged2["length"]), [5, 8])
    for r, ln in enumerate(lens):
        want, _ = decode_step(params, caches[r], nxt[r : r + 1], cfg)
        np.testing.assert_array_equal(
            np.asarray(got[r : r + 1]), np.asarray(want)
        )
        # teacher-forcing oracle on top: the full forward at that length
        ref = forward(params, tokens[r : r + 1, : ln + 1], cfg)[:, -1]
        np.testing.assert_allclose(
            np.asarray(got[r : r + 1]), np.asarray(ref), atol=1e-4
        )


def test_prefill_ragged_matches_per_row_generate():
    """Right-padded batched prefill + ragged decode == each row alone."""
    from flextree_tpu.models.generate import prefill_ragged

    cfg, params, tokens = _setup(t=12)
    lens = [5, 9]
    padded = np.zeros((2, 9), np.int32)
    for r, ln in enumerate(lens):
        padded[r, :ln] = np.asarray(tokens[r, :ln])
    logits, cache = prefill_ragged(params, jnp.asarray(padded), lens, cfg, 16)
    np.testing.assert_array_equal(np.asarray(cache["length"]), lens)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    outs = [[int(tok[0])], [int(tok[1])]]
    for _ in range(3):
        logits, cache = decode_step(params, cache, tok, cfg)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for r in range(2):
            outs[r].append(int(tok[r]))
    for r, ln in enumerate(lens):
        want = generate(
            params, tokens[r : r + 1, :ln], cfg, max_new_tokens=4, max_len=16
        )
        np.testing.assert_array_equal(np.asarray(want)[0], outs[r])


def test_top_k_sampling_stays_inside_top_k():
    cfg, params, tokens = _setup(t=4)
    k = jax.random.PRNGKey(3)
    out = generate(
        params, tokens, cfg, max_new_tokens=6, temperature=1.0, top_k=2, key=k
    )
    assert out.shape == (2, 6)
    # replay: every sampled token must be inside that step's top-2 set
    logits, cache = prefill(params, tokens, cfg, max_len=10)
    keys = jax.random.split(k, 6)
    for i in range(6):
        top2 = np.asarray(jax.lax.top_k(logits, 2)[1])
        for b in range(2):
            assert int(out[b, i]) in top2[b]
        if i < 5:
            logits, cache = decode_step(params, cache, out[:, i], cfg)
    # determinism: same key, same tokens
    again = generate(
        params, tokens, cfg, max_new_tokens=6, temperature=1.0, top_k=2, key=k
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))


def test_top_k_without_temperature_raises():
    cfg, params, tokens = _setup(t=4)
    with pytest.raises(ValueError, match="top_k"):
        generate(params, tokens, cfg, max_new_tokens=2, top_k=4)


def test_stop_tokens_retire_rows_and_pad():
    """Greedy generate with the oracle's own 3rd token declared a stop
    token for row 0: row 0 must stop there (length counts the stop token),
    row 1 runs to max_new_tokens, padding fills row 0's tail."""
    cfg, params, tokens = _setup(t=6)
    free = generate(params, tokens, cfg, max_new_tokens=6)
    stop_tok = int(free[0, 2])
    out, lens = generate(
        params, tokens, cfg, max_new_tokens=6, stop_tokens=(stop_tok,),
        pad_token=-1,
    )
    # rows match the unconstrained run up to each row's stop (the stop
    # token may greedily occur before index 2 — find its first hit)
    row0_stop = int(np.argmax(np.asarray(free[0]) == stop_tok))
    np.testing.assert_array_equal(
        np.asarray(out[0, : row0_stop + 1]), np.asarray(free[0, : row0_stop + 1])
    )
    assert int(lens[0]) == row0_stop + 1
    assert all(int(x) == -1 for x in np.asarray(out[0, row0_stop + 1 :]))
    if stop_tok not in np.asarray(free[1]):
        np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(free[1]))
        assert int(lens[1]) == 6


def test_stop_tokens_all_rows_early_exit_jits():
    """When every row stops early the while_loop exits before
    max_new_tokens — and the whole thing still jits."""
    cfg, params, tokens = _setup(t=6)
    free = generate(params, tokens, cfg, max_new_tokens=4)
    stops = tuple(int(t) for t in np.asarray(free[:, 1]))
    fn = jax.jit(
        lambda p, tok: generate(
            p, tok, cfg, max_new_tokens=4, max_len=10, stop_tokens=stops
        )
    )
    out, lens = fn(params, tokens)
    ref_out, ref_lens = generate(
        params, tokens, cfg, max_new_tokens=4, max_len=10, stop_tokens=stops
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref_out))
    np.testing.assert_array_equal(np.asarray(lens), np.asarray(ref_lens))
    assert int(max(np.asarray(lens))) <= 4


def test_decode_teacher_forcing_exact_bf16():
    cfg = _cfg(dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    logits, cache = prefill(params, tokens[:, :4], cfg, max_len=8)
    for i in range(4):
        ref = forward(params, tokens[:, : 4 + i], cfg)[:, -1]
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(ref))
        logits, cache = decode_step(params, cache, tokens[:, 4 + i], cfg)
