"""Autoregressive generation with a static KV cache.

Completes the dense model family's serving path: prefill runs the full
forward once while recording every layer's K/V; decode then advances one
token at a time, attending over the cache.  Everything is static-shaped
for XLA: the cache is allocated at ``max_len`` up front, the causal bound
is a mask on cached positions (not a dynamic slice), and the decode loop
is a ``lax.scan`` — so the whole ``generate`` call jits to two compiled
programs (prefill + scanned decode) regardless of token count.

Cache lengths are **per sequence** (``cache["length"]`` is a ``(B,)``
int32 vector): a freshly-prefilled request can join a batch of mid-decode
sequences at a different position, which is what the continuous batcher
(``flextree_tpu.serving``) needs.  RoPE positions and the causal mask
honor the per-row position; cache writes go through a vmapped dynamic
update so each row lands at its own offset.

Sampling is deterministic and key-threaded (no RNG inside the trace):
greedy is the default, ``temperature``/``top_k`` sampling requires an
explicit ``key=``.  ``stop_tokens=`` switches the decode loop from
``lax.scan`` to ``lax.while_loop`` so generation exits as soon as every
sequence has emitted a stop token — the per-sequence retirement signal
the serving batcher consumes one request at a time.

Single-device by design: generation is latency-bound, and the framework's
sharded story lives in the training steps; a tp-sharded decode would reuse
the same cache layout with heads split over the axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.paged_attention import (
    paged_attention,
    paged_attention_gather,
    put_rows,
)
from .transformer import (
    TransformerConfig,
    apply_rope,
    final_logits,
    mlp_block,
    rms_norm,
)

__all__ = [
    "init_kv_cache",
    "prefill",
    "prefill_dense",
    "paged_decode_dense",
    "prefill_suffix",
    "prefill_ragged",
    "decode_step",
    "generate",
    "sample_token",
    "cached_attention",
]


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int) -> dict:
    """Per-layer (B, max_len, H, Dh) K/V buffers in the compute dtype.
    ``length`` is per-sequence (B,) so ragged batches can share a cache."""
    shape = (batch, max_len, cfg.n_heads, cfg.head_dim)
    return {
        "k": [jnp.zeros(shape, cfg.dtype) for _ in range(cfg.n_layers)],
        "v": [jnp.zeros(shape, cfg.dtype) for _ in range(cfg.n_layers)],
        "length": jnp.zeros((batch,), jnp.int32),
    }


def _qkv(layer, h, cfg: TransformerConfig):
    b, t = h.shape[:2]
    shape = (b, t, cfg.n_heads, cfg.head_dim)
    q = (h @ layer["wq"].astype(cfg.dtype)).reshape(shape)
    k = (h @ layer["wk"].astype(cfg.dtype)).reshape(shape)
    v = (h @ layer["wv"].astype(cfg.dtype)).reshape(shape)
    return q, k, v


def cached_attention(q, k_cache, v_cache, q_pos, window: int | None = None):
    """Attend (B, Tq, H, D) queries over cached positions ``<= q_pos``
    (global query positions, (Tq,) shared or (B, Tq) per-sequence); the
    causal bound alone masks out every not-yet-written cache slot — masked
    scores softmax to exactly 0.0 in f32, so whatever a masked slot holds
    contributes exactly nothing (the paged cache's gather path leans on
    this).  Math order mirrors ``attention_reference`` exactly (einsum in
    the compute dtype, then f32) so decode logits are teacher-forcing-exact
    in every dtype.

    The cache may hold fewer heads than the queries have (grouped
    queries: query head ``h`` reads K/V head ``h // (H // Hkv)``), and
    ``window`` bounds the keys from below: a query at ``p`` sees
    positions ``p - window + 1 .. p``."""
    scale = 1.0 / (q.shape[-1] ** 0.5)
    hkv = k_cache.shape[2]
    grouped = hkv != q.shape[2]
    if grouped:
        b, tq, h, d = q.shape
        qg = q.reshape(b, tq, hkv, h // hkv, d)
        # grouped scores leave the product in f32 (the dense line below
        # rounds them to the compute dtype first, which its bitwise
        # contracts with attention_reference pin)
        s = jnp.einsum(
            "bqkgd,bskd->bkgqs", qg, k_cache,
            preferred_element_type=jnp.float32,
        ).reshape(b, h, tq, k_cache.shape[1]) * scale
    else:
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cache).astype(jnp.float32) * scale
    kpos = jnp.arange(k_cache.shape[1])
    qp = q_pos[..., None]  # (Tq, 1) shared, or (B, Tq, 1) per-sequence
    mask = kpos <= qp
    if window is not None:
        mask &= kpos > qp - window
    # (Tq, K) over all rows, or (B, 1, Tq, K)
    mask = mask[None, None] if q_pos.ndim == 1 else mask[:, None]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    if grouped:
        out = jnp.einsum(
            "bkgqs,bskd->bqkgd", p.reshape(b, hkv, h // hkv, tq, -1),
            v_cache.astype(jnp.float32),
        ).reshape(b, tq, h, d)
    else:
        out = jnp.einsum("bhqk,bkhd->bqhd", p, v_cache.astype(jnp.float32))
    return out.astype(q.dtype)


def _forward_cached(params, tokens, cache, start_pos, cfg: TransformerConfig):
    """Forward ``tokens`` (B, T) writing K/V at ``start_pos..start_pos+T``;
    returns (logits, cache).  ``start_pos`` may be traced, scalar (all rows
    at the same offset — the prefill case) or (B,) per-sequence (ragged
    decode); the returned ``cache["length"]`` is always (B,)."""
    b, t = tokens.shape
    start = jnp.asarray(start_pos, jnp.int32)
    ragged = start.ndim == 1
    positions = (start[:, None] if ragged else start) + jnp.arange(t)
    if ragged:
        # each row lands at its own offset: vmap the length-axis update
        upd = jax.vmap(
            lambda c, u, s: lax.dynamic_update_slice_in_dim(c, u, s, axis=0)
        )
    x = params["embed"][tokens].astype(cfg.dtype)
    new_k, new_v = [], []
    for layer, kc, vc in zip(params["layers"], cache["k"], cache["v"]):
        h = rms_norm(x, layer["ln1"])
        q, k, v = _qkv(layer, h, cfg)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        if ragged:
            kc = upd(kc, k, start)
            vc = upd(vc, v, start)
        else:
            kc = lax.dynamic_update_slice_in_dim(kc, k, start, axis=1)
            vc = lax.dynamic_update_slice_in_dim(vc, v, start, axis=1)
        new_k.append(kc)
        new_v.append(vc)
        attn = cached_attention(q, kc, vc, positions)
        o = attn.reshape(b, t, -1) @ layer["wo"].astype(cfg.dtype)
        x = x + o
        x = mlp_block(layer, x, cfg)
    logits = final_logits(params["embed"], params["ln_f"], x)
    length = jnp.broadcast_to(start + t, (b,)).astype(jnp.int32)
    cache = {"k": new_k, "v": new_v, "length": length}
    return logits, cache


def prefill(params, tokens, cfg, max_len: int):
    """Run the prompt through the model once.  Returns
    ``(last_logits, cache)`` with the cache filled for ``tokens``: the
    walk of ``cfg``'s block (``models.configs.block_of``; the dense one
    is :func:`prefill_dense`)."""
    from .configs import block_of

    return block_of(cfg).prefill(params, tokens, cfg, max_len)


def prefill_dense(params, tokens, cfg: TransformerConfig, max_len: int):
    """:func:`prefill` for the dense block."""
    b, t = tokens.shape
    if t > max_len:
        raise ValueError(f"prompt length {t} exceeds max_len {max_len}")
    cache = init_kv_cache(cfg, b, max_len)
    logits, cache = _forward_cached(params, tokens, cache, 0, cfg)
    return logits[:, -1], cache


def prefill_suffix(params, tokens, prefix_kv, cfg: TransformerConfig,
                   max_len: int):
    """Suffix-only prefill over an already-computed prefix: run ONLY the
    ``tokens`` (B, Ts) that follow a cached prefix whose per-layer K/V is
    ``prefix_kv = {"k": [(B, C, H, Dh)], "v": [...]}``.  Returns
    ``(last_logits, cache)`` exactly like :func:`prefill` of the full
    ``C + Ts`` prompt would.

    Offset-aware by construction: RoPE positions and the causal mask
    start at the cached length ``C`` (the prefix shape carries it, so it
    is static per compile — one program per (C, Ts) bucket), and the
    cache writes land at ``C ..`` so cached positions are never
    rewritten.  Bitwise identity with the full prefill follows from two
    facts the paged stack already leans on: a prefix position's K/V is a
    pure function of the prefix tokens (absolute positions, causal
    masking), and every masked cache slot contributes exactly 0.0 —
    so the suffix queries attend over the very same values, in the same
    ``max_len``-wide reduction, full prefill's suffix rows see.

    Caveat the batcher's admission math honors: a ONE-token suffix puts
    the attention matmuls in the ``Tq=1`` shape class, which XLA lowers
    with a different accumulation order than the multi-row prefill —
    numerically fine, but not bitwise against the full prefill.  Callers
    that need the bitwise guarantee must pass at least two suffix
    tokens.  XLA:CPU (jaxlib 0.9.0) has a second class boundary in the
    same P·V contraction, between 16 and 17 query rows: across it the
    identity holds to ~1e-7, not to the bit.
    """
    b, t = tokens.shape
    ks = prefix_kv["k"]
    if len(ks) != cfg.n_layers or len(prefix_kv["v"]) != cfg.n_layers:
        raise ValueError(
            f"prefix_kv holds {len(ks)} layers, model has {cfg.n_layers}"
        )
    c = int(ks[0].shape[1])
    if t < 1:
        raise ValueError("prefill_suffix needs at least one suffix token "
                         "(the last prompt token's logits come from it)")
    if c + t > max_len:
        raise ValueError(
            f"cached {c} + suffix {t} exceeds max_len {max_len}"
        )
    cache = init_kv_cache(cfg, b, max_len)
    cache["k"] = [
        kc.at[:, :c].set(pk.astype(kc.dtype))
        for kc, pk in zip(cache["k"], prefix_kv["k"])
    ]
    cache["v"] = [
        vc.at[:, :c].set(pv.astype(vc.dtype))
        for vc, pv in zip(cache["v"], prefix_kv["v"])
    ]
    logits, cache = _forward_cached(params, tokens, cache, c, cfg)
    return logits[:, -1], cache


def prefill_ragged(params, tokens, lengths, cfg: TransformerConfig,
                   max_len: int):
    """Right-padded batched prefill: row ``b`` of ``tokens`` (B, T) is
    real up to ``lengths[b]`` and padding after.  Returns ``(logits,
    cache)`` with ``logits[b]`` taken at row ``b``'s LAST REAL token and
    ``cache["length"] = lengths`` — so the first decode write lands at
    each row's own length, progressively overwriting the pad K/V, and
    the causal mask keeps not-yet-overwritten pad entries invisible
    (every attended position <= q_pos has been written by then).  Decoded
    continuations are therefore exactly what each row would produce
    alone."""
    b, t = tokens.shape
    if t > max_len:
        raise ValueError(f"padded prompt length {t} exceeds max_len {max_len}")
    cache = init_kv_cache(cfg, b, max_len)
    logits, cache = _forward_cached(params, tokens, cache, 0, cfg)
    lengths = jnp.asarray(lengths, jnp.int32)
    last = logits[jnp.arange(b), lengths - 1]
    return last, {**cache, "length": lengths}


def decode_step(params, cache, token, cfg: TransformerConfig):
    """One decode step: ``token`` (B,) int32, each row at its own position
    ``cache['length'][b]``.  Returns ``(logits, cache)`` for the next
    position."""
    logits, cache = _forward_cached(
        params, token[:, None], cache, cache["length"], cfg
    )
    return logits[:, 0], cache


def paged_decode_dense(params, pools, tables, lengths, tokens,
                       cfg: TransformerConfig, fused: bool = False):
    """The dense block's decode step over the paged pool
    (``serving.kv_cache.paged_decode_step`` has the contract).  The
    per-layer math calls the SAME helpers as the contiguous decode
    (``_qkv`` / ``apply_rope`` / ``mlp_block`` / ``final_logits``):
    ``fused=False`` attends through ``ops.paged_attention_gather``, whose
    gathered view has the (S, P*bs) key length the contiguous cache would,
    which plus exact-zero masking is the whole bitwise-identity argument;
    ``fused=True`` through ``ops.paged_attention``."""
    s = tokens.shape[0]
    positions = lengths[:, None].astype(jnp.int32)  # (S, 1) per-sequence
    bs = pools["k"][0].shape[1]
    row = jnp.arange(s)
    blk = tables[row, lengths // bs]  # (S,) current block per slot
    off = lengths % bs
    attend = paged_attention if fused else paged_attention_gather
    x = params["embed"][tokens[:, None]].astype(cfg.dtype)
    new_k, new_v = [], []
    for layer, pk, pv in zip(params["layers"], pools["k"], pools["v"]):
        h = rms_norm(x, layer["ln1"])
        q, k, v = _qkv(layer, h, cfg)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        attn = attend(
            q[:, 0], k[:, 0], v[:, 0], pk, pv, tables, lengths
        )[:, None]
        o = attn.reshape(s, 1, -1) @ layer["wo"].astype(cfg.dtype)
        x = x + o
        x = mlp_block(layer, x, cfg)
        # scatter the appended K/V back into each row's current block
        new_k.append(put_rows(pk, blk, off, k[:, 0]))
        new_v.append(put_rows(pv, blk, off, v[:, 0]))
    logits = final_logits(params["embed"], params["ln_f"], x)
    return logits[:, 0], {"k": new_k, "v": new_v}


def sample_token(logits, *, temperature: float = 0.0, top_k: int | None = None,
                 key=None):
    """Next-token choice from (B, vocab) f32 logits — deterministic and
    key-threaded, never RNG-in-trace.

    ``temperature <= 0`` is greedy argmax (the default; ``key`` unused).
    Otherwise ``key`` is required: logits are scaled by ``1/temperature``,
    optionally truncated to the ``top_k`` highest (ties at the k-th value
    are all kept), and sampled via ``jax.random.categorical``.  The same
    ``(logits, key)`` always yields the same token.
    """
    if temperature <= 0:
        if top_k is not None:
            # greedy over top-k IS greedy — a silently ignored knob is the
            # artifact-comparison hazard; fail loudly instead
            raise ValueError("top_k requires temperature > 0")
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if key is None:
        raise ValueError("temperature > 0 requires an explicit key=")
    scaled = logits / temperature
    if top_k is not None:
        if not 1 <= top_k:
            raise ValueError(f"top_k must be >= 1, got {top_k}")
        kth = lax.top_k(scaled, min(top_k, scaled.shape[-1]))[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def generate(
    params,
    prompt,
    cfg: TransformerConfig,
    *,
    max_new_tokens: int,
    max_len: int | None = None,
    temperature: float = 0.0,
    top_k: int | None = None,
    key=None,
    stop_tokens=None,
    pad_token: int = 0,
):
    """Greedy (``temperature=0``) or sampled continuation of ``prompt``
    (B, T) int32 -> (B, max_new_tokens) int32.  Sampling requires an
    explicit ``key``; ``top_k`` truncates the sampled distribution.

    With ``stop_tokens`` (a sequence of token ids) the decode loop becomes
    a ``lax.while_loop`` that exits as soon as every row has emitted a
    stop token (per-sequence early exit): rows that already stopped emit
    ``pad_token``, and the return value becomes ``(tokens, lengths)`` with
    ``lengths`` (B,) counting each row's real tokens (stop token included).
    """
    b, t = prompt.shape
    if max_len is None:
        max_len = t + max_new_tokens
    if t + max_new_tokens > max_len:
        raise ValueError(
            f"prompt ({t}) + max_new_tokens ({max_new_tokens}) exceeds "
            f"max_len ({max_len})"
        )
    sampling = temperature > 0
    if sampling and key is None:
        raise ValueError("temperature > 0 requires an explicit key=")

    logits, cache = prefill(params, prompt, cfg, max_len)

    def pick(logits, k):
        return sample_token(logits, temperature=temperature, top_k=top_k, key=k)

    keys = jax.random.split(key, max_new_tokens) if sampling else None
    # first token comes straight from the prefill logits; the loop then
    # decodes at most max_new_tokens - 1 times (no trailing wasted forward)
    tok0 = pick(logits, keys[0] if sampling else None)

    if stop_tokens is None:
        def step(carry, k):
            tok, cache = carry
            logits, cache = decode_step(params, cache, tok, cfg)
            nxt = pick(logits, k)
            return (nxt, cache), nxt

        xs = keys[1:] if sampling else None
        (_, _), rest = lax.scan(
            step, (tok0, cache), xs,
            length=None if sampling else max_new_tokens - 1,
        )
        return jnp.concatenate([tok0[:, None], rest.T], axis=1)

    stop = jnp.asarray(tuple(stop_tokens), jnp.int32).reshape(-1)

    def hit(tok):  # (B,) bool: did this token retire its row?
        return (tok[:, None] == stop[None, :]).any(axis=1)

    # pad-initialized so columns past an early all-rows exit read as pad
    out0 = jnp.full((b, max_new_tokens), pad_token, jnp.int32).at[:, 0].set(tok0)
    carry0 = (
        jnp.int32(1), tok0, cache, hit(tok0), out0, jnp.ones((b,), jnp.int32)
    )

    def cond(carry):
        i, _, _, done, _, _ = carry
        return (i < max_new_tokens) & ~done.all()

    def body(carry):
        i, tok, cache, done, out, lens = carry
        logits, cache = decode_step(params, cache, tok, cfg)
        k = (
            lax.dynamic_index_in_dim(keys, i, keepdims=False)
            if sampling else None
        )
        nxt = jnp.where(done, jnp.int32(pad_token), pick(logits, k))
        out = out.at[:, i].set(nxt)
        lens = lens + (~done).astype(jnp.int32)
        return (i + 1, nxt, cache, done | hit(nxt), out, lens)

    _, _, _, _, out, lens = lax.while_loop(cond, body, carry0)
    return out, lens
