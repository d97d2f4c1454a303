"""Flagship model: a decoder-only transformer LM, sharded TPU-first.

The reference repo is a collectives library with no model layer (SURVEY
§2.6); this model is the framework's demonstration workload — the thing the
hierarchical allreduce, ring attention, and planner exist to serve.  Design
is MXU-friendly and mesh-native:

- **Tensor parallelism** over the ``tp`` mesh axis: QKV and MLP-up are
  column-parallel (each shard owns a contiguous slice of heads / hidden
  units), attention-out and MLP-down are row-parallel; the row-parallel
  partial sums are combined with the framework's own topology-parameterized
  ``flextree_tpu.parallel.allreduce`` — our collective is the TP backend,
  the moral equivalent of the reference interposing its allreduce under a
  host framework (``mpi_mod.hpp:1167-1171``).
- **Sequence parallelism** over the ``sp`` mesh axis, strategy selected by
  ``sp_impl``: ``ring_attention`` (K/V blocks walk the ring, flash-style
  accumulation) or ``ulysses_attention`` (all-to-all head/sequence
  re-shard, full-sequence local attention).
- **RoPE** positions (global offsets derived from the ``sp`` axis index),
  RMSNorm, GELU MLP, tied input/output embeddings — no learned position
  table, so sequence length is bounded only by memory.
- Pure functional: params are a plain dict pytree; ``forward`` works both
  as an ordinary single-device function (no axes bound) and as a
  collective-context function inside ``shard_map``.

Every phase of the block traces under one ``jax.named_scope`` of its own
(``ft_embed``, ``ft_norm``, ``ft_attn``, ``ft_mlp``, ``ft_head``,
``ft_loss``), never one inside another, so that the first ``ft_`` name on
an operation's path, forward or ``transpose(jvp(...))``, is its phase: the
step's device time is read by phase from a profile (PERF.md §3).  Scopes
are metadata; they change no operation.

All matmuls keep a (tokens, features) trailing structure with static shapes
so XLA tiles them onto the MXU; compute dtype is configurable (bfloat16 for
TPU), accumulation and softmax stay float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel.allreduce import allreduce
from ..parallel.ring_attention import local_attention, ring_attention
from ..parallel.ulysses import ulysses_attention
from ..parallel.zigzag import zigzag_ring_attention

__all__ = [
    "TransformerConfig",
    "init_params",
    "param_specs",
    "forward",
    "layer_forward",
    "attention_block",
    "mlp_block",
    "final_logits",
    "global_positions",
    "cross_entropy_loss",
]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 256
    d_model: int = 128
    n_heads: int = 8
    n_layers: int = 2
    d_ff: int = 512
    rope_theta: float = 10000.0
    dtype: Any = jnp.float32  # compute dtype; params stay float32
    # topology spec for the TP-combining allreduce (None -> FT_TOPO/flat)
    tp_topo: Any = None
    # sequence-parallel attention strategy: "ring" (K/V walk the ring,
    # heads unconstrained), "zigzag" (the ring with the load-balanced
    # chunk-pair layout — critical path 2-1/n of plain causal ring's,
    # see ZIGZAG_ACCOUNTING.json; even local length),
    # or "ulysses" (two all-to-alls, needs the local head count divisible
    # by the sp axis size)
    sp_impl: str = "ring"
    # local attention compute: "reference" (jnp full-matrix) or "flash"
    # (fused Pallas kernel, ops.pallas_attention) — applies wherever the
    # full sequence is local (no sp axis, or the Ulysses inner attention)
    attn_impl: str = "reference"
    # extra kwargs for the flash kernel on the full-sequence-local path
    # (block_q / block_k / variant), as a hashable tuple of (key, value)
    # pairs so the frozen config stays usable as a jit static — e.g.
    # (("block_q", 1024), ("variant", "kvgrid")) to run the autotuned
    # winner instead of library defaults
    attn_opts: tuple = ()

    @property
    def n_kv_heads(self) -> int:
        """Heads of the K/V cache: every query head has its own here."""
        return self.n_heads

    @property
    def head_dim(self) -> int:
        if self.d_model % self.n_heads:
            raise ValueError("d_model must be divisible by n_heads")
        return self.d_model // self.n_heads


def _dense_init(key, shape, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.float32)


def init_params(key, cfg: TransformerConfig) -> dict:
    """Full (unsharded) parameter pytree; shard_map in_specs slice it."""
    d, ff = cfg.d_model, cfg.d_ff
    keys = jax.random.split(key, 2 + cfg.n_layers)
    params = {
        "embed": _dense_init(keys[0], (cfg.vocab_size, d), 1.0 / math.sqrt(d)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": [],
    }
    out_scale = 1.0 / math.sqrt(d * 2 * cfg.n_layers)
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 6)
        params["layers"].append(
            {
                "ln1": jnp.ones((d,), jnp.float32),
                "wq": _dense_init(k[0], (d, d), 1.0 / math.sqrt(d)),
                "wk": _dense_init(k[1], (d, d), 1.0 / math.sqrt(d)),
                "wv": _dense_init(k[2], (d, d), 1.0 / math.sqrt(d)),
                "wo": _dense_init(k[3], (d, d), out_scale),
                "ln2": jnp.ones((d,), jnp.float32),
                "w1": _dense_init(k[4], (d, ff), 1.0 / math.sqrt(d)),
                "w2": _dense_init(k[5], (ff, d), out_scale),
            }
        )
    return params


def param_specs(cfg: TransformerConfig, tp_axis: str | None = "tp") -> dict:
    """PartitionSpec pytree matching ``init_params`` structure.

    Column-parallel weights shard their output dim over ``tp_axis``,
    row-parallel weights their input dim; everything else is replicated.
    """
    t = tp_axis
    layer = {
        "ln1": P(None),
        "wq": P(None, t),
        "wk": P(None, t),
        "wv": P(None, t),
        "wo": P(t, None),
        "ln2": P(None),
        "w1": P(None, t),
        "w2": P(t, None),
    }
    return {
        "embed": P(None, None),
        "ln_f": P(None),
        "layers": [dict(layer) for _ in range(cfg.n_layers)],
    }


@jax.named_scope("ft_norm")
def rms_norm(x, scale, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 / rms) * scale).astype(x.dtype)


def apply_rope(x, positions, theta: float):
    """Rotary embedding on (B, T, H, Dh) with global ``positions`` — (T,)
    shared across the batch, or (B, T) per-sequence (the ragged decode
    batches of the serving path)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., T, half)
    if ang.ndim == 2:
        ang = ang[None]  # shared positions broadcast over the batch
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return rotated.astype(x.dtype)


def _tp_combine(partial, tp_axis, cfg: TransformerConfig):
    """Sum row-parallel partials across TP shards with *our* allreduce."""
    if tp_axis is None:
        return partial
    return allreduce(partial, tp_axis, topo=cfg.tp_topo, op="sum")




def attention_block(
    layer,
    x,
    positions,
    cfg: TransformerConfig,
    *,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
):
    """Pre-norm attention residual half of a block (shared by the dense and
    MoE models): ``x + W_o attn(RoPE(QKV(norm(x))))`` with the row-parallel
    output combined through the FlexTree allreduce."""
    b, t_local, _ = x.shape
    head_dim = cfg.head_dim
    attn_opts = dict(cfg.attn_opts)
    if attn_opts and cfg.attn_impl != "flash":
        # a tuned config silently running with library defaults is exactly
        # the artifact-comparison hazard ADVICE r5 flagged — fail loudly
        raise ValueError(
            f"attn_opts {sorted(attn_opts)} require attn_impl='flash', "
            f"got {cfg.attn_impl!r}"
        )
    h = rms_norm(x, layer["ln1"])
    with jax.named_scope("ft_attn"):
        q = (h @ layer["wq"].astype(cfg.dtype)).reshape(b, t_local, -1, head_dim)
        k = (h @ layer["wk"].astype(cfg.dtype)).reshape(b, t_local, -1, head_dim)
        v = (h @ layer["wv"].astype(cfg.dtype)).reshape(b, t_local, -1, head_dim)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        if sp_axis is None:
            attn = local_attention(
                q, k, v, causal=True, impl=cfg.attn_impl, **attn_opts
            )
        elif cfg.sp_impl == "ulysses":
            # Ulysses' inner attention is also full-sequence-local flash —
            # the tuned opts apply there too (ADVICE r5)
            attn = ulysses_attention(
                q, k, v, sp_axis, causal=True, impl=cfg.attn_impl, **attn_opts
            )
        elif attn_opts:
            # ring/zigzag hop kernels run library defaults; a tuned config
            # that cannot be honored must fail, not silently degrade
            raise ValueError(
                f"attn_opts {sorted(attn_opts)} are not supported by "
                f"sp_impl={cfg.sp_impl!r} (only the full-sequence-local and "
                f"ulysses paths take flash kwargs)"
            )
        elif cfg.sp_impl == "ring":
            attn = ring_attention(q, k, v, sp_axis, causal=True, impl=cfg.attn_impl)
        elif cfg.sp_impl == "zigzag":
            # contiguous layout at the model boundary: RoPE positions above are
            # contiguous-shard positions, so convert around the attention only
            attn = zigzag_ring_attention(
                q, k, v, sp_axis, layout="contiguous", impl=cfg.attn_impl
            )
        else:
            raise ValueError(f"unknown sp_impl {cfg.sp_impl!r}")
        o = attn.reshape(b, t_local, -1) @ layer["wo"].astype(cfg.dtype)
        return x + _tp_combine(o, tp_axis, cfg)


def layer_forward(
    layer,
    x,
    positions,
    cfg: TransformerConfig,
    *,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
):
    """One transformer block on hidden states ``x`` (B, T_local, d).

    ``positions``: (T_local,) global token positions (RoPE + causal mask).
    Factored out of :func:`forward` so the pipeline-parallel runner
    (``flextree_tpu.parallel.pipeline``) can ``lax.scan`` it over a stacked
    per-stage parameter slice.
    """
    x = attention_block(
        layer, x, positions, cfg, tp_axis=tp_axis, sp_axis=sp_axis
    )
    return mlp_block(layer, x, cfg, tp_axis=tp_axis)


def mlp_block(layer, x, cfg: TransformerConfig, *, tp_axis: str | None = None):
    """Pre-norm GELU MLP residual half (column/row-parallel over tp)."""
    h = rms_norm(x, layer["ln2"])
    with jax.named_scope("ft_mlp"):
        u = jax.nn.gelu(h @ layer["w1"].astype(cfg.dtype))
        y = u @ layer["w2"].astype(cfg.dtype)
        return x + _tp_combine(y, tp_axis, cfg)


def final_logits(embed, ln_f, h):
    """The LM head: final RMSNorm + tied-embedding projection to f32
    logits.  The ONE definition shared by :func:`forward`,
    ``moe.moe_forward``, and the overlap engines' per-segment head
    (``parallel.overlap``) — the overlap path's bitwise contract depends
    on these never drifting apart."""
    x = rms_norm(h, ln_f)
    with jax.named_scope("ft_head"):
        return x.astype(jnp.float32) @ embed.T.astype(jnp.float32)


def global_positions(t_local: int, sp_axis: str | None):
    """(T_local,) global positions for this device's sequence shard."""
    offset = lax.axis_index(sp_axis) * t_local if sp_axis is not None else 0
    return offset + jnp.arange(t_local)


def forward(
    params,
    tokens,
    cfg: TransformerConfig,
    *,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
):
    """Logits for ``tokens`` (B, T_local) int32.

    With no axes bound this is a plain single-device forward.  Inside
    ``shard_map``: batch may be sharded over a data axis (invisible here),
    sequence over ``sp_axis``, and heads/hidden over ``tp_axis`` (params
    pre-sliced by ``param_specs``).  Returns (B, T_local, vocab) logits in
    float32, replicated over ``tp_axis``.
    """
    with jax.named_scope("ft_embed"):
        positions = global_positions(tokens.shape[1], sp_axis)
        x = params["embed"][tokens].astype(cfg.dtype)
    for layer in params["layers"]:
        x = layer_forward(
            layer, x, positions, cfg, tp_axis=tp_axis, sp_axis=sp_axis
        )
    return final_logits(params["embed"], params["ln_f"], x)


@jax.named_scope("ft_loss")
def cross_entropy_loss(logits, targets):
    """Per-token cross entropy, summed — (loss_sum, token_count).

    Summed (not meaned) so callers can normalize by a *global* token count
    psum'd over the mesh, which keeps gradients exact under dp/sp sharding.
    """
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = (logz - gold).sum()
    count = jnp.asarray(targets.size, jnp.float32)
    return loss, count
