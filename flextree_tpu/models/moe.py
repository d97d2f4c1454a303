"""Mixture-of-Experts transformer with expert parallelism over an ``ep`` axis.

The framework's second model family (next to the dense
``flextree_tpu.models.transformer``), built TPU-first:

- **Router**: top-k gating (softmax over experts, k greedy picks), with a
  *static* per-device expert capacity ``C = ceil(S * k * capacity_factor /
  E)`` — tokens beyond an expert's capacity are dropped (their combine
  weight is zero, the residual stream carries them unchanged).  Everything
  is dense masked einsums over (tokens, experts, capacity) one-hots: no
  dynamic shapes, no sorting — the layout XLA can tile onto the MXU.
- **Expert parallelism**: the stacked expert weights shard their leading
  expert axis over the ``ep`` mesh axis; dispatch is one
  ``lax.all_to_all`` sending each device's per-expert capacity slots to
  the expert's owner, and a second all-to-all brings outputs back — the
  all-to-all counterpart of the hierarchical allreduce's grouped stages
  (the reference parameterizes *how* a collective routes,
  ``allreduce_over_mpi/mpi_mod.hpp:882-929``; here the route is the
  expert assignment itself).
- **Composition**: expert FFNs are also tensor-parallel (hidden dim over
  ``tp``, row-parallel combine through the FlexTree allreduce), attention
  is the dense model's (ring/Ulysses sequence parallelism over ``sp``),
  so one MoE mesh runs dp x ep x sp x tp.
- **Load balancing**: the Switch-style auxiliary loss ``E * sum_e(
  token_frac_e * prob_mass_e)`` (1.0 at perfect balance), returned per
  layer and weighted into the training loss by ``router_aux_weight``.
- **Dropless share** (the SERVED path; ``models.laguna``):
  :func:`route_topk_normalized` + :func:`dropless_experts` route over all
  the experts, sort the picks by expert and run grouped products over
  the experts HELD here (``lax.ragged_dot``; in a decode round on a TPU
  the Pallas kernel of ``ops.grouped_matmul``).  No capacity, no
  one-hot over (tokens, experts, slots), no dropped pick whatever the
  skew; picks of experts held elsewhere keep their weight and add
  nothing, and on one chip there is no exchange.

Determinism note: routing is greedy argmax with first-come-first-served
capacity slots (position = running count of earlier same-expert tokens), so
a sharded run equals the single-device oracle exactly whenever capacity is
not exceeded *per shard* — the equivalence the tests pin down.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.grouped_matmul import (
    group_visits, grouped_matmul, runs_grouped_kernel,
)
from ..parallel.allreduce import allreduce
from .transformer import (
    TransformerConfig,
    _dense_init,
    attention_block,
    final_logits,
    global_positions,
    mlp_block,
    rms_norm,
)

__all__ = [
    "MoEConfig",
    "init_moe_params",
    "moe_param_specs",
    "moe_forward",
    "moe_layer",
    "route_topk",
    "expert_capacity",
    "MOE_COUNTS",
    "ROUTER_SCORES",
    "route_topk_normalized",
    "dropless_experts",
    "expert_kernel_layers",
    "gated_ffn",
    "expert_layer",
    "stack_router",
    "round_counts",
]


@dataclasses.dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 2.0
    # every ``moe_every``-th block uses an MoE FFN (1 = all blocks);
    # blocks are counted 1-based so moe_every=2 -> layers 1, 3, ... are MoE
    moe_every: int = 1
    router_aux_weight: float = 1e-2
    # topology spec for the ep-axis collectives is implicit: dispatch is a
    # single all-to-all, which has no tree analog — the FlexTree topology
    # applies to the tp combine (tp_topo) and the gradient sync (grad_topo)

    def is_moe_layer(self, i: int) -> bool:
        return (i % self.moe_every) == (self.moe_every - 1)


def expert_capacity(n_tokens: int, cfg: MoEConfig) -> int:
    """Static per-shard, per-expert capacity."""
    return max(
        1,
        math.ceil(n_tokens * cfg.top_k * cfg.capacity_factor / cfg.n_experts),
    )


def init_moe_params(key, cfg: MoEConfig) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    keys = jax.random.split(key, 2 + cfg.n_layers)
    params = {
        "embed": _dense_init(keys[0], (cfg.vocab_size, d), 1.0 / math.sqrt(d)),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": [],
    }
    out_scale = 1.0 / math.sqrt(d * 2 * cfg.n_layers)
    for i in range(cfg.n_layers):
        k = jax.random.split(keys[2 + i], 7)
        layer = {
            "ln1": jnp.ones((d,), jnp.float32),
            "wq": _dense_init(k[0], (d, d), 1.0 / math.sqrt(d)),
            "wk": _dense_init(k[1], (d, d), 1.0 / math.sqrt(d)),
            "wv": _dense_init(k[2], (d, d), 1.0 / math.sqrt(d)),
            "wo": _dense_init(k[3], (d, d), out_scale),
            "ln2": jnp.ones((d,), jnp.float32),
        }
        if cfg.is_moe_layer(i):
            layer["router"] = _dense_init(k[6], (d, e), 1.0 / math.sqrt(d))
            layer["w1e"] = _dense_init(k[4], (e, d, ff), 1.0 / math.sqrt(d))
            layer["w2e"] = _dense_init(k[5], (e, ff, d), out_scale)
        else:
            layer["w1"] = _dense_init(k[4], (d, ff), 1.0 / math.sqrt(d))
            layer["w2"] = _dense_init(k[5], (ff, d), out_scale)
        params["layers"].append(layer)
    return params


def moe_param_specs(
    cfg: MoEConfig,
    tp_axis: str | None = "tp",
    ep_axis: str | None = "ep",
) -> dict:
    """Expert leaves shard (expert axis over ep, hidden over tp); the rest
    matches the dense model's specs."""
    t, e = tp_axis, ep_axis
    layers = []
    for i in range(cfg.n_layers):
        layer = {
            "ln1": P(None),
            "wq": P(None, t),
            "wk": P(None, t),
            "wv": P(None, t),
            "wo": P(t, None),
            "ln2": P(None),
        }
        if cfg.is_moe_layer(i):
            layer["router"] = P(None, None)
            layer["w1e"] = P(e, None, t)
            layer["w2e"] = P(e, t, None)
        else:
            layer["w1"] = P(None, t)
            layer["w2"] = P(t, None)
        layers.append(layer)
    return {"embed": P(None, None), "ln_f": P(None), "layers": layers}


# ------------------------------------------------------------------ router


def route_topk(probs: jax.Array, k: int, capacity: int):
    """Greedy top-k routing with first-come-first-served capacity.

    ``probs``: (S, E) router probabilities.  Returns ``(dispatch, combine)``
    with ``dispatch`` (S, E, C) in {0,1} — token s occupies capacity slot c
    of expert e — and ``combine`` (S, E, C) the normalized gate weights.
    Greedy pick ``i`` routes each token to its i-th-highest expert; a
    token's slot is its running count among earlier tokens routed to the
    same expert this pick plus all previous picks (dropped tokens still
    consume positions, keeping the assignment a pure prefix-sum — no
    compaction, fully static shapes).
    """
    s, e = probs.shape
    if k > e:
        raise ValueError(f"top_k={k} cannot exceed n_experts={e}")
    dispatch = jnp.zeros((s, e, capacity), probs.dtype)
    gates = jnp.zeros((s, e), probs.dtype)
    counts = jnp.zeros((e,), jnp.int32)
    masked = probs
    for _ in range(k):
        sel = jnp.argmax(masked, axis=-1)  # (S,)
        onehot = jax.nn.one_hot(sel, e, dtype=probs.dtype)  # (S, E)
        oh_i = onehot.astype(jnp.int32)
        pos = counts[None, :] + jnp.cumsum(oh_i, axis=0) - oh_i  # (S, E)
        pos_sel = jnp.take_along_axis(pos, sel[:, None], axis=1)[:, 0]
        keep = (pos_sel < capacity).astype(probs.dtype)
        slot = jax.nn.one_hot(pos_sel, capacity, dtype=probs.dtype)  # (S, C)
        dispatch = dispatch + (
            onehot[:, :, None] * slot[:, None, :] * keep[:, None, None]
        )
        gates = gates + probs * onehot * keep[:, None]
        counts = counts + oh_i.sum(axis=0)
        masked = masked * (1.0 - onehot)
    denom = gates.sum(axis=-1, keepdims=True)
    norm = gates / jnp.where(denom > 0, denom, 1.0)
    combine = dispatch * norm[:, :, None]
    return dispatch, combine


def _aux_loss(probs: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Switch-style load-balance loss on local tokens: ``E * sum_e(
    token_frac_e * prob_mass_e)`` — 1.0 at perfect balance."""
    s, e = probs.shape
    token_frac = dispatch.sum(axis=(0, 2)) / jnp.maximum(
        dispatch.sum(), 1.0
    )  # (E,)
    prob_mass = probs.mean(axis=0)  # (E,)
    return e * jnp.sum(token_frac * prob_mass)


# ------------------------------------------------------------------- layer


def moe_layer(
    layer: dict,
    x: jax.Array,
    cfg: MoEConfig,
    *,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
):
    """MoE FFN on hidden states ``x`` (B, T_local, d) -> (out, aux).

    Dispatch -> all-to-all -> local experts (tp-parallel hidden) ->
    all-to-all back -> combine.  With ``ep_axis=None`` all experts are
    local and the all-to-alls vanish — that path is the single-device
    oracle the sharded path must match.
    """
    b, t, d = x.shape
    s = b * t
    e = cfg.n_experts
    cap = expert_capacity(s, cfg)
    tokens = x.reshape(s, d)

    probs = jax.nn.softmax(
        tokens.astype(jnp.float32) @ layer["router"].astype(jnp.float32), axis=-1
    )
    dispatch, combine = route_topk(probs, cfg.top_k, cap)
    aux = _aux_loss(probs, dispatch)

    # (S, E, C) x (S, d) -> (E, C, d) expert inboxes
    slots = jnp.einsum(
        "sec,sd->ecd", dispatch.astype(cfg.dtype), tokens.astype(cfg.dtype)
    )

    n_ep = lax.axis_size(ep_axis) if ep_axis is not None else 1
    if n_ep > 1:
        if e % n_ep:
            raise ValueError(
                f"n_experts={e} must be divisible by ep axis size {n_ep}"
            )
        # (E, C, d) -> (E/n, n*C, d): each device keeps its local experts,
        # holding every source device's capacity slots
        slots = lax.all_to_all(
            slots, ep_axis, split_axis=0, concat_axis=1, tiled=True
        )

    # local experts: w1e/w2e leading axis is the *local* expert slice
    w1 = layer["w1e"].astype(cfg.dtype)
    w2 = layer["w2e"].astype(cfg.dtype)
    hidden = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", slots, w1))
    out_slots = jnp.einsum("ecf,efd->ecd", hidden, w2)
    if tp_axis is not None:  # row-parallel combine of the tp-sharded hidden
        out_slots = allreduce(out_slots, tp_axis, topo=cfg.tp_topo, op="sum")

    if n_ep > 1:
        out_slots = lax.all_to_all(
            out_slots, ep_axis, split_axis=1, concat_axis=0, tiled=True
        )

    out = jnp.einsum(
        "sec,ecd->sd", combine.astype(jnp.float32), out_slots.astype(jnp.float32)
    )
    return out.reshape(b, t, d).astype(x.dtype), aux


def moe_forward(
    params,
    tokens,
    cfg: MoEConfig,
    *,
    tp_axis: str | None = None,
    sp_axis: str | None = None,
    ep_axis: str | None = None,
):
    """Logits + mean router aux loss for ``tokens`` (B, T_local) int32.

    Attention blocks are the dense model's (``layer_forward`` attention
    half); FFNs alternate dense / MoE per ``cfg.moe_every``.
    """
    b, t_local = tokens.shape
    positions = global_positions(t_local, sp_axis)
    x = params["embed"][tokens].astype(cfg.dtype)
    aux_total = jnp.zeros((), jnp.float32)
    n_moe = 0
    for i, layer in enumerate(params["layers"]):
        x = attention_block(
            layer, x, positions, cfg, tp_axis=tp_axis, sp_axis=sp_axis
        )
        if cfg.is_moe_layer(i):
            h = rms_norm(x, layer["ln2"])
            y, aux = moe_layer(
                layer, h, cfg, tp_axis=tp_axis, ep_axis=ep_axis
            )
            x = x + y
            aux_total = aux_total + aux
            n_moe += 1
        else:
            x = mlp_block(layer, x, cfg, tp_axis=tp_axis)
    logits = final_logits(params["embed"], params["ln_f"], x)
    aux_mean = aux_total / max(n_moe, 1)
    return logits, aux_mean


# ----------------------------------------------------- the dropless share


#: what a decode round counts over its sparse layers and active slots, in
#: the order of the ``counts`` vector a decode program with routed experts
#: hands out (:func:`round_counts`)
MOE_COUNTS = (
    "picks", "local_picks", "experts_hit", "experts_held", "max_expert_load",
)

#: a router's score function by name: ``softmax`` over the experts
#: (``models.laguna``), or an independent ``sigmoid`` of each logit
#: (``models.pangu_ultra_moe``)
ROUTER_SCORES = {
    "softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
    "sigmoid": jax.nn.sigmoid,
}


def route_topk_normalized(h, router_w, k: int, scale: float = 1.0,
                          normalize: bool = True, score: str = "softmax"):
    """Scores, choices and weights of a router: ``scores`` (N, E) float32
    ``score`` (:data:`ROUTER_SCORES`) of ``h @ router_w`` (accumulated in
    f32), ``choices`` (N, k) int32 the ``k`` largest, ``weights`` (N, k)
    f32 their scores, divided by their sum when ``normalize``, times
    ``scale``."""
    logits = jnp.dot(h, router_w, preferred_element_type=jnp.float32)
    scores = ROUTER_SCORES[score](logits)
    top, choices = lax.top_k(scores, k)
    if normalize:
        top = top / top.sum(axis=-1, keepdims=True)
    return scores, choices.astype(jnp.int32), top * scale


def dropless_experts(h, choices, weights, experts, held, rows=None, *,
                     impl: str | None = None):
    """The part of a routed layer's output that the experts held here
    give.  ``h`` (N, d); ``choices`` (N, k) expert ids over ALL the
    experts; ``weights`` (N, k) f32; ``experts`` the stacked gated-SiLU
    weights ``w_gate``/``w_up`` (n_held, d, f) and ``w_down`` (n_held, f,
    d) of experts ``held = (lo, hi)``; ``rows`` (N,) bool, rows whose
    picks are dispatched at all (default: every row).

    Picks are sorted by expert (absent experts' picks last), each held
    expert's rows are multiplied by its own matrices in grouped products,
    and the results return to their tokens weighted.  Every local pick is
    computed, however many land on one expert.  Returns ``(out, sizes)``:
    (N, d) float32 and the (n_held,) int32 picks each held expert got.

    One algorithm, two lowerings of its three grouped products, chosen
    from the backend and the static shapes
    (``ops.grouped_matmul.runs_grouped_kernel``): where an expert gets a
    handful of rows (a decode round on a TPU) the Pallas kernel
    ``moe_grouped_matmul`` in row tiles of 32, gate, up and the
    activation one call and down another; elsewhere (a prefill's thousands
    of rows an expert, the CPU backend, shapes that are no whole tiles)
    ``lax.ragged_dot``.  The arithmetic is one: operands in the held type,
    f32 accumulation, the activation cast to the held type before the
    down product.  ``impl`` (``"pallas"`` / ``"ragged"``) forces one, for
    the tests."""
    if impl not in (None, "pallas", "ragged"):
        raise ValueError(f"unknown grouped-product impl {impl!r}")
    lo, hi = held
    n_held = hi - lo
    n, k = choices.shape
    local = (choices >= lo) & (choices < hi)
    if rows is not None:
        local = local & rows[:, None]
    key = jnp.where(local, choices - lo, n_held).reshape(-1)
    order = jnp.argsort(key, stable=True)  # picks by expert, absent last
    sizes = jnp.zeros((n_held + 1,), jnp.int32).at[key].add(1)[:n_held]
    xs = h[order // k]  # (N*k, d): row j is the token of sorted pick j
    f32 = jnp.float32
    if impl == "pallas" or (
        impl is None and runs_grouped_kernel(xs, experts["w_gate"])
    ):
        visits = group_visits(sizes, n * k)
        act = grouped_matmul(
            xs, experts["w_up"], sizes, experts["w_gate"], visits=visits
        )
        ys = grouped_matmul(act, experts["w_down"], sizes, visits=visits)
    else:
        gate = lax.ragged_dot(
            xs, experts["w_gate"], sizes, preferred_element_type=f32
        )
        up = lax.ragged_dot(xs, experts["w_up"], sizes, preferred_element_type=f32)
        act = (jax.nn.silu(gate) * up).astype(h.dtype)
        ys = lax.ragged_dot(
            act, experts["w_down"], sizes, preferred_element_type=f32
        )
    # back to pick order; rows past the held experts' groups were never
    # computed, so they are selected away, not multiplied by zero
    per_pick = ys[jnp.argsort(order)].reshape(n, k, -1)
    per_pick = jnp.where(local[..., None], per_pick, 0.0)
    return jnp.einsum("nkd,nk->nd", per_pick, weights.astype(f32)), sizes


def expert_kernel_layers(cfg, slots: int) -> tuple:
    """``(expert layers of a decode program over ``slots`` slots, those of
    them whose grouped products run the Pallas kernel)``, every one alike:
    fixed by the backend and the static shapes, by the function that
    chooses the lowering in :func:`dropless_experts`."""
    lo, hi = cfg.experts_held
    took = runs_grouped_kernel(
        jax.ShapeDtypeStruct((slots * cfg.top_k, cfg.d_model), cfg.dtype),
        jax.ShapeDtypeStruct(
            (hi - lo, cfg.d_model, cfg.d_expert), cfg.param_dtype
        ),
    )
    return cfg.n_sparse, cfg.n_sparse * took


def gated_ffn(w, h):
    """``W_down(silu(W_gate h) * W_up h)`` in the held type, the two inner
    products accumulated in f32."""
    g = jnp.dot(h, w["w_gate"], preferred_element_type=jnp.float32)
    u = jnp.dot(h, w["w_up"], preferred_element_type=jnp.float32)
    return (jax.nn.silu(g) * u).astype(h.dtype) @ w["w_down"]


def expert_layer(layer, flat, *, top_k: int, scale: float, normalize: bool,
                 held, score: str = "softmax", rows=None):
    """A served expert layer on normed rows ``flat`` (N, d): the router
    over ALL the experts (``layer["router"]``), the part of the routed sum
    that the experts ``held`` here give (``layer["experts"]``), plus the
    shared expert (``layer["shared"]``), ungated.  Returns ``(y, moe)``:
    (N, d) in ``flat``'s dtype, and what the router did: ``scores`` (N, E)
    f32, ``choices`` (N, k) int32, ``sizes`` (n_held,) picks of each held
    expert.  ``rows`` (N,) bool: rows whose picks are dispatched and
    counted (a decode round's inactive slots are not).  Scopes:
    ``ft_moe_router``, ``ft_moe_experts``, ``ft_moe_shared``."""
    with jax.named_scope("ft_moe_router"):
        scores, choices, weights = route_topk_normalized(
            flat, layer["router"], top_k, scale, normalize, score
        )
    with jax.named_scope("ft_moe_experts"):
        routed, sizes = dropless_experts(
            flat, choices, weights, layer["experts"], held, rows
        )
    with jax.named_scope("ft_moe_shared"):
        shared = gated_ffn(layer["shared"], flat)
        y = (routed + shared.astype(jnp.float32)).astype(flat.dtype)
    return y, {"scores": scores, "choices": choices, "sizes": sizes}


def stack_router(moes):
    """What the sparse layers' routers did, stacked over those layers."""
    return {
        k: jnp.stack([m[k] for m in moes]) for k in ("scores", "choices")
    }


def round_counts(moes, active, *, top_k: int, held, n_experts: int):
    """A decode round's :data:`MOE_COUNTS`, int32, over the sparse layers
    ``moes`` (:func:`expert_layer`'s second results) and the ACTIVE slots
    (``active`` (S,) bool; an empty slot's row dispatches nothing)."""
    lo, hi = held
    picked = jnp.stack([m["choices"] for m in moes])  # (L_s, S, k)
    counted = active[None, :, None]
    local = (picked >= lo) & (picked < hi) & counted
    sizes = jnp.stack([m["sizes"] for m in moes])  # (L_s, n_held)
    loads = jnp.zeros((len(moes), n_experts), jnp.int32).at[
        jnp.arange(len(moes))[:, None, None], picked
    ].add(counted.astype(jnp.int32))
    return jnp.stack([
        active.sum() * top_k * len(moes), local.sum(),
        (sizes > 0).sum(), jnp.asarray((hi - lo) * len(moes)), loads.max(),
    ]).astype(jnp.int32)
