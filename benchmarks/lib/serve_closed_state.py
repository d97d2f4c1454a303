"""Driver for traffic of kind ``serve_closed_state``: the closed loop of
``serve_closed_model`` (its engine factory, window loop and window rule,
``ModelLoop``, deck, estimators and ``obs`` keys, imported) over a model
whose layers hold a recurrent STATE a slot beside a latent row a position
(``model_type`` ``kimi_linear``).

What differs is the comparison that decides ``correct``.  Its three
numeric parts are ``serve_closed_model``'s (router scores, near-tie picks
counted, logits with the reference following the program's picks), on the
engine's own programs as the engine runs them: ``_prefill`` of a
``check_prompt``-token prompt (the chunked scan over its chunks),
``_write_slot`` (the latent rows into their blocks AND the final state
into the slot's place), and ``check_steps`` teacher-forced
``_decode_round`` steps through the latent pool and the state, against ONE
forward of ``benchmarks/reference/kimi_linear_decoder.py`` (the recurrence
token by token) over all ``check_prompt + check_steps`` tokens, under
limits read for THIS configuration (below).  Its pool check is this
file's (:func:`pool_ok`): a K/V pool under the KDA layers, or a state kept
a position, would pass every numeric limit.

**The deal.**  The deck is the traffic file's, the generator's own
multiset (``traffic.request_deck``: 200 cards, nine size pairs, and the
opening that meets every shape once), and ``--seed`` still decides its
order; but the order is not the plain shuffle of the other kinds.  Each
size pair's cards are SPREAD evenly through the deck, at a phase and with
ties that the seed draws (:func:`spread_deck`), so that any stretch of the
deck holds nearly the deck's own mix.  The plain shuffle was measured
first (PERF.md section 6, PR 34): this cell's window holds about 110 of
the deck's 200 requests, a 4,096-token prompt costs seven times a
512-token one, and 128 slots whose answers are 256, 512 or 1,024 rounds
long admit in waves, so six seeds spread 7.8% in ``serve_tokens_per_s``
(half its bound is 3.5%), and ``serve_gap_p95_ms`` read 308 ms in five
runs and 123 ms in the sixth: whether a round that admits a 4,096-token
prompt holds more or less than a twentieth of the window's gaps (6.2% of
them, +-0.9) is the seed's draw.  A replay of the schedule on the CPU,
which reproduces that order of the six rates and the sixth run's gap,
gives the spread deal 0.7% and no such run in sixty.
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from . import serve_closed_model as base, traffic as traffic_lib
from .harness import Cell, Run
from .serve_closed_model import (  # noqa: F401  (the driver's surface)
    ModelLoop, build_engine, compare_routing,
)

__all__ = ["run", "build_engine", "check_against_reference", "pool_ok",
           "spread_deck", "StateLoop"]

# The limits, each between two readings on the chip at the published
# widths and the cell's own check (4,096-token prefill, the write of rows
# and state, 8 decode rounds; my chip runs, PR 34; PERF.md section 6).
# The bf16 program against the float32 reference, twelve seeds:
#   logits 0.0092 to 0.0117 (prefill) and 0.0103 to 0.0150 (decode steps)
#   of the largest reference logit; router scores 0.0155 to 0.0200 of a
#   position's largest; 4,108 to 4,374 of 393,984 picks differing, the
#   furthest 0.0063 to 0.0104 off the cut.
# The same program against the reference computed on parameters cut to 3
# mantissa bits (float8_e4m3's, at bfloat16's range: the nearest precision
# below the stated one), the same twelve seeds:
#   logits 0.133 to 0.177 (prefill) and 0.146 to 0.195 (decode); scores
#   0.201 to 0.239; 49,840 to 50,753 picks differing, up to 0.103 to 0.128
#   off the cut: not correct by each limit.
# Each limit stands between: 3.0 times the worst clean logits reading and
# 3.0 times under the lowest low-precision one; 3.0 times the worst clean
# score reading and 3.4 times under the lowest low one (as the bound on a
# differing pick's distance from the cut: 5.8 times over, 1.7 times
# under).  A sigmoid score's error is nearly an absolute one, as in
# ``serve_closed_latent``; the readings stand this close together over
# seeds only since the seeded embedding keeps a token's own row the larger
# part of the first layers' input (``models/kimi_linear.py::_leaf_shapes``:
# at openPangu's scale the clean logits read 0.019 to 0.113 over three
# seeds, PERF.md section 6).
SCORE_REL_TOL = 6e-2  # share of the position's largest reference score
LOGITS_REL_TOL = 4.5e-2  # share of the largest reference logit


def spread_deck(traffic: dict, seed: int) -> dict:
    """``traffic.request_deck``'s deck (the same opening, the same
    multiset of cards) with each size pair's cards spread evenly through
    it: a pair that holds ``c`` of the ``n`` cards gets the places ``(j +
    u) / c`` for ``j < c``, ``u`` uniform in [0, 1) from the seed, and the
    cards are issued by place (ties by a second draw).  Every seed gives
    another order; every stretch of any order holds about the deck's mix."""
    deck = traffic_lib.request_deck(traffic, seed)
    cards = deck["cards"]
    rng = np.random.default_rng([int(seed), 0x5EAD])
    placed = []
    for pair in sorted(set(cards)):
        count, phase = cards.count(pair), rng.random()
        placed += [
            ((j + phase) / count, rng.random(), pair) for j in range(count)
        ]
    return {"opening": deck["opening"],
            "cards": [pair for _, _, pair in sorted(placed)]}


class StateLoop(ModelLoop):
    """``ModelLoop`` that issues the deck as :func:`spread_deck` deals it."""

    def __init__(self, engine, traffic, seed, vocab, window: int):
        super().__init__(engine, traffic, seed, vocab, window)
        self.deck = spread_deck(traffic, seed)


def pool_ok(engine, config: dict) -> bool:
    """What the engine holds is what the layers' kinds say.  The paged
    pools: ONE part, an array an MLA layer and none under a KDA layer,
    (blocks, block size, ``kv_lora_rank + qk_rope_head_dim``) in the
    compute dtype.  The state: TWO parts, an array a KDA layer each, one
    float32 of (slots, heads, width, width) and one in the compute dtype of
    (slots, taps - 1, 3 x heads x width), the convolution's last inputs;
    no axis of either counts positions."""
    import jax.numpy as jnp

    lin = config["linear_attn_config"]
    n = int(config["num_hidden_layers"])
    n_kda = sum(1 for i in lin["kda_layers"] if i <= n)
    heads, width = int(lin["num_heads"]), int(lin["head_dim"])
    taps = int(lin["short_conv_kernel_size"])
    row = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    dtype = jnp.dtype(config.get("compute_dtype", "bfloat16"))
    slots = engine.bcfg.slots
    pools, state = engine.pools, engine.state
    paged = len(pools) == 1 and all(
        len(layers) == n - n_kda and all(
            p.ndim == 3 and p.shape[2] == row and p.dtype == dtype
            for p in layers
        )
        for layers in pools.values()
    )
    want = sorted([
        ((slots, heads, width, width), jnp.dtype("float32")),
        ((slots, taps - 1, 3 * heads * width), dtype),
    ], key=str)
    held = len(state) == 2 and all(
        len(layers) == n_kda for layers in state.values()
    ) and sorted(
        ((tuple(layers[0].shape), layers[0].dtype) for layers in state.values()),
        key=str,
    ) == want and all(
        a.shape == layers[0].shape and a.dtype == layers[0].dtype
        for layers in state.values() for a in layers
    )
    return bool(paged and held)


def _run_programs(engine, seq, prompt_len: int, steps: int, n_blocks: int):
    """Logits (1 + steps, V), router scores (L_s, T, E) and choices
    (L_s, T, k) of the engine's own programs on ``seq``: prefill of the
    first ``prompt_len`` tokens, the write of its rows (blocks 1..n, which
    no request holds yet) and of its state (slot 0's), ``steps`` decode
    rounds in slot 0 with every other slot inactive."""
    slots, width = engine.bcfg.slots, engine.pcfg.blocks_per_seq
    blocks = np.arange(1, n_blocks + 1, dtype=np.int32)
    logits, cache = engine._prefill(engine.params, seq[None, :prompt_len])
    engine._write_slot(0, cache, blocks)
    got = [np.asarray(logits[0], np.float32)]
    scores = [np.asarray(cache["moe"]["scores"])]
    choices = [np.asarray(cache["moe"]["choices"])]
    del cache
    tables = np.zeros((slots, width), np.int32)
    tables[0, :n_blocks] = blocks
    for i in range(steps):
        lengths = np.zeros((slots,), np.int32)
        tokens = np.zeros((slots,), np.int32)
        lengths[0], tokens[0] = prompt_len + i, seq[prompt_len + i]
        out, moe = engine._decode_round(tables, lengths, tokens)
        got.append(np.asarray(out[0], np.float32))
        scores.append(np.asarray(moe["scores"][:, :1]))
        choices.append(np.asarray(moe["choices"][:, :1]))
    return (np.stack(got), np.concatenate(scores, axis=1),
            np.concatenate(choices, axis=1))


def _verdict(got, scores, choices, want: dict, engine, config: dict) -> dict:
    """The programs' results against the reference's, under the limits."""
    routing = compare_routing(scores, choices, want["scores"], want["choices"])
    scale = float(np.abs(want["logits"]).max())
    errs = np.abs(got - want["logits"]).max(axis=1) / scale
    held = pool_ok(engine, config)
    ok = (
        got.shape == want["logits"].shape and bool(np.isfinite(got).all())
        and float(errs.max()) < LOGITS_REL_TOL
        and routing["score_rel_err"] < SCORE_REL_TOL
        and routing["differing_off_cut_max"] < SCORE_REL_TOL
        and held
    )
    return {"ok": bool(ok), "prefill_rel_err": float(errs[0]),
            "decode_rel_err_max": float(errs[1:].max()) if len(errs) > 1 else 0.0,
            "pool_ok": bool(held), **routing}


def check_against_reference(engine, config: dict, seed: int, prompt_len: int,
                            steps: int, n_blocks: int,
                            reference_params=None,
                            reference_config=None) -> dict:
    """The comparison of the module docstring.  ``reference_params`` and
    ``reference_config`` are the tests' way to make the two sides
    disagree."""
    seq = base._check_sequence(config, seed, prompt_len + steps)
    got, scores, choices = _run_programs(
        engine, seq, prompt_len, steps, n_blocks
    )
    want = base._reference(
        engine.params if reference_params is None else reference_params,
        config if reference_config is None else reference_config,
        seq, choices, prompt_len,
    )
    return _verdict(got, scores, choices, want, engine, config)


def run(cell: Cell, seed: int, seconds: float, trace_dir, t_start: float,
        counter) -> Run:
    """``serve_closed_model.run`` with this file's comparison and this
    file's deal in place of its own, and nothing else of it changed: the
    window loop and the window rule are shared by import, as
    ``serve_closed_latent.run`` shares them (no file of the benchmark may
    be edited by the PR that brought this one; PERF.md section 7 queues
    the ``benchmark`` PR that folds the closed-loop drivers)."""
    with mock.patch.object(
        base, "check_against_reference", check_against_reference
    ), mock.patch.object(base, "ModelLoop", StateLoop):
        return base.run(cell, seed, seconds, trace_dir, t_start, counter)
