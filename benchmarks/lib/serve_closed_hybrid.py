"""Driver for traffic of kind ``serve_closed_hybrid``: the closed loop of
``serve_closed_model`` (its engine factory, window loop and window rule,
``ModelLoop``, estimators and ``obs`` keys, imported) over a model whose
layers hold a recurrent STATE a slot beside plain K and V rows a position
and route nothing (``model_type`` ``olmo_hybrid``).

What differs is the comparison that decides ``correct``.  There is no
router to compare, so its numeric part is the logits alone, on the engine's
own programs as the engine runs them: ``_prefill`` of a ``check_prompt``-
token prompt (the chunked scan over its chunks, the flash forward),
``_write_slot`` (K and V rows into their blocks AND the final state into
the slot's place), and ``check_steps`` teacher-forced ``_decode_round``
steps through the pools and the state, against ONE forward of
``benchmarks/reference/olmo_hybrid_decoder.py`` (the recurrence token by
token, the attention a head at a time) over all ``check_prompt +
check_steps`` tokens, under a limit read for THIS configuration (below).
Its pool check is this file's (:func:`pool_ok`): K and V under a linear
layer, a state kept a position or held in the compute dtype would pass the
numeric limit.

**The deal: ONE order for every seed.**  The deck is the traffic file's,
the generator's own multiset and opening (``traffic.request_deck``: 100
cards, nine size pairs, the opening that meets every shape once), issued in
the order :func:`nested_spread_deck` gives for ``DEAL_SEED``, whatever
``--seed`` is; ``--seed`` makes the weights and every token id.  Why the
order is no longer the seed's: a window holds 26 to 32 of the deck's 100
requests, a prefill stalls all 8 slots, and a 16,384-token prompt's is
0.66 s, a fiftieth of the window, so the rate IS the prefill seconds a
window happens to hold and one long prompt more or less is 2.7% of it.
Four seed-drawn deals were read on the chip, six seeds a set (my chip runs,
PR 38; PERF.md section 6; half the bound of ``serve_tokens_per_s`` is
3.5%): ``serve_closed_state``'s spread of the nine size pairs 2.80% and
3.4% (5.5% with a run on a chip that ran every round a tenth slower); the
three prompt lengths spread first and each length's answers through its
places (:func:`nested_spread_deck` with the run's seed) 3.66%; the prompt
lengths in one cycle and the answers by the seed 4.82%; a greedy balance of
the running sum of prompt tokens 251.8 to 277.8 tokens/s with a TTFT p50 of
669 ms in two windows of six.  A replay of the schedule on the CPU (a round
22.2 ms, a prefill 69, 322 or 657 ms, the window rule as it is) gives the
same mean (253.6 for 253.4) and the same spreads, and puts 33%, 14% and 9%
of the first three deals' sets over half the bound: the rates fall in
three clumps a long prefill apart, whatever the order.  The service times
themselves are steady (a round 22.1 to 22.3 ms, an 8,192-token prefill
321.4 to 321.8 ms over twelve runs), so ONE order is one schedule and one
window.  What is given up is what ``traffic.py`` asks of a deal (two seeds,
two orders, so that no tail is one order's property): the three end-to-end
metrics of this cell are a rate, a median first token (an 8,192-token
prefill in every window of every deal but the greedy one) and the p95 of
plain rounds, none of them a tail of an order.  A window of whole periods
of the schedule would let the seed deal again: a ``benchmark`` PR's.
"""

from __future__ import annotations

import collections
import importlib
from unittest import mock

import numpy as np

from . import serve_closed_model as base, traffic as traffic_lib
from .harness import Cell, Run
from .serve_closed_model import (  # noqa: F401  (the driver's surface)
    ModelLoop, build_engine,
)

__all__ = ["run", "build_engine", "check_against_reference", "pool_ok",
           "nested_spread_deck", "HybridLoop"]

#: the seed of the one order every run issues (above)
DEAL_SEED = 0

# The limit stands between two readings on the chip at the published widths
# and the cell's own check (8,192-token prefill, the write of rows and
# state, 8 decode rounds; my chip runs, PR 38; PERF.md section 6).
# The bf16 program against the float32 reference, twelve seeds (and the
# checks of thirty-odd runs of the cell, inside them but for one decode reading
# of 0.0174):
#   logits 0.0095 to 0.0118 (prefill) and 0.0125 to 0.0147 (decode steps) of
#   the largest reference logit (4.7 to 5.3).
# The same program against the reference computed on parameters cut to 3
# mantissa bits (float8_e4m3's, at bfloat16's range: the nearest precision
# below the stated one), the same twelve seeds:
#   logits 0.200 to 0.299 (prefill) and 0.218 to 0.307 (decode): not correct.
# The limit stands between: 3.4 times the worst clean reading and 3.3 times
# under the lowest low-precision one.  The readings stand this close
# together over seeds only since the seeded embedding is drawn at 1.0 a
# number (``models/olmo_hybrid.py::_EMBED_STD``: at 0.25 one seed read 0.016
# and 0.022 clean, 0.33 and 0.38 cut).
LOGITS_REL_TOL = 6e-2  # share of the largest reference logit


def _spread(counts: dict, rng) -> list:
    """The keys of ``counts``, each as often as it counts, every key's
    copies evenly spaced through the whole: copy ``j`` of a key that counts
    ``c`` takes the place ``(j + u) / c``, ``u`` uniform in [0, 1) a key
    from ``rng``, ties by a second draw (``serve_closed_state.
    spread_deck``'s rule, for any keys)."""
    placed = []
    for key in sorted(counts):
        phase = rng.random()
        placed += [
            ((j + phase) / counts[key], rng.random(), key)
            for j in range(counts[key])
        ]
    return [key for _, _, key in sorted(placed)]


def nested_spread_deck(traffic: dict, seed: int) -> dict:
    """``traffic.request_deck``'s deck (the same opening, the same multiset
    of cards) with the PROMPT lengths spread evenly through it, and each
    prompt length's answer lengths spread evenly through that length's
    places; every phase is ``seed``'s.  Every stretch of the order holds
    each prompt length within a card and a half of the deck's share."""
    deck = traffic_lib.request_deck(traffic, seed)
    cards = deck["cards"]
    rng = np.random.default_rng([int(seed), 0x5EAD2])
    prompts = _spread(collections.Counter(p for p, _ in cards), rng)
    dealt = [None] * len(cards)
    for p in sorted(set(prompts)):
        answers = _spread(
            collections.Counter(m for q, m in cards if q == p), rng
        )
        for i, m in zip([i for i, q in enumerate(prompts) if q == p], answers):
            dealt[i] = (p, m)
    return {"opening": deck["opening"], "cards": dealt}


class HybridLoop(ModelLoop):
    """``ModelLoop`` that issues the deck in the one order of
    ``DEAL_SEED``; the run's seed still makes every prompt's tokens."""

    def __init__(self, engine, traffic, seed, vocab, window: int):
        super().__init__(engine, traffic, seed, vocab, window)
        self.deck = nested_spread_deck(traffic, DEAL_SEED)


def pool_ok(engine, config: dict) -> bool:
    """What the engine holds is what the layers' kinds say.  The paged
    pools: TWO parts, ``k`` and ``v``, an array each a FULL layer and none
    under a linear layer, (blocks, block size, K/V heads, head width) in
    the compute dtype.  The state: TWO parts, an array a linear layer
    each, one float32 of (slots, heads, key width, value width) and one in
    the compute dtype of (slots, taps - 1, heads x (2 key widths + value
    width)), the convolution's last inputs; no axis of either counts
    positions."""
    import jax.numpy as jnp

    n = int(config["num_hidden_layers"])
    n_lin = sum(k == "linear_attention" for k in config["layer_types"][:n])
    heads = int(config["linear_num_value_heads"])
    dk = int(config["linear_key_head_dim"])
    dv = int(config["linear_value_head_dim"])
    taps = int(config["linear_conv_kernel_dim"])
    q_heads = int(config["num_attention_heads"])
    row = (int(config.get("num_key_value_heads") or q_heads),
           int(config.get("head_dim") or int(config["hidden_size"]) // q_heads))
    dtype = jnp.dtype(config.get("compute_dtype", "bfloat16"))
    slots = engine.bcfg.slots
    pools, state = engine.pools, engine.state
    lead = (engine.pcfg.num_blocks, engine.pcfg.block_size)
    paged = sorted(pools) == ["k", "v"] and all(
        len(layers) == n - n_lin and all(
            tuple(p.shape) == lead + row and p.dtype == dtype for p in layers
        )
        for layers in pools.values()
    )
    want = sorted([
        ((slots, heads, dk, dv), jnp.dtype("float32")),
        ((slots, taps - 1, heads * (2 * dk + dv)), dtype),
    ], key=str)
    held = len(state) == 2 and all(
        len(layers) == n_lin for layers in state.values()
    ) and sorted(
        ((tuple(layers[0].shape), layers[0].dtype) for layers in state.values()),
        key=str,
    ) == want and all(
        a.shape == layers[0].shape and a.dtype == layers[0].dtype
        for layers in state.values() for a in layers
    )
    return bool(paged and held)


def _run_programs(engine, seq, prompt_len: int, steps: int, n_blocks: int):
    """Logits (1 + steps, V) of the engine's own programs on ``seq``:
    prefill of the first ``prompt_len`` tokens, the write of its rows
    (blocks 1..n, which no request holds yet) and of its state (slot 0's),
    ``steps`` decode rounds in slot 0 with every other slot inactive."""
    slots, width = engine.bcfg.slots, engine.pcfg.blocks_per_seq
    blocks = np.arange(1, n_blocks + 1, dtype=np.int32)
    logits, cache = engine._prefill(engine.params, seq[None, :prompt_len])
    engine._write_slot(0, cache, blocks)
    got = [np.asarray(logits[0], np.float32)]
    del cache
    tables = np.zeros((slots, width), np.int32)
    tables[0, :n_blocks] = blocks
    for i in range(steps):
        lengths = np.zeros((slots,), np.int32)
        tokens = np.zeros((slots,), np.int32)
        lengths[0], tokens[0] = prompt_len + i, seq[prompt_len + i]
        (out,) = engine._decode_round(tables, lengths, tokens)
        got.append(np.asarray(out[0], np.float32))
    return np.stack(got)


def _reference(params, config: dict, seq, prompt_len: int) -> np.ndarray:
    """One full forward of the configuration's plain reference over
    ``seq``; logits from the prompt's last position on."""
    import jax

    ref = importlib.import_module(
        f"benchmarks.reference.{config['model_type']}_decoder"
    )
    want = jax.jit(
        lambda p, tok: ref.forward(p, tok, config, logits_from=prompt_len - 1)
    )(params, seq)
    return np.asarray(want["logits"])


def _verdict(got, want, engine, config: dict) -> dict:
    """The programs' logits against the reference's, under the limit."""
    scale = float(np.abs(want).max())
    same = got.shape == want.shape
    errs = np.abs(got - want).max(axis=1) / scale if same else np.full(1, np.inf)
    held = pool_ok(engine, config)
    ok = (
        same and bool(np.isfinite(got).all())
        and float(errs.max()) < LOGITS_REL_TOL and held
    )
    return {"ok": bool(ok), "prefill_rel_err": float(errs[0]),
            "decode_rel_err_max": float(errs[1:].max()) if len(errs) > 1 else 0.0,
            "pool_ok": bool(held)}


def check_against_reference(engine, config: dict, seed: int, prompt_len: int,
                            steps: int, n_blocks: int,
                            reference_params=None,
                            reference_config=None) -> dict:
    """The comparison of the module docstring.  ``reference_params`` and
    ``reference_config`` are the tests' way to make the two sides
    disagree."""
    seq = base._check_sequence(config, seed, prompt_len + steps)
    got = _run_programs(engine, seq, prompt_len, steps, n_blocks)
    want = _reference(
        engine.params if reference_params is None else reference_params,
        config if reference_config is None else reference_config,
        seq, prompt_len,
    )
    return _verdict(got, want, engine, config)


def run(cell: Cell, seed: int, seconds: float, trace_dir, t_start: float,
        counter) -> Run:
    """``serve_closed_model.run`` with this file's comparison and this
    file's deal in place of its own, and nothing else of it changed: the
    window loop and the window rule are shared by import, as
    ``serve_closed_state.run`` shares them."""
    with mock.patch.object(
        base, "check_against_reference", check_against_reference
    ), mock.patch.object(base, "ModelLoop", HybridLoop):
        return base.run(cell, seed, seconds, trace_dir, t_start, counter)
