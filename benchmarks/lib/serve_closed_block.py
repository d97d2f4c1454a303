"""Driver for traffic of kind ``serve_closed_block``: the closed loop of
``serve_closed_model`` (its engine factory, window loop and window rule,
``ModelLoop``, estimators and ``obs`` keys, imported) over a model that
GENERATES BY DIFFUSION OVER BLOCKS (``model_type`` ``sdar_moe``), whose
round delivers a whole block of tokens to a slot or none.

What differs:

**The tokens a round counts** are the ones it delivered to requests
(``engine.step()``'s ``emitted``), not its slots (``decoded``): a
denoising pass that reveals two places delivers nothing, the pass that
reveals a block's last place delivers all of its tokens at one stamp, and
a commit pass delivers nothing.  Every rate and every gap of the window is
read from those deliveries; the first token of a request is its first
block.

**The deal: ONE order for every seed**, as ``serve_closed_hybrid`` deals:
``serve_closed_state.spread_deck``'s order (every size pair's cards spread
evenly through the deck) for ``DEAL_SEED``, whatever ``--seed`` is;
``--seed`` makes the weights and every token id, prompt ids drawn below the
mask token's id.  Why: answers of fixed length that start together end
together, so the slots refill in waves that the deal shapes, and a first
token waits for the prefills admitted in its round.  With the order drawn
by the seed, two sets of six seeds (my chip runs, PERF.md section 6) read
``serve_ttft_p50_ms`` spreads of 3.07% and 3.59% where half the bound is
2.5%, though their medians differed by 0.45%; the service times are
steady (a round 16.15 to 16.26 ms), so one order is one schedule: four
seeds of it read spreads of 0.63% (first token), 0.70% (rate) and 0.57%
(gap p95).

**The comparison that decides ``correct``**, on the engine's own programs
at the published widths, with EVERY slot live (as many as ``check_blocks``
pool blocks a slot leave room for): each slot's ``_prefill`` of its
prompt's whole blocks and the write into its own blocks, then
``check_steps`` blocks generated in all of them at once, each block
``denoise_steps`` denoising passes and one commit pass of ``_decode``,
each round's reveal by ``_unmask``.  The prompts (``check_prompts``) are
``check_prompt`` tokens in the even slots and about half that in the odd
ones, with a tail of 0 to B - 1 tokens past their whole blocks; slot ``s``
starts ``s % (denoise_steps + 1)`` rounds late, so that most rounds hold
denoising and commit passes side by side.  Then:

(r) every round, every slot: the program's ids and reveal are the ones
    numpy computes from the same round's logits (``reveal_verdicts``),
    bar a swap of two places whose probabilities lie within ``TIE_REL``;

and for the slots of ``CHECK_SLOTS`` (the first three, which start at
every phase, and the last), against forwards of
``benchmarks/reference/sdar_moe_decoder.py`` over the sequence as it stood
at each denoising pass (the prompt, the committed blocks, the open block
with its masks, padded to one length: under the block-causal mask the
padding after the open block changes nothing), the reference FOLLOWING the
program's expert picks and reveals:

(a), (b) router scores and picks held apart as ``serve_closed_model``
    holds them (``compare_routing``), over the prompt's positions, every
    denoising pass's open block and every committed block's commit pass;
(c) each denoising pass's logits at the places still masked, against the
    reference's at the same places, over the largest of those reference
    logits;
(d) the pools: ``k`` and ``v`` an array a layer, (blocks, block size,
    K/V heads, head width) in the compute dtype, nothing a slot; and the
    rows the commit passes kept are the reference's K/V of the CLEAN
    blocks, within ``KV_REL_TOL`` of the largest, and NOT those of the
    slot's last block as its last denoising pass saw it (off by at least
    that, in every slot compared);
(e) every request returns exactly ``max_new_tokens`` tokens (the window
    loop's own check).
"""

from __future__ import annotations

import dataclasses
import importlib
import time
from unittest import mock

import numpy as np

from . import serve_closed_model as base
from .harness import Cell, Run, span
from .serve_closed_model import (  # noqa: F401  (the driver's surface)
    ModelLoop, build_engine, compare_routing,
)
from .serve_closed_state import spread_deck

__all__ = ["run", "build_engine", "check_against_reference", "pool_ok",
           "check_prompts", "reveal_verdicts", "BlockLoop",
           "LOGITS_REL_TOL", "SCORE_REL_TOL", "KV_REL_TOL", "TIE_REL",
           "CHECK_SLOTS", "DEAL_SEED"]

# The limits stand between two readings on the chip at the published widths
# and the cell's own check, every slot live (PERF.md section 6).  The bf16
# program against the float32 reference, twenty runs: logits 0.0057 to
# 0.0079 of the largest reference logit compared, router scores 0.028 to
# 0.036 of a position's largest (the furthest differing pick 0.012 to 0.021
# off the cut), committed K/V 0.0094 to 0.0127 of the largest; every reveal
# numpy's.  The same program against the reference computed on parameters
# cut to 3 mantissa bits (float8_e4m3's, at bfloat16's range), three seeds:
# logits 0.136 to 0.161, scores 0.497 to 0.514 (the furthest differing pick
# 0.28 to 0.30 off the cut), K/V 0.171 to 0.179: not correct by every
# limit.  Each limit stands between, 3.9 to 7.6 times over the worst clean
# reading and 2.3 to 3.4 times under the least low-precision one.
SCORE_REL_TOL = 0.2  # (a), (b): share of the position's largest score
LOGITS_REL_TOL = 6e-2  # (c): share of the largest reference logit compared
KV_REL_TOL = 5e-2  # (d): share of the largest reference K or V number
# (r): two float32 sums of a vocabulary's exponentials, in two orders
TIE_REL = 1e-4
# the slots held to the reference, taken modulo the slots live
CHECK_SLOTS = (0, 1, 2, -1)
#: the seed of the one order every run issues (module docstring)
DEAL_SEED = 0


class BlockLoop(ModelLoop):
    """``ModelLoop`` whose rounds count the tokens delivered, issuing the
    deck in the one order ``spread_deck`` deals for ``DEAL_SEED``, with
    prompt ids below the mask id (the run's seed makes them)."""

    def __init__(self, engine, traffic, seed, vocab, window: int):
        super().__init__(
            engine, traffic, seed, min(vocab, engine.cfg.mask_id), window
        )
        self.deck = spread_deck(traffic, DEAL_SEED)

    def round(self) -> None:
        t0 = time.monotonic()
        with span("engine_step"):
            out = self.engine.step()
        t1 = time.monotonic()
        slots = self.engine.batcher.slots
        live = sum(s.length for s in slots if s is not None)
        self.rounds.append((t1, t1 - t0, out["emitted"], out["decoded"], live))
        self.live_capped.append(live)
        with span("submit"):
            for _ in range(out["finished"]):
                self.issue()


def pool_ok(engine, config: dict) -> bool:
    """What the engine holds is what the layer says: TWO paged parts, ``k``
    and ``v``, an array each layer, (blocks, block size, K/V heads, head
    width) in the compute dtype; nothing a slot."""
    import jax.numpy as jnp

    n = int(config["num_hidden_layers"])
    row = (int(config["num_key_value_heads"]), int(config["head_dim"]))
    lead = (engine.pcfg.num_blocks, engine.pcfg.block_size)
    dtype = jnp.dtype(config.get("compute_dtype", "bfloat16"))
    return bool(
        sorted(engine.pools) == ["k", "v"] and not engine.state
        and all(
            len(layers) == n and all(
                tuple(p.shape) == lead + row and p.dtype == dtype
                for p in layers
            )
            for layers in engine.pools.values()
        )
    )


def check_prompts(rng, prompt_len: int, live: int, b: int, mask_id: int) -> list:
    """Slot ``s``'s prompt for the comparison: ``prompt_len`` tokens in an
    even slot, half of that plus ``(s // 2) % b`` in an odd one (so the odd
    slots open their first block on 0 to ``b - 1`` of the prompt's tokens),
    ids drawn below the mask id.  Two lengths of whole blocks at most,
    so two prefill programs."""
    lens = [prompt_len if s % 2 == 0 else prompt_len // 2 + (s // 2) % b
            for s in range(live)]
    return [rng.integers(0, mask_id, (n,)).astype(np.int32) for n in lens]


def reveal_verdicts(logits, masked, reveal, ids, revealed):
    """The reveal recomputed on the host from the pass's logits (numpy,
    nothing of the program's): the first index of each place's maximum, its
    softmax probability in float32, the ``reveal`` most probable masked
    places, ties to the lower place.  (S, B, V) logits, (S, B) ``masked``,
    (S,) ``reveal``, and the program's (S, B) ``ids`` and ``revealed`` ->
    (S,) int: 0 where the program's ids and reveal are numpy's, 1 where
    only the reveal differs and by a near tie (the places swapped lie
    within ``TIE_REL`` of each other: the two sums of a vocabulary's
    exponentials are taken in different orders), 2 where they differ
    otherwise."""
    logits = np.asarray(logits, np.float32)
    top = logits.max(axis=-1, keepdims=True)
    conf = (1.0 / np.exp(logits - top).sum(axis=-1)).astype(np.float32)
    out = np.zeros(masked.shape[0], np.int32)
    for s in range(masked.shape[0]):
        if not np.array_equal(ids[s], logits[s].argmax(axis=-1)):
            out[s] = 2
            continue
        places = [j for j in range(masked.shape[1]) if masked[s, j]]
        order = sorted(places, key=lambda j: (-conf[s, j], j))
        want = np.zeros(masked.shape[1], bool)
        want[order[: int(reveal[s])]] = True
        if np.array_equal(want, revealed[s]):
            continue
        got = revealed[s]
        rest = masked[s] & ~got
        near = (
            not (got & ~masked[s]).any() and got.sum() == want.sum()
            and rest.any()
            and conf[s][got].min() >= conf[s][rest].max() * (1 - TIE_REL)
        )
        out[s] = 1 if near else 2
    return out


def _open(q: dict, diff) -> None:
    """Open slot ``q``'s next block: the prompt's tail first, if any."""
    b, tail = diff.length, q.pop("tail", [])
    q.update(tokens=tail + [diff.mask_id] * (b - len(tail)),
             masked=[False] * len(tail) + [True] * (b - len(tail)),
             reveals=diff.reveals(b - len(tail)), phase=0)


def _run_programs(engine, prompts, blocks: int, n_blocks: int,
                  compared) -> dict:
    """The engine's own programs on ``prompts``, one a slot: each slot's
    prefill of its prompt's whole blocks into its own pool blocks
    (``1 + s * n_blocks ..``, which no request holds yet), then ``blocks``
    diffusion blocks in every slot at once, slot ``s`` starting
    ``s % (steps + 1)`` rounds late so that one round holds denoising and
    commit passes, blocks with a prompt's tail and without, slots at
    different lengths.  Every round's reveal is held to
    :func:`reveal_verdicts`.  Returns per ``compared`` slot what the
    comparison needs (each denoising pass's sequence as it stood, its
    masked places with their logits, the router's scores and picks; each
    commit pass's picks; the prefill's; the final tokens; the slot's
    blocks), and the round counts."""
    diff = engine.batcher.diffusion
    b, steps = diff.length, diff.steps
    slots, width = engine.bcfg.slots, engine.pcfg.blocks_per_seq
    table = np.zeros((slots, width), np.int32)
    seqs = []
    for s, prompt in enumerate(prompts):
        table[s, :n_blocks] = 1 + s * n_blocks + np.arange(n_blocks)
        whole = len(prompt) - len(prompt) % b
        q = {"passes": [], "commits": [], "whole": whole, "done": 0,
             "start": s % (steps + 1), "blocks": table[s, :n_blocks].copy(),
             "committed": [int(t) for t in prompt[:whole]],
             "tail": [int(t) for t in prompt[whole:]]}
        if whole:
            _, cache = engine._prefill(engine.params, prompt[None, :whole])
            engine._write_slot(s, cache, table[s, :n_blocks])
            if s in compared:
                q["prefill"] = {k: np.asarray(v) for k, v in cache["moe"].items()}
            del cache
        _open(q, diff)
        seqs.append(q)
    verdicts, mixed, rounds = np.zeros(3, np.int64), 0, 0
    while any(q["done"] < blocks for q in seqs):
        lengths = np.zeros((slots,), np.int32)
        arr = np.zeros((slots, b), np.int32)
        mask = np.zeros((slots, b), bool)
        reveal = np.zeros((slots,), np.int32)
        active = np.zeros((slots,), bool)
        tab = np.zeros_like(table)
        for s, q in enumerate(seqs):
            if rounds < q["start"] or q["done"] == blocks:
                continue
            active[s], tab[s] = True, table[s]
            lengths[s], arr[s] = len(q["committed"]), q["tokens"]
            if q["phase"] < steps:
                mask[s], reveal[s] = q["masked"], q["reveals"][q["phase"]]
        logits, engine.pools, moe = engine._decode(
            engine.params, engine.pools, tab, lengths, arr, active
        )
        ids, revealed = engine._unmask(logits, mask, reveal)
        logits, ids, revealed = (np.asarray(a) for a in (logits, ids, revealed))
        verdicts += np.bincount(
            reveal_verdicts(logits, mask, reveal, ids, revealed)[active],
            minlength=3,
        )
        phases = {seqs[s]["phase"] == steps for s in np.flatnonzero(active)}
        mixed += phases == {True, False}
        rounds += 1
        for s in np.flatnonzero(active):
            q = seqs[s]
            routed = {k: np.asarray(moe[k][:, s * b : (s + 1) * b])
                      for k in ("scores", "choices")} if s in compared else {}
            if q["phase"] == steps:  # the commit pass
                q["commits"].append(routed)
                q["committed"] += q["tokens"]
                q["done"] += 1
                _open(q, diff)
                continue
            if s in compared:
                rows = np.flatnonzero(q["masked"])
                q["passes"].append({
                    "seq": q["committed"] + q["tokens"], "masked": list(q["masked"]),
                    "logits": logits[s, rows].astype(np.float32), **routed,
                })
            for j in np.flatnonzero(revealed[s]):
                q["tokens"][j], q["masked"][j] = int(ids[s, j]), False
            q["phase"] += 1
    for q in seqs:
        q["final"] = q["committed"]
    return {"slots": {s: seqs[s] for s in compared}, "rounds": rounds,
            "rounds_mixed": mixed, "reveal_agrees": int(verdicts[0]),
            "reveal_ties": int(verdicts[1]), "reveal_wrong": int(verdicts[2])}


def _choices_for(got: dict, length: int, upto: int, open_choices=None):
    """(L, length, k) picks for a reference forward: the prefill's over the
    prompt's whole blocks, the commit passes' over the committed blocks
    (``upto`` of them), the open block's (``open_choices``, (L, B, k)) after
    them, zeros over the padding."""
    lead, _, k = got["passes"][0]["choices"].shape
    out = np.zeros((lead, length, k), np.int32)
    at = 0
    if "prefill" in got:
        out[:, : got["whole"]] = got["prefill"]["choices"]
        at = got["whole"]
    for commit in got["commits"][:upto]:
        picks = commit["choices"]
        out[:, at : at + picks.shape[1]] = picks
        at += picks.shape[1]
    if open_choices is not None:
        out[:, at : at + open_choices.shape[1]] = open_choices
    return out


def check_against_reference(engine, config: dict, seed: int, prompt_len: int,
                            steps: int, n_blocks: int,
                            reference_params=None,
                            reference_config=None) -> dict:
    """The comparison of the module docstring: ``steps`` blocks generated
    in every slot that ``n_blocks`` pool blocks a slot leave room for, on
    :func:`check_prompts`' prompts, the slots of :data:`CHECK_SLOTS` held
    to the reference.  ``reference_params`` and ``reference_config`` are
    the tests' way to make the two sides disagree."""
    import jax
    import jax.numpy as jnp

    b = engine.block_len
    live = min(engine.bcfg.slots, (engine.pcfg.num_blocks - 1) // n_blocks)
    rng = np.random.default_rng([int(seed), 0xC4EC])
    prompts = check_prompts(rng, prompt_len, live, b, engine.cfg.mask_id)
    compared = sorted({s % live for s in CHECK_SLOTS})
    got = _run_programs(engine, prompts, steps, n_blocks, compared)
    params = engine.params if reference_params is None else reference_params
    conf = config if reference_config is None else reference_config
    ref = importlib.import_module(
        f"benchmarks.reference.{conf['model_type']}_decoder"
    )
    # one length for every slot's forwards (under the block-causal mask
    # the padding after a sequence changes nothing in it); one program a
    # length of whole prompt blocks
    length = max(len(q["final"]) for q in got["slots"].values())
    forward = jax.jit(
        lambda p, tok, picks, whole: ref.forward(
            p, tok, conf, choices=picks, logits_from=whole),
        static_argnums=3,
    )

    def padded(seq):
        return jnp.asarray(seq + [0] * (length - len(seq)), jnp.int32)

    per_block = engine.batcher.diffusion.steps
    logit_errs, scale = [], 0.0
    mine_s, mine_c, want_s, want_c = [], [], [], []
    kv_err, kv_masked = 0.0, float("inf")
    for q in got["slots"].values():
        whole, masked_kv = q["whole"], None
        for n, p in enumerate(q["passes"]):
            start = len(p["seq"]) - b
            want = forward(params, padded(p["seq"]), jnp.asarray(
                _choices_for(q, length, n // per_block, p["choices"])), whole)
            rows = np.flatnonzero(p["masked"])
            if rows.size:  # a pass after a block's last reveal has none
                ref_logits = np.asarray(want["logits"][start - whole + rows])
                scale = max(scale, float(np.abs(ref_logits).max()))
                logit_errs.append(
                    float(np.abs(p["logits"] - ref_logits).max()))
            mine_s.append(p["scores"])
            mine_c.append(p["choices"])
            want_s.append(np.asarray(want["scores"][:, start : start + b]))
            want_c.append(np.asarray(want["choices"][:, start : start + b]))
            if n == len(q["passes"]) - 1:
                masked_kv = {k: np.asarray(want[k][:, start : start + b])
                             for k in ("k", "v")}
        end = len(q["final"])
        final = forward(params, padded(q["final"]), jnp.asarray(
            _choices_for(q, length, len(q["commits"]))), whole)
        # the committed blocks' commit passes and the prompt's positions
        for n, commit in enumerate(q["commits"]):
            at = whole + n * b
            mine_s.append(commit["scores"])
            mine_c.append(commit["choices"])
            want_s.append(np.asarray(final["scores"][:, at : at + b]))
            want_c.append(np.asarray(final["choices"][:, at : at + b]))
        if "prefill" in q:
            mine_s.append(q["prefill"]["scores"])
            mine_c.append(q["prefill"]["choices"])
            want_s.append(np.asarray(final["scores"][:, :whole]))
            want_c.append(np.asarray(final["choices"][:, :whole]))
        for part in ("k", "v"):
            rows = _pool_rows(engine, part, whole, end, q["blocks"])
            clean = np.asarray(final[part][:, whole:end])
            top = float(np.abs(clean).max())
            kv_err = max(kv_err, float(np.abs(rows - clean).max()) / top)
            kv_masked = min(kv_masked, float(
                np.abs(rows[:, -b:] - masked_kv[part]).max()) / top)
    routing = compare_routing(
        np.concatenate(mine_s, axis=1), np.concatenate(mine_c, axis=1),
        np.concatenate(want_s, axis=1), np.concatenate(want_c, axis=1),
    )
    errs = np.asarray(logit_errs) / scale
    held = pool_ok(engine, config)
    ok = (
        bool(np.isfinite(errs).all()) and float(errs.max()) < LOGITS_REL_TOL
        and routing["score_rel_err"] < SCORE_REL_TOL
        and routing["differing_off_cut_max"] < SCORE_REL_TOL
        and kv_err < KV_REL_TOL <= kv_masked and held
        and got["reveal_wrong"] == 0
    )
    return {"ok": bool(ok), "slots_live": live, "slots_compared": len(compared),
            "passes": sum(len(q["passes"]) + len(q["commits"])
                          for q in got["slots"].values()),
            "rounds": got["rounds"], "rounds_mixed": got["rounds_mixed"],
            "reveal_agrees": got["reveal_agrees"],
            "reveal_ties": got["reveal_ties"],
            "reveal_wrong": got["reveal_wrong"],
            "logits_rel_err_max": float(errs.max()),
            "kv_rel_err": kv_err, "kv_rel_err_masked": kv_masked,
            "pool_ok": bool(held), **routing}


def _pool_rows(engine, part: str, start: int, end: int, blocks):
    """(L, end - start, Hkv, D) float32: positions ``start..end-1`` of the
    pool blocks ``blocks`` in order, every layer."""
    return np.stack([
        np.asarray(pool[np.asarray(blocks)], np.float32)
        .reshape(-1, *pool.shape[2:])[start:end]
        for pool in engine.pools[part]
    ])


def run(cell: Cell, seed: int, seconds: float, trace_dir, t_start: float,
        counter) -> Run:
    """``serve_closed_model.run`` with this file's comparison and loop in
    place of its own, and nothing else of it changed: the window loop and
    the window rule are shared by import, as ``serve_closed_state.run``
    shares them.  The window loop caps a window layer's positions at
    ``sliding_window``, which this block states as null (it has no window
    layer): the loop is handed 0, which it reads as no window."""
    config = {**cell.config, "sliding_window": cell.config["sliding_window"] or 0}
    with mock.patch.object(
        base, "check_against_reference", check_against_reference
    ), mock.patch.object(base, "ModelLoop", BlockLoop):
        return base.run(
            dataclasses.replace(cell, config=config), seed, seconds,
            trace_dir, t_start, counter,
        )
