"""Driver for traffic of kind ``serve_closed_model``: the closed loop of
``serve_closed`` (its ``ClosedLoop``, deck, estimators and ``obs`` keys,
imported) over an engine that the PROGRAM builds from the configuration
file (``ServingEngine.from_config``: the ``model_type`` chooses the
block), checked against the configuration's own reference
(``benchmarks/reference/<model_type>_decoder.py``).

What differs from ``serve_closed`` besides: the window opens once every
slot has retired a request (and no sooner than ``warmup_rounds``), since
a cell whose longest answer outlasts a fixed warm-up would open on slots
that all started together; and each round also records the cached
positions a window layer can see (``live_capped``), for the decode
roofline.

The comparison that decides ``correct`` (``check_against_reference``),
on the timed programs at the published widths: the engine's own
``_prefill`` of a ``check_prompt``-token prompt, ``_write``, and
``check_steps`` teacher-forced ``_decode`` steps through the paged cache,
against ONE full reference forward.  With random weights a router's k-th
and (k+1)-th scores are often closer than bf16's rounding of the layer
input, and one swapped expert moves a token's logits by far more than
any rounding does, so three things are held apart:

(a) the program's router scores against the reference's, every sparse
    layer and position: ``max |program - reference|`` over a position's
    experts, as a share of the position's largest reference score, under
    ``SCORE_REL_TOL``;
(b) the chosen sets: an expert that one side chose and the other did not
    must have a reference score within that same tolerance of the cut
    (the reference's k-th largest score at the position); such
    near-ties are counted and reported, anything else fails;
(c) logits (prefill's last position and each decode step) with the
    reference FOLLOWING the program's choices: ``max |program -
    reference|`` over the largest reference logit, under
    ``LOGITS_REL_TOL``.

And the pools are held in the configuration's compute dtype with its K/V
heads (a float32 pool would pass every numeric limit and take twice the
memory the cell states).
"""

from __future__ import annotations

import importlib
import statistics
import time

import numpy as np

from . import estimators
from .harness import Cell, Run, say, say_setup, seed32, traced, window_seconds
from .serve_closed import PERCENTILE_NAME, ClosedLoop, _occupancy_snapshot

__all__ = ["run", "build_engine", "check_against_reference", "ModelLoop"]

# The limits, each between two readings on the chip at the published
# widths (my chip runs, PR 28; PERF.md section 6).  The bf16 program
# against the float32 reference, thirteen seeds: logits 0.016 to 0.027 of the
# largest reference logit (the residual stream is rounded to bf16 after
# each of ten residual adds, and a max over 100,352 logits of 9 positions
# is taken), router scores 0.061 to 0.090 of a position's largest (a max
# over 41,280 positions x 256 experts of an error whose typical size is a
# fifth of that: the layer input differs by bf16's rounding of four
# layers), 643 to 723 of 41,280 picks differing, the furthest 0.046 off
# the cut.  The same program against the reference computed on parameters
# cut to 3 mantissa bits (float8_e4m3's, at bfloat16's range: the nearest
# precision below the stated one), two seeds: logits 0.24 and 0.29,
# scores 0.92 and 1.13, 6,845 and 6,970 picks differing, up to 0.63 off
# the cut.  Each limit stands between, 2.2 times the worst clean
# reading and 4 to 5 times under the low-precision one.
SCORE_REL_TOL = 0.2  # (a), (b): share of the position's largest score
LOGITS_REL_TOL = 6e-2  # (c): share of the largest reference logit


def build_engine(cell: Cell, seed: int):
    """The engine as the program's own factory builds it from the
    configuration file."""
    from flextree_tpu.serving import (
        BatcherConfig, PagedCacheConfig, ServingEngine,
    )

    t = cell.traffic
    pcfg = PagedCacheConfig(
        num_blocks=int(t["num_blocks"]), block_size=int(t["block_size"]),
        blocks_per_seq=int(t["blocks_per_seq"]),
    )
    return ServingEngine.from_config(
        cell.config, pcfg,
        BatcherConfig(slots=int(t["slots"]), admission=t["admission"]),
        seed=seed32(seed), fused=bool(t["fused_decode"]),
        decode_impl=t["decode_impl"],
    )


def _run_programs(engine, seq, prompt_len: int, steps: int, n_blocks: int):
    """Logits (1 + steps, V), router scores (L_s, T, E) and choices
    (L_s, T, k) of the engine's own programs on ``seq``: prefill of the
    first ``prompt_len`` tokens, the pool write, ``steps`` decode steps in
    slot 0 (blocks 1..n, which no request holds yet)."""
    slots, width = engine.bcfg.slots, engine.pcfg.blocks_per_seq
    blocks = np.arange(1, n_blocks + 1, dtype=np.int32)
    logits, cache = engine._prefill(engine.params, seq[None, :prompt_len])
    engine.pools = engine._write(engine.pools, cache, blocks)
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
        out, engine.pools, moe = engine._decode(
            engine.params, engine.pools, tables, lengths, tokens
        )
        got.append(np.asarray(out[0], np.float32))
        scores.append(np.asarray(moe["scores"][:, :1]))
        choices.append(np.asarray(moe["choices"][:, :1]))
    return (np.stack(got), np.concatenate(scores, axis=1),
            np.concatenate(choices, axis=1))


def compare_routing(scores, choices, ref_scores, ref_choices) -> dict:
    """(a) and (b) of the module docstring, on (L_s, T, E) scores and
    (L_s, T, k) choices: the largest score error, how many picks differ,
    and the furthest any differing expert's reference score lies from the
    cut, the errors as shares of the position's largest reference score."""
    top = ref_scores.max(axis=-1)  # (L, T)
    score_err = float((np.abs(scores - ref_scores).max(axis=-1) / top).max())
    mine = np.zeros(ref_scores.shape, bool)
    np.put_along_axis(mine, choices, True, axis=-1)
    theirs = np.zeros(ref_scores.shape, bool)
    np.put_along_axis(theirs, ref_choices, True, axis=-1)
    differ = mine != theirs  # (L, T, E)
    cut = np.take_along_axis(ref_scores, ref_choices, axis=-1).min(axis=-1)
    off_cut = np.abs(ref_scores - cut[..., None]) / top[..., None]
    return {
        "score_rel_err": score_err,
        "picks_differing": int(differ.sum()) // 2,
        "picks": int(np.prod(choices.shape)),
        "differing_off_cut_max": float(off_cut[differ].max()) if differ.any() else 0.0,
    }


def _check_sequence(config: dict, seed: int, length: int) -> np.ndarray:
    rng = np.random.default_rng([int(seed), 0xC4EC])
    return rng.integers(0, int(config["vocab_size"]), (length,)).astype(np.int32)


def _reference(params, config: dict, seq, choices, prompt_len: int) -> dict:
    """One full forward of the configuration's plain reference over
    ``seq``, following ``choices``; logits from the prompt's last
    position on."""
    import jax
    import jax.numpy as jnp

    ref = importlib.import_module(
        f"benchmarks.reference.{config['model_type']}_decoder"
    )
    want = jax.jit(
        lambda p, tok, picks: ref.forward(
            p, tok, config, choices=picks, logits_from=prompt_len - 1
        )
    )(params, seq, jnp.asarray(choices))
    return {k: np.asarray(v) for k, v in want.items()}


def _verdict(got, scores, choices, want: dict, engine, config: dict) -> dict:
    """The programs' results against the reference's, under the limits."""
    import jax.numpy as jnp

    routing = compare_routing(scores, choices, want["scores"], want["choices"])
    scale = float(np.abs(want["logits"]).max())
    errs = np.abs(got - want["logits"]).max(axis=1) / scale
    pool = engine.pools["k"][0]
    pool_ok = (
        pool.dtype == jnp.dtype(config.get("compute_dtype", "bfloat16"))
        and pool.shape[2] == int(config["num_key_value_heads"])
    )
    ok = (
        got.shape == want["logits"].shape and bool(np.isfinite(got).all())
        and float(errs.max()) < LOGITS_REL_TOL
        and routing["score_rel_err"] < SCORE_REL_TOL
        and routing["differing_off_cut_max"] < SCORE_REL_TOL
        and pool_ok
    )
    return {"ok": bool(ok), "prefill_rel_err": float(errs[0]),
            "decode_rel_err_max": float(errs[1:].max()) if len(errs) > 1 else 0.0,
            "pool_ok": bool(pool_ok), **routing}


def check_against_reference(engine, config: dict, seed: int, prompt_len: int,
                            steps: int, n_blocks: int,
                            reference_params=None,
                            reference_config=None) -> dict:
    """The comparison of the module docstring.  ``reference_params`` and
    ``reference_config`` are the tests' way to make the two sides
    disagree."""
    seq = _check_sequence(config, seed, prompt_len + steps)
    got, scores, choices = _run_programs(engine, seq, prompt_len, steps, n_blocks)
    want = _reference(
        engine.params if reference_params is None else reference_params,
        config if reference_config is None else reference_config,
        seq, choices, prompt_len,
    )
    return _verdict(got, scores, choices, want, engine, config)


class ModelLoop(ClosedLoop):
    """``ClosedLoop`` that also records, each round, the cached positions
    a layer with a window can see (every slot's, capped at the window)."""

    def __init__(self, engine, traffic, seed, vocab, window: int):
        super().__init__(engine, traffic, seed, vocab)
        self.window = window
        self.live_capped: list = []

    def round(self) -> None:
        super().round()
        self.live_capped.append(sum(
            min(s.length, self.window)
            for s in self.engine.batcher.slots if s is not None
        ))


def run(cell: Cell, seed: int, seconds: float, trace_dir, t_start: float,
        counter) -> Run:
    t_imp = time.monotonic()
    import jax  # noqa: F401

    import flextree_tpu.serving  # noqa: F401

    setup = {"imports_s": time.monotonic() - t_imp}
    t, c = cell.traffic, cell.config
    k, warm_rounds = int(t["block_rounds"]), int(t["warmup_rounds"])
    clients = int(t["clients"])

    t0 = time.monotonic()
    engine = build_engine(cell, seed)
    setup["params_and_engine_s"] = time.monotonic() - t0

    t0 = time.monotonic()
    check = check_against_reference(
        engine, c, seed, int(t["check_prompt"]), int(t["check_steps"]),
        int(t["check_blocks"]),
    )
    setup["reference_check_s"] = time.monotonic() - t0
    say(f"reference check: {check}")

    loop = ModelLoop(engine, t, seed, int(c["vocab_size"]),
                     int(c.get("sliding_window", 0)) or 1 << 30)
    t0 = time.monotonic()
    for _ in range(clients):
        loop.issue()
    # the first `clients` requests are the slots' first occupants: when
    # all have completed, every slot has retired a request
    first = range(clients)
    while len(loop.rounds) < warm_rounds or not all(
        rid in engine.completed for rid in first
    ):
        loop.round()
    setup["warmup_rounds_s"] = time.monotonic() - t0
    say(f"warm-up: {len(loop.rounds)} rounds")
    compiles_before = len(counter.stamps)

    length = window_seconds(cell, seconds, trace_dir)
    occ_open = _occupancy_snapshot(engine)
    first_round = len(loop.rounds)
    with traced(trace_dir):
        t_open = time.monotonic()
        loop.round()
        while loop.rounds[-1][0] - t_open < length:
            loop.round()
    t_close = loop.rounds[-1][0]
    occ_close = _occupancy_snapshot(engine)
    setup_s = t_open - t_start
    say_setup(setup, setup_s, "; programs compiled or loaded in set-up: "
              f"{compiles_before}")

    window = (t_open, t_close)
    rounds = loop.rounds[first_round:]
    stamps = [t_open] + [r[0] for r in rounds]
    rates = estimators.block_rates(stamps, [r[2] for r in rounds], k)

    ttfts, gaps, prefills = [], [], []
    finished = short = 0
    for rid, arrival, admitted, times, done in loop.token_records():
        if times and estimators.in_window(times[0], window):
            ttfts.append(times[0] - arrival)
            prefills.append(times[0] - admitted)
        gaps += [
            b - a for a, b in zip(times, times[1:])
            if estimators.in_window(b, window)
        ]
        if done and estimators.in_window(times[-1], window):
            finished += 1
            short += len(times) != loop.sizes[rid][1]
    all_whole = all(
        done.n_tokens == loop.sizes[done.rid][1]
        for done in engine.completed.values()
    )
    rate = estimators.window_rate([r[2] for r in rounds], window)
    block_median = statistics.median(rates) if rates else float("nan")
    say(f"block rates (tokens/s): {[round(r, 1) for r in rates]}")
    say(f"window: {len(rounds)} rounds, {len(rates)} blocks, "
        f"{t_close - t_open:.3f} s, {rate:.2f} tokens/s (block median "
        f"{block_median:.2f}); first tokens {len(ttfts)}, gaps {len(gaps)}, "
        f"requests finished {finished}, rejected {loop.rejected}, "
        f"short {short}")

    def pct(values, qs):
        return {f"p{q}": round(estimators.percentile(values, q) * 1e3, 3)
                for q in qs}

    say(f"time to first token (ms): {pct(ttfts, (50, 75, 90, 95, 99, 100))} "
        f"mean {statistics.fmean(ttfts) * 1e3:.3f}; gaps (ms): "
        f"{pct(gaps, (50, 90, 95, 99, 100))}")
    counters = engine.report()["counters"]
    say("engine counters: " + str({
        n: counters[n] for n in sorted(counters) if n.startswith("serve.moe")
    }))
    end_to_end = {"serve_tokens_per_s": rate}
    for m in cell.end_to_end:
        named = PERCENTILE_NAME.match(m["name"])
        if named:
            samples = {"ttft": ttfts, "gap": gaps}[named[1]]
            end_to_end[m["name"]] = \
                estimators.percentile(samples, int(named[2])) * 1e3
    return Run(
        correct=bool(check["ok"] and all_whole and loop.rejected == 0),
        attempted=finished + loop.rejected,
        failed=short + loop.rejected,
        end_to_end=end_to_end,
        obs={
            "kind": "serve_closed",
            "rounds": rounds,
            "live_capped": loop.live_capped[first_round:],
            "block_rates": rates,
            "slots": int(t["slots"]),
            "ttft_s": ttfts,
            "gap_s": gaps,
            "prefill_s": prefills,
            "occupancy_open": occ_open,
            "occupancy_close": occ_close,
            "window_mono": window,
            "compiles_in_window": counter.between(t_open, t_close),
            "reference_check": check,
            "schedule_issued": loop.issued,
            "setup": setup,
        },
        setup_s=setup_s,
        trace_dir=trace_dir,
    )
