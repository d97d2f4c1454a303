"""Driver for traffic of kind ``serve_closed``: one ``ServingEngine``
under a closed loop of as many clients as the traffic file says, each
sending its next request the moment its last completes.

Everything runs in ONE thread: requests are submitted between
``engine.step()`` calls, so the schedule (who is admitted in which round,
how many tokens a round emits) is a function of token counts alone: two
runs of one seed differ in durations only.  ``--seed`` makes the weights,
the token ids and the ORDER in which the traffic file's fixed multiset of
request sizes is issued.  The window opens after a fixed number of warm-up
rounds, by which every slot has retired a request and the clients have
drifted out of step; every shape the traffic uses has been met by then
(``traffic.request_deck``), so what compiles does so in set-up.  It
closes with the first round that ends ``--seconds`` or more after it
opened, and ``serve_tokens_per_s`` is every token of those rounds over
that span.

Times are the engine's own stamps (``time.monotonic``, taken after each
round's logits reached the host) and the harness's stamps round
``engine.step()``.
"""

from __future__ import annotations

import re
import statistics
import time

import numpy as np

from . import estimators, traffic as traffic_lib
from .harness import (
    Cell, Run, say, say_setup, seed32, span, traced, window_seconds,
)

__all__ = ["run", "build_engine", "check_against_reference", "ClosedLoop"]

# Tolerance of the comparison with benchmarks/reference/dense_decoder.py:
# logits of the engine's prefill (last position of a 512-token prompt) and
# of 8 teacher-forced decode steps through the paged cache, against the
# reference's one full forward over the 520 tokens (float32, matmuls at
# "highest").  The engine computes in bf16 and keeps K/V in bf16 (eps
# 3.9e-3 a rounding, compounding over 8 layers), and its float32 output
# head runs at the TPU's default matmul precision; seen on the chip, two
# seeds: prefill 0.81e-2 to 0.97e-2, decode steps 1.00e-2 to 1.09e-2
# (PERF.md section 6), so the tolerance stands at three times the worst.
# A wrong block table, position or mask moves logits by O(1) of the
# largest; so would a cache write that lands one slot off.
LOGITS_REL_TOL = 3e-2  # max |engine - reference| / max |reference|

PERCENTILE_NAME = re.compile(r"^serve_(ttft|gap)_p(\d{1,2})_ms$")


def build_engine(cell: Cell, seed: int):
    """(engine, model_cfg): parameters made on the device in one jitted
    call from the seed, the engine built as ``python -m
    flextree_tpu.serving`` builds it."""
    import jax
    import jax.numpy as jnp

    from flextree_tpu.models.transformer import TransformerConfig, init_params
    from flextree_tpu.serving import (
        BatcherConfig, PagedCacheConfig, ServingEngine,
    )

    c, t = cell.config, cell.traffic
    cfg = TransformerConfig(
        vocab_size=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_layers=int(c["num_hidden_layers"]),
        d_ff=int(c["intermediate_size"]),
        rope_theta=float(c["rotary_emb_base"]),
        dtype=getattr(jnp, c["compute_dtype"]),
    )
    params = jax.jit(lambda key: init_params(key, cfg))(
        jax.random.PRNGKey(seed32(seed))
    )
    jax.block_until_ready(params)
    pcfg = PagedCacheConfig(
        num_blocks=int(t["num_blocks"]), block_size=int(t["block_size"]),
        blocks_per_seq=int(t["blocks_per_seq"]),
    )
    engine = ServingEngine(
        params, cfg, pcfg,
        BatcherConfig(slots=int(t["slots"]), admission=t["admission"]),
        fused=bool(t["fused_decode"]), decode_impl=t["decode_impl"],
    )
    return engine, cfg


def check_against_reference(engine, cfg, seed: int, prompt_len: int,
                            steps: int, n_blocks: int,
                            reference_params=None) -> dict:
    """Prefill of one prompt, then ``steps`` teacher-forced decode steps
    through the paged cache, against the reference's full forward.  Uses
    the engine's own compiled programs and pool (blocks 1..n, which no
    request holds yet; every later admission overwrites what it is
    given).  ``reference_params``: the tests' way to make the two
    disagree."""
    import jax

    from benchmarks.reference import dense_decoder as ref

    rng = np.random.default_rng([int(seed), 0xC4EC])
    seq = rng.integers(0, cfg.vocab_size, (prompt_len + steps,)).astype(np.int32)
    slots, width = engine.bcfg.slots, engine.pcfg.blocks_per_seq
    blocks = np.arange(1, n_blocks + 1, dtype=np.int32)

    logits, cache = engine._prefill(engine.params, seq[None, :prompt_len])
    engine.pools = engine._write(engine.pools, cache, blocks)
    got = [np.asarray(logits[0], np.float32)]
    tables = np.zeros((slots, width), np.int32)
    tables[0, :n_blocks] = blocks
    for i in range(steps):
        lengths = np.zeros((slots,), np.int32)
        tokens = np.zeros((slots,), np.int32)
        lengths[0], tokens[0] = prompt_len + i, seq[prompt_len + i]
        out, engine.pools = engine._decode(
            engine.params, engine.pools, tables, lengths, tokens
        )
        got.append(np.asarray(out[0], np.float32))
    got = np.stack(got)

    def reference(p, tok):
        hidden = ref.hidden_states(p, tok, cfg.n_heads, cfg.rope_theta)
        return ref.logits_at(p, hidden[prompt_len - 1 :])

    want = np.asarray(jax.jit(reference)(
        engine.params if reference_params is None else reference_params, seq
    ), np.float32)
    scale = float(np.abs(want).max())
    errs = np.abs(got - want).max(axis=1) / scale
    ok = (
        got.shape == want.shape and bool(np.isfinite(got).all())
        and float(errs.max()) < LOGITS_REL_TOL
    )
    return {"ok": ok, "prefill_rel_err": float(errs[0]),
            "decode_rel_err_max": float(errs[1:].max()) if steps else 0.0}


class ClosedLoop:
    """The clients.  ``issue()`` submits the next request of the deck;
    ``round()`` runs one engine round, then replaces every request that
    finished in it."""

    def __init__(self, engine, traffic: dict, seed: int, vocab: int):
        from flextree_tpu.serving import Request

        self._request = Request
        self.engine = engine
        self.seed = seed
        self.vocab = vocab
        self.deck = traffic_lib.request_deck(traffic, seed)
        self.issued = 0
        self.rejected = 0
        self.sizes: dict = {}  # rid -> (prompt_len, max_new)
        # per round: end stamp, seconds in engine.step(), tokens emitted,
        # slots that decoded, live cached positions after the round
        self.rounds: list = []

    def issue(self) -> None:
        rid = self.issued
        prompt_len, max_new = traffic_lib.request_size(self.deck, rid)
        self.issued += 1
        self.sizes[rid] = (prompt_len, max_new)
        ok = self.engine.submit(self._request(
            rid=rid,
            prompt=traffic_lib.prompt_tokens(self.seed, rid, prompt_len, self.vocab),
            max_new_tokens=max_new,
        ))
        if not ok:
            self.rejected += 1

    def round(self) -> None:
        t0 = time.monotonic()
        with span("engine_step"):
            out = self.engine.step()
        t1 = time.monotonic()
        slots = self.engine.batcher.slots
        live = sum(s.length for s in slots if s is not None)
        self.rounds.append(
            (t1, t1 - t0, out["admitted"] + out["decoded"], out["decoded"], live)
        )
        with span("submit"):
            for _ in range(out["finished"]):
                self.issue()

    def token_records(self) -> list:
        """(rid, arrival, admitted, token stamps, done?) of every request
        that has a token, finished or still resident."""
        recs = [
            (c.rid, c.arrival_s, c.admitted_s, tuple(c.token_times), True)
            for c in self.engine.completed.values()
        ]
        recs += [
            (s.rid, s.request.arrival_s, s.admitted_s, tuple(s.token_times), False)
            for s in self.engine.batcher.slots if s is not None
        ]
        return recs


def _occupancy_snapshot(engine) -> dict:
    h = engine.metrics.histogram("serve.cache_occupancy")
    return {"buckets": list(h.edges), "counts": list(h.counts)}


def run(cell: Cell, seed: int, seconds: float, trace_dir, t_start: float,
        counter) -> Run:
    t_imp = time.monotonic()
    import jax  # noqa: F401

    import flextree_tpu.serving  # noqa: F401

    setup = {"imports_s": time.monotonic() - t_imp}
    t = cell.traffic
    k, warm_rounds = int(t["block_rounds"]), int(t["warmup_rounds"])

    t0 = time.monotonic()
    engine, cfg = build_engine(cell, seed)
    setup["params_and_engine_s"] = time.monotonic() - t0

    t0 = time.monotonic()
    check = check_against_reference(
        engine, cfg, seed, int(t["check_prompt"]), int(t["check_steps"]),
        int(t["check_blocks"]),
    )
    setup["reference_check_s"] = time.monotonic() - t0
    say(f"reference check: {check}")

    loop = ClosedLoop(engine, t, seed, cfg.vocab_size)
    t0 = time.monotonic()
    for _ in range(int(t["clients"])):
        loop.issue()
    for _ in range(warm_rounds):
        loop.round()
    setup["warmup_rounds_s"] = time.monotonic() - t0
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
    # every request, in the window or before it, returned what it asked for
    all_whole = all(
        c.n_tokens == loop.sizes[c.rid][1] for c in engine.completed.values()
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
    # BENCHMARK.json says which percentiles the cell reports end to end,
    # by name: serve_ttft_p<q>_ms, serve_gap_p<q>_ms
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
