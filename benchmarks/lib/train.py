"""Driver for traffic of kind ``train``: the training user's path,
``flextree_tpu.trainer.build`` + ``parallel.loop.fit``, on the mesh, batch
and sequence length the traffic file gives and the model the configuration
file gives, every other option at the trainer's default.

The window is ONE ``fit`` call of a step count fixed before it starts
(``--seconds`` over the warm-up's steady step time, rounded up to whole
blocks), so a run does a fixed amount of work.  ``train_tokens_per_s`` is
every token of that call over the host clock round it, entry and final
loss fetch included.  Step times come from the flight recorder's
``step_start`` stamps: ``fit``'s NaN guard fetches the loss every step, so
consecutive stamps bracket finished device work.
"""

from __future__ import annotations

import math
import statistics
import time

import numpy as np

from . import estimators
from .harness import (
    Cell, Run, say, say_setup, seed32, span, traced, window_seconds,
)

__all__ = ["run", "trainer_argv", "check_against_reference"]

# Tolerances of the comparison with benchmarks/reference/dense_decoder.py
# (float32, matmuls at "highest"), on one seeded sequence at the published
# widths.  The program computes in bf16 (8 bits of mantissa, eps 3.9e-3):
# every layer rounds its activations to bf16 and the flash kernel rounds
# the probabilities, and its float32 output head runs at the TPU's default
# matmul precision (bf16 passes).  Over 4 layers that is about 1e-2 of the
# largest logit (seen on the chip, eight seeds: 0.86e-2 to 0.97e-2, and
# 0.9e-4 to 1.2e-3 nats of loss; PERF.md section 6), so the tolerances stand
# at three and four times the worst seen.
LOGITS_REL_TOL = 3e-2  # max |program - reference| / max |reference|
LOSS_TOL = 5e-3  # |program loss - reference loss|, nats


def trainer_argv(cell: Cell, seed: int) -> list:
    """The trainer's own command line for this cell."""
    c, t = cell.config, cell.traffic
    return [
        "--model", "dense",
        "--d-model", str(c["hidden_size"]),
        "--n-heads", str(c["num_attention_heads"]),
        "--n-layers", str(c["num_hidden_layers"]),
        "--d-ff", str(c["intermediate_size"]),
        "--vocab", str(c["vocab_size"]),
        "--dtype", c["compute_dtype"],
        "--attn-impl", t["attn_impl"],
        "--mesh", t["mesh"],
        "--batch", str(t["batch"]),
        "--seq-len", str(t["seq_len"]),
        "--corpus-tokens", str(t["corpus_tokens"]),
        "--seed", str(seed32(seed)),
    ]


def check_against_reference(params, model_cfg, tokens, targets,
                            reference_params=None) -> dict:
    """The program's forward pass and loss on one sequence against the
    plain reference, both on the same parameters (``reference_params`` is
    the tests' way to make the two disagree)."""
    import jax

    from benchmarks.reference import dense_decoder as ref
    from flextree_tpu.models.transformer import cross_entropy_loss, forward

    def program(p, tok, tgt):
        logits = forward(p, tok[None], model_cfg)
        loss_sum, count = cross_entropy_loss(logits, tgt[None])
        return logits[0], loss_sum / count

    def reference(p, tok, tgt):
        logits = ref.forward_logits(
            p, tok, model_cfg.n_heads, model_cfg.rope_theta
        )
        return logits, ref.token_loss(logits, tgt)

    got, got_loss = jax.jit(program)(params, tokens, targets)
    want, want_loss = jax.jit(reference)(
        params if reference_params is None else reference_params,
        tokens, targets,
    )
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    dloss = abs(float(got_loss) - float(want_loss))
    ok = (
        got.shape == want.shape
        and bool(np.isfinite(got).all())
        and rel < LOGITS_REL_TOL
        and dloss < LOSS_TOL
    )
    return {"ok": ok, "logits_rel_err": rel, "loss_err": dloss,
            "loss": float(got_loss), "reference_loss": float(want_loss)}


def _step_stamps(events, first_step: int):
    """(stamps, end): ``step_start`` times of steps >= ``first_step`` and
    the ``fit_end`` that closed the last of them."""
    stamps = [
        e["ts"] for e in events
        if e["kind"] == "step_start" and e["step"] >= first_step
    ]
    end = [e["ts"] for e in events if e["kind"] == "fit_end"][-1]
    return stamps, end


def run(cell: Cell, seed: int, seconds: float, trace_dir, t_start: float,
        counter) -> Run:
    t_imp = time.monotonic()
    import jax

    from flextree_tpu import trainer
    from flextree_tpu.data import LMDataset, synthetic_tokens
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.obs import flight_recorder
    from flextree_tpu.parallel.loop import FitConfig, fit

    import jax.numpy as jnp

    setup = {"imports_s": time.monotonic() - t_imp}
    t, c = cell.traffic, cell.config
    args = trainer.parse_args(trainer_argv(cell, seed))
    warm, k = int(t["warmup_steps"]), int(t["block_steps"])
    tokens_per_step = int(t["batch"]) * int(t["seq_len"])

    with flight_recorder(None, capacity=1 << 18) as rec:
        t0 = time.monotonic()
        state, step_fn, mesh, sspecs, pack, unpack = trainer.build(args)
        jax.block_until_ready(state)
        dataset = LMDataset(
            synthetic_tokens(args.corpus_tokens, args.vocab, seed=args.seed),
            batch=args.batch, seq_len=args.seq_len, seed=args.seed,
        )
        setup["build_and_init_s"] = time.monotonic() - t0

        t0 = time.monotonic()
        model_cfg = TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=args.d_ff, attn_impl=args.attn_impl,
            dtype=getattr(jnp, args.dtype),
        )
        tok, tgt = dataset.batch_at(0)
        check = check_against_reference(
            state["params"], model_cfg, tok[0], tgt[0]
        )
        setup["reference_check_s"] = time.monotonic() - t0
        say(f"reference check: {check}")

        # The trainer does not donate its state (train.py:768): a step
        # holds its arguments and its outputs together.  A third copy held
        # by THIS frame (the initial state, or the warm-up's result) would
        # be the harness's own 3.4 GiB, so each state is handed to fit as
        # the only reference to it: popped from a list in the call itself.
        hand_over = [state]
        del state

        def fit_to(num_steps, log_every):
            return fit(
                hand_over.pop(), step_fn, dataset,
                FitConfig(num_steps=num_steps, log_every=log_every),
                mesh=mesh, state_specs=sspecs,
                state_pack=pack, state_unpack=unpack,
            )

        # warm-up: the step compiles twice (the state arrives unsharded,
        # then mesh-sharded); the steps after that time the steady state
        t0 = time.monotonic()
        warm_run = fit_to(warm, 1)
        hand_over.append(warm_run.state)
        warm_run.state = None
        stamps, end = _step_stamps(list(rec.events), 0)
        warm_steps = estimators.diffs(stamps + [end])
        steady = statistics.median(warm_steps[3:])
        setup["warmup_steps_s"] = time.monotonic() - t0
        setup["first_two_steps_s"] = sum(warm_steps[:2])

        length = window_seconds(cell, seconds, trace_dir)
        n_steps = k * max(1, math.ceil(length / steady / k))
        say(f"warm-up: {warm} steps, steady step {steady * 1e3:.2f} ms; "
            f"window: {n_steps} steps in blocks of {k}")

        with traced(trace_dir):
            t_open = time.monotonic()
            with span("fit"):
                result = fit_to(warm + n_steps, args.log_every)
            t_close = time.monotonic()
        events = list(rec.events)

    setup_s = t_open - t_start
    say_setup(setup, setup_s)
    stamps, end = _step_stamps(events, warm)
    stamps = stamps + [end]
    work = [tokens_per_step] * (len(stamps) - 1)
    rates = estimators.block_rates(stamps, work, k)
    losses0 = warm_run.losses[0][1]
    window_losses = [loss for _, loss in result.losses]
    skipped = [s for s in result.report.skipped_steps if s >= warm]
    # step-0 loss is ln(vocab) plus half the logits' variance under random
    # weights (about +0.4): within 1.0 of ln(vocab); the random-walk corpus
    # is learnable, so the last logged loss is below the first
    loss_ok = (
        all(math.isfinite(x) for x in [losses0] + window_losses)
        and abs(losses0 - math.log(int(c["vocab_size"]))) < 1.0
        and bool(window_losses) and window_losses[-1] < losses0
    )
    rate = estimators.window_rate(work, (t_open, t_close))
    block_median = statistics.median(rates) if rates else float("nan")
    say(f"block rates (tokens/s): {[round(r) for r in rates]}")
    say(f"window: {result.steps_run} steps, {len(rates)} blocks, "
        f"{t_close - t_open:.3f} s, {rate:.1f} tokens/s (block median "
        f"{block_median:.1f}); loss {losses0:.4f} -> "
        f"{window_losses[-1] if window_losses else float('nan'):.4f}; "
        f"skipped {len(skipped)}")
    return Run(
        correct=bool(check["ok"] and loss_ok and result.steps_run == n_steps),
        attempted=result.steps_run,
        failed=len(skipped),
        end_to_end={"train_tokens_per_s": rate},
        obs={
            "kind": "train",
            "step_stamps": stamps,
            "block_rates": rates,
            "tokens_per_step": tokens_per_step,
            "seq_len": int(t["seq_len"]),
            "batch": int(t["batch"]),
            "chips": int(mesh.devices.size),
            "steps": result.steps_run,
            "window_mono": (t_open, t_close),
            "compiles_in_window": counter.between(t_open, t_close),
            "reference_check": check,
            "setup": setup,
        },
        setup_s=setup_s,
        trace_dir=trace_dir,
    )
