"""Timing utilities: a chrono-style stopwatch and a device-aware benchmark
loop.

The stopwatch mirrors the reference planner's ``newplan::Timer``
(``cost_model/timer.h:15-130``: Start/Stop/elapsed in s/ms/µs/ns).  The
benchmark loop is the analog of the reference harness's barrier+MPI_Wtime
pattern (``benchmark.cpp:149-174``) done right for an async dispatch model:
``block_until_ready`` gates both the warmup and every timed repetition (the
reference relied on the collective being blocking — SURVEY §8 notes the
missing completion gate).
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass

import jax

__all__ = [
    "Timer",
    "BenchResult",
    "time_jax_fn",
    "time_jax_fn_inplace",
    "time_interleaved",
    "time_device_loop",
]


class Timer:
    """Minimal stopwatch: ``Timer()`` starts it; ``elapsed_*`` reads it."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._stopped: float | None = None

    def restart(self) -> None:
        self._t0 = time.perf_counter()
        self._stopped = None

    def stop(self) -> float:
        self._stopped = time.perf_counter()
        return self._stopped - self._t0

    @property
    def elapsed_s(self) -> float:
        end = self._stopped if self._stopped is not None else time.perf_counter()
        return end - self._t0

    @property
    def elapsed_ms(self) -> float:
        return self.elapsed_s * 1e3

    @property
    def elapsed_us(self) -> float:
        return self.elapsed_s * 1e6

    @property
    def elapsed_ns(self) -> float:
        return self.elapsed_s * 1e9


@dataclass(frozen=True)
class BenchResult:
    """Per-repetition wall times plus the min/avg summary the reference
    harness logs (``benchmark.cpp:215``)."""

    times_s: tuple[float, ...]
    compile_s: float

    @property
    def min_s(self) -> float:
        return min(self.times_s)

    @property
    def avg_s(self) -> float:
        return sum(self.times_s) / len(self.times_s)

    @property
    def median_s(self) -> float:
        ts = sorted(self.times_s)
        n = len(ts)
        mid = n // 2
        return ts[mid] if n % 2 else 0.5 * (ts[mid - 1] + ts[mid])


def time_jax_fn(fn, *args, repeat: int = 10, warmup: int = 2) -> BenchResult:
    """Time ``fn(*args)`` with compile excluded and every rep fully gated.

    The first call (compile + run) is timed separately; ``warmup`` extra
    calls absorb autotuning; then ``repeat`` reps are timed individually
    with ``jax.block_until_ready`` inside the timed region (the
    ``MPI_Barrier``/``MPI_Wtime`` analog of ``benchmark.cpp:151-157``).
    """
    t = Timer()
    jax.block_until_ready(fn(*args))
    compile_s = t.stop()
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    times = []
    for _ in range(repeat):
        t.restart()
        jax.block_until_ready(fn(*args))
        times.append(t.stop())
    return BenchResult(tuple(times), compile_s)


def time_jax_fn_inplace(fn, x, repeat: int = 10, warmup: int = 2) -> BenchResult:
    """Time ``fn`` in-place: each output feeds the next call's input.

    This is the protocol of the reference benchmark's compounding
    ``MPI_IN_PLACE`` loop (``benchmark.cpp:149-159``): the same buffer is
    reduced again and again.  It is the only valid way to time a *donating*
    jit (the donated input is consumed, so re-calling on the original array
    would die), and it works identically for non-donating ``fn`` — so both
    sides of an A/B can share it.  ``fn``'s output must match its input in
    shape/dtype/sharding.
    """
    t = Timer()
    acc = fn(x)
    jax.block_until_ready(acc)
    compile_s = t.stop()
    for _ in range(warmup):
        acc = fn(acc)
    jax.block_until_ready(acc)
    times = []
    for _ in range(repeat):
        t.restart()
        acc = fn(acc)
        jax.block_until_ready(acc)
        times.append(t.stop())
    return BenchResult(tuple(times), compile_s)


def time_interleaved(calls: dict, repeat: int) -> dict:
    """Per-variant min/avg ms with the timed reps INTERLEAVED per round in
    a (deterministically) shuffled order instead of back-to-back blocks: on
    a timeshared host a sustained contention episode otherwise lands
    entirely on one variant and swings the A/B ratio ~20% run-to-run, and
    a FIXED round-robin order adds a position bias — each variant always
    inherits the cache state its fixed predecessor leaves behind.
    ``calls`` maps name -> (jitted_fn, args); every fn must already be
    compiled/warm."""
    order = list(calls)
    shuffler = random.Random(0)
    times: dict[str, list[float]] = {name: [] for name in calls}
    for _ in range(repeat):
        shuffler.shuffle(order)
        for name in order:
            fn, fargs = calls[name]
            t = Timer()
            jax.block_until_ready(fn(*fargs))
            times[name].append(t.stop())
    return {
        name: {
            "min_ms": min(ts) * 1e3,
            "avg_ms": sum(ts) / len(ts) * 1e3,
            # raw per-round samples (round i of every variant ran in the
            # same shuffled round), so callers can form PAIRED per-round
            # statistics — on a heavily timeshared host the min of two
            # variants' independent draws swings far more than any
            # per-round ratio does
            "times_ms": [t * 1e3 for t in ts],
        }
        for name, ts in times.items()
    }


def time_device_loop(
    fn,
    x0,
    *rest,
    n_lo: int = 2,
    n_hi: int = 12,
    best_of: int = 4,
    samples: int = 1,
) -> float:
    """Device-only per-call seconds for ``fn(x0, *rest)`` via an in-jit
    chained loop at two iteration counts.

    Protocol: jit ``lax.fori_loop(0, n, lambda i, a: fn(a, *rest), x0)``
    followed by a host scalar fetch, at ``n_lo`` and ``n_hi`` iterations;
    per-call time is the slope ``(t_hi - t_lo) / (n_hi - n_lo)`` with each
    endpoint the best of ``best_of`` runs.  The output→input chain makes
    every iteration data-dependent (unfakeable by an async backend) and the
    slope cancels the *fixed* dispatch cost per jit call, which for a short
    kernel can exceed the kernel.
    Requires ``fn``'s output to match its first argument in shape/dtype.
    ``samples > 1`` repeats the slope measurement (reusing the compiled
    loops) and returns the median slope.
    """
    import statistics

    import jax.numpy as jnp
    from jax import lax

    def make_loop(n):
        def loop(x, *r):
            acc = lax.fori_loop(0, n, lambda i, a: fn(a, *r), x)
            return jnp.sum(acc.astype(jnp.float32))

        return jax.jit(loop)

    loop_lo, loop_hi = make_loop(n_lo), make_loop(n_hi)
    float(loop_lo(x0, *rest))  # compile + warm
    float(loop_hi(x0, *rest))

    def best(loop, k):
        b = float("inf")
        for _ in range(k):
            t0 = time.perf_counter()
            float(loop(x0, *rest))
            b = min(b, time.perf_counter() - t0)
        return b

    slopes = []
    for _ in range(samples):
        # dispatch noise can exceed the added work when fn is tiny, making
        # the slope non-positive; retry with more best-of samples before
        # giving up loudly rather than returning a <=0 "time" (which would
        # publish as a negative/infinite TFLOP/s)
        k = best_of
        for attempt in range(3):
            slope = (best(loop_hi, k) - best(loop_lo, k)) / (n_hi - n_lo)
            if slope > 0:
                break
            k *= 2
        else:
            raise RuntimeError(
                f"time_device_loop: non-positive slope ({slope:.3e}s) after "
                f"3 attempts — fn is too small relative to dispatch noise "
                f"at n_hi={n_hi}; raise n_hi or time it with time_jax_fn"
            )
        slopes.append(slope)
    return statistics.median(slopes)
