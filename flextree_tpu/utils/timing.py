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

import time
from dataclasses import dataclass

import jax

__all__ = [
    "Timer",
    "BenchResult",
    "time_jax_fn",
    "time_jax_fn_inplace",
    "time_chained",
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


def time_chained(fn, q, *rest, n_calls: int = 10) -> float:
    """Per-call seconds for ``fn(q, *rest)`` with each output fed back as
    the next first argument and a final host scalar fetch.

    The data-dependency chain is a completion gate no backend can fake:
    the final fetch cannot produce bytes until every chained call has
    executed.  Per-call dispatch cost is included (``time_device_loop``
    cancels it).  Requires ``fn``'s output to have the shape/dtype of its
    first argument.
    """
    import jax.numpy as jnp

    warm = fn(q, *rest)
    float(jnp.sum(warm.astype(jnp.float32)))  # compile + forced warmup
    t0 = time.perf_counter()
    acc = q
    for _ in range(n_calls):
        acc = fn(acc, *rest)
    float(jnp.sum(acc.astype(jnp.float32)))
    return (time.perf_counter() - t0) / n_calls
