"""Readers any kind of cell can use.  A reader takes the context
(``lib.harness.ReaderContext``) and the ``args`` of its metric file, and
returns a number, or None where there is nothing to read."""

from __future__ import annotations

import statistics

from benchmarks.lib import xplane
from benchmarks.lib.harness import peak_memory_bytes


def compiles_in_window(ctx):
    """Programs compiled (or fetched from the cache) while the window was
    open; the warm-up is meant to leave none."""
    return ctx.obs.get("compiles_in_window")


def block_rate_p50(ctx):
    """Median over the window's consecutive blocks (of steps, or of engine
    rounds) of each block's tokens over its own span: what the rate is
    when nothing holds the loop up.  The end-to-end rate is all tokens
    over the whole window; a gap between the two is a stall."""
    rates = ctx.obs.get("block_rates")
    return statistics.median(rates) if rates else None


def peak_hbm_gib(ctx):
    peak = peak_memory_bytes()
    return peak / 2**30 if peak else None


def idle_share(ctx):
    """1 - union of device operations over the traced window, in %,
    averaged over the chips."""
    if ctx.trace is None or not xplane.device_planes(ctx.trace):
        return None
    span_s = (ctx.window[1] - ctx.window[0]) / 1e9
    return 100.0 * (1.0 - xplane.busy_seconds(ctx.trace, ctx.window) / span_s)


def op_share(ctx, match: str):
    """Own time of the operations matching ``match`` over device busy
    time, in %."""
    if ctx.trace is None or not xplane.device_planes(ctx.trace):
        return None
    busy = xplane.busy_seconds(ctx.trace, ctx.window)
    if busy <= 0:
        return None
    return 100.0 * xplane.matching_seconds(ctx.trace, match, ctx.window) / busy
