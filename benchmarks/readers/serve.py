"""Readers for cells of kind ``serve_closed``."""

from __future__ import annotations

import statistics

from benchmarks.lib import counts, estimators, xplane


def round_ms_p50(ctx):
    """Host seconds round ``engine.step()``, over rounds that decoded."""
    secs = [r[1] for r in ctx.obs["rounds"] if r[3] > 0]
    return statistics.median(secs) * 1e3 if secs else None


def prefill_ms_p50(ctx):
    """Admission to first token (``admitted_s`` -> first stamp)."""
    p = ctx.obs["prefill_s"]
    return statistics.median(p) * 1e3 if p else None


def percentile_ms(ctx, of: str, q: int):
    """The ``q``-th percentile of the window's ``of`` samples (``ttft_s``:
    arrival to first token; ``gap_s``: between a request's tokens)."""
    samples = ctx.obs[of]
    return estimators.percentile(samples, q) * 1e3 if samples else None


def slot_occupancy(ctx):
    rounds = ctx.obs["rounds"]
    if not rounds:
        return None
    return 100.0 * statistics.fmean(r[3] for r in rounds) / ctx.obs["slots"]


def block_occupancy_p50(ctx):
    """Median share of the pool's blocks in use, from the engine's own
    ``serve.cache_occupancy`` histogram (10% buckets: the upper edge of
    the bucket the median round fell in)."""
    p = estimators.histogram_delta_percentile(
        ctx.obs["occupancy_open"], ctx.obs["occupancy_close"], 50
    )
    return None if p is None else 100.0 * p


def decode_roofline(ctx, match: str):
    """Least time a round's reads could take (weights as the engine holds
    them and the live K/V, over the HBM peak) over the decode program's
    traced time a round, in %.  Memory binds: a round of 32 tokens does
    64 FLOPs per weight read."""
    if ctx.peaks is None or ctx.trace is None \
            or not xplane.device_planes(ctx.trace):
        return None
    secs, runs = xplane.module_seconds(ctx.trace, match, ctx.window)
    if runs == 0 or secs <= 0:
        return None
    m = counts.dims_of(ctx.cell.config)
    live = statistics.fmean(r[4] for r in ctx.obs["rounds"] if r[3] > 0)
    itemsize = {"float32": 4, "bfloat16": 2}[ctx.cell.config["param_dtype"]]
    least = counts.decode_round_bytes(m, live, weight_itemsize=itemsize) \
        / ctx.peaks.hbm_bytes_per_s
    return 100.0 * least / (secs / runs)
