"""Readers for cells of kind ``train``."""

from __future__ import annotations

import statistics

from benchmarks.lib import counts, estimators, xplane


def step_ms_p50(ctx):
    return statistics.median(estimators.diffs(ctx.obs["step_stamps"])) * 1e3


def mfu(ctx):
    """FLOPs the passes need a token (nothing recomputed) x the window's
    tokens/s (all its tokens over all its time), over chips x the bf16
    peak, in %."""
    if ctx.peaks is None:
        return None
    m = counts.dims_of(ctx.cell.config)
    rate = ctx.run.end_to_end["train_tokens_per_s"]
    peak = ctx.peaks.bf16_flops * ctx.obs["chips"]
    return 100.0 * counts.train_flops_per_token(m, ctx.obs["seq_len"]) * rate / peak


def _flash_seconds(ctx, match):
    if ctx.peaks is None or ctx.trace is None \
            or not xplane.device_planes(ctx.trace):
        return None
    secs = xplane.matching_seconds(ctx.trace, match, ctx.window)
    return secs if secs > 0 else None


def flash_roofline(ctx, match: str):
    """Least time the chip could take for the flash kernels' work (the
    larger of FLOPs over peak FLOP/s and bytes over peak bytes/s, forward
    and backward, every layer, every step of the window) over their
    traced time, in %.  Per chip: a chip holds batch/chips sequences."""
    secs = _flash_seconds(ctx, match)
    if secs is None:
        return None
    m = counts.dims_of(ctx.cell.config)
    peaks = ctx.peaks
    shape = (ctx.obs["batch"] // ctx.obs["chips"], m.heads,
             ctx.obs["seq_len"], m.head_dim)
    least = 0.0
    for flops, nbytes in (
        (counts.flash_fwd_flops(*shape), counts.flash_fwd_bytes(*shape)),
        (counts.flash_bwd_flops(*shape), counts.flash_bwd_bytes(*shape)),
    ):
        least += max(flops / peaks.bf16_flops, nbytes / peaks.hbm_bytes_per_s)
    return 100.0 * least * m.layers * ctx.obs["steps"] / secs
