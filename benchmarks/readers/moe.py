"""Readers for cells whose model routes tokens to experts (kind
``serve_closed_model``)."""

from __future__ import annotations

import statistics

from benchmarks.lib import counts_laguna, xplane
from benchmarks.readers import spans


def moe_decode_roofline(ctx, match: str, span: str, hit: str):
    """Least time a decode round's reads could take over the decode
    program's traced time a round, in %: the weights outside the routed
    experts and the head, the routed experts HIT that round (the count the
    decode program hands out, on the ``span`` spans of the window) times an
    expert's bytes, and the K/V each layer must see (a window layer's
    capped at the window), over the HBM peak.  None where the program
    records no such count (a parent commit) or the trace no device."""
    if ctx.peaks is None or ctx.trace is None \
            or not xplane.device_planes(ctx.trace):
        return None
    hits = [
        float(s.stats[hit]) for s in spans._named(ctx, span) if hit in s.stats
    ]
    secs, runs = xplane.module_seconds(ctx.trace, match, ctx.window)
    decoded = [
        (r[4], capped)
        for r, capped in zip(ctx.obs["rounds"], ctx.obs.get("live_capped", ()))
        if r[3] > 0
    ]
    if not hits or not decoded or runs == 0 or secs <= 0:
        return None
    least = counts_laguna.decode_round_bytes(
        ctx.cell.config, statistics.fmean(hits),
        statistics.fmean(d[0] for d in decoded),
        statistics.fmean(d[1] for d in decoded),
    ) / ctx.peaks.hbm_bytes_per_s
    return 100.0 * least / (secs / runs)


def experts_share(ctx, scopes: list, match: str):
    """Own time of the expert layer's device operations over busy time, in
    %: those under ``scopes`` (dispatch, activation, combine) and the
    grouped products themselves, which XLA:TPU's rewrite of ``ragged_dot``
    renames (``op_name="ragged-dot-none"``: the scope is lost), so they
    are found by ``match`` on the operation's name.  None where the
    program has no such scope."""
    scoped = spans.scope_share(ctx, scopes)
    if scoped is None:
        return None
    busy = xplane.busy_seconds(ctx.trace, ctx.window)
    return scoped + 100.0 * xplane.matching_seconds(
        ctx.trace, match, ctx.window) / busy
