"""The arithmetic from stamps and counts to metrics.  Pure Python over
lists of floats, so the tests can work it by hand.

An end-to-end rate is all the work of the window over all its time
(``window_rate``): a stall, a recompile or a pause inside the window costs
the user that time, so it has to move the reading.  The median over
consecutive blocks (``block_rates``) stands beside it as a per-layer
metric: one slow stretch moves one block and not the median, so the two
together say whether a run was slow throughout or held up once.
"""

from __future__ import annotations

import math

__all__ = [
    "window_rate",
    "block_rates",
    "percentile",
    "diffs",
    "in_window",
    "histogram_delta_percentile",
]


def window_rate(work, window) -> float:
    """All the work done in the window over the window's whole length."""
    span = window[1] - window[0]
    if span <= 0:
        raise ValueError(f"window has span {span}")
    return sum(work) / span


def block_rates(stamps, work, k: int) -> list:
    """Rates of consecutive blocks of ``k`` units.

    ``stamps`` has one more entry than ``work``: unit ``i`` ran from
    ``stamps[i]`` to ``stamps[i + 1]`` and did ``work[i]``.  Block ``j``
    covers units ``j*k .. (j+1)*k - 1``; a trailing partial block is left
    out (it is shorter than the clock's resolution allows for)."""
    if len(stamps) != len(work) + 1:
        raise ValueError(
            f"{len(stamps)} stamps for {len(work)} units; need one more stamp"
        )
    if k < 1:
        raise ValueError(f"block size must be >= 1, got {k}")
    rates = []
    for j in range(len(work) // k):
        span = stamps[(j + 1) * k] - stamps[j * k]
        if span <= 0:
            raise ValueError(f"block {j} has span {span}")
        rates.append(sum(work[j * k : (j + 1) * k]) / span)
    return rates


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics (numpy's default rule), over ALL the values given."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def diffs(stamps) -> list:
    return [b - a for a, b in zip(stamps, stamps[1:])]


def in_window(t: float, window) -> bool:
    """A sample belongs to the window when its stamp is after the window
    opened and not after it closed: ``open < t <= close``."""
    return window[0] < t <= window[1]


def histogram_delta_percentile(before: dict, after: dict, q: float):
    """Percentile of what a cumulative bucketed histogram observed between
    two snapshots ``{"buckets": [upper edges], "counts": [per bucket, last
    = overflow]}``: the upper edge of the bucket the ``q``-th observation
    falls in (a bucketed reading can say no more).  None if nothing was
    observed in between."""
    edges = list(after["buckets"])
    delta = [a - b for a, b in zip(after["counts"], before["counts"])]
    total = sum(delta)
    if total <= 0:
        return None
    rank = total * q / 100.0
    seen = 0
    for i, n in enumerate(delta):
        seen += n
        if seen >= rank:
            return edges[i] if i < len(edges) else math.inf
    return math.inf
