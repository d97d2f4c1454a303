"""Plan provenance for comm events: which plan, and what it predicted.

A bucket span that only says "4.2 MB over dp" answers *what* moved;
the question a cost-model-driven system has to answer is *why* — which
widths/family/codec/sharded plan the planner chose, and what it
predicted the move would cost.  :func:`bucket_provenance` packages that
into the JSON-safe dict ``comm_span`` attaches to the recorded event, so
every merged timeline carries predicted-vs-measured per-phase residual
material for free (the motivation of arXiv:2409.04202's measured-phase
treatment).

Free when telemetry is off: the helper returns ``None`` immediately when
no flight recorder is installed, so tracing a step in an
un-instrumented run never pays the cost-model call.
"""

from __future__ import annotations

import dataclasses

from .recorder import current_recorder

__all__ = ["topo_spec", "bucket_provenance"]


def topo_spec(topo) -> str:
    """The ``FT_TOPO``-style spec of a resolved topology (``"4,2"``,
    ``"3,2+2"``, ``"ring"``); the native-collective sentinel (None) reads
    ``"psum"``."""
    if topo is None:
        return "psum"
    if getattr(topo, "is_ring", False):
        return "ring"
    return str(topo).replace("*", ",")


def bucket_provenance(
    axes,
    topos,
    nbytes: int,
    *,
    n_leaves: int | None = None,
    dtype: str | None = None,
    codec=None,
    chunks: int = 1,
    sharded: bool = False,
    fired: bool = False,
    packed: bool | None = None,
) -> dict | None:
    """The plan-provenance payload for one bucket's comm event, or None
    when no recorder is installed (zero trace-time cost while telemetry
    is off).

    ``axes``/``topos``: the replication axes the bucket reduces over and
    their resolved topologies (``None`` = native psum).  The predicted
    :class:`~flextree_tpu.planner.cost_model.CostBreakdown` is computed
    per scheduled axis with the default calibrated params and summed —
    the same model the planner chose the bucket size with, so the
    residual read off a timeline is against the plan as priced, not a
    re-derivation.  ``packed``: whether the bucket's leaves share one flat
    buffer (False for a leaf that goes alone, in its own shape)."""
    if current_recorder() is None:
        return None
    axes = tuple(axes)
    prov: dict = {
        "axes": list(axes),
        "topo": {ax: topo_spec(topos.get(ax)) for ax in axes},
        # world size per axis (None for the native-psum sentinel, whose
        # group size the resolved topology doesn't carry): the residual
        # extractor pairs planned and measured spans on (topo, world,
        # codec, sharded, nbytes) — without the world a "ring" spec is
        # ambiguous across group sizes (planner/feedback.py)
        "world": {
            ax: (
                int(topos.get(ax).num_nodes)
                if topos.get(ax) is not None
                else None
            )
            for ax in axes
        },
        "nbytes": int(nbytes),
        "chunks": int(chunks),
        "codec": getattr(codec, "name", None) or (str(codec) if codec else "f32"),
        "sharded": bool(sharded),
        "fired": bool(fired),
    }
    if n_leaves is not None:
        prov["n_leaves"] = int(n_leaves)
    if dtype is not None:
        prov["dtype"] = str(dtype)
    if packed is not None:
        prov["packed"] = bool(packed)
    try:
        from ..planner.calibrate import default_params
        from ..planner.cost_model import allreduce_cost, lonely_allreduce_cost
        from ..schedule.stages import LonelyTopology

        # the LIVE calibrated constants (FLEXTREE_CALIBRATION), not the
        # invented dataclass defaults: the provenance contract is "the
        # plan as priced" — the same params the planner chose the bucket
        # size with, so per-step residuals judge the live model
        params = default_params()
        total = 0.0
        breakdown: dict[str, float] = {}
        for ax in axes:
            topo = topos.get(ax)
            if topo is None:
                continue  # native psum: the model has no term for it
            if isinstance(topo, LonelyTopology):
                cost = lonely_allreduce_cost(
                    topo.tree, topo.lonely, int(nbytes), params, codec=codec
                )
            else:
                cost = allreduce_cost(topo, int(nbytes), params, codec=codec)
            total += cost.total_us
            for key, val in dataclasses.asdict(cost).items():
                breakdown[key] = round(breakdown.get(key, 0.0) + val, 3)
        if breakdown:
            prov["predicted"] = breakdown
            prov["predicted_us"] = round(total, 3)
    except Exception:  # provenance must never break a trace
        prov["predicted_error"] = True
    return prov
