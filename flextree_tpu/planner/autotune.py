"""Measured plan autotuner: close the analytic -> measured loop.

"Revisiting the Time Cost Model of AllReduce" (PAPERS.md) argues α-β
models must be anchored to measurement; ``calibrate.py`` does that for
the model's *constants* but the final plan pick was still pure argmin.
This module finishes the loop: take the top-K **analytic** candidates
over the (tree shape x wire codec) product, time each with the
shuffled-interleaved rep protocol (``utils.timing.time_interleaved``) on
the live backend, pick the
**measured** winner, and persist it in a plan cache so the second run is
a pure cache hit.

Cache contract: entries are keyed by ``plan_cache_key(fingerprint, n,
nbytes, dtype, codecs)`` — the same fingerprint helper the calibration
file uses (``calibrate.backend_fingerprint``), so a plan measured on one
host/chip is never silently replayed on another; a fingerprint mismatch
is a miss and the candidates are re-measured.  The cache file is JSON
(an explicit ``cache_path``, else ``FLEXTREE_PLAN_CACHE``, else the
user-level :data:`DEFAULT_CACHE_PATH` — persistence must hold out of the
box), one entry per key, schema-versioned by :data:`PLAN_CACHE_SCHEMA` (its
own constant — calibration-file schema bumps must not orphan plan
caches under older checkouts).

The measured winner can only improve on the analytic argmin: the argmin
is always in the shortlist, so ``min(measured)`` is never slower than the
argmin's own measured time (asserted in ``tests/test_autotune.py`` with
an injected fake timer, alongside the first-run-measures /
second-run-cache-hits demo).
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass

from ..schedule.ir import IRFamilySpec
from ..schedule.stages import LonelyTopology, Topology
from .calibrate import (
    backend_fingerprint,
    default_params,
    plan_cache_key,
)
from .choose import choose_topology

#: Plan-cache file schema — deliberately DECOUPLED from
#: ``calibrate.CALIBRATION_SCHEMA``: the two files evolve independently,
#: and stamping plan caches with the calibration constant would make a
#: calibration-only bump (e.g. schema 4's provenance ``source`` stamp)
#: silently discard — and on the next rewrite destroy — a fresh plan
#: cache under any older checkout sharing the user-level cache file.
#: Bump this one only when the plan-cache ENTRY format itself changes.
PLAN_CACHE_SCHEMA = 3

__all__ = [
    "TunedPlan",
    "analytic_shortlist",
    "autotune_plan",
    "invalidate_plan_cache",
    "DEFAULT_CODECS",
    "DEFAULT_IR_FAMILIES",
]

DEFAULT_CODECS = ("f32", "bf16", "int8")

#: schedule-IR families offered to the measured search by default
#: (ISSUE 8): the analytic model ranks them honestly (swing pays its
#: distance-weighted wire, generalized its per-round launches), and when
#: one makes the shortlist the measurement — not the model — decides.
#: They enter only for the identity codec and unsharded plans (no
#: compressed / split-phase lowering for IR families yet).
DEFAULT_IR_FAMILIES = ("swing", "generalized")


@dataclass(frozen=True)
class TunedPlan:
    """Autotuner output: the winning (shape, codec) plus provenance.

    ``family`` records which schedule family won: ``"tree"`` for every
    legacy shape (ring included) or an IR family (``"swing"`` /
    ``"generalized"``).  Cache entries persist it, so an IR winner can
    never be replayed as (or aliased against) a legacy widths vector —
    the no-alias guard of the plan cache."""

    num_nodes: int
    nbytes: int
    dtype: str
    widths: tuple[int, ...]
    lonely: int
    codec: str
    predicted_us: float
    measured_us: float | None
    source: str  # "measured" | "cache" | "analytic"
    #: ranked shortlist rows: (shape, lonely, codec, predicted_us,
    #: measured_us) — ``shape`` is a widths tuple for legacy rows, an
    #: ``"swing"``/``"gen:..."`` spec string for IR rows
    table: tuple = ()
    family: str = "tree"
    ports: int = 0

    def to_ft_topo(self) -> str:
        if self.family == "swing":
            return "swing"
        if self.family == "generalized":
            return f"gen:{','.join(map(str, self.widths))}@{self.ports}"
        spec = ",".join(map(str, self.widths))
        if self.lonely:
            spec += f"+{self.lonely}"
        return spec

    @property
    def topology(self):
        if self.family == "swing":
            return IRFamilySpec("swing", self.num_nodes)
        if self.family == "generalized":
            return IRFamilySpec(
                "generalized", self.num_nodes, self.widths, self.ports
            )
        if self.widths == (1,):
            return Topology.ring(self.num_nodes)
        if self.lonely:
            return LonelyTopology(
                self.num_nodes,
                Topology(self.num_nodes - self.lonely, self.widths),
                self.lonely,
            )
        return Topology(self.num_nodes, self.widths)


def analytic_shortlist(
    n: int,
    nbytes: int,
    codecs=DEFAULT_CODECS,
    params=None,
    top_k: int = 4,
    sharded: bool = False,
    ir_families=DEFAULT_IR_FAMILIES,
) -> list[tuple]:
    """Top-K ``(shape, lonely, codec, predicted_us)`` over the shape x
    codec product, cheapest first — ``shape`` is a widths tuple for
    legacy candidates or an ``IRFamilySpec`` for swing/generalized rows
    (offered under the identity codec only).  The overall analytic
    argmin is rank 0 by construction.  ``sharded`` prices one ZeRO sync
    round (grad reduce-scatter + param all-gather —
    ``choose_topology(collective="sharded")``) instead of the fused
    allreduce, and excludes IR families (no split-phase lowering)."""
    if params is None:
        params = default_params()
    rows: list[tuple] = []
    for codec in codecs:
        offer_ir = (
            tuple(ir_families) if codec == "f32" and not sharded else ()
        )
        plan = choose_topology(
            n, nbytes, params=params, codec=codec,
            collective="sharded" if sharded else "allreduce",
            ir_families=offer_ir,
        )
        for c in plan.candidates:
            if c.family == "tree":
                rows.append((c.widths, c.lonely, codec, c.total_us))
            else:
                fam = (
                    IRFamilySpec("swing", n)
                    if c.family == "swing"
                    else IRFamilySpec("generalized", n, c.widths, c.ports)
                )
                rows.append((fam, 0, codec, c.total_us))
    rows.sort(key=lambda r: r[3])
    return rows[: max(1, top_k)]


# ------------------------------------------------------------- cache


#: Default on-disk plan cache when neither ``cache_path`` nor
#: ``FLEXTREE_PLAN_CACHE`` names one — persistence is the documented
#: contract ("the second run is a pure cache hit"), so it must hold out
#: of the box, not only for users who exported an env var.  Entries are
#: keyed by backend fingerprint, so a shared user-level cache is safe
#: across hosts/backends.
DEFAULT_CACHE_PATH = os.path.join(
    os.path.expanduser("~"), ".cache", "flextree_tpu", "plan_cache.json"
)


def _cache_path(cache_path):
    if cache_path is not None:
        return cache_path
    return os.environ.get("FLEXTREE_PLAN_CACHE") or DEFAULT_CACHE_PATH


def _cache_load(path) -> dict:
    if not path or not os.path.exists(path):
        return {"schema": PLAN_CACHE_SCHEMA, "entries": {}}
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {"schema": PLAN_CACHE_SCHEMA, "entries": {}}
    if doc.get("schema", 1) > PLAN_CACHE_SCHEMA:
        return {"schema": PLAN_CACHE_SCHEMA, "entries": {}}
    doc.setdefault("entries", {})
    return doc


def _cache_store(path, doc) -> None:
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)  # atomic: a concurrent reader never sees a torn file


def invalidate_plan_cache(predicate, cache_path=None) -> int:
    """Drop plan-cache entries matching ``predicate(key, entry) -> bool``;
    returns how many were removed.

    The drift-invalidation seam of the closed feedback loop
    (``planner/feedback.py``, ISSUE 12): when measured comm residuals show
    a cached plan was priced by stale constants, the matching entries are
    removed so the next ``maybe_autotune_grad_topo`` / ``autotune_plan``
    call **re-measures** the shortlist instead of riding the stale winner.
    ``predicate`` receives the flat cache key string
    (:func:`~flextree_tpu.planner.calibrate.plan_cache_key` layout) and
    the stored entry dict (which carries the measuring ``fingerprint``) —
    :func:`flextree_tpu.planner.feedback.cache_invalidation_predicate`
    builds the standard fingerprint+world matcher.  A missing/empty cache
    is a no-op (0), and an untouched cache file is not rewritten.
    """
    path = _cache_path(cache_path)
    if not path or not os.path.exists(path):
        return 0
    doc = _cache_load(path)
    keep = {}
    removed = 0
    for key, entry in doc["entries"].items():
        if predicate(key, entry):
            removed += 1
        else:
            keep[key] = entry
    if removed:
        doc["entries"] = keep
        _cache_store(path, doc)
    return removed


# ------------------------------------------------------------ measure


def _default_timer(candidates, n, nbytes, dtype, repeat, sharded: bool = False):
    """Measure every candidate with the shuffled-interleaved protocol
    (one warmed jitted fn per candidate, reps interleaved in shuffled
    rounds so a host-contention episode cannot land on one candidate).
    Returns measured seconds per candidate, aligned with ``candidates``.
    ``sharded`` times the split round the ZeRO step actually runs
    (``all_gather(reduce_scatter(x))`` with the codec on both wires).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from ..parallel.allreduce import all_gather, reduce_scatter
    from ..parallel.compressed import compressed_allreduce
    from ..parallel.mesh import flat_mesh
    from ..utils.timing import time_interleaved

    mesh = flat_mesh(n, "ft")
    size = max(1, nbytes // jnp.dtype(dtype).itemsize)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((n, size)).astype(np.float32), dtype=jnp.dtype(dtype)
    )

    calls = {}
    for i, (widths, lonely, codec, _pred) in enumerate(candidates):
        if isinstance(widths, IRFamilySpec):
            spec = widths.spec  # "swing" / "gen:...": allreduce resolves it
        else:
            spec = ",".join(map(str, widths)) + (f"+{lonely}" if lonely else "")

        def device_fn(row, spec=spec, codec=codec):
            if sharded:
                shard = reduce_scatter(row[0], "ft", topo=spec, codec=codec)
                return all_gather(
                    shard, "ft", topo=spec, out_shape=row[0].shape, codec=codec
                )[None]
            return compressed_allreduce(row[0], "ft", topo=spec, codec=codec)[None]

        fn = jax.jit(
            jax.shard_map(
                device_fn, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"),
                check_vma=False,
            )
        )
        jax.block_until_ready(fn(x))  # compile outside the timed reps
        calls[str(i)] = (fn, (x,))
    rows = time_interleaved(calls, repeat)
    return [rows[str(i)]["min_ms"] * 1e-3 for i in range(len(candidates))]


# ------------------------------------------------------------- entry


def autotune_plan(
    n: int,
    nbytes: int,
    *,
    dtype: str = "float32",
    codecs=DEFAULT_CODECS,
    top_k: int = 4,
    params=None,
    cache_path=None,
    timer=None,
    repeat: int = 5,
    use_cache: bool = True,
    overlap: bool = False,
    sharded: bool = False,
    ir_families=DEFAULT_IR_FAMILIES,
) -> TunedPlan:
    """Pick the gradient-sync plan by measurement.

    First run: rank the shape x codec product analytically, measure the
    top-``top_k`` candidates (``timer(candidates, n, nbytes, dtype,
    repeat) -> [seconds]``, defaulting to the live-backend protocol
    above), persist the winner under the backend-fingerprinted key.
    Second run with the same key: pure cache hit — no timing, no compile.

    ``codecs=("f32",)`` tunes shape only (the measured twin of
    ``choose_topology``); the default product also offers the wire codecs
    so the planner can trade shape against precision.

    ``overlap`` tags the cache key: a plan measured for the serialized
    sync must never be silently replayed for the readiness-ordered
    overlapped sync (or vice versa) — the overlapped step issues its
    collectives mid-backward, where the best shape can differ (smaller
    latency-bound buckets win when comm hides under compute).  The
    shortlist and measurement protocol are shared; only the key differs.

    ``sharded`` switches both the analytic costing AND the measured
    protocol to the ZeRO split round (grad reduce-scatter + param
    all-gather), and grows the cache key with a sharding component —
    sharded and replicated plans never alias (same rule as overlap, new
    guard in ``tests/test_sharded.py``).
    """
    codecs = tuple(codecs)
    shortlist = analytic_shortlist(
        n, nbytes, codecs, params=params, top_k=top_k, sharded=sharded,
        ir_families=ir_families,
    )
    fp = backend_fingerprint()
    key = plan_cache_key(
        fp, f"n{n}", f"{nbytes}B", dtype, ",".join(codecs),
        "overlap" if overlap else "serial",
        "sharded" if sharded else "replicated",
    )
    path = _cache_path(cache_path)

    if use_cache and path:
        doc = _cache_load(path)
        hit = doc["entries"].get(key)
        if hit is not None and hit.get("fingerprint") == fp:
            return TunedPlan(
                n, nbytes, dtype,
                tuple(hit["widths"]), int(hit.get("lonely", 0)), hit["codec"],
                float(hit["predicted_us"]), float(hit["measured_us"]),
                source="cache",
                table=tuple(tuple(r) for r in hit.get("table", ())),
                # the no-alias guard: an IR-family winner is stored WITH
                # its family and can never round-trip as a tree widths
                # vector (tests/test_schedule_ir.py pins this)
                family=hit.get("family", "tree"),
                ports=int(hit.get("ports", 0)),
            )

    if timer is None:
        def timer(c, n_, nb, dt, rep, _sharded=sharded):
            return _default_timer(c, n_, nb, dt, rep, sharded=_sharded)
    measured_s = timer(shortlist, n, nbytes, dtype, repeat)
    if len(measured_s) != len(shortlist):
        raise ValueError(
            f"timer returned {len(measured_s)} times for "
            f"{len(shortlist)} candidates"
        )
    def _row_shape(shape):
        return shape.spec if isinstance(shape, IRFamilySpec) else shape

    table = tuple(
        (_row_shape(shape), lonely, codec, pred, t * 1e6)
        for (shape, lonely, codec, pred), t in zip(shortlist, measured_s)
    )
    best_i = min(range(len(shortlist)), key=lambda i: measured_s[i])
    shape, lonely, codec, pred = shortlist[best_i]
    if isinstance(shape, IRFamilySpec):
        family, widths, ports = shape.family, shape.widths, shape.ports
    else:
        family, widths, ports = "tree", shape, 0
    plan = TunedPlan(
        n, nbytes, dtype, widths, lonely, codec, pred,
        measured_s[best_i] * 1e6, source="measured", table=table,
        family=family, ports=ports,
    )
    if use_cache and path:
        doc = _cache_load(path)
        doc["entries"][key] = {
            "fingerprint": fp,
            "widths": list(widths),
            "lonely": lonely,
            "codec": codec,
            "family": family,
            "ports": ports,
            "predicted_us": pred,
            "measured_us": plan.measured_us,
            "table": [
                [list(w) if not isinstance(w, str) else w, l, c, p, m]
                for (w, l, c, p, m) in table
            ],
        }
        _cache_store(path, doc)
    return plan
