#!/usr/bin/env python
"""Back the zigzag "~2x" claim with per-hop critical-path accounting
— committed as ZIGZAG_ACCOUNTING.json.

The claim is about the SPMD critical path: at every ring hop all devices
advance in lockstep (the ppermute is a barrier), so the hop costs what the
slowest device's visibility branch costs.  This tool derives each device's
branch at each hop from the SAME predicates the kernels execute —
``zigzag.hop_branches`` for zigzag, the plain ring's
``src==idx -> diag / src<idx -> past / else future`` switch
(``ring_attention.py:184-187``) — converts branches to exact visible-FLOP
units, and sums the per-hop maxima.

Units: one full chunk-vs-chunk attention block = 1 (chunk = T/2n rows); a
plain-ring block is 2 chunks, so its full hop = 4 and its causal diagonal
= 2.  Exact closed form that falls out: plain critical path = 4n - 2,
zigzag = 2n, ratio = 2 - 1/n -> 2x as the ring grows.  Total executed
work (sum over devices) is IDENTICAL (2n^2) — zigzag rebalances the
causal triangle, it does not shrink it.

The tool also wall-clock-times both on the 8-virtual-device CPU mesh and
records the result with its caveat: this host has ONE physical core, so
the 8 "devices" serialize and wall-clock tracks *total* work — equal by
construction — not the critical path.  The wall-clock rows exist to show
the measurement was taken honestly, not to support the claim; silicon
with real parallel devices is where the critical path becomes wall time.

Usage: python tools/zigzag_accounting.py [--out ZIGZAG_ACCOUNTING.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def plain_branch(src: int, idx: int) -> str:
    # ring_attention.py:184-187, re-expressed on host ints
    return "diag" if src == idx else ("past" if src < idx else "future")


def schedule_tables(n: int) -> dict:
    """Per-hop, per-device visible-work units for both schedules, derived
    from the kernels' own branch predicates."""
    from flextree_tpu.parallel.zigzag import hop_branches

    # chunk-block units: full chunk-vs-chunk = 1, causal diagonal = 0.5
    UNIT = {"diag": 0.5, "past": 1.0, "future": 0.0}

    plain_hops = []   # each entry: list over devices of units (in chunk^2)
    zig_hops = []
    for s in range(n):
        p_row, z_row = [], []
        for idx in range(n):
            src = (idx - s) % n
            # plain ring: one (2-chunk x 2-chunk) block -> 4x chunk units
            p_row.append(4.0 * UNIT[plain_branch(src, idx)])
            # zigzag: early pair + late pair (hop_branches, the kernel's
            # exact predicate) + the always-full late-q-vs-early-k block
            br_e, br_l = hop_branches(src, idx)
            names = ["diag", "past", "future"]
            z_row.append(
                UNIT[names[int(br_e)]] + UNIT[names[int(br_l)]] + 1.0
            )
        plain_hops.append(p_row)
        zig_hops.append(z_row)

    plain_cp = sum(max(r) for r in plain_hops)
    zig_cp = sum(max(r) for r in zig_hops)
    plain_total = sum(sum(r) for r in plain_hops)
    zig_total = sum(sum(r) for r in zig_hops)
    return {
        "n": n,
        "plain_per_hop_units": plain_hops,
        "zigzag_per_hop_units": zig_hops,
        "plain_critical_path": plain_cp,
        "zigzag_critical_path": zig_cp,
        "critical_path_ratio": round(plain_cp / zig_cp, 4),
        "closed_form_ratio": round(2.0 - 1.0 / n, 4),
        "plain_total_work": plain_total,
        "zigzag_total_work": zig_total,
        "total_work_equal": plain_total == zig_total,
    }


def wall_clock_8vdev(t_total: int = 2048, reps: int = 6) -> dict:
    """Time both schedules on the 8-vdev CPU mesh (caveat applies)."""
    import time

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from flextree_tpu.parallel.ring_attention import ring_attention
    from flextree_tpu.parallel.zigzag import zigzag_ring_attention

    n = 8
    b, h, d = 1, 4, 64
    mesh = Mesh(np.array(jax.devices()[:n]), ("sp",))
    rng = np.random.default_rng(0)

    def mk():
        return jnp.asarray(
            rng.standard_normal((b, t_total, h, d)), dtype=jnp.float32
        )

    q, k, v = mk(), mk(), mk()
    spec = P(None, "sp", None, None)

    def timed(fn):
        f = jax.jit(
            jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                          check_vma=False)  # pallas_call outputs carry no
        )                                   # vma spec (see ulysses.py:74)
        jax.block_until_ready(f(q, k, v))  # compile
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f(q, k, v))
            ts.append(time.perf_counter() - t0)
        return {"min_s": min(ts), "avg_s": sum(ts) / len(ts), "reps": reps}

    rows = {}
    for impl in ("reference", "flash"):
        plain = timed(
            lambda q, k, v, impl=impl: ring_attention(
                q, k, v, "sp", causal=True, impl=impl)
        )
        zig = timed(
            lambda q, k, v, impl=impl: zigzag_ring_attention(
                q, k, v, "sp", impl=impl)
        )
        rows[impl] = {
            "plain_ring": plain,
            "zigzag": zig,
            "wall_ratio_plain_over_zigzag": round(
                plain["min_s"] / zig["min_s"], 3
            ),
        }
    return {
        "shape": f"b{b}_t{t_total}_h{h}_d{d}_f32_8vdev",
        "impls": rows,
        "reading": {
            "reference": "plain ring's jnp impl computes EVERY hop densely "
            "and masks (uniform SPMD schedule, ring_attention.py step); "
            "zigzag's lax.switch skips future chunks — so this ratio "
            "measures the ~2x TOTAL-work difference between dense-masked "
            "and switch-skipped schedules, which a serialized 1-core host "
            "CAN see",
            "flash": "both sides switch-skip masked hops, so total work is "
            "equal and a 1-core host (devices serialize) should show ~1.0 "
            "regardless of balance — the balance win is a CRITICAL-PATH "
            "effect that needs genuinely parallel devices; see the "
            "schedules tables for that accounting",
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "ZIGZAG_ACCOUNTING.json"))
    ap.add_argument("--skip-wallclock", action="store_true")
    args = ap.parse_args()

    # CPU pinning must precede ANY backend touch (hop_branches calls jnp)
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

    tables = {f"n={n}": schedule_tables(n) for n in (2, 4, 8, 16)}
    doc = {
        "description": "Zigzag vs plain causal ring attention: per-hop "
        "critical-path accounting derived from the kernels' own branch "
        "predicates (zigzag.hop_branches / ring_attention.py:184-187). "
        "Units: full chunk-vs-chunk attention = 1 (chunk = T/2n rows). "
        "Ratio = 2 - 1/n; total executed FLOPs identical.",
        "schedules": tables,
        "headline": {
            "critical_path_ratio_n8": tables["n=8"]["critical_path_ratio"],
            "asymptote": 2.0,
        },
    }
    if not args.skip_wallclock:
        doc["wall_clock_1core_host"] = wall_clock_8vdev()
    try:
        from flextree_tpu.utils.buildstamp import artifact_meta

        doc["build"] = artifact_meta()
    except Exception:
        pass
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    hl = doc["headline"]
    print(f"critical-path ratio at n=8: {hl['critical_path_ratio_n8']}")
    if "wall_clock_1core_host" in doc:
        for impl, row in doc["wall_clock_1core_host"]["impls"].items():
            print(f"wall ratio [{impl}] (1-core caveat): "
                  f"{row['wall_ratio_plain_over_zigzag']}")
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
