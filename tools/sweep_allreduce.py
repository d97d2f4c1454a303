#!/usr/bin/env python
"""Allreduce A/B sweep over the BASELINE.md config matrix.

Runs every BASELINE.md config (4/8/16/64/60 ranks) as a virtual-CPU-device
mesh A/B — FlexTree topologies vs ``lax.psum`` — and writes
``BENCH_ALLREDUCE.json``.  This is the rebuild of the reference's per-run
result files workflow (``benchmark.cpp:193-213``): the reference wrote one
``{tag}.{N}.{size}.{topo}...txt`` per run; this writes the aggregate.  It
is a CPU measurement of the schedules' control flow, not of the chip: the
file is git-ignored, and the collective's speed is what ``chip_smoke.py``
and the benchmark's four-chip cell read.

Each rank count runs in a subprocess because ``jax_num_cpu_devices`` must be
set before the backend initializes.  Timing protocol: in-place chained loop
with buffer donation (the reference benchmark's ``MPI_IN_PLACE`` compounding
loop, ``benchmark.cpp:149-159``); the psum baseline takes the best of its
donated and non-donated variants (see ``bench/harness.py``).

Usage:  python tools/sweep_allreduce.py [--out BENCH_ALLREDUCE.json] [--quick]
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MB = 1 << 20  # bytes; element counts below are float32 (4 B)


def config_matrix(quick: bool) -> list[dict]:
    """The BASELINE.md configs + size sweeps at 8/16 ranks.

    Config 4 is scaled from 1 GB/rank to 16 MB/rank: 64 ranks x 1 GB = 64 GB
    of live buffers does not fit a single-core CI host's memory/time budget;
    the scaled config keeps the same rank count and topology, which is what
    exercises the 2-level schedule.
    """
    cfgs = [
        dict(name="cfg1_ring_4r_1MB", ranks=4, size_mb=1, repeat=10,
             primary="1", topos=["1", "4", "2,2"],
             baseline_ref="BASELINE.md config 1: flat ring allreduce, 1MB, 4 ranks"),
        dict(name="cfg2_hd_8r_64MB", ranks=8, size_mb=64, repeat=5,
             primary="2,2,2", topos=["2,2,2", "8", "4,2"],
             baseline_ref="BASELINE.md config 2: recursive halving-doubling, 64MB, 8 ranks"),
        dict(name="cfg3_planner_16r_256MB", ranks=16, size_mb=256, repeat=3,
             primary="planner", topos=["planner", "16", "4,4", "8,2"],
             baseline_ref="BASELINE.md config 3: cost-model k-ary tree, 256MB, 16 ranks"),
        dict(name="cfg4_hier_64r_16MB", ranks=64, size_mb=16, repeat=3,
             primary="8,8", topos=["8,8", "64", "4,4,4"],
             baseline_ref="BASELINE.md config 4: 2-level hierarchical, 64 ranks "
                          "(payload scaled 1GB->16MB/rank for the 1-core CI host)"),
        dict(name="cfg5_np2_60r_4MB", ranks=60, size_mb=4, repeat=5,
             primary="planner", topos=["planner", "60", "4,15", "5,12", "3,4,5"],
             baseline_ref="BASELINE.md config 5: non-power-of-2 world size (60 ranks)"),
        dict(name="cfg6_prime_7r_4MB", ranks=7, size_mb=4, repeat=10,
             primary="planner", topos=["planner", "7", "1", "6+1", "3,2+1"],
             baseline_ref="prime world size: flat/ring vs EXECUTABLE lonely "
                          "shapes (the reference's disabled +1 design; "
                          "tests/test_lonely.py) — expected ordering on a "
                          "uniform 1-core fabric: flat > lonely (2 extra "
                          "full-payload hops), per the cost model"),
        # size sweeps: where is the crossover vs psum?
        dict(name="sweep_8r", ranks=8, size_mb=[1, 4, 16, 64], repeat=5,
             primary="8", topos=["8", "4,2", "2,2,2"],
             baseline_ref="size sweep, 8 ranks"),
        dict(name="sweep_16r", ranks=16, size_mb=[1, 4, 16, 64], repeat=5,
             primary="16", topos=["16", "4,4"],
             baseline_ref="size sweep, 16 ranks"),
    ]
    if quick:
        for c in cfgs:
            if isinstance(c["size_mb"], list):
                c["size_mb"] = c["size_mb"][:2]
            c["size_mb"] = (min(c["size_mb"], 4)
                            if isinstance(c["size_mb"], int) else c["size_mb"])
            c["repeat"] = min(c["repeat"], 3)
    return cfgs


def run_child(cfg: dict) -> list[dict]:
    """Run one rank-count config in a subprocess; returns its result rows."""
    payload = json.dumps(cfg)
    code = (
        "import sys, json\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from tools.sweep_allreduce import child_main\n"
        f"child_main(json.loads({payload!r}))\n"
    )
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("FT_TOPO", None)
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, env=env, timeout=3600,
    )
    rows = []
    for line in p.stdout.splitlines():
        if line.startswith("ROW "):
            rows.append(json.loads(line[4:]))
    if p.returncode != 0 and not rows:
        rows.append({"config": cfg["name"], "error": p.stderr[-2000:]})
    return rows


def child_main(cfg: dict) -> None:
    """Subprocess body: set up the virtual mesh, run the A/B, print rows."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", int(cfg["ranks"]))
    import logging

    logging.disable(logging.INFO)
    from flextree_tpu.bench import BenchConfig, measure_points, run_allreduce_bench
    from flextree_tpu.planner import choose_topology, fit_cost_params

    n = int(cfg["ranks"])
    # calibrate the cost model on THIS host before asking the planner —
    # the r02 sweep ranked with the invented v5e defaults, so its "planner"
    # row predicted ICI behavior on a 1-core host
    cal_params = None
    if "planner" in cfg["topos"]:
        cal_topos = [t for t in cfg["topos"] if t != "planner"]
        if n <= 16:
            cal_topos.append("1")
        try:
            pts = measure_points(
                cal_topos, [1 << 14, 1 << 17], repeat=8, devices=n
            )
            cal_params = fit_cost_params(pts)
        except Exception as e:  # noqa: BLE001 — degenerate fit -> defaults
            print(f"calibration failed ({e}); planner uses defaults",
                  flush=True)
    sizes = cfg["size_mb"] if isinstance(cfg["size_mb"], list) else [cfg["size_mb"]]
    for size_mb in sizes:
        elems = size_mb * MB // 4
        base = run_allreduce_bench(
            BenchConfig(size=elems, repeat=cfg["repeat"], comm_type="xla")
        )
        rows = {
            "config": cfg["name"], "ranks": n, "size_mb": size_mb,
            "baseline_ref": cfg["baseline_ref"], "primary_topo": cfg["primary"],
            "psum_min_ms": round(base.result.min_s * 1e3, 3),
            "psum_bus_GBps": round(base.bus_bw_GBps, 3),
            "topos": {},
        }
        for topo in cfg["topos"]:
            spec = topo
            if topo == "planner":
                kw = {"params": cal_params} if cal_params is not None else {}
                plan = choose_topology(n, elems * 4, **kw)
                spec = plan.to_ft_topo()
            rep = run_allreduce_bench(
                BenchConfig(size=elems, repeat=cfg["repeat"],
                            comm_type="flextree", topo=spec)
            )
            rows["topos"][topo] = {
                "widths": rep.topo,
                "min_ms": round(rep.result.min_s * 1e3, 3),
                "bus_GBps": round(rep.bus_bw_GBps, 3),
                "vs_psum": round(rep.bus_bw_GBps / rows["psum_bus_GBps"], 3)
                if rows["psum_bus_GBps"] else 0.0,
                "correct": rep.correct,
            }
        best = max(rows["topos"], key=lambda t: rows["topos"][t]["bus_GBps"])
        rows["best_topo"] = best
        rows["best_vs_psum"] = rows["topos"][best]["vs_psum"]
        print("ROW " + json.dumps(rows), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_ALLREDUCE.json"))
    ap.add_argument("--quick", action="store_true",
                    help="small sizes / few reps (smoke test)")
    args = ap.parse_args()

    t0 = time.time()
    all_rows: list[dict] = []
    for cfg in config_matrix(args.quick):
        print(f"== {cfg['name']} (ranks={cfg['ranks']}) ...", flush=True)
        rows = run_child(cfg)
        for r in rows:
            all_rows.append(r)
            if "error" in r:
                print(f"   ERROR: {r['error'][:300]}", flush=True)
            else:
                print(
                    f"   {r['ranks']}r {r['size_mb']}MB: best {r['best_topo']} "
                    f"= {r['best_vs_psum']}x psum "
                    f"({r['topos'][r['best_topo']]['bus_GBps']} vs "
                    f"{r['psum_bus_GBps']} GB/s)",
                    flush=True,
                )
    from flextree_tpu.utils.buildstamp import artifact_meta

    doc = {
        "description": "FlexTree allreduce vs lax.psum, BASELINE.md config "
                       "matrix on virtual CPU-device meshes (the reference's "
                       "--comm-type A/B, benchmark.cpp:147-174)",
        "build": artifact_meta(),
        "protocol": "in-place chained timing with buffer donation on the "
                    "flextree side; psum baseline takes best of donated and "
                    "non-donated (see flextree_tpu/bench/harness.py)",
        "host": {
            "platform": platform.platform(),
            "cpus": os.cpu_count(),
            "note": "single-core host: virtual devices timeshare one core, so "
                    "per-collective launch overhead and total memory traffic "
                    "dominate; ICI bandwidth effects are not modeled here",
        },
        "diagnosis": "On a 1-core host cost is monotone in collective-stage "
            "count (each stage = one more serialized N-vdev dispatch + one "
            "more full memory pass), so flat-loses-to-psum and "
            "ring-loses-worst is the expected ordering, not a FlexTree "
            "defect. Root-cause floor measurements and the ICI/DCN win "
            "case: WINS.md ('Why the single-host benchmark cannot show "
            "this') and tests/test_planner_wins.py. The 'planner' rows "
            "here use host-calibrated cost params (fit_cost_params on "
            "small measured points).",
        "elapsed_s": None,  # filled below
        "results": all_rows,
    }
    doc["elapsed_s"] = round(time.time() - t0, 1)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out} ({doc['elapsed_s']}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
