#!/usr/bin/env python
"""Gradient-bucketing A/B artifact: fused/chunked sync vs per-leaf sync.

Produces ``BENCH_BUCKETING.json`` — the commit-able evidence for the
bucketing tentpole (ISSUE 2): the many-small-leaves regime where per-leaf
sync pays k x the per-dispatch overhead, the single-large-tensor regime
where fusion must not regress, and the end-to-end ``train_step_ms`` A/B on
the 50-leaf transformer.  Run on the 8-virtual-device CPU mesh (same
protocol as tools/sweep_allreduce.py):

    python tools/bench_bucketing.py [--quick] [--out BENCH_BUCKETING.json]
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_BUCKETING.json"))
    ap.add_argument("--quick", action="store_true",
                    help="small sizes / few reps (smoke test)")
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

    from flextree_tpu.bench.harness import (
        GradSyncBenchConfig,
        TrainStepBenchConfig,
        run_grad_sync_bench,
        run_train_step_bench,
    )
    from flextree_tpu.utils.buildstamp import artifact_meta

    rep_sync = 5 if args.quick else 30
    rep_step = 3 if args.quick else 16
    t0 = time.time()
    results = {}

    # regime 1: many small leaves — the transformer bias/layernorm tail
    # (48 x 16 KB).  Per-leaf sync dispatches 48 collective sequences;
    # fused runs one per bucket.
    cfg = GradSyncBenchConfig(n_leaves=48, leaf_size=4096, repeat=rep_sync)
    print(f"== grad sync, many-small ({cfg.n_leaves} leaves) ...", flush=True)
    results["sync_many_small"] = run_grad_sync_bench(cfg)

    # regime 2: one large tensor (4 MB) — fusion has nothing to fuse and
    # must not regress; the chunked row is the pipelining A/B.
    cfg = GradSyncBenchConfig(
        n_leaves=1, leaf_size=(1 << 18) if args.quick else (1 << 20),
        repeat=rep_sync,
    )
    print("== grad sync, single-large ...", flush=True)
    results["sync_single_large"] = run_grad_sync_bench(cfg)

    # end-to-end: train_step_ms on the many-small-leaves transformer
    # (50 gradient leaves), pure-dp mesh — the production path A/B.
    tcfg = TrainStepBenchConfig(
        n_layers=2 if args.quick else 6, repeat=rep_step
    )
    print("== train step ...", flush=True)
    results["train_step"] = run_train_step_bench(tcfg)

    doc = {
        "description": "Bucketed/fused + chunk-pipelined FlexTree gradient "
                       "sync vs per-leaf sync (ISSUE 2 tentpole), 8 virtual "
                       "CPU devices; rows per regime: per_leaf, ours_fused, "
                       "ours_chunked (see flextree_tpu/bench/harness.py)",
        "build": artifact_meta(),
        "protocol": "time_jax_fn (compile excluded, block_until_ready gated) "
                    "on jitted shard_map'd sync_grads / make_train_step; "
                    "'identical' asserts the fused output (and the fused "
                    "step's updated params) are BITWISE equal to per-leaf; "
                    "sync_ms/compute_ms attribute the step via a sync-only "
                    "jit of the same gradient tree",
        "host": {
            "platform": platform.platform(),
            "cpus": os.cpu_count(),
            "note": "single-core host: virtual devices timeshare one core, "
                    "so per-collective dispatch overhead dominates small "
                    "collectives — the regime message fusion targets; real "
                    "ICI pipelining overlap (the chunked mode's target) is "
                    "NOT modeled by a serializing host",
        },
        "diagnosis": None,  # filled below from the measured rows
        "elapsed_s": None,
        "results": results,
    }

    small = results["sync_many_small"]["rows"]
    large = results["sync_single_large"]["rows"]
    step = results["train_step"]["rows"]
    doc["diagnosis"] = (
        f"Many-small-leaves sync: fused {small['ours_fused']['vs_per_leaf']:.2f}x "
        f"per-leaf ({small['per_leaf']['min_ms']:.2f} -> "
        f"{small['ours_fused']['min_ms']:.2f} ms, "
        f"{results['sync_many_small']['n_buckets']} bucket(s) for "
        f"{results['sync_many_small']['config']['n_leaves']} leaves) — the "
        "per-leaf path pays one collective dispatch sequence per leaf, the "
        "fused path one per bucket (CPU bucket cap 128 KiB: in-step cache "
        "locality, see bucketing.CPU_MAX_BUCKET_BYTES). Single-large-"
        f"tensor: fused {large['ours_fused']['vs_per_leaf']:.2f}x — with "
        "one leaf the two paths compile to the IDENTICAL program modulo "
        "op-name metadata (machine-checked: tests/test_bucketing.py::"
        "test_single_leaf_bucket_compiles_identically), so deviation from "
        "1.0 here is timeshared-host noise, not a fusion cost; chunked "
        f"{large['ours_chunked']['vs_per_leaf']:.2f}x (on this serializing "
        "1-core host chunking only adds dispatches; its overlap win needs "
        "real parallel fabric — see WINS.md). Train step (50 leaves): "
        f"per-leaf {step['per_leaf']['train_step_ms']:.1f} ms vs fused "
        f"{step['ours_fused']['train_step_ms']:.1f} ms "
        f"({step['ours_fused']['vs_per_leaf']:.2f}x), sync-only "
        f"{step['per_leaf']['sync_ms']:.1f} -> "
        f"{step['ours_fused']['sync_ms']:.1f} ms with bitwise-identical "
        "updated params."
    )
    doc["elapsed_s"] = round(time.time() - t0, 1)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out} ({doc['elapsed_s']}s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
