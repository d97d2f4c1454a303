#!/usr/bin/env python
"""Quantized-collective A/B artifact: wire codecs vs the fused f32 sync.

Produces ``BENCH_QUANT.json`` — the committed evidence for the
compression tentpole (ISSUE 5), machine-checked with a non-zero exit on
any violation:

1. **Cross-process rows (the headline)**: a 2-process gloo cluster on
   this host (1 virtual device per process — the same production
   ``init_distributed`` bring-up as ``tools/multiproc_bringup.py``), so
   every collective byte genuinely crosses a process boundary through
   loopback TCP.  This is the regime wire compression exists for: the
   wire is real, and fewer bytes are honestly less time.  Rows time the
   production ``compressed_allreduce`` per codec (f32 identity / bf16 /
   int8) at 1/4/16 MB per device with the shuffled-interleaved rep
   protocol.  Checks: int8 >= 1.3x the fused-f32 row at the largest
   bucket, measured error within the documented codec bound, identity
   row bitwise-equal to the uncompressed allreduce.
2. **In-process rows (the honest caveat)**: the same A/B on the
   8-virtual-device single-process mesh every test uses.  There the
   "wire" is a memcpy inside one address space running at memory
   bandwidth, while quantize/dequantize passes compete for the same
   cores — compression CANNOT win there and the artifact says so, with
   numbers (same honesty contract as WINS.md's bucketing blind spot).

Usage: python tools/bench_quantize.py [--quick] [--out BENCH_QUANT.json]
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NUM_PROCESSES = 2
#: per-device f32 element counts: 1 MB, 4 MB, 16 MB (largest = headline)
SIZES = (1 << 18, 1 << 20, 1 << 22)
QUICK_SIZES = (1 << 18, 1 << 20)
CODECS = ("f32", "bf16", "int8")
MIN_INT8_SPEEDUP = 1.3  # the ISSUE-5 acceptance floor, largest bucket


def child_main(sizes, repeat) -> int:
    """One rank of the 2-process world (``--child``): time every codec row
    interleaved, verify numerics, emit JSON on rank 0."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import random

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flextree_tpu.ops.quantize import get_codec
    from flextree_tpu.parallel.allreduce import allreduce
    from flextree_tpu.parallel.compressed import compressed_allreduce
    from flextree_tpu.parallel.launch import (
        ClusterConfig,
        flatten_mesh,
        hybrid_mesh,
        init_distributed,
    )

    init_distributed(ClusterConfig.from_env())
    pid = jax.process_index()
    n = jax.device_count()
    mesh = hybrid_mesh(ici_shape=(1,), dcn_shape=(NUM_PROCESSES,))
    fmesh = flatten_mesh(mesh)
    sharding = NamedSharding(fmesh, P("ft"))
    topo = str(n)  # flat tree: one grouped exchange per phase

    def smap(fn):
        return jax.jit(
            jax.shard_map(
                fn, mesh=fmesh, in_specs=P("ft"), out_specs=P("ft"),
                check_vma=False,
            )
        )

    results = {}
    for size in sizes:
        # rank r data = seeded f(r): every child can reconstruct the
        # exact global sum without fetching non-addressable shards
        def rank_rows(r):
            return np.random.default_rng(1000 + r).standard_normal(size).astype(
                np.float32
            )

        local = rank_rows(pid)[None]
        arr = jax.make_array_from_process_local_data(
            sharding, local.reshape(-1), (n * size,)
        )
        exact = sum(rank_rows(r).astype(np.float64) for r in range(n))
        amax = max(float(np.abs(rank_rows(r)).max()) for r in range(n))

        fns = {
            "plain_f32": smap(lambda v: allreduce(v, "ft", topo=topo)),
        }
        for codec in CODECS:
            fns[codec] = smap(
                lambda v, codec=codec: compressed_allreduce(
                    v, "ft", topo=topo, codec=codec, step=0
                )
            )
        outs = {k: jax.block_until_ready(fn(arr)) for k, fn in fns.items()}

        # numerics on the local shard (the only addressable piece; the
        # allreduce result is replicated, so every shard IS the global sum)
        shard = {
            k: np.asarray(v.addressable_shards[0].data) for k, v in outs.items()
        }
        checks = {
            "identity_bitwise": bool(
                shard["f32"].tobytes() == shard["plain_f32"].tobytes()
            )
        }
        for codec in ("bf16", "int8"):
            c = get_codec(codec)
            bound = c.error_bound(amax, n, (n,)) + 1e-5
            err = float(np.abs(shard[codec].astype(np.float64) - exact).max())
            checks[f"{codec}_max_err"] = err
            checks[f"{codec}_bound"] = bound
            checks[f"{codec}_within_bound"] = bool(err <= bound)
        checks["f32_exact"] = bool(
            np.allclose(
                shard["f32"].astype(np.float64), exact, rtol=1e-5, atol=1e-5
            )
        )

        # shuffled-interleaved timing; the shuffle seed is shared so both
        # ranks run the identical order (collectives must stay matched
        # across the process boundary)
        times = {k: [] for k in fns}
        order = list(fns)
        shuf = random.Random(0)
        for _ in range(repeat):
            shuf.shuffle(order)
            for k in order:
                t0 = time.perf_counter()
                jax.block_until_ready(fns[k](arr))
                times[k].append(time.perf_counter() - t0)
        rows = {
            k: {"min_ms": min(ts) * 1e3, "avg_ms": sum(ts) / len(ts) * 1e3}
            for k, ts in times.items()
        }
        for codec in CODECS:
            rows[codec]["vs_fused_f32"] = rows["f32"]["min_ms"] / rows[codec]["min_ms"]
        results[str(size * 4)] = {"rows": rows, "checks": checks}
        if pid == 0:
            print(
                f"[quant x-proc] {size * 4 >> 20}MB/device: "
                + " ".join(
                    f"{c}={rows[c]['min_ms']:.1f}ms({rows[c]['vs_fused_f32']:.2f}x)"
                    for c in CODECS
                ),
                flush=True,
            )
    if pid == 0:
        print("RESULT_JSON: " + json.dumps(results), flush=True)
    return 0


def run_cluster(sizes, repeat, timeout_s=900) -> dict:
    """Spawn the 2-process world and collect rank 0's results."""
    with socket.socket() as s:  # a free loopback port for the coordinator
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env_base = dict(os.environ)
    env_base.pop("JAX_PLATFORMS", None)
    procs = []
    for rank in range(NUM_PROCESSES):
        env = dict(
            env_base,
            FT_COORDINATOR=f"127.0.0.1:{port}",
            FT_NUM_PROCESSES=str(NUM_PROCESSES),
            FT_PROCESS_ID=str(rank),
        )
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, os.path.abspath(__file__), "--child",
                    "--sizes", ",".join(map(str, sizes)),
                    "--repeat", str(repeat),
                ],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout_s)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    if any(p.returncode != 0 for p in procs):
        tail = "\n".join(o[-1500:] for o in outs)
        raise RuntimeError(f"cluster child failed:\n{tail}")
    for line in outs[0].splitlines():
        if line.startswith("RESULT_JSON: "):
            return json.loads(line[len("RESULT_JSON: "):])
    raise RuntimeError(f"no RESULT_JSON from rank 0:\n{outs[0][-1500:]}")


def run_in_process(quick: bool) -> dict:
    """The honest single-process rows: same A/B on the 8-vdev mesh."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    from flextree_tpu.bench.harness import GradSyncBenchConfig, run_grad_sync_bench

    cfg = GradSyncBenchConfig(
        n_leaves=1,
        leaf_size=(1 << 18) if quick else (1 << 20),
        repeat=8 if quick else 16,
        codecs=("bf16", "int8"),
    )
    return run_grad_sync_bench(cfg)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_QUANT.json"))
    ap.add_argument("--quick", action="store_true",
                    help="small sizes / few reps (smoke test)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--sizes", type=str, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--repeat", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    sizes = QUICK_SIZES if args.quick else SIZES
    repeat = 4 if args.quick else 8
    if args.child:
        sizes = tuple(int(s) for s in args.sizes.split(","))
        return child_main(sizes, args.repeat)

    t0 = time.time()
    print(f"== cross-process rows ({NUM_PROCESSES}-proc gloo cluster) ...",
          flush=True)
    xproc = run_cluster(sizes, repeat)
    print("== in-process rows (8 vdev, one address space) ...", flush=True)
    inproc = run_in_process(args.quick)

    largest = str(max(sizes) * 4)
    head = xproc[largest]
    violations = []
    int8_speedup = head["rows"]["int8"]["vs_fused_f32"]
    if int8_speedup < MIN_INT8_SPEEDUP and not args.quick:
        # --quick caps the largest bucket at 4 MB where the byte savings
        # cannot yet dominate the fixed exchange cost; the committed
        # artifact is always a full run, where the floor is enforced
        violations.append(
            f"int8 vs fused-f32 at largest bucket = {int8_speedup:.2f}x "
            f"< required {MIN_INT8_SPEEDUP}x"
        )
    for size_key, sec in xproc.items():
        ck = sec["checks"]
        for key in ("identity_bitwise", "f32_exact", "bf16_within_bound",
                    "int8_within_bound"):
            if not ck[key]:
                violations.append(f"{size_key}B: check {key} failed")

    doc = {
        "description": "Wire-codec A/B for the FlexTree collectives "
                       "(ISSUE 5 tentpole): production compressed_allreduce "
                       "(f32 identity / bf16 / int8 block-scaled) vs the "
                       "fused f32 collective",
        "protocol": {
            "cross_process": f"{NUM_PROCESSES} processes x 1 virtual CPU "
                             "device, production init_distributed + gloo "
                             "(tools/multiproc_bringup.py bring-up); every "
                             "collective byte crosses a process boundary; "
                             "shuffled-interleaved reps (shared shuffle "
                             "seed so ranks stay matched), min-of-reps",
            "in_process": "8 virtual devices in one address space "
                          "(run_grad_sync_bench, single 4MB leaf): the "
                          "'wire' is a memcpy at memory bandwidth and "
                          "encode/decode competes for the same cores — "
                          "included as the honest negative control",
            "checks": f"int8 >= {MIN_INT8_SPEEDUP}x fused f32 at the "
                      "largest cross-process bucket; identity codec "
                      "bitwise-equal to the uncompressed allreduce; "
                      "bf16/int8 error within Codec.error_bound; non-zero "
                      "exit on any violation",
        },
        "host": {
            "platform": platform.platform(),
            "cpus": os.cpu_count(),
        },
        "cross_process": xproc,
        "in_process": {
            "rows": inproc["rows"],
            "num_devices": inproc["num_devices"],
            "total_mb": inproc["total_mb"],
        },
        "headline": {
            "bucket_bytes": int(largest),
            "int8_vs_fused_f32": round(int8_speedup, 3),
            "bf16_vs_fused_f32": round(
                head["rows"]["bf16"]["vs_fused_f32"], 3
            ),
            "int8_max_err": head["checks"]["int8_max_err"],
            "int8_bound": head["checks"]["int8_bound"],
        },
        "violations": violations,
        "elapsed_s": round(time.time() - t0, 1),
    }
    doc["diagnosis"] = (
        f"Across a real process boundary (gloo/TCP wire) the int8 "
        f"block-scaled codec syncs the largest bucket "
        f"{int8_speedup:.2f}x faster than the fused f32 collective "
        f"(bf16: {doc['headline']['bf16_vs_fused_f32']:.2f}x), with max "
        f"error {head['checks']['int8_max_err']:.4f} inside the documented "
        f"bound {head['checks']['int8_bound']:.4f}. In-process on the "
        f"8-vdev mesh the same codecs measure "
        f"{inproc['rows']['ours_fused_int8']['vs_per_leaf'] / inproc['rows']['ours_fused']['vs_per_leaf']:.2f}x "
        f"the fused f32 sync: a single-address-space 'wire' is a memcpy "
        f"at memory bandwidth, so quantize/dequantize passes cost more "
        f"than the bytes they save — compression pays exactly where the "
        f"wire is real, which is the deployment regime (the paper's MPI "
        f"cluster, multi-host TPU DCN)."
    )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out} ({doc['elapsed_s']}s)")
    if violations:
        print("MACHINE-CHECK VIOLATIONS:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    print(f"checks passed: int8 {int8_speedup:.2f}x >= {MIN_INT8_SPEEDUP}x "
          f"at {int(largest) >> 20}MB, errors within bounds, identity bitwise")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
