#!/usr/bin/env python
"""ZeRO-1 sharded-optimizer A/B artifact: the split collectives + sharded
step vs the replicated fused f32 baseline, on a REAL 2-process gloo wire.

Produces ``BENCH_SHARDED.json`` — the committed evidence for the PR 7
tentpole, machine-checked with a non-zero exit on any violation:

1. **Wire bytes (the acceptance floor)**: per-chip collective wire bytes
   of the lowered train-step programs, counted from the StableHLO by
   ``analysis.hlo_lint.collective_wire_bytes`` (loop-free flat plan, so
   the static count is exact).  Floor: the sharded-quantized (int8) step
   moves <= 0.6x the bytes of the replicated fused f32 step.  The f32
   sharded step is asserted EXACTLY 1.0x — same wire, relocated seam —
   which is the honest statement of where sharding alone does and does
   not save bytes (docs/SHARDED.md).
2. **In-run bitwise**: the f32 sharded step's updated parameters after
   several steps are bitwise-equal to the replicated step's, computed on
   the live 2-process cluster.
3. **Per-rank optimizer-state memory**: measured from the LIVE device
   buffers (``addressable_shards[0].data.nbytes`` summed over the moment
   entries), asserted ~ 1/N of the replicated layout (tails stay
   replicated, so the measured ratio sits a hair above 1/N — the analytic
   expectation from ``zero.zero_shard_bytes`` is checked too).
4. **Sync wall-clock**: the split sync round (grad reduce-scatter + param
   all-gather, both wires quantized) vs the fused f32 allreduce at 4/16
   MB per device, shuffled-interleaved reps over the real TCP wire.
   Floor: int8 sharded sync >= 1.3x the f32 fused sync at the largest
   bucket (the same regime BENCH_QUANT.json proved for the fused codec
   path — the sharded seam keeps that win while also halving optimizer
   memory).
5. **Step time**: the full jitted steps timed on the cluster — reported,
   with NO-REGRESSION guards rather than win floors (f32 <= 2.2x, int8
   <= 3.0x the replicated step; see the guard constants for why).  The
   wire win is rows 1 and 4; the artifact's honesty note says exactly
   that (same contract as BENCH_QUANT.json's in-process negative
   control).

Usage: python tools/bench_sharded.py [--quick] [--out BENCH_SHARDED.json]
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
SYNC_SIZES = (1 << 20, 1 << 22)  # f32 elements/device: 4 MB, 16 MB
QUICK_SYNC_SIZES = (1 << 18,)
MAX_WIRE_RATIO = 0.6  # acceptance floor: sharded-int8 vs replicated-f32 bytes
MIN_INT8_SYNC_SPEEDUP = 1.3  # largest bucket, real wire
#: step-time NO-REGRESSION guards, not wins: the tiny bench model's step
#: is compute-dominated on this 1-core host, and the sharded step pays
#: real in-step host work the wire savings cannot buy back there — the
#: block-interleaved bucket pack/unpack is a strided copy of the full
#: gradient (measured ~1.8x on the f32 step here, where an accelerator
#: runs the same reshapes as fused HBM-bound ops dwarfed by the
#: matmuls), and int8 additionally pays encode/decode compute on the
#: same core that runs the model.  The honest wins are the wire-byte and
#: sync-time rows; these bounds exist so a catastrophic step regression
#: cannot ship behind them.
MAX_STEP_SLOWDOWN_F32 = 2.2
MAX_STEP_SLOWDOWN_INT8 = 3.0


def _leaf_device_bytes(tree) -> int:
    import jax

    total = 0
    for l in jax.tree.leaves(tree):
        shards = getattr(l, "addressable_shards", None)
        total += shards[0].data.nbytes if shards else l.nbytes
    return total


def child_main(sync_sizes, repeat, steps_n) -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 1)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import random

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flextree_tpu.analysis.hlo_lint import collective_wire_bytes
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.parallel.allreduce import all_gather, allreduce, reduce_scatter
    from flextree_tpu.parallel.launch import (
        ClusterConfig,
        flatten_mesh,
        hybrid_mesh,
        init_distributed,
    )
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_train_step,
    )
    from jax.sharding import Mesh

    init_distributed(ClusterConfig.from_env())
    pid = jax.process_index()
    n = jax.device_count()
    fmesh = flatten_mesh(hybrid_mesh(ici_shape=(1,), dcn_shape=(NUM_PROCESSES,)))
    sharding = NamedSharding(fmesh, P("ft"))
    topo = str(n)

    # ---- 1+2+3+5: the train steps on a (dp=n, sp=1, tp=1) mesh ----------
    mesh = Mesh(fmesh.devices.reshape(n, 1, 1), ("dp", "sp", "tp"))
    model_cfg = TransformerConfig(
        vocab_size=2048, d_model=128, n_heads=4, n_layers=4, d_ff=512
    )
    variants = {
        "replicated_f32": TrainConfig(grad_topo=topo),
        "sharded_f32": TrainConfig(grad_topo=topo, shard_optimizer=True),
        "sharded_int8": TrainConfig(
            grad_topo=topo, shard_optimizer=True, codec="int8"
        ),
    }
    rng = np.random.default_rng(0)
    tok_local = rng.integers(0, 2048, (2, 64)).astype(np.int32)
    toks = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp", None)), tok_local, (2 * n, 64)
    )

    steps, states, lowered = {}, {}, {}
    for name, tc in variants.items():
        st = init_train_state(jax.random.PRNGKey(0), model_cfg, tc, mesh=mesh)
        step = make_train_step(mesh, model_cfg, tc)
        state_sds = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), st
        )
        lowered[name] = step.lower(state_sds, toks, toks).as_text()
        states[name] = st
        steps[name] = step

    # wire bytes, statically from the lowered programs (flat plan: loop-free)
    wire = {k: collective_wire_bytes(ir) for k, ir in lowered.items()}
    wire_ratio_int8 = wire["sharded_int8"]["total"] / wire["replicated_f32"]["total"]
    wire_ratio_f32 = wire["sharded_f32"]["total"] / wire["replicated_f32"]["total"]

    # run the steps: bitwise in-run check + timing
    outs = {}
    for name in variants:
        st = states[name]
        for _ in range(steps_n):
            st, m = jax.block_until_ready(steps[name](st, toks, toks))
        outs[name] = st

    # per-rank optimizer-state bytes, from the LIVE post-step buffers (the
    # step outputs carry the real shard_map out-shardings; the host-side
    # init state does not)
    def opt_bytes(name):
        st = outs[name]
        keys = (
            ("mu", "nu")
            if name == "replicated_f32"
            else tuple(k for k in st if k.startswith(("mu_", "nu_", "master_")))
        )
        return sum(_leaf_device_bytes(st[k]) for k in keys)

    opt = {name: opt_bytes(name) for name in variants}

    def params_bytes_of(name):
        return b"".join(
            np.asarray(l.addressable_shards[0].data).tobytes()
            for l in jax.tree.leaves(outs[name]["params"])
        )

    bitwise = params_bytes_of("sharded_f32") == params_bytes_of("replicated_f32")

    times = {k: [] for k in variants}
    order = list(variants)
    shuf = random.Random(0)
    fresh = {k: states[k] for k in variants}
    for _ in range(repeat):
        shuf.shuffle(order)
        for k in order:
            t0 = time.perf_counter()
            jax.block_until_ready(steps[k](fresh[k], toks, toks))
            times[k].append(time.perf_counter() - t0)
    step_rows = {
        k: {"min_ms": min(ts) * 1e3, "avg_ms": sum(ts) / len(ts) * 1e3}
        for k, ts in times.items()
    }

    # ---- 4: the sync round alone, on grad-sized flat buffers -------------
    def smap(fn):
        return jax.jit(
            jax.shard_map(
                fn, mesh=fmesh, in_specs=P("ft"), out_specs=P("ft"),
                check_vma=False,
            )
        )

    sync_rows = {}
    for size in sync_sizes:
        local = np.random.default_rng(1000 + pid).standard_normal(size).astype(
            np.float32
        )
        arr = jax.make_array_from_process_local_data(
            sharding, local[None].reshape(-1), (n * size,)
        )
        fns = {
            "fused_f32": smap(lambda v: allreduce(v, "ft", topo=topo)),
            "sharded_f32": smap(
                lambda v: all_gather(
                    reduce_scatter(v, "ft", topo=topo), "ft", topo=topo,
                    out_shape=v.shape,
                )
            ),
            "sharded_int8": smap(
                lambda v: all_gather(
                    reduce_scatter(v, "ft", topo=topo, codec="int8", step=0),
                    "ft", topo=topo, out_shape=v.shape, codec="int8", step=0,
                )
            ),
        }
        for fn in fns.values():
            jax.block_until_ready(fn(arr))
        t = {k: [] for k in fns}
        order2 = list(fns)
        for _ in range(repeat):
            shuf.shuffle(order2)
            for k in order2:
                t0 = time.perf_counter()
                jax.block_until_ready(fns[k](arr))
                t[k].append(time.perf_counter() - t0)
        rows = {
            k: {"min_ms": min(ts) * 1e3, "avg_ms": sum(ts) / len(ts) * 1e3}
            for k, ts in t.items()
        }
        for k in ("sharded_f32", "sharded_int8"):
            rows[k]["vs_fused_f32"] = rows["fused_f32"]["min_ms"] / rows[k]["min_ms"]
        sync_rows[str(size * 4)] = rows
        if pid == 0:
            print(
                f"[sharded x-proc] {size * 4 >> 20}MB/device sync: "
                + " ".join(
                    f"{k}={rows[k]['min_ms']:.1f}ms" for k in rows
                ),
                flush=True,
            )

    if pid == 0:
        print(
            "RESULT_JSON: "
            + json.dumps(
                {
                    "wire_bytes": wire,
                    "wire_ratio_int8": wire_ratio_int8,
                    "wire_ratio_f32": wire_ratio_f32,
                    "opt_state_bytes": opt,
                    "bitwise_f32": bool(bitwise),
                    "step_rows": step_rows,
                    "sync_rows": sync_rows,
                    "n": n,
                }
            ),
            flush=True,
        )
    return 0


def run_cluster(sync_sizes, repeat, steps_n, timeout_s=1800) -> dict:
    with socket.socket() as s:
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
                    "--sizes", ",".join(map(str, sync_sizes)),
                    "--repeat", str(repeat), "--steps", str(steps_n),
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_SHARDED.json"))
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--sizes", type=str, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--repeat", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--steps", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    sync_sizes = QUICK_SYNC_SIZES if args.quick else SYNC_SIZES
    repeat = 4 if args.quick else 8
    steps_n = 2 if args.quick else 3
    if args.child:
        return child_main(
            tuple(int(s) for s in args.sizes.split(",")), args.repeat, args.steps
        )

    t0 = time.time()
    print(f"== sharded A/B ({NUM_PROCESSES}-proc gloo cluster) ...", flush=True)
    res = run_cluster(sync_sizes, repeat, steps_n)
    n = res["n"]

    violations = []
    if not res["bitwise_f32"]:
        violations.append("f32 sharded step NOT bitwise-equal to replicated")
    if res["wire_ratio_int8"] > MAX_WIRE_RATIO:
        violations.append(
            f"sharded-int8 wire bytes = {res['wire_ratio_int8']:.3f}x "
            f"replicated f32 > required {MAX_WIRE_RATIO}x"
        )
    if abs(res["wire_ratio_f32"] - 1.0) > 1e-6:
        violations.append(
            f"sharded-f32 wire ratio {res['wire_ratio_f32']:.6f} != 1.0 "
            f"(the seam must relocate bytes, not change them)"
        )
    opt_ratio = (
        res["opt_state_bytes"]["sharded_f32"]
        / res["opt_state_bytes"]["replicated_f32"]
    )
    # tails stay replicated, so the measured ratio sits a hair above 1/N
    if not (1.0 / n - 0.02 <= opt_ratio <= 1.0 / n + 0.10):
        violations.append(
            f"per-rank optimizer-state ratio {opt_ratio:.3f} not ~ 1/{n}"
        )
    largest = str(max(sync_sizes) * 4)
    int8_sync = res["sync_rows"][largest]["sharded_int8"]["vs_fused_f32"]
    if int8_sync < MIN_INT8_SYNC_SPEEDUP and not args.quick:
        violations.append(
            f"int8 sharded sync at largest bucket = {int8_sync:.2f}x "
            f"< required {MIN_INT8_SYNC_SPEEDUP}x vs fused f32"
        )
    step_ratio = (
        res["step_rows"]["sharded_int8"]["min_ms"]
        / res["step_rows"]["replicated_f32"]["min_ms"]
    )
    step_ratio_f32 = (
        res["step_rows"]["sharded_f32"]["min_ms"]
        / res["step_rows"]["replicated_f32"]["min_ms"]
    )
    if step_ratio_f32 > MAX_STEP_SLOWDOWN_F32 and not args.quick:
        violations.append(
            f"sharded-f32 step {step_ratio_f32:.2f}x replicated f32 step "
            f"> allowed {MAX_STEP_SLOWDOWN_F32}x"
        )
    if step_ratio > MAX_STEP_SLOWDOWN_INT8 and not args.quick:
        violations.append(
            f"sharded-int8 step {step_ratio:.2f}x replicated f32 step "
            f"> allowed {MAX_STEP_SLOWDOWN_INT8}x"
        )

    doc = {
        "description": "ZeRO-1 sharded-optimizer A/B (PR 7 tentpole): "
                       "split FlexTree collectives + sharded AdamW vs the "
                       "replicated fused f32 baseline on a real 2-process "
                       "gloo/TCP wire",
        "protocol": {
            "cluster": f"{NUM_PROCESSES} processes x 1 virtual CPU device, "
                       "production init_distributed + gloo; every collective "
                       "byte crosses a process boundary",
            "wire_bytes": "per-chip collective wire bytes counted from the "
                          "lowered StableHLO (hlo_lint.collective_wire_bytes; "
                          "flat plan = loop-free, so the count is exact)",
            "memory": "per-rank optimizer-state bytes measured from live "
                      "device buffers (addressable shard nbytes of the "
                      "moment entries)",
            "timing": "shuffled-interleaved reps, min-of-reps (shared "
                      "shuffle seed so ranks stay matched)",
            "checks": f"sharded-int8 step wire <= {MAX_WIRE_RATIO}x "
                      f"replicated f32 (and sharded-f32 EXACTLY 1.0x); f32 "
                      f"sharded step bitwise == replicated in-run; per-rank "
                      f"optimizer state ~ 1/N; int8 sharded sync >= "
                      f"{MIN_INT8_SYNC_SPEEDUP}x fused f32 at the largest "
                      f"bucket; step-time no-regression guards "
                      f"(f32 <= {MAX_STEP_SLOWDOWN_F32}x, int8 <= "
                      f"{MAX_STEP_SLOWDOWN_INT8}x — see the module "
                      f"docstring for why these are guards, not wins); "
                      f"non-zero exit on any violation",
        },
        "host": {"platform": platform.platform(), "cpus": os.cpu_count()},
        "model": "dense d128 L4 ff512 vocab2048 (~1.3M params, ~5.3MB f32)",
        "wire_bytes": res["wire_bytes"],
        "opt_state_bytes": res["opt_state_bytes"],
        "step_rows": res["step_rows"],
        "sync_rows": res["sync_rows"],
        "headline": {
            "wire_ratio_int8_vs_replicated_f32": round(res["wire_ratio_int8"], 3),
            "wire_ratio_f32_vs_replicated_f32": round(res["wire_ratio_f32"], 6),
            "opt_state_ratio": round(opt_ratio, 4),
            "bitwise_f32_in_run": res["bitwise_f32"],
            "int8_sync_vs_fused_f32_at_largest": round(int8_sync, 3),
            "step_time_ratio_f32": round(step_ratio_f32, 3),
            "step_time_ratio_int8": round(step_ratio, 3),
        },
        "violations": violations,
        "elapsed_s": round(time.time() - t0, 1),
    }
    doc["diagnosis"] = (
        f"On a real 2-process gloo wire the quantized ZeRO-1 step moves "
        f"{res['wire_ratio_int8']:.2f}x the collective bytes of the "
        f"replicated fused f32 step (f32 sharding alone is exactly 1.0x — "
        f"the seam relocates the allgather from gradients to parameters, "
        f"it does not remove it; the codec is what shrinks BOTH phases), "
        f"holds {opt_ratio:.2f}x the per-rank optimizer-state bytes "
        f"(~1/{n}: mu/nu shards + replicated <N tails), and the int8 "
        f"sharded sync runs {int8_sync:.2f}x faster than the fused f32 "
        f"allreduce at the largest bucket. The tiny bench model's step is "
        f"compute-dominated on this 1-core host and the sharded step's "
        f"interleaved bucket pack/unpack is a strided host-side copy "
        f"there, so the step-time ratios (f32 {step_ratio_f32:.2f}x, int8 "
        f"{step_ratio:.2f}x) are no-regression checks, not the win — the "
        f"wire win is the wire-byte and sync rows, and it grows with "
        f"world size (docs/SHARDED.md, including where sharding honestly "
        f"loses)."
    )
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out} ({doc['elapsed_s']}s)")
    if violations:
        print("MACHINE-CHECK VIOLATIONS:", file=sys.stderr)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    print(
        f"checks passed: wire {res['wire_ratio_int8']:.3f}x <= "
        f"{MAX_WIRE_RATIO}, opt-state {opt_ratio:.3f} ~ 1/{n}, f32 bitwise, "
        f"int8 sync {int8_sync:.2f}x >= {MIN_INT8_SYNC_SPEEDUP}x"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
