#!/usr/bin/env python
"""Execute the L5 deployment layer for real: a 2-process cluster on one host.

The reference's cluster path actually *ran*: ``make sync`` deployed the
binary to 16 hosts and ``mpirun --hostfile mpi_config_file`` spawned ranks
across them (``allreduce_over_mpi/Makefile:8-24``, ``mpi_config_file:1-16``).
Until now our analog (``flextree_tpu.parallel.launch``) was unit-tested but
never executed across a real process boundary.

This tool is the executed bring-up: the parent spawns two child processes,
each pins 4 virtual CPU devices and calls the production
``init_distributed`` with the launcher env triple (``FT_COORDINATOR`` /
``FT_NUM_PROCESSES`` / ``FT_PROCESS_ID`` — the MPI-rank analog), giving an
8-device world spanning 2 processes with gloo cross-process collectives.
Each child then:

1. builds the production ``hybrid_mesh`` (dcn=(2,) processes x ici=(4,)
   local devices) — ``_is_multi_granule`` sees 2 real process granules, so
   the DCN axis genuinely crosses the process boundary;
2. asks ``plan_for_mesh`` for stage widths (the DCN axis priced with DCN
   constants);
3. runs the FlexTree tree allreduce over the flattened mesh on a global
   array built with ``make_array_from_process_local_data``, plus a ring
   run, and checks both against the ``lax.psum`` oracle *and* the analytic
   sum — across the process boundary.

The parent collects both children's logs and writes the committed artifact
``MULTIPROC_BRINGUP.json``.

Usage: python tools/multiproc_bringup.py [--out MULTIPROC_BRINGUP.json]
       (also runnable via tests/test_multiproc_bringup.py)
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NUM_PROCESSES = 2
LOCAL_DEVICES = 4


def child_main() -> int:
    """One process of the 2-process world (invoked with --child); the
    coordinator address arrives via the FT_* launcher env triple."""
    import jax

    # CPU pinning must precede any backend touch; gloo is the CPU
    # cross-process collective transport (the MPI-of-this-world)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", LOCAL_DEVICES)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flextree_tpu.parallel.allreduce import allreduce
    from flextree_tpu.parallel.launch import (
        ClusterConfig,
        flatten_mesh,
        hybrid_mesh,
        init_distributed,
        plan_for_mesh,
    )

    # the production L5 entry, fed by the launcher env triple
    init_distributed(ClusterConfig.from_env())
    pid = jax.process_index()
    nproc = jax.process_count()
    n = jax.device_count()
    log = lambda msg: print(f"[proc {pid}] {msg}", flush=True)
    log(f"bring-up: {nproc} processes, {jax.local_device_count()} local / "
        f"{n} global devices")
    if nproc != NUM_PROCESSES or n != NUM_PROCESSES * LOCAL_DEVICES:
        log(f"FAIL: expected {NUM_PROCESSES} procs x {LOCAL_DEVICES} devices")
        return 1

    mesh = hybrid_mesh(ici_shape=(LOCAL_DEVICES,), dcn_shape=(NUM_PROCESSES,))
    granules = [
        {d.process_index for d in row} for row in mesh.devices
    ]
    if any(len(g) != 1 for g in granules):
        log(f"FAIL: dcn axis does not align with process granules: {granules}")
        return 1
    plan = plan_for_mesh(mesh, 4 << 20)
    log(f"hybrid mesh {dict(mesh.shape)}; planner picked "
        f"FT_TOPO={plan.to_ft_topo()} for 4 MB")

    fmesh = flatten_mesh(mesh)
    sharding = NamedSharding(fmesh, P("ft"))
    length = 8192  # 1024 elements per device
    global_shape = (n, length)
    local = np.stack(
        [
            np.arange(length, dtype=np.float64) * (r + 1)
            for r in range(pid * LOCAL_DEVICES, (pid + 1) * LOCAL_DEVICES)
        ]
    )
    x = jax.make_array_from_process_local_data(sharding, local, global_shape)
    expected0 = float(sum(r + 1 for r in range(n)))  # coefficient at col 1

    def run(topo):
        f = jax.jit(
            jax.shard_map(
                lambda v: allreduce(v, "ft", topo=topo),
                mesh=fmesh, in_specs=P("ft"), out_specs=P("ft"),
            )
        )
        return f(x)

    oracle = jax.jit(
        jax.shard_map(
            lambda v: jax.lax.psum(v, "ft"),
            mesh=fmesh, in_specs=P("ft"), out_specs=P("ft"),
        )
    )(x)
    ora = np.asarray(oracle.addressable_shards[0].data)

    results = {}
    for name, topo in [
        ("planner:" + plan.to_ft_topo(), plan.topology),
        ("ring", "1"),
    ]:
        out = run(topo)
        got = np.asarray(out.addressable_shards[0].data)
        ok = bool(
            np.allclose(got, ora, rtol=1e-12)
            and np.isclose(got[0, 1], expected0)
        )
        results[name] = ok
        log(f"allreduce[{name}] across process boundary: "
            f"{'OK' if ok else 'MISMATCH'} "
            f"(col1 {got[0, 1]:.0f}, expected {expected0:.0f})")
    if not all(results.values()):
        return 1

    # --- the measured hierarchy A/B across the real slow link.
    # The gloo fabric is a genuine two-level hierarchy: intra-
    # process device "transfers" are shared-memory, cross-process ones
    # serialize through loopback TCP — a DCN/ICI analog.  Time flat vs
    # two-level vs ring vs psum on a bandwidth-sized buffer.  Caveat
    # (recorded in the artifact): this host has ONE physical core, so all
    # 8 virtual devices serialize — wall-clock here measures total work
    # incl. per-byte transport cost, not overlap/critical path.
    import time as _time

    tlen = int(os.environ.get("FT_BRINGUP_TIMING_ELEMS", str(1 << 20)))
    tsharding = NamedSharding(fmesh, P("ft"))
    tx = jax.make_array_from_process_local_data(
        tsharding,
        np.ones((LOCAL_DEVICES, tlen), dtype=np.float32),
        (n, tlen),
    )

    def timed(fn, repeat=8, warmup=2):
        jax.block_until_ready(fn(tx))  # compile
        for _ in range(warmup):
            jax.block_until_ready(fn(tx))
        ts = []
        for _ in range(repeat):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(tx))
            ts.append(_time.perf_counter() - t0)
        return ts

    def ft_fn(topo):
        return jax.jit(
            jax.shard_map(
                lambda v: allreduce(v, "ft", topo=topo),
                mesh=fmesh, in_specs=P("ft"), out_specs=P("ft"),
            )
        )

    psum_fn = jax.jit(
        jax.shard_map(
            lambda v: jax.lax.psum(v, "ft"),
            mesh=fmesh, in_specs=P("ft"), out_specs=P("ft"),
        )
    )
    configs = [
        ("psum", psum_fn),
        ("flat:8", ft_fn("8")),
        ("two_level:4,2", ft_fn("4,2")),
        ("two_level:2,4", ft_fn("2,4")),
        ("ring", ft_fn("1")),
    ]
    timings = {}
    for name, fn in configs:  # identical order on both ranks: collectives
        ts = timed(fn)        # stay matched across the process boundary
        timings[name] = {
            "min_s": min(ts),
            "avg_s": sum(ts) / len(ts),
            "reps": len(ts),
        }
        log(f"timing[{name}]: min {min(ts)*1e3:.2f} ms "
            f"avg {sum(ts)/len(ts)*1e3:.2f} ms")
    if pid == 0:
        payload = {
            "buffer_bytes_per_device": tlen * 4,
            "planner_pick": plan.to_ft_topo(),
            "configs": timings,
        }
        print("TIMING_JSON: " + json.dumps(payload), flush=True)
    log("PASS")
    return 0


def spawn(port: int, out_path: str | None) -> int:
    env_base = {
        **os.environ,
        "FT_COORDINATOR": f"localhost:{port}",
        "FT_NUM_PROCESSES": str(NUM_PROCESSES),
        # never let an ambient calibration file skew plan_for_mesh
        "FLEXTREE_CALIBRATION": "",
    }
    env_base.pop("FLEXTREE_CALIBRATION")
    procs = []
    for pid in range(NUM_PROCESSES):
        env = {**env_base, "FT_PROCESS_ID": str(pid)}
        procs.append(
            subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), "--child"],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    logs, rcs = [], []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += "\n[parent] TIMEOUT after 300s"
        logs.append(out)
        rcs.append(p.returncode)
    ok = all(rc == 0 for rc in rcs) and all("PASS" in l for l in logs)
    timings = None
    for l in logs:
        for line in l.splitlines():
            if line.startswith("TIMING_JSON: "):
                timings = json.loads(line[len("TIMING_JSON: "):])
    if timings:
        cfgs = timings["configs"]
        flat = cfgs.get("flat:8", {}).get("min_s")
        two = min(
            (cfgs[k]["min_s"] for k in cfgs if k.startswith("two_level:")),
            default=None,
        )
        if flat and two:
            win = two < flat
            best_two = min(
                (k for k in cfgs if k.startswith("two_level:")),
                key=lambda k: cfgs[k]["min_s"],
            )
            timings["hierarchy_win"] = win
            timings["two_level_vs_flat"] = round(flat / two, 3)
            measured = (
                f"measured here: flat:8 {flat * 1e3:.1f} ms vs {best_two} "
                f"{two * 1e3:.1f} ms min at "
                f"{timings['buffer_bytes_per_device'] >> 20} MB/device "
                f"(planner pick: {timings['planner_pick']})"
            )
            if win:
                timings["analysis"] = (
                    "the two-level shape crosses the process boundary "
                    "with 1/4 the bytes of flat:8 (its cross stage "
                    "operates on quarter shards) and the measured win "
                    "shows the cross link's per-byte cost dominating — "
                    "the reference's core result "
                    "(cost_model/CostModel.h:82-119) reproduced on the "
                    f"gloo fabric. {measured}."
                )
            else:
                timings["analysis"] = (
                    "the two-level shape crosses the process boundary "
                    "with 1/4 the bytes of flat:8 (its cross stage "
                    "operates on quarter shards), so on a fabric where "
                    "the cross link's per-byte cost dominates it must "
                    "win — the reference's core result "
                    "(cost_model/CostModel.h:82-119) on its 16-host 1GbE "
                    "fabric. Here it does not: this host has one "
                    "physical core, so gloo loopback-TCP bytes cost "
                    "about the same as intra-process shared-memory bytes "
                    "(both are serialized memcpys), the 4x cross-byte "
                    "reduction buys ~nothing, and the second stage's "
                    "extra launches/copies make the two-level shape "
                    f"slower. {measured}. The planner still picks a "
                    "two-level shape because its DCN constants price the "
                    "cross link ~10x slower than ICI — true of real DCN, "
                    "false of loopback on one core. Conclusion: this "
                    "fabric lacks the link asymmetry the hierarchy "
                    "exploits; the win needs genuinely unequal per-byte "
                    "cost (real ICI/DCN)."
                )
    for i, l in enumerate(logs):
        print(f"----- process {i} (rc={rcs[i]}) -----")
        print(l)
    if out_path:
        from flextree_tpu.utils.buildstamp import artifact_meta

        doc = {
            "description": "Executed 2-process jax.distributed bring-up on "
                           "one host (the reference's mpirun-over-hostfile "
                           "cluster path, Makefile:8-24 + mpi_config_file): "
                           "production init_distributed + hybrid_mesh with "
                           "a REAL process-granule DCN axis, planner-picked "
                           "FlexTree tree + ring allreduce across the "
                           "process boundary vs the psum oracle, gloo "
                           "transport on 2x4 virtual CPU devices",
            "build": artifact_meta(),
            "ok": ok,
            "num_processes": NUM_PROCESSES,
            "local_devices_per_process": LOCAL_DEVICES,
            "returncodes": rcs,
            "timings": timings,
            "timing_caveat": "single-core host: the 8 virtual devices "
                             "serialize, so wall-clock measures total work "
                             "(incl. per-byte gloo socket cost), not "
                             "overlapped critical path",
            "logs": [l.splitlines() for l in logs],
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {out_path} (ok={ok})")
    return 0 if ok else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--port", type=int, default=19877)
    ap.add_argument("--out", default=os.path.join(REPO, "MULTIPROC_BRINGUP.json"))
    ap.add_argument("--no-artifact", action="store_true")
    args = ap.parse_args()
    if args.child:
        return child_main()
    return spawn(args.port, None if args.no_artifact else args.out)


if __name__ == "__main__":
    raise SystemExit(main())
