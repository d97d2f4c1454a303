#!/usr/bin/env python
"""Chaos bring-up: kill/restart/degrade a real 2-process cluster mid-handshake.

``tools/multiproc_bringup.py`` proved the happy path of the L5 deployment
layer (a genuine 2-process ``jax.distributed`` world on one host).  This
tool proves the *failure* paths of ``flextree_tpu.parallel.launch`` — the
retry/backoff wrapper, the error taxonomy, and degrade-to-survivors
replanning (docs/FAILURE_MODEL.md) — by injecting real process faults:

- ``retry``: the coordinator starts several seconds LATE, past the
  children's per-attempt handshake deadline (``FT_INIT_TIMEOUT``), so the
  non-coordinator's first attempt(s) genuinely fail and the exponential
  backoff loop must reconnect (asserted: ``attempts > 1`` in its report);
- ``restart``: one of the two processes is killed mid-handshake (it exits
  before ever reaching ``jax.distributed.initialize``) and restarted by
  the launcher; the surviving coordinator, still inside its handshake
  deadline, never notices — both processes then run the planner-picked
  FlexTree tree + ring allreduce across the process boundary vs the psum
  oracle;
- ``degrade``: the second process NEVER joins; the launcher (the only
  party that knows its children died) reports the survivor count, and
  ``init_distributed_or_degrade`` forms the degraded world directly —
  never entering the doomed full-world barrier, whose in-handshake
  deadline hard-aborts the process on this JAX pin — with the allreduce
  topology replanned for the surviving devices via
  ``flextree_tpu.planner.replan_for_survivors``.

The parent collects every child log and writes the committed artifact
``CHAOS_BRINGUP.json`` (``flextree_tpu.utils.logging.write_result_file``
convention).  Runnable standalone or via the slow/chaos-marked test in
``tests/test_chaos.py``.

Usage: python tools/chaos_bringup.py [--out CHAOS_BRINGUP.json]
       [--scenario retry|restart|degrade] [--port 19930]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

NUM_PROCESSES = 2
LOCAL_DEVICES = 4
SCENARIOS = ("retry", "restart", "degrade")


# --------------------------------------------------------------------------
# child
# --------------------------------------------------------------------------


def child_main() -> int:
    """One process of the world; behavior driven by FT_CHAOS_* env vars."""
    if os.environ.get("FT_CHAOS_DIE") == "1":
        # the injected fault: crash before ever reaching the handshake
        print("[chaos] dying mid-handshake (injected)", flush=True)
        os._exit(3)

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", LOCAL_DEVICES)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from flextree_tpu.parallel.allreduce import allreduce
    from flextree_tpu.parallel.launch import (
        BringupTimeout,
        ClusterConfig,
        flatten_mesh,
        hybrid_mesh,
        init_distributed,
        init_distributed_or_degrade,
    )
    from flextree_tpu.planner import replan_for_survivors

    scenario = os.environ.get("FT_CHAOS_SCENARIO", "restart")
    pid_cfg = os.environ.get("FT_PROCESS_ID", "?")
    log = lambda msg: print(f"[proc {pid_cfg}] {msg}", flush=True)

    degraded_plan = None
    if scenario == "degrade":
        survivors = int(os.environ["FT_CHAOS_SURVIVORS"])
        try:
            report, degraded_plan = init_distributed_or_degrade(
                ClusterConfig.from_env(), nbytes=4 << 20, survivors=survivors
            )
        except BringupTimeout as e:
            log(f"FAIL: bring-up did not degrade: {e}")
            return 1
        if report.degraded_to != survivors:
            log(f"FAIL: expected degraded_to={survivors}, got {report.degraded_to}")
            return 1
    else:
        try:
            report = init_distributed(ClusterConfig.from_env())
        except BringupTimeout as e:
            log(f"FAIL: bring-up exhausted retries: {e}")
            for err in e.errors:
                log(f"  attempt error: {err}")
            return 1

    n = jax.device_count()
    nproc = jax.process_count()
    log(
        f"bring-up OK after {report.attempts} attempt(s): {nproc} processes, "
        f"{n} global devices"
        + (f" (degraded from {NUM_PROCESSES})" if report.degraded_to else "")
    )
    if os.environ.get("FT_CHAOS_EXPECT_RETRIES") == "1" and report.attempts < 2:
        log("FAIL: expected the retry loop to fire (attempts < 2)")
        return 1

    # the allreduce check: planner-picked tree + ring vs the psum oracle,
    # across whatever world (full or degraded) actually assembled
    if degraded_plan is not None:
        # replan at device granularity for the surviving world
        plan = replan_for_survivors(
            n, 4 << 20, configured=NUM_PROCESSES * LOCAL_DEVICES
        )
        mesh = hybrid_mesh(ici_shape=(LOCAL_DEVICES,), dcn_shape=(nproc,))
    else:
        mesh = hybrid_mesh(ici_shape=(LOCAL_DEVICES,), dcn_shape=(nproc,))
        from flextree_tpu.parallel.launch import plan_for_mesh

        plan = plan_for_mesh(mesh, 4 << 20)
    fmesh = flatten_mesh(mesh)
    sharding = NamedSharding(fmesh, P("ft"))
    length = 1024
    local = np.stack(
        [
            np.arange(length, dtype=np.float64) * (r + 1)
            for r in range(
                jax.process_index() * LOCAL_DEVICES,
                (jax.process_index() + 1) * LOCAL_DEVICES,
            )
        ]
    )
    x = jax.make_array_from_process_local_data(sharding, local, (n, length))
    expected1 = float(sum(r + 1 for r in range(n)))  # coefficient at col 1

    def run(topo):
        return jax.jit(
            jax.shard_map(
                lambda v: allreduce(v, "ft", topo=topo),
                mesh=fmesh, in_specs=P("ft"), out_specs=P("ft"),
            )
        )(x)

    oracle = jax.jit(
        jax.shard_map(
            lambda v: jax.lax.psum(v, "ft"),
            mesh=fmesh, in_specs=P("ft"), out_specs=P("ft"),
        )
    )(x)
    ora = np.asarray(oracle.addressable_shards[0].data)

    ok = True
    for name, topo in [(f"planner:{plan.to_ft_topo()}", plan.topology), ("ring", "1")]:
        got = np.asarray(run(topo).addressable_shards[0].data)
        good = bool(
            np.allclose(got, ora, rtol=1e-12) and np.isclose(got[0, 1], expected1)
        )
        ok &= good
        log(f"allreduce[{name}]: {'OK' if good else 'MISMATCH'}")
    if not ok:
        return 1

    payload = {
        "attempts": report.attempts,
        "errors": report.errors,
        "degraded_to": report.degraded_to,
        "world_devices": n,
        "topo": plan.to_ft_topo(),
    }
    print("CHAOS_JSON: " + json.dumps(payload), flush=True)
    log("PASS")
    return 0


# --------------------------------------------------------------------------
# parent: scenario drivers
# --------------------------------------------------------------------------


def _spawn_child(pid: int, port: int, scenario: str, extra_env=None):
    env = {
        **os.environ,
        "FT_COORDINATOR": f"localhost:{port}",
        "FT_NUM_PROCESSES": str(NUM_PROCESSES),
        "FT_PROCESS_ID": str(pid),
        "FT_CHAOS_SCENARIO": scenario,
        **(extra_env or {}),
    }
    env.pop("FLEXTREE_CALIBRATION", None)
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child"],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )


def _drain(procs, timeout=240):
    logs, rcs = [], []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            out += f"\n[parent] TIMEOUT after {timeout}s"
        logs.append(out)
        rcs.append(p.returncode)
    return logs, rcs


def run_retry(port: int) -> dict:
    """Coordinator starts LATE: the non-coordinator's backoff loop must
    survive >= 1 failed handshake attempt and reconnect."""
    attempt_timeout = 3
    late_by = 7  # > 1 failed attempt at timeout=3 + backoff, < the budget
    p1 = _spawn_child(
        1, port, "retry",
        {
            "FT_INIT_TIMEOUT": str(attempt_timeout),
            "FT_INIT_RETRIES": "8",
            "FT_CHAOS_EXPECT_RETRIES": "1",
        },
    )
    time.sleep(late_by)
    # the late coordinator gets a roomy single-attempt window so the
    # already-backing-off child can land in it
    p0 = _spawn_child(
        0, port, "retry", {"FT_INIT_TIMEOUT": "60", "FT_INIT_RETRIES": "2"}
    )
    logs, rcs = _drain([p0, p1])
    return _summarize("retry", logs, rcs, expect_pass=2)


def run_restart(port: int) -> dict:
    """Kill one process mid-handshake, restart it; the surviving
    coordinator (inside its handshake deadline) never notices."""
    env = {"FT_INIT_TIMEOUT": "90", "FT_INIT_RETRIES": "2"}
    p0 = _spawn_child(0, port, "restart", env)
    doomed = _spawn_child(1, port, "restart", {**env, "FT_CHAOS_DIE": "1"})
    doomed_out, _ = doomed.communicate(timeout=60)
    doomed_rc = doomed.returncode
    # the launcher observes the death and restarts the rank
    p1 = _spawn_child(1, port, "restart", env)
    logs, rcs = _drain([p0, p1])
    summary = _summarize("restart", logs, rcs, expect_pass=2)
    summary["killed_process"] = {"rc": doomed_rc, "log": doomed_out.splitlines()}
    summary["ok"] = summary["ok"] and doomed_rc == 3
    return summary


def run_degrade(port: int) -> dict:
    """Process 1 never joins: the coordinator times out, degrades to the
    survivor count, and replans the topology for the surviving devices."""
    env = {
        "FT_INIT_TIMEOUT": "5",
        "FT_INIT_RETRIES": "0",
        "FT_CHAOS_SURVIVORS": "1",
    }
    p0 = _spawn_child(0, port, "degrade", env)
    logs, rcs = _drain([p0])
    summary = _summarize("degrade", logs, rcs, expect_pass=1)
    info = summary.get("reports", [])
    summary["ok"] = summary["ok"] and any(
        r.get("degraded_to") == 1 for r in info
    )
    return summary


def _summarize(name: str, logs, rcs, expect_pass: int) -> dict:
    reports = []
    for l in logs:
        for line in l.splitlines():
            if line.startswith("CHAOS_JSON: "):
                reports.append(json.loads(line[len("CHAOS_JSON: "):]))
    ok = (
        all(rc == 0 for rc in rcs)
        and sum("PASS" in l for l in logs) == expect_pass
    )
    return {
        "scenario": name,
        "ok": ok,
        "returncodes": rcs,
        "reports": reports,
        "logs": [l.splitlines() for l in logs],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--port", type=int, default=19930)
    ap.add_argument("--scenario", choices=SCENARIOS, action="append")
    ap.add_argument("--out", default=os.path.join(REPO, "CHAOS_BRINGUP.json"))
    ap.add_argument("--no-artifact", action="store_true")
    args = ap.parse_args(argv)
    if args.child:
        return child_main()

    which = tuple(args.scenario) if args.scenario else SCENARIOS
    runners = {"retry": run_retry, "restart": run_restart, "degrade": run_degrade}
    results = []
    for i, name in enumerate(which):
        print(f"=== scenario {name} ===", flush=True)
        try:
            res = runners[name](args.port + i)
        except Exception as e:
            # a crashed driver is a FAILED scenario, recorded in the
            # artifact and reflected in the exit code — never a scenario
            # that silently vanishes from the JSON while the tool exits 0
            res = {
                "scenario": name,
                "ok": False,
                "returncodes": [],
                "reports": [],
                "logs": [[f"driver error: {type(e).__name__}: {e}"]],
            }
        results.append(res)
        print(f"scenario {name}: {'OK' if res['ok'] else 'FAIL'}", flush=True)
        for l in res["logs"]:
            for line in l:
                print(f"  {line}")
    # the gate CI relies on: ANY scenario failing to recover -> exit 1,
    # with the artifact still written below so the postmortem has it
    ok = all(r["ok"] for r in results)

    if not args.no_artifact:
        from flextree_tpu.utils.buildstamp import artifact_meta
        from flextree_tpu.utils.logging import write_result_file

        write_result_file(
            args.out,
            {
                "description": "Executed chaos bring-up on one host: late "
                               "coordinator (retry/backoff reconnect), "
                               "kill+restart of a process mid-handshake, and "
                               "never-joining process (degrade-to-survivors "
                               "with replanned topology) — the failure paths "
                               "of flextree_tpu.parallel.launch, see "
                               "docs/FAILURE_MODEL.md",
                "build": artifact_meta(),
                "ok": ok,
                "num_processes": NUM_PROCESSES,
                "local_devices_per_process": LOCAL_DEVICES,
                "scenarios": results,
            },
        )
        print(f"wrote {args.out} (ok={ok})")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
