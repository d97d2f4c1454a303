#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs ONE cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with the
keys ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` in a traced run).  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a shorter,
profiled window.  Earlier lines are for people: the set-up breakdown,
sample counts, the reference check.

Without a TPU holding the chips the cell asks for it exits 2 and prints no
result.  ``--rehearsal`` (CPU only, ``JAX_PLATFORMS=cpu``) runs the same
control flow at the tiny sizes under ``benchmarks/rehearsal/`` and prints
a line whose every metric value is null: a number from the CPU is never
written under a device metric's name.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # process start, as near as Python can see it

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="benchmarks/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                    "run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny sizes, no metric values")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "flextree_tpu")):
        print("benchmark: the program (flextree_tpu/) is not in this "
              "directory; there is nothing to measure.", file=sys.stderr)
        return 2

    from benchmarks.lib import harness

    bench = harness.load_benchmark(REPO)
    cell = harness.load_cell(args.workload, bench)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    import jax

    if args.rehearsal:
        harness.apply_rehearsal(cell)
        jax.config.update("jax_num_cpu_devices", max(cell.chips, 1))
    device = harness.require_device(cell, args.rehearsal)

    from flextree_tpu.utils.backend import enable_compile_cache

    harness.say(f"compile cache: {enable_compile_cache()}")
    # JAX keeps only programs that took a second to compile; a cell runs a
    # dozen smaller ones (pool writes, slices), and every run is a new
    # process: keep them all, so a run after the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    counter = harness.CompileCounter()

    work = os.path.join(HERE, ".work")
    trace_dir = (
        os.path.join(work, "trace", args.workload) if args.trace else None
    )
    driver = importlib.import_module(f"benchmarks.lib.{cell.traffic['kind']}")
    harness.say(f"set-up before the driver (Python, JAX, the device runtime "
                f"coming up): {time.monotonic() - T_START:.2f} s")
    run = driver.run(cell, args.seed, seconds, trace_dir, T_START, counter)

    device["memory_peak_bytes"] = harness.peak_memory_bytes()
    breakdown = None
    if args.trace:
        from benchmarks.lib import xplane

        trace = xplane.load(xplane.find_xplane(trace_dir))
        window = xplane.window_of(trace)
        from benchmarks.lib.peaks import peaks_for

        ctx = harness.ReaderContext(
            cell, run, device, trace, window,
            peaks=None if args.rehearsal else peaks_for(device["kind"]),
        )
        metrics = harness.per_layer_metrics(ctx)
        device["busy_s"] = xplane.busy_seconds(trace, window)
        device["window_s"] = (window[1] - window[0]) / 1e9
        breakdown = {
            "device_ops": xplane.top_ops(trace, window),
            "idle_gaps": xplane.idle_gaps(trace, window),
        }
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        values = dict(run.end_to_end, setup_s=run.setup_s)
        metrics = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in cell.end_to_end
        }

    if args.rehearsal:
        harness.say("rehearsal readings (CPU, tiny sizes; NOT results): "
                    + json.dumps(metrics))
        metrics = {k: {"value": None, "unit": v["unit"]} for k, v in metrics.items()}
        print(harness.result_line(run, metrics, device, rehearsal=True),
              flush=True)
        return 0
    if args.trace and device["busy_s"] <= 0:
        print("benchmark: the traced window holds no device operation.",
              file=sys.stderr)
        return 3
    print(harness.result_line(run, metrics, device, breakdown), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
