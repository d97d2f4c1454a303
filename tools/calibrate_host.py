#!/usr/bin/env python
"""Generate/refresh the committed CALIBRATION.json.

Two sections:

- ``cpu``: the 4 cost-model constants fitted on the 8-virtual-device CPU
  mesh (``fit_cost_params`` over measured (topology, size) points — the
  same calibrate-then-trust protocol tools/sweep_allreduce.py uses).  These
  are the constants the planner should use when ranking topologies for
  *this host's* virtual meshes.
- ``tpu_v5e`` (only when a TPU is reachable): ``reduce_bw_GBps`` measured
  by the local-reduce roofline (``tools/roofline_reduce.py`` machinery, the
  allreduce's only compute term), merged with datasheet ICI/DCN link
  constants — each field's provenance is recorded in ``meta.sources``.
  Multi-chip link constants cannot be measured on one chip; they stay
  datasheet until a slice is attached.

The reference compiled its calibrated constants into the planner
(``cost_model/CostModel.h:1-30``); this file is our runtime-loadable
equivalent: ``choose_topology`` picks it up via ``$FLEXTREE_CALIBRATION``
or ``python -m flextree_tpu.planner --calibration CALIBRATION.json``.

Usage: python tools/calibrate_host.py [--out CALIBRATION.json] [--skip-tpu]
"""

from __future__ import annotations

import argparse
import datetime
import os
import platform
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from flextree_tpu.utils.buildstamp import artifact_meta  # noqa: E402


def cpu_section(out: str) -> None:
    """Fit on the 8-vdev CPU mesh in THIS process (cpu-pinned)."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    from flextree_tpu.bench import measure_points
    from flextree_tpu.planner import fit_cost_params, save_calibration

    topos = ["8", "4,2", "2,2,2", "2,4", "1"]
    sizes = [1 << 14, 1 << 17, 1 << 20]
    points = measure_points(topos, sizes, repeat=10, devices=8)
    params = fit_cost_params(points)
    save_calibration(
        out,
        params,
        backend="cpu",
        source="measured",  # direct-measurement protocol, not a feedback refit
        meta={
            "build": artifact_meta(),
            "date": datetime.date.today().isoformat(),
            "host": platform.platform(),
            "cpus": os.cpu_count(),
            "protocol": "fit_cost_params (relative NNLS) over "
            f"{len(points)} in-place-timed points: topos={topos}, "
            f"sizes={sizes}, repeat=10, median stat",
            "sources": {"all": "measured on 8 virtual CPU devices"},
        },
    )
    print(f"cpu section written: {params}")


def tpu_section(out: str, timeout_s: int = 240) -> bool:
    """Measure reduce_bw on the real chip in a SUBPROCESS: this process is
    pinned to the CPU for the cpu section and never touches the chip, so
    the child is the one process that holds it."""
    code = f"""
import sys, json
sys.path.insert(0, {REPO!r})
import jax
assert any(d.platform != "cpu" for d in jax.devices())
sys.path.insert(0, {os.path.join(REPO, "tools")!r})
from roofline_reduce import chip_peak_hbm_GBps, measure_point
from measure_launch import measure_launch_bracket
# the allreduce reduce term folds w copies; w=8 at 64 MB is the
# representative point (BASELINE.md config sizes) — large enough that the
# slope subtraction is stable (16 MB samples swing 190-580 GB/s run to
# run); median of 5 full slope samples
dt, gbps, isolated = measure_point(w=8, length=1 << 24, dtype_name="float32",
                                   rows_tile=1024, samples=5)
try:
    launch = measure_launch_bracket()
except Exception as e:  # supplementary: never lose the reduce_bw result
    print("launch bracket failed:", e, file=sys.stderr)
    launch = {{}}
print("RESULT " + json.dumps({{
    "achieved_GBps": gbps,
    "peak_GBps": chip_peak_hbm_GBps(),
    "device": jax.devices()[0].device_kind,
    "isolated": isolated,
    "launch": launch,
}}))
"""
    try:
        p = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        print("tpu section skipped: the chip measurement timed out")
        return False
    line = next(
        (l for l in p.stdout.splitlines() if l.startswith("RESULT ")), None
    )
    if p.returncode != 0 or line is None:
        print(f"tpu section skipped: {p.stderr[-300:]}")
        return False
    import json

    r = json.loads(line[len("RESULT "):])
    from flextree_tpu.planner import (
        DCN_DEFAULT,
        ICI_DEFAULT,
        TpuCostParams,
        save_calibration,
    )

    # derive the section name from what was actually measured — committing
    # v4 numbers under a "tpu_v5e" label would poison the prefix-fallback
    # lookup on every other chip.  Shared normalizer with the MFU table so
    # the two can't drift.
    from flextree_tpu.utils.device import tpu_generation

    section = f"tpu_{tpu_generation(r['device'])}"

    launch = r.get("launch", {})
    params = TpuCostParams(
        reduce_bw_GBps=round(r["achieved_GBps"], 1),
        launch_us=launch.get("launch_us", TpuCostParams().launch_us),
    )
    save_calibration(
        out,
        params,
        backend=section,
        source="measured",
        meta={
            "build": artifact_meta(),
            "date": datetime.date.today().isoformat(),
            "device": r["device"],
            "protocol": "reduce_bw_GBps = pallas_reduce roofline, w=8 x "
            "64MB f32 rows_tile=1024, median of 5 slope samples minus "
            "kernel-free chain (tools/roofline_reduce.py); achieved "
            f"{r['achieved_GBps']:.0f} of {r['peak_GBps']:.0f} GB/s peak"
            + ("" if r.get("isolated", True)
               else " [NOT chain-isolated: uncorrected slope]"),
            "sources": {
                "reduce_bw_GBps": "measured on the attached chip",
                "ici_*": f"datasheet default ({ICI_DEFAULT})",
                "dcn_*": f"datasheet default ({DCN_DEFAULT})",
                "launch_us": "measured: " + launch.get(
                    "provenance", "bracket unavailable (kept default)"
                ) if launch else "default (measurement failed)",
                "control_us_per_width": "default (single chip cannot "
                "measure multi-chip group-control scaling)",
            },
        },
    )
    print(
        f"{section} section written: reduce_bw={params.reduce_bw_GBps} GB/s, "
        f"launch={params.launch_us} us"
    )
    return True


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO, "CALIBRATION.json"))
    ap.add_argument("--skip-tpu", action="store_true")
    ap.add_argument("--skip-cpu", action="store_true")
    args = ap.parse_args()
    if not args.skip_cpu:
        cpu_section(args.out)
    if not args.skip_tpu:
        tpu_section(args.out)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
