"""CLI: run the full static-analysis suite and emit a JSON report.

    python -m flextree_tpu.analysis --report ANALYSIS.json

Exit status is the CI contract: 0 iff the clean tree reports zero
violations AND every seeded corruption class is caught by its layer.
``--skip-hlo`` runs only the JAX-less layers (schedule model checker,
jit hygiene, control-plane protocol checker, concurrency lint) for
environments without a usable backend; the committed report is always
produced by a full run.

``--programs SUBSTR [SUBSTR ...]`` filters the schedule / split-phase /
IR-family / ir-equivalence matrices to rows whose name contains any of
the substrings — the growing matrix stays debuggable one program at a
time.  The report carries per-program wall-times (``program_times``) so
a row creeping toward the 60 s budget is visible in the artifact, not
just in CI duration graphs.  (Both the filter flag and the timing block
are excluded from the CI staleness comparison —
``tools/run_static_checks.py`` strips the volatile keys.)
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _configure_cpu_mesh() -> None:
    """Pin 8 virtual CPU devices before any backend initializes, like
    ``tests/conftest.py``: the analysis never needs (or takes) a chip."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    try:
        jax.config.update("jax_num_cpu_devices", 8)
    except RuntimeError:
        pass  # backends already up (e.g. under pytest): use what exists


def build_report(include_hlo: bool = True, programs=None) -> dict:
    """One report from the SAME library loops the tests and gates call —
    ``programs``/``times`` are hooks on those functions, never a second
    copy of their matrix logic (the drift class this PR exists to kill)."""
    from ..schedule.analysis import traffic_summary
    from ..schedule.stages import Topology
    from .base import violations_to_json
    from .concurrency_lint import run_concurrency_lint
    from .jit_hygiene import run_jit_hygiene
    from .mutation import run_mutation_selftest
    from .protocol_check import run_protocol_check
    from .schedule_check import (
        check_ir_families,
        check_split_schedules,
        check_standard_schedules,
    )

    t0 = time.perf_counter()
    report: dict = {"layers": {}}
    times: dict = {}
    violations = []

    for layer, fn in (
        ("schedule_check", check_standard_schedules),
        ("split_schedule_check", check_split_schedules),
        ("ir_check", check_ir_families),
    ):
        layer_times: dict = {}
        vs, checked = fn(programs=programs, times=layer_times)
        violations += vs
        report["layers"][layer] = {
            "programs_checked": checked,
            "violations": len(vs),
        }
        times[layer] = layer_times

    if include_hlo:
        from .hlo_lint import run_hlo_lint
        from .ir_equivalence import run_ir_equivalence

        hlo_v, hlo_detail = run_hlo_lint(full=True)
        violations += hlo_v
        report["layers"]["hlo_lint"] = {
            "entrypoints": hlo_detail,
            "violations": len(hlo_v),
        }

        # ir_equivalence: the lowered StableHLO's collective sequence
        # must match the IR stage list (count/kind/width/pairs/bytes)
        eq_times: dict = {}
        eq_v, eq_detail = run_ir_equivalence(programs=programs, times=eq_times)
        violations += eq_v
        report["layers"]["ir_equivalence"] = {
            "entrypoints": eq_detail,
            "violations": len(eq_v),
        }
        times["ir_equivalence"] = eq_times

    jit_v, jit_detail = run_jit_hygiene()
    violations += jit_v
    report["layers"]["jit_hygiene"] = {**jit_detail, "violations": len(jit_v)}

    # layer 4: exhaustive small-world exploration of the control-plane
    # protocol models (JAX-less — runs in --skip-hlo environments too)
    proto_times: dict = {}
    proto_v, proto_detail = run_protocol_check(
        programs=programs, times=proto_times
    )
    violations += proto_v
    report["layers"]["protocol_check"] = {
        **proto_detail, "violations": len(proto_v),
    }
    times["protocol_check"] = proto_times

    # layer 5: concurrency / lock-discipline lint over the threaded
    # host code (also JAX-less)
    conc_times: dict = {}
    conc_v, conc_detail = run_concurrency_lint(
        programs=programs, times=conc_times
    )
    violations += conc_v
    report["layers"]["concurrency_lint"] = {
        **conc_detail, "violations": len(conc_v),
    }
    times["concurrency_lint"] = conc_times

    report["mutation_selftest"] = run_mutation_selftest(include_hlo=include_hlo)
    report["violations"] = violations_to_json(violations)
    report["analysis_violations"] = len(violations)
    # traffic accounting for the report's headline shapes (schedule/analysis)
    report["traffic"] = {
        "4,2@8x64xf32": traffic_summary(Topology(8, (4, 2)), 64, 4),
        "2,2,2@8x64xf32": traffic_summary(Topology(8, (2, 2, 2)), 64, 4),
    }
    report["program_times"] = times
    report["elapsed_s"] = round(time.perf_counter() - t0, 2)
    report["ok"] = (
        not violations and report["mutation_selftest"]["all_caught"]
    )
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m flextree_tpu.analysis")
    ap.add_argument("--report", metavar="PATH", help="write the JSON report here")
    ap.add_argument(
        "--skip-hlo",
        action="store_true",
        help="skip the HLO lint layer (no JAX backend required)",
    )
    ap.add_argument(
        "--programs",
        nargs="+",
        metavar="SUBSTR",
        help="only check matrix programs whose name contains a substring "
        "(e.g. --programs swing '4,2@8')",
    )
    args = ap.parse_args(argv)

    if not args.skip_hlo:
        _configure_cpu_mesh()
    report = build_report(
        include_hlo=not args.skip_hlo, programs=args.programs
    )

    if args.report:
        with open(args.report, "w", encoding="utf-8") as fh:
            json.dump(report, fh, indent=2, sort_keys=False)
            fh.write("\n")

    n_v = report["analysis_violations"]
    mut = report["mutation_selftest"]
    caught = sum(1 for c in mut["classes"].values() if c["caught"])
    print(
        f"flextree static analysis: {n_v} violations; mutation self-test "
        f"{caught}/{len(mut['classes'])} classes caught; "
        f"{report['elapsed_s']}s"
    )
    slowest = sorted(
        (
            (ms, f"{layer}:{name}")
            for layer, rows in report["program_times"].items()
            for name, ms in rows.items()
        ),
        reverse=True,
    )[:3]
    for ms, name in slowest:
        print(f"  slowest: {name} {ms}ms")
    for row in report["violations"]:
        print(f"  {row['layer']}/{row['kind']} @ {row['where']}: {row['detail']}")
    for name, row in mut["classes"].items():
        if not row["caught"]:
            print(f"  MUTATION ESCAPED: {name} (expected {row['expected']})")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
