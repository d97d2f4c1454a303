"""Executed L5: a real 2-process jax.distributed world on this host.

The reference ran its cluster path (``Makefile:8-24`` scp-deploy +
``mpirun --hostfile``); this is the analog actually executing — production
``init_distributed`` + ``hybrid_mesh`` with a genuine process-granule DCN
axis, FlexTree tree + ring allreduce across the process boundary.  The committed artifact is ``MULTIPROC_BRINGUP.json``
(regenerate with ``python tools/multiproc_bringup.py``).
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_two_process_bringup_allreduce():
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "multiproc_bringup.py"),
         "--no-artifact", "--port", "19911"],
        capture_output=True,
        text=True,
        timeout=360,
        cwd=REPO,
    )
    assert p.returncode == 0, f"bring-up failed:\n{p.stdout[-3000:]}"
    # both processes must report both topologies OK across the boundary
    assert p.stdout.count("PASS") == 2, p.stdout[-3000:]
    assert "allreduce[ring] across process boundary: OK" in p.stdout


def test_committed_bringup_artifact_carries_timings():
    """The committed MULTIPROC_BRINGUP.json must carry the measured
    hierarchy A/B across the real process boundary:
    per-config min/avg timings, the planner's pick, and — since this
    1-core fabric lacks the link asymmetry the hierarchy exploits — the
    honest analysis of why flat wins here (hierarchy_win recorded either
    way, never omitted)."""
    import json

    with open(os.path.join(REPO, "MULTIPROC_BRINGUP.json")) as f:
        doc = json.load(f)
    assert doc["ok"] is True
    t = doc["timings"]
    for cfg in ("psum", "flat:8", "two_level:4,2", "two_level:2,4", "ring"):
        assert t["configs"][cfg]["min_s"] > 0, cfg
        assert t["configs"][cfg]["avg_s"] >= t["configs"][cfg]["min_s"], cfg
    # the pick is host/calibration dependent (regenerating the artifact
    # after a cost-model change can legitimately flip 4,2 <-> 2,4); it must
    # simply be one of the configs the A/B actually timed (ADVICE r5)
    timed = {k.split(":", 1)[1] for k in t["configs"] if ":" in k} | {"1"}
    assert t["planner_pick"] in timed, (t["planner_pick"], sorted(timed))
    assert isinstance(t["hierarchy_win"], bool)
    if not t["hierarchy_win"]:
        # honesty requirement: a losing hierarchy must carry the analysis
        assert "analysis" in t and "asymmetry" in t["analysis"]
    assert "single-core host" in doc["timing_caveat"]
