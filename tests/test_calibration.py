"""Cost-model calibration: the fitted model must *predict measured
orderings* — the property the reference's hand-calibrated constants
implicitly had (``cost_model/CostModel.h:1-30``) and round 1's invented
defaults did not.

Validation: Spearman rank correlation >= 0.8 between
predicted and measured times over 5 shapes x 2 sizes on the 8-vdev mesh,
and the planner's argmin must be the measured winner or within noise of it.
"""

import numpy as np
import pytest

import jax

from flextree_tpu.bench import measure_points
from flextree_tpu.planner import (
    choose_topology,
    fit_cost_params,
    predict_us,
    spearman,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)

TOPOS = ["8", "4,2", "2,4", "2,2,2", "1"]
SIZES = [1 << 16, 1 << 18, 1 << 20]  # 256 KB, 1 MB, 4 MB float32


@pytest.fixture(scope="module")
def fitted():
    # median-of-10 per point: min-of-3 on a timeshared single-core host is
    # noise-bound and produced an unreproducible fit
    points = measure_points(TOPOS, SIZES, repeat=10, devices=8, stat="median")
    params = fit_cost_params(points)
    return points, params


@pytest.mark.perf
def test_fitted_model_rank_correlates(fitted):
    points, params = fitted
    measured = [p.measured_us for p in points]
    predicted = [
        predict_us(params, p.widths, p.num_nodes, p.nbytes) for p in points
    ]
    detail = "\n".join(
        f"  {p.widths} @ {p.nbytes >> 10}KB: measured {m:.0f}us "
        f"(+-{p.noise_us:.0f}), predicted {q:.0f}us"
        for p, m, q in zip(points, measured, predicted)
    )
    # Non-degeneracy first: the fit must actually discriminate shapes at
    # each size, by more than the measurement noise — otherwise the rank
    # assertion below would be judging tie-broken noise (an earlier
    # fit predicted a 1.17x spread where measurements spread
    # 1.9x, i.e. the shape features had been zeroed out).
    for nb in sorted({p.nbytes for p in points}):
        idx = [i for i, p in enumerate(points) if p.nbytes == nb]
        pred_spread = max(predicted[i] for i in idx) - min(
            predicted[i] for i in idx
        )
        noise = float(np.median([points[i].noise_us for i in idx]))
        assert pred_spread > max(noise, 1e-9), (
            f"degenerate fit at {nb >> 10}KB: predicted spread "
            f"{pred_spread:.0f}us <= noise {noise:.0f}us\n{detail}"
        )
    rho = spearman(predicted, measured)
    assert rho >= 0.8, f"Spearman {rho:.3f} < 0.8\n{detail}"


@pytest.mark.perf
def test_planner_argmin_is_measured_winner(fitted):
    points, params = fitted
    for nbytes in [s * 4 for s in SIZES]:
        plan = choose_topology(8, nbytes, params=params)
        chosen = plan.widths
        at_size = [p for p in points if p.nbytes == nbytes]
        best = min(at_size, key=lambda p: p.measured_us)
        chosen_meas = next(
            (p.measured_us for p in at_size if p.widths == chosen), None
        )
        assert chosen_meas is not None, f"planner chose unmeasured {chosen}"
        # winner, or within 15% of the winner (measurement noise on a
        # timeshared single-core host)
        assert chosen_meas <= best.measured_us * 1.15, (
            f"planner chose {chosen} ({chosen_meas:.0f}us) but measured "
            f"winner is {best.widths} ({best.measured_us:.0f}us)"
        )


def test_fit_recovers_synthetic_constants():
    """Fit on model-generated data must recover the generating ordering
    exactly (pure math, no devices)."""
    from flextree_tpu.planner import LinkParams, TpuCostParams
    from flextree_tpu.planner.calibrate import MeasuredPoint

    true = TpuCostParams(
        ici=LinkParams(bandwidth_GBps=2.0, latency_us=50.0),
        dcn=LinkParams(bandwidth_GBps=2.0, latency_us=50.0),
        reduce_bw_GBps=8.0,
        control_us_per_width=0.0,
        launch_us=400.0,
    )
    shapes = [(8,), (4, 2), (2, 4), (2, 2, 2), (1,)]
    pts = [
        MeasuredPoint(w, 8, nb, predict_us(true, w, 8, nb))
        for w in shapes
        for nb in [1 << 18, 1 << 22]
    ]
    fit = fit_cost_params(pts)
    for p in pts:
        got = predict_us(fit, p.widths, p.num_nodes, p.nbytes)
        assert abs(got - p.measured_us) <= 0.05 * p.measured_us + 1.0


def test_fit_quality_under_noise_deterministic():
    """Fit on noise-corrupted model data must still rank shapes correctly
    (the live rank tests are opt-in ``perf``; this pins
    fit *quality* in every default run, deterministically).

    Seeded +-15% multiplicative noise on every point — comparable to the
    rep-to-rep spread observed on this host — then assert the fitted
    model's predictions rank-correlate with the TRUE (noise-free) costs.
    A fit that zeroes the shape-discriminating features (the degenerate
    round-2 failure) flattens the prediction spread and fails the rho
    bound."""
    from flextree_tpu.planner import LinkParams, TpuCostParams
    from flextree_tpu.planner.calibrate import MeasuredPoint

    true = TpuCostParams(
        ici=LinkParams(bandwidth_GBps=2.0, latency_us=50.0),
        dcn=LinkParams(bandwidth_GBps=2.0, latency_us=50.0),
        reduce_bw_GBps=8.0,
        control_us_per_width=0.0,
        launch_us=400.0,
    )
    shapes = [(8,), (4, 2), (2, 4), (2, 2, 2), (1,)]
    sizes = [1 << 16, 1 << 18, 1 << 20, 1 << 22]
    rng = np.random.default_rng(20260730)
    pts = [
        MeasuredPoint(
            w, 8, nb,
            predict_us(true, w, 8, nb) * float(rng.uniform(0.85, 1.15)),
        )
        for w in shapes
        for nb in sizes
    ]
    fit = fit_cost_params(pts)
    truth = [predict_us(true, p.widths, p.num_nodes, p.nbytes) for p in pts]
    pred = [predict_us(fit, p.widths, p.num_nodes, p.nbytes) for p in pts]
    rho = spearman(pred, truth)
    assert rho >= 0.9, f"Spearman vs true costs {rho:.3f} < 0.9"
    # per-size rank quality is the planner's actual job (argmin at a size)
    for nb in sizes:
        idx = [i for i, p in enumerate(pts) if p.nbytes == nb]
        rho_s = spearman([pred[i] for i in idx], [truth[i] for i in idx])
        assert rho_s >= 0.8, f"per-size Spearman {rho_s:.3f} < 0.8 at {nb}B"


def test_fit_quality_on_recorded_timings():
    """Fit on a committed recording of real 8-vdev measurements (one
    ``measure_points`` run on this host, ``tests/data/
    recorded_points_cpu8.json``) and assert rank correlation of predicted
    vs recorded cost — real-world noise, fully deterministic re-run."""
    import json
    import os

    from flextree_tpu.planner.calibrate import MeasuredPoint

    path = os.path.join(
        os.path.dirname(__file__), "data", "recorded_points_cpu8.json"
    )
    with open(path) as f:
        doc = json.load(f)
    pts = [
        MeasuredPoint(
            tuple(d["widths"]), d["num_nodes"], d["nbytes"],
            d["measured_us"], tuple(d.get("times_us", ())),
        )
        for d in doc["points"]
    ]
    fit = fit_cost_params(pts)
    measured = [p.measured_us for p in pts]
    pred = [predict_us(fit, p.widths, p.num_nodes, p.nbytes) for p in pts]
    rho = spearman(pred, measured)
    detail = "\n".join(
        f"  {p.widths} @ {p.nbytes >> 10}KB: recorded {m:.0f}us, "
        f"predicted {q:.0f}us"
        for p, m, q in zip(pts, measured, pred)
    )
    assert rho >= 0.8, f"Spearman {rho:.3f} < 0.8 on recorded points\n{detail}"


# ---------------------------------------------------------------- persistence


def test_calibration_roundtrip(tmp_path):
    """save_calibration/load_calibration preserve every constant, per
    backend, and merge sections instead of clobbering the file."""
    from flextree_tpu.planner import (
        LinkParams,
        TpuCostParams,
        load_calibration,
        save_calibration,
    )

    path = tmp_path / "CALIBRATION.json"
    p_cpu = TpuCostParams(
        ici=LinkParams(1.25, 10.0), dcn=LinkParams(1.25, 10.0),
        reduce_bw_GBps=2.5, control_us_per_width=0.0, launch_us=61.4,
    )
    p_tpu = TpuCostParams(reduce_bw_GBps=600.0)
    save_calibration(path, p_cpu, backend="cpu", meta={"src": "test"})
    save_calibration(path, p_tpu, backend="tpu_v5e")
    got_cpu = load_calibration(path, backend="cpu")
    got_tpu = load_calibration(path, backend="tpu_v5e")
    assert got_cpu == p_cpu
    assert got_tpu == p_tpu
    assert load_calibration(path, backend="nope") is None
    assert load_calibration(tmp_path / "missing.json", backend="cpu") is None


def test_choose_topology_loads_calibration_from_env(tmp_path, monkeypatch):
    """With $FLEXTREE_CALIBRATION set, a bare choose_topology() prices with
    the measured constants: a huge launch cost must steer the argmin to
    the fewest-stage (flat) shape even at sizes where the invented
    defaults would pick otherwise."""
    from flextree_tpu.planner import (
        LinkParams,
        TpuCostParams,
        choose_topology,
        save_calibration,
    )

    path = tmp_path / "CALIBRATION.json"
    # launch-dominated host (like this repo's 1-core CI): 10 ms per
    # collective dwarfs everything else
    save_calibration(
        path,
        TpuCostParams(
            ici=LinkParams(1.0, 10.0), dcn=LinkParams(1.0, 10.0),
            reduce_bw_GBps=2.0, control_us_per_width=0.0, launch_us=10_000.0,
        ),
        backend="cpu",
    )
    monkeypatch.setenv("FLEXTREE_CALIBRATION", str(path))
    monkeypatch.setenv("FLEXTREE_CALIBRATION_BACKEND", "cpu")
    plan = choose_topology(8, 1 << 22)
    assert plan.widths == (8,), plan.summary()
    # without the env var the same call must return to the invented
    # defaults — compare against an EXPLICIT default-params plan so a
    # regression that kept consulting the file cannot pass vacuously
    monkeypatch.delenv("FLEXTREE_CALIBRATION")
    base = choose_topology(8, 1 << 22)
    explicit = choose_topology(8, 1 << 22, params=TpuCostParams())
    assert base.summary() == explicit.summary()
    # a backend with no section (and no prefix match) must fall back to
    # the invented defaults, never guess another section
    monkeypatch.setenv("FLEXTREE_CALIBRATION", str(path))
    from flextree_tpu.planner import default_params

    assert default_params(backend="gpu") == TpuCostParams()


def test_planner_cli_calibration_flag(tmp_path, capsys):
    from flextree_tpu.planner import TpuCostParams, save_calibration
    from flextree_tpu.planner.__main__ import main

    path = tmp_path / "CALIBRATION.json"
    save_calibration(
        path, TpuCostParams(launch_us=10_000.0), backend="cpu"
    )
    rc = main(["--n", "8", "--size-mb", "4", "--calibration", str(path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "FT_TOPO=8" in out  # launch-dominated -> flat


def test_load_calibration_platform_prefix_fallback(tmp_path):
    """backend='tpu' (what jax.default_backend() says) must find the file's
    more specific 'tpu_v5e' section — unless two tpu_* sections make the
    choice ambiguous."""
    from flextree_tpu.planner import (
        TpuCostParams,
        load_calibration,
        save_calibration,
    )

    path = tmp_path / "CALIBRATION.json"
    p = TpuCostParams(reduce_bw_GBps=612.0)
    save_calibration(path, p, backend="tpu_v5e")
    assert load_calibration(path, backend="tpu") == p
    save_calibration(path, TpuCostParams(reduce_bw_GBps=1000.0), backend="tpu_v6e")
    assert load_calibration(path, backend="tpu") is None  # ambiguous
