"""Measured plan autotuner + fingerprinted caches (ISSUE 5).

The acceptance demo, with an injected fake timer so the assertions are
about the *machinery*, not the noisy host: the first run measures the
analytic top-K (shape x codec) candidates and persists the winner; the
second run is a pure cache hit (zero timer calls) that picks a plan no
slower than the analytic argmin's own measured time.  Plus the
calibration-side satellite: save/load embeds a backend fingerprint and
schema version so constants fitted on one host are never silently
reused on another.
"""

import json

import pytest

import jax

from flextree_tpu.planner import (
    CALIBRATION_SCHEMA,
    TpuCostParams,
    analytic_shortlist,
    autotune_plan,
    backend_fingerprint,
    choose_topology,
    load_calibration,
    plan_cache_key,
    save_calibration,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


def make_fake_timer(log, fastest_index=-1):
    """Deterministic injected timer: records calls, makes the candidate at
    ``fastest_index`` the measured winner."""

    def timer(cands, n, nbytes, dtype, repeat):
        log.append([c[:3] for c in cands])
        base = [0.010 + 0.001 * i for i in range(len(cands))]
        base[fastest_index] = 0.001
        return base

    return timer


class TestShortlist:
    def test_argmin_is_rank_zero(self):
        rows = analytic_shortlist(8, 1 << 20, top_k=6)
        best_by_codec = [
            (choose_topology(8, 1 << 20, codec=c).candidates[0], c)
            for c in ("f32", "bf16", "int8")
        ]
        overall = min(best_by_codec, key=lambda bc: bc[0].total_us)
        assert rows[0][0] == overall[0].widths
        assert rows[0][2] == overall[1]
        assert rows == sorted(rows, key=lambda r: r[3])

    def test_codec_changes_costing(self):
        f32 = analytic_shortlist(8, 4 << 20, codecs=("f32",), top_k=1)[0]
        int8 = analytic_shortlist(8, 4 << 20, codecs=("int8",), top_k=1)[0]
        assert int8[3] != f32[3]  # the codec term moved the prediction


class TestAutotune:
    def test_first_run_measures_second_is_cache_hit(self, tmp_path):
        path = str(tmp_path / "plans.json")
        log = []
        t1 = autotune_plan(
            8, 1 << 20, timer=make_fake_timer(log), cache_path=path, top_k=3
        )
        assert t1.source == "measured" and len(log) == 1 and len(log[0]) == 3
        # measured winner is never slower than the analytic argmin's own
        # measured time (the argmin is always in the shortlist)
        argmin_measured = t1.table[0][4]
        assert t1.measured_us <= argmin_measured
        # acceptance demo: second run is a PURE cache hit — no timing
        t2 = autotune_plan(
            8, 1 << 20, timer=make_fake_timer(log), cache_path=path, top_k=3
        )
        assert t2.source == "cache"
        assert len(log) == 1  # timer never called again
        assert (t2.widths, t2.lonely, t2.codec) == (t1.widths, t1.lonely, t1.codec)
        assert t2.measured_us == t1.measured_us

    def test_cache_key_separates_contexts(self, tmp_path):
        path = str(tmp_path / "plans.json")
        log = []
        autotune_plan(8, 1 << 20, timer=make_fake_timer(log), cache_path=path)
        autotune_plan(8, 1 << 18, timer=make_fake_timer(log), cache_path=path)
        autotune_plan(
            8, 1 << 20, timer=make_fake_timer(log), cache_path=path,
            dtype="bfloat16",
        )
        autotune_plan(
            8, 1 << 20, timer=make_fake_timer(log), cache_path=path,
            codecs=("f32",),
        )
        assert len(log) == 4  # nbytes / dtype / codec set all key separately
        autotune_plan(8, 1 << 20, timer=make_fake_timer(log), cache_path=path)
        assert len(log) == 4  # original key still hits

    def test_fingerprint_mismatch_is_a_miss(self, tmp_path):
        path = str(tmp_path / "plans.json")
        log = []
        autotune_plan(8, 1 << 20, timer=make_fake_timer(log), cache_path=path)
        with open(path) as f:
            doc = json.load(f)
        for entry in doc["entries"].values():
            entry["fingerprint"] = "tpu|v9|n4096|jax9.9.9"  # someone else's
        with open(path, "w") as f:
            json.dump(doc, f)
        autotune_plan(8, 1 << 20, timer=make_fake_timer(log), cache_path=path)
        assert len(log) == 2  # re-measured, not silently replayed

    def test_winner_is_executable(self, tmp_path):
        """The tuned plan's topology must resolve and its spec round-trip
        through the FT_TOPO grammar."""
        from flextree_tpu.schedule.stages import Topology

        t = autotune_plan(
            8, 1 << 20, timer=make_fake_timer([], fastest_index=0),
            cache_path=str(tmp_path / "p.json"),
        )
        resolved = Topology.resolve(8, t.to_ft_topo())
        assert resolved is not None and t.topology is not None

    def test_real_timer_smoke(self):
        """One tiny live-backend run through the default shuffled-
        interleaved timer: compiles the candidates, returns a measured
        winner.  Small payload + 2 candidates keeps this a smoke test,
        not a perf assertion."""
        t = autotune_plan(
            8, 1 << 12, top_k=2, repeat=2, codecs=("f32",), use_cache=False
        )
        assert t.source == "measured" and t.measured_us > 0


class TestTrainAutotuneKnob:
    def test_builder_resolves_topo_from_cache(self, tmp_path, monkeypatch):
        """TrainConfig.autotune wiring: the step builder resolves
        grad_topo through the plan cache (pre-seeded here, so no live
        measurement runs in the test)."""
        from flextree_tpu.models.transformer import TransformerConfig
        from flextree_tpu.parallel.train import (
            TrainConfig,
            make_mesh_nd,
            maybe_autotune_grad_topo,
        )

        path = str(tmp_path / "plans.json")
        monkeypatch.setenv("FLEXTREE_PLAN_CACHE", path)
        model_cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
        )
        mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
        # seed the cache for every (axis size 2) context the builder asks for
        import jax as _jax

        shapes = _jax.eval_shape(
            lambda k: __import__(
                "flextree_tpu.models.transformer", fromlist=["init_params"]
            ).init_params(k, model_cfg),
            _jax.random.PRNGKey(0),
        )
        nbytes = sum(
            l.size * l.dtype.itemsize for l in _jax.tree.leaves(shapes)
        )
        autotune_plan(
            2, nbytes, codecs=("f32",), top_k=3, repeat=3,
            timer=make_fake_timer([]), cache_path=path,
        )
        tc = maybe_autotune_grad_topo(
            mesh, model_cfg, TrainConfig(autotune=True), ("dp", "sp", "tp")
        )
        assert isinstance(tc.grad_topo, dict)
        assert set(tc.grad_topo) == {"dp", "sp", "tp"}
        assert not tc.autotune  # resolved once, not re-run per build

    def test_noop_without_flag_or_with_explicit_topo(self):
        from flextree_tpu.models.transformer import TransformerConfig
        from flextree_tpu.parallel.train import (
            TrainConfig,
            make_mesh_nd,
            maybe_autotune_grad_topo,
        )

        mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
        )
        tc = TrainConfig()
        assert maybe_autotune_grad_topo(mesh, cfg, tc, ("dp", "sp", "tp")) is tc
        tc2 = TrainConfig(autotune=True, grad_topo="2,2,2")
        assert (
            maybe_autotune_grad_topo(mesh, cfg, tc2, ("dp", "sp", "tp")) is tc2
        )


class TestCalibrationFingerprint:
    def test_roundtrip_same_host(self, tmp_path):
        path = str(tmp_path / "CALIBRATION.json")
        save_calibration(path, TpuCostParams(), backend="cpu", meta={"t": 1})
        with open(path) as f:
            doc = json.load(f)
        assert doc["cpu"]["schema"] == CALIBRATION_SCHEMA
        assert doc["cpu"]["fingerprint"] == backend_fingerprint()
        assert load_calibration(path, backend="cpu") == TpuCostParams()

    def test_foreign_fingerprint_rejected(self, tmp_path):
        path = str(tmp_path / "CALIBRATION.json")
        save_calibration(
            path, TpuCostParams(), backend="cpu",
            fingerprint="cpu|other-host|n64|jax0.0.1",
        )
        assert load_calibration(path, backend="cpu") is None
        # explicit matching fingerprint overrides the computed one
        assert (
            load_calibration(
                path, backend="cpu", fingerprint="cpu|other-host|n64|jax0.0.1"
            )
            == TpuCostParams()
        )

    def test_legacy_section_loads_with_warning(self, tmp_path):
        path = str(tmp_path / "CALIBRATION.json")
        legacy = {
            "cpu": {
                "params": {
                    "ici_bandwidth_GBps": 1.0, "ici_latency_us": 1.0,
                    "dcn_bandwidth_GBps": 1.0, "dcn_latency_us": 1.0,
                    "reduce_bw_GBps": 1.0, "control_us_per_width": 0.0,
                    "launch_us": 1.0,
                }
            }
        }
        with open(path, "w") as f:
            json.dump(legacy, f)
        # pre-fingerprint sections still load (the committed tpu_v5e
        # section is one) — with a warning on the repo logger, and the
        # codec term falls back to its default
        params = load_calibration(path, backend="cpu")
        assert params is not None
        assert params.codec_bw_GBps == TpuCostParams.codec_bw_GBps

    def test_newer_schema_rejected(self, tmp_path):
        path = str(tmp_path / "CALIBRATION.json")
        save_calibration(path, TpuCostParams(), backend="cpu")
        with open(path) as f:
            doc = json.load(f)
        doc["cpu"]["schema"] = CALIBRATION_SCHEMA + 1
        with open(path, "w") as f:
            json.dump(doc, f)
        assert load_calibration(path, backend="cpu") is None

    def test_plan_cache_key(self):
        assert plan_cache_key("a", 1, None, "x") == "a|1|~|x"
        fp = backend_fingerprint()
        assert fp is None or "|" in fp
