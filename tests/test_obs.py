"""Unified telemetry (flextree_tpu.obs): flight recorder, metrics
registry, cross-rank timeline merger — plus the ISSUE-10 satellite
contracts (result-file disambiguation, SpanLedger suffix parsing,
rank-aware logging)."""

from __future__ import annotations

import json
import logging
import math
import os
import threading

import numpy as np
import pytest

from flextree_tpu.obs import (
    FlightRecorder,
    MetricsRegistry,
    bucket_provenance,
    dump_current,
    flight_recorder,
    get_registry,
    merge_dir,
    merge_events,
    record_event,
    validate_trace,
    write_trace,
)
from flextree_tpu.obs.metrics import Histogram
from flextree_tpu.obs.recorder import current_recorder
from flextree_tpu.obs.timeline import read_dir, read_events


# ---------------------------------------------------------------- recorder


class TestFlightRecorder:
    def test_record_and_ring_bound(self, tmp_path):
        rec = FlightRecorder(tmp_path, rank=0, capacity=10, spill_every=3)
        for i in range(25):
            rec.record("tick", i=i)
        assert len(rec.events) == 10  # ring bounded
        assert rec.recorded == 25
        assert [e["i"] for e in rec.events] == list(range(15, 25))
        rec.close()
        # every event spilled to the JSONL, in seq order, none lost
        events = read_events(rec.event_path)
        assert [e["i"] for e in events] == list(range(25))
        assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)

    def test_flush_kind_spills_immediately(self, tmp_path):
        rec = FlightRecorder(tmp_path, rank=0, spill_every=1000)
        rec.record("step_start", step=0)
        # buffered: spill_every not reached, no flush kind yet
        assert read_events(rec.event_path) == []
        rec.record("step_end", step=0)  # FLUSH_KINDS member
        events = read_events(rec.event_path)
        assert [e["kind"] for e in events] == ["step_start", "step_end"]
        rec.close()

    def test_event_ordering_under_rotation_and_threads(self, tmp_path):
        rec = FlightRecorder(tmp_path, rank=3, capacity=16, spill_every=5)

        def worker(tid):
            for i in range(200):
                rec.record("tick", tid=tid, i=i)

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        rec.close()
        events = read_events(rec.event_path)
        assert len(events) == 800  # nothing lost to rotation
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs) and len(set(seqs)) == 800
        for tid in range(4):  # per-thread order preserved
            mine = [e["i"] for e in events if e["tid"] == tid]
            assert mine == list(range(200))

    def test_dump_sidecar(self, tmp_path):
        rec = FlightRecorder(tmp_path, rank=1, capacity=5)
        for i in range(8):
            rec.record("tick", i=i)
        path = rec.dump("test_failure", step=7)
        assert path and os.path.exists(path)
        with open(path) as f:
            dump = json.load(f)
        assert dump["reason"] == "test_failure"
        assert dump["rank"] == 1 and dump["step"] == 7
        # ring context: the last `capacity` events, incl. the marker
        assert dump["events"][-1]["kind"] == "dump"
        assert [e["i"] for e in dump["events"][:-1]] == [4, 5, 6, 7]
        rec.close()

    def test_memory_only_recorder(self):
        rec = FlightRecorder(None, rank=0)
        rec.record("tick")
        assert rec.dump("r") is None and rec.event_path is None

    def test_dump_nonblocking_skips_under_held_lock(self, tmp_path):
        # a signal handler runs ON the interrupted thread: if that frame
        # holds the recorder lock, the handler must skip, never block
        rec = FlightRecorder(tmp_path, rank=0)
        rec.record("tick")
        with rec._lock:
            assert rec.dump_nonblocking("signal", signum=15) is None
        # lock free again: the dump goes through
        path = rec.dump_nonblocking("signal", signum=15)
        assert path and os.path.exists(path)
        rec.close()

    def test_spill_failure_drops_batch_never_duplicates(self, tmp_path):
        rec = FlightRecorder(tmp_path, rank=0, spill_every=2)
        rec.record("a")

        class _FailOnce:
            def __init__(self, fh):
                self.fh, self.fail = fh, True

            def write(self, s):
                return self.fh.write(s)  # buffered write "succeeds"

            def flush(self):
                if self.fail:
                    self.fail = False
                    raise OSError("ENOSPC")
                return self.fh.flush()

            def close(self):
                return self.fh.close()

        rec._fh = _FailOnce(rec._fh)
        rec.record("b")  # spill_every hit -> flush raises -> batch dropped
        assert rec.spill_errors == 1
        rec.record("c")
        rec.record("d")  # next spill succeeds
        rec.close()
        events = read_events(rec.event_path)
        # no duplicated seq (the partially-landed batch is never
        # re-written); the dropped events are still in the ring
        seqs = [e["seq"] for e in events]
        assert len(seqs) == len(set(seqs))
        assert [e["kind"] for e in rec.events] == ["a", "b", "c", "d"]

    def test_ambient_install_and_noop(self, tmp_path):
        assert current_recorder() is None
        record_event("ignored")  # no recorder: must be a silent no-op
        assert dump_current("ignored") is None
        with flight_recorder(tmp_path, rank=2) as rec:
            assert current_recorder() is rec
            record_event("step_end", step=1)
            get_registry().counter("x").inc(3)
        assert current_recorder() is None and get_registry() is None
        events = read_events(rec.event_path)
        assert [e["kind"] for e in events] == ["step_end"]
        assert events[0]["rank"] == 2
        with open(tmp_path / "metrics_00002.json") as f:
            assert json.load(f)["counters"]["x"] == 3

    def test_nested_install_restores_outer(self, tmp_path):
        with flight_recorder(tmp_path / "a", rank=0) as outer:
            with flight_recorder(tmp_path / "b", rank=1) as inner:
                assert current_recorder() is inner
            assert current_recorder() is outer


# ----------------------------------------------------------------- metrics


class TestMetrics:
    def test_counter_gauge(self):
        reg = MetricsRegistry()
        reg.counter("a").inc()
        reg.counter("a").inc(2)
        reg.gauge("g").set(7.5)
        with pytest.raises(ValueError):
            reg.counter("a").inc(-1)
        with pytest.raises(TypeError):
            reg.gauge("a")  # kind mismatch is loud, never shadowed
        snap = reg.snapshot()
        assert snap["counters"]["a"] == 3
        assert snap["gauges"]["g"] == 7.5

    @pytest.mark.parametrize("dist", ["uniform", "lognormal", "exponential"])
    def test_percentiles_vs_numpy_oracle(self, dist):
        rng = np.random.default_rng(hash(dist) % (2**32))
        vals = {
            "uniform": rng.uniform(0, 90, 5000),
            "lognormal": rng.lognormal(1.0, 1.0, 5000),
            "exponential": rng.exponential(20.0, 5000),
        }[dist]
        h = Histogram()  # DEFAULT_MS_BUCKETS
        for v in vals:
            h.observe(v)
        edges = (0.0,) + h.edges
        for q in (50, 90, 95, 99):
            got = h.percentile(q)
            want = float(np.percentile(vals, q))
            # "within bucket resolution": the bucket containing the true
            # percentile bounds the error
            i = int(np.searchsorted(h.edges, want))
            lo = edges[i]
            hi = h.edges[i] if i < len(h.edges) else float(np.max(vals))
            width = hi - lo
            assert abs(got - want) <= width + 1e-9, (q, got, want, width)

    def test_percentile_edges(self):
        h = Histogram(buckets=(1.0, 10.0))
        assert math.isnan(h.percentile(50))
        h.observe(5.0)
        assert h.percentile(0) <= h.percentile(100) <= 10.0
        with pytest.raises(ValueError):
            h.percentile(101)

    def test_overflow_clamps_to_max(self):
        h = Histogram(buckets=(1.0,))
        for v in (50.0, 60.0, 70.0):
            h.observe(v)
        assert h.percentile(99) <= 70.0
        assert h.to_payload()["buckets"] == {"+inf": 3}

    def test_histogram_payload_schema(self):
        h = Histogram(buckets=(1.0, 2.0))
        h.observe(0.5)
        h.observe(1.5)
        p = h.to_payload()
        assert p["count"] == 2 and p["sum"] == 2.0
        assert p["min"] == 0.5 and p["max"] == 1.5
        assert set(p["buckets"]) == {"1.0", "2.0"}
        json.dumps(p)  # snapshot must be JSON-stable


# ---------------------------------------------------------------- timeline


def _mk(rank, seq, ts, kind, **fields):
    return {"ts": ts, "rank": rank, "src": "train", "seq": seq,
            "kind": kind, **fields}


class TestTimeline:
    def test_step_pairing_and_duration(self):
        doc = merge_events(
            [
                _mk(0, 0, 10.0, "step_start", step=0),
                _mk(0, 1, 10.25, "step_end", step=0),
                _mk(1, 0, 10.1, "step_start", step=0),
                _mk(1, 1, 10.2, "step_end", step=0),
            ]
        )
        assert validate_trace(doc) == []
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert {e["pid"] for e in xs} == {0, 1}
        r0 = next(e for e in xs if e["pid"] == 0)
        assert r0["dur"] == pytest.approx(0.25e6, rel=1e-3)

    def test_unfinished_step_surfaces(self):
        doc = merge_events(
            [
                _mk(0, 0, 1.0, "step_start", step=9),  # never finished
                _mk(0, 1, 1.5, "dump", reason="watchdog_timeout"),
            ]
        )
        assert validate_trace(doc) == []
        names = [e["name"] for e in doc["traceEvents"]]
        assert "step 9 (unfinished)" in names
        assert "dump" in names

    def test_bucket_provenance_span(self):
        prov = {"name": "ft_bucket0_dp_10leaves_4096B",
                "topo": {"dp": "4,2"}, "codec": "f32", "nbytes": 4096,
                "predicted_us": 123.4,
                "predicted": {"latency_us": 100.0, "bandwidth_us": 23.4}}
        doc = merge_events([_mk(0, 0, 5.0, "bucket_planned", **prov)])
        assert validate_trace(doc) == []
        span = next(
            e for e in doc["traceEvents"] if e.get("cat") == "comm-plan"
        )
        assert span["ph"] == "X" and span["dur"] == pytest.approx(123.4)
        assert span["args"]["topo"] == {"dp": "4,2"}
        assert span["args"]["predicted"]["latency_us"] == 100.0

    def test_request_flow(self):
        doc = merge_events(
            [
                _mk(0, 0, 1.0, "serve_admit", rid=5, slot=0),
                _mk(0, 1, 1.1, "serve_prefill", rid=5, slot=0),
                _mk(1, 0, 2.0, "serve_admit", rid=5, slot=1),  # re-route
                _mk(1, 1, 2.5, "serve_retire", rid=5, n_tokens=4),
            ]
        )
        assert validate_trace(doc) == []
        flows = [e for e in doc["traceEvents"] if e.get("cat") == "request"]
        phs = [e["ph"] for e in flows]
        assert phs[0] == "s" and phs[-1] == "f"
        assert {e["id"] for e in flows} == {5}

    def test_merge_dedups_identical_lines_keeps_restarted_seq(self):
        a = _mk(0, 0, 1.0, "step_start", step=0)
        b = _mk(0, 1, 1.2, "step_end", step=0)
        # same rank, seq restarted by a LATER process (different ts):
        # distinct events, must survive the dedup
        c = _mk(0, 0, 9.0, "step_start", step=5)
        d = _mk(0, 1, 9.1, "step_end", step=5)
        doc = merge_events([a, b, dict(a), dict(b), c, d])  # a/b duplicated
        assert validate_trace(doc) == []
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert sorted(e["name"] for e in xs) == ["step 0", "step 5"]
        assert doc["otherData"]["events"] == 4

    def test_validate_catches_garbage(self):
        assert validate_trace({"traceEvents": "nope"})
        assert validate_trace({"traceEvents": [{"ph": "?"}]})
        bad = validate_trace(
            {"traceEvents": [{"name": "x", "ph": "X", "ts": 0, "pid": 0,
                              "tid": 0, "dur": -1}]}
        )
        assert any("dur" in b for b in bad)
        bad = validate_trace(
            {"traceEvents": [{"name": "f", "ph": "f", "ts": 0, "pid": 0,
                              "tid": 0, "id": 1}]}
        )
        assert any("finish without start" in b for b in bad)

    def test_merge_dir_roundtrip_and_torn_tail(self, tmp_path):
        with flight_recorder(tmp_path, rank=0):
            record_event("step_start", step=0)
            record_event("step_end", step=0)
            dump_current("test")
        with flight_recorder(tmp_path, rank=1, source="peer"):
            record_event("step_start", step=0)
        # torn final line (SIGKILL mid-write): everything before survives
        with open(tmp_path / "flight_00001.jsonl", "a") as f:
            f.write('{"ts": 1.0, "kind": "tru')
        events, dumps = read_dir(str(tmp_path))
        assert {e["rank"] for e in events} == {0, 1}
        assert dumps[0]["reason"] == "test"
        doc = merge_events(events, dumps)
        assert validate_trace(doc) == []
        assert doc["otherData"]["dumps"]["0"]["reason"] == "test"
        out = write_trace(doc, tmp_path / "timeline.json")
        with open(out) as f:
            assert validate_trace(json.load(f)) == []
        assert validate_trace(merge_dir(str(tmp_path))) == []


# -------------------------------------------------------------- provenance


class TestProvenance:
    def test_none_when_no_recorder(self):
        from flextree_tpu.schedule.stages import Topology

        assert (
            bucket_provenance(("dp",), {"dp": Topology.resolve(8, "4,2")}, 1024)
            is None
        )

    def test_payload_with_recorder(self, tmp_path):
        from flextree_tpu.schedule.stages import Topology

        topos = {"dp": Topology.resolve(8, "4,2"), "sp": None}
        with flight_recorder(tmp_path, rank=0):
            prov = bucket_provenance(
                ("dp", "sp"), topos, 1 << 20, n_leaves=12, dtype="float32",
                chunks=2,
            )
        assert prov["topo"] == {"dp": "4,2", "sp": "psum"}
        assert prov["codec"] == "f32" and prov["nbytes"] == 1 << 20
        assert prov["predicted_us"] > 0
        assert set(prov["predicted"]) >= {"latency_us", "bandwidth_us"}
        json.dumps(prov)  # must be event-embeddable

    def test_lonely_and_ring_and_codec(self, tmp_path):
        from flextree_tpu.ops.quantize import get_codec
        from flextree_tpu.schedule.stages import Topology

        with flight_recorder(tmp_path, rank=0):
            ring = bucket_provenance(
                ("dp",), {"dp": Topology.resolve(8, "1")}, 4096
            )
            lonely = bucket_provenance(
                ("dp",), {"dp": Topology.resolve(8, "3,2+2")}, 4096,
                codec=get_codec("int8"),
            )
        assert ring["topo"]["dp"] == "ring" and ring["predicted_us"] > 0
        assert lonely["topo"]["dp"] == "3,2+2"
        assert lonely["codec"] == "int8" and lonely["predicted_us"] > 0


# -------------------------------------------------- fit + serving telemetry


class TestFitTelemetry:
    def _toy(self):
        class D:
            def batch_at(self, step):
                t = np.full((2, 4), float(step + 1))
                return t, t

        def step_fn(state, tokens, targets):
            s = int(np.asarray(state["step"]))
            loss = float("nan") if s == 2 else 0.5
            return (
                {"step": np.int64(s + 1), "w": np.asarray(state["w"]) - 1.0},
                {"loss": loss},
            )

        return D(), step_fn, {"step": np.int64(0), "w": np.zeros(2)}

    def test_fit_events_and_report_view(self, tmp_path):
        from flextree_tpu.parallel.loop import FitConfig, fit

        data, step_fn, state = self._toy()
        with flight_recorder(tmp_path / "obs", rank=0) as rec:
            result = fit(
                state, step_fn, data,
                FitConfig(num_steps=4, log_every=0, prefetch=0),
            )
        events = read_events(rec.event_path)
        kinds = [e["kind"] for e in events]
        assert kinds[0] == "fit_start" and kinds[-1] == "fit_end"
        assert kinds.count("step_start") == 4  # NaN step still started
        assert "nan_skip" in kinds
        # run_report is a view over the same registry
        m = result.report.metrics
        assert m["counters"]["train.anomalies"] == 1
        assert m["counters"]["train.steps"] == 4
        doc = merge_dir(str(tmp_path / "obs"))
        assert validate_trace(doc) == []
        # fit_start/fit_end pair into ONE span despite different step
        # fields (start=0, end=4), and a clean run has no forensic
        # "(unfinished)" markers
        names = [e["name"] for e in doc["traceEvents"]]
        assert names.count("fit 0") == 1
        fit_span = next(e for e in doc["traceEvents"] if e["name"] == "fit 0")
        assert fit_span["ph"] == "X"
        assert not any("(unfinished)" in n for n in names)

    def test_fit_without_recorder_unchanged(self):
        from flextree_tpu.parallel.loop import FitConfig, fit

        data, step_fn, state = self._toy()
        result = fit(
            state, step_fn, data,
            FitConfig(num_steps=4, log_every=0, prefetch=0),
        )
        assert result.report.metrics is None
        assert result.report.anomalies == 1

    def test_watchdog_timeout_dump(self, tmp_path):
        import time as _time

        from flextree_tpu.parallel.loop import FitConfig, Supervision, fit

        data, _, state = self._toy()
        hang = {1}

        def step_fn(state, tokens, targets):
            s = int(np.asarray(state["step"]))
            if s in hang:
                hang.discard(s)
                _time.sleep(1.5)
            return (
                {"step": np.int64(s + 1), "w": np.asarray(state["w"])},
                {"loss": 0.5},
            )

        with flight_recorder(tmp_path, rank=0) as rec:
            result = fit(
                state, step_fn, data,
                FitConfig(num_steps=3, log_every=0, prefetch=0),
                supervision=Supervision(
                    step_timeout_s=0.4, max_step_retries=1
                ),
            )
        assert result.report.step_timeouts == 1
        with open(rec.dump_path) as f:
            dump = json.load(f)
        assert dump["reason"] == "watchdog_timeout"
        kinds = [e["kind"] for e in read_events(rec.event_path)]
        assert "watchdog_timeout" in kinds and "dump" in kinds


# ------------------------------------------------------------- satellites


class TestResultFileDisambiguation:
    def test_same_second_names_differ(self, monkeypatch):
        import flextree_tpu.utils.logging as L

        monkeypatch.setattr(L.time, "time", lambda: 1234567890.0)
        a = L.result_file_name("tag", 8, 100, "4,2")
        b = L.result_file_name("tag", 8, 100, "4,2")
        assert a != b  # the seed-era scheme silently overwrote here
        # scheme positions preserved for field-indexed tooling
        for name in (a, b):
            parts = name.split(".")
            assert parts[:5] == ["tag", "8", "100", "4-2", "ar_test"]
            assert parts[5].startswith("1234567890-")
            assert parts[6] == "json"

    def test_monotonic_across_calls(self):
        from flextree_tpu.utils.logging import result_file_name

        seqs = [
            int(result_file_name("t", 1, 1, "").split(".")[5].split("-")[1])
            for _ in range(3)
        ]
        assert seqs == sorted(seqs) and len(set(seqs)) == 3


class TestSpanLedgerSuffix:
    def test_strict_bytes_suffix(self):
        from flextree_tpu.utils.profiling import SpanLedger, span_bytes

        ledger = SpanLedger()
        for name in (
            "ft_bucket0_dp_3leaves_4096B",   # counts: 4096
            "ft_bucket1_dp_2leaves_100B",    # counts: 100
            "ft_bucket2_dp_fooB",            # last token merely ends in B
            "ft_bucket3_dp_0xB",             # hex-ish garbage
            "ft_bucket4_dp_12B_extra",       # suffix not terminal
            "ft_bucket5_dp_B",               # no digits
        ):
            ledger.record(name)
        assert ledger.total_bytes("ft_bucket") == 4196
        assert span_bytes("x_77B") == 77
        assert span_bytes("x_fooB") is None
        assert span_bytes("x_8B_more") is None


class TestRankAwareLogging:
    def test_rank_field_from_env(self, monkeypatch, capsys):
        from flextree_tpu.utils.logging import get_logger, logger_rank

        monkeypatch.setenv("FT_RANK", "3")
        assert logger_rank() == 3
        log = get_logger("flextree.test_rank_env")
        log.error("hello")
        err = capsys.readouterr().err
        assert "r3" in err and "hello" in err

    def test_explicit_rank_and_absent(self, monkeypatch, capsys):
        from flextree_tpu.utils.logging import get_logger, logger_rank

        monkeypatch.delenv("FT_RANK", raising=False)
        assert logger_rank() is None
        get_logger("flextree.test_rank_exp", rank=7).error("seven")
        assert "r7" in capsys.readouterr().err
        get_logger("flextree.test_rank_none").error("bare")
        assert "r" + "0" not in capsys.readouterr().err.split("]")[0]

    def test_bad_env_value_is_none(self, monkeypatch):
        from flextree_tpu.utils.logging import logger_rank

        monkeypatch.setenv("FT_RANK", "not-a-rank")
        assert logger_rank() is None

    def teardown_method(self):
        # drop the uniquely-named test loggers' handlers
        for name in (
            "flextree.test_rank_env",
            "flextree.test_rank_exp",
            "flextree.test_rank_none",
        ):
            logging.getLogger(name).handlers.clear()


# ---------------------------------------------------- serving registry view


class TestServingTelemetry:
    @pytest.fixture()
    def engine(self):
        import jax

        from flextree_tpu.models.transformer import (
            TransformerConfig,
            init_params,
        )
        from flextree_tpu.serving.batcher import BatcherConfig
        from flextree_tpu.serving.engine import ServingEngine
        from flextree_tpu.serving.kv_cache import PagedCacheConfig

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64
        )
        params = init_params(jax.random.PRNGKey(0), cfg)
        pcfg = PagedCacheConfig(num_blocks=16, block_size=8, blocks_per_seq=4)
        return ServingEngine(params, cfg, pcfg, BatcherConfig(slots=2))

    def test_engine_metrics_and_events(self, engine, tmp_path):
        from flextree_tpu.serving.batcher import Request

        with flight_recorder(tmp_path, rank=0, source="serve") as rec:
            engine.submit(
                Request(rid=1, prompt=np.arange(4), max_new_tokens=3)
            )
            engine.run_until_idle()
        snap = engine.metrics.snapshot()
        assert snap["counters"]["serve.submitted"] == 1
        assert snap["counters"]["serve.finished"] == 1
        assert snap["histograms"]["serve.ttft_ms"]["count"] == 1
        report = engine.report()
        assert report["completed"] == 1
        assert report["counters"] == snap["counters"]  # report IS a view
        kinds = [e["kind"] for e in read_events(rec.event_path)]
        assert "serve_admit" in kinds and "serve_retire" in kinds
        doc = merge_dir(str(tmp_path))
        assert validate_trace(doc) == []
        flows = [
            e for e in doc["traceEvents"] if e.get("cat") == "request"
        ]
        assert [e["ph"] for e in flows] == ["s", "t", "f"]

    def test_pool_report_is_registry_view(self, engine):
        # pool counters are registry-backed; the legacy attributes read
        # the same numbers (pinned here so they can't diverge again)
        from flextree_tpu.serving.pool import PoolConfig, ReplicaPool

        import tempfile

        with tempfile.TemporaryDirectory() as hb:
            pool = ReplicaPool([engine], PoolConfig(heartbeat_dir=hb))
            try:
                from flextree_tpu.serving.batcher import Request

                pool.submit(
                    Request(rid=9, prompt=np.arange(4), max_new_tokens=2)
                )
                for _ in range(200):
                    if pool.idle:
                        break
                    pool.step()
                report = pool.report()
            finally:
                pool.shutdown()
        assert report["submitted"] == 1
        assert report["completed"] == 1
        assert report["metrics"]["counters"]["pool.submitted"] == 1
        assert pool.submitted == 1 and pool.reroutes == 0
        assert 0 in report["replica_metrics"]
        assert (
            report["replica_metrics"][0]["counters"]["serve.finished"] >= 1
        )


# -------------------------------------- per-step span clock (ISSUE 15)


def _prov(nb=4096, pred=None):
    pred = pred or {
        "latency_us": 30.0, "bandwidth_us": 8.0, "reduce_us": 2.0,
        "control_us": 1.0, "codec_us": 0.0,
    }
    return {
        "axes": ["dp"], "topo": {"dp": "8"}, "world": {"dp": 8},
        "nbytes": nb, "codec": "f32", "sharded": False,
        "predicted": pred, "predicted_us": sum(pred.values()),
    }


class TestPlanCapture:
    def test_capture_collects_provenance_spans_only(self):
        from flextree_tpu.utils.profiling import comm_span, plan_capture

        with plan_capture() as cap:
            with comm_span("ft_bucket0_dp_4096B", provenance=_prov()):
                pass
            with comm_span("bare_span_128B"):
                pass
        assert [name for name, _ in cap] == ["ft_bucket0_dp_4096B"]

    def test_nested_captures_both_record(self):
        from flextree_tpu.utils.profiling import comm_span, plan_capture

        with plan_capture() as outer:
            with plan_capture() as inner:
                with comm_span("ft_bucket0_dp_4096B", provenance=_prov()):
                    pass
        assert len(outer) == 1 and len(inner) == 1


class TestStepSpanClock:
    def test_plan_from_capture_groups_phases(self):
        from flextree_tpu.obs.stepclock import plan_from_capture

        plan = plan_from_capture(
            [("b0", _prov(4096)), ("b1", _prov(8192)),
             ("bad", {"predicted_error": True})]
        )
        assert len(plan.buckets) == 2
        assert plan.fixed_us == pytest.approx(2 * 31.0)
        assert plan.bytes_us == pytest.approx(2 * 10.0)
        assert plan.predicted_us == pytest.approx(2 * 41.0)

    def test_plan_sig_distinguishes_bucket_sizes(self):
        from flextree_tpu.obs.stepclock import plan_from_capture

        a = plan_from_capture([("b", _prov(4096))])
        b = plan_from_capture([("b", _prov(8192))])
        assert a.sig != b.sig

    def test_first_step_per_plan_is_dropped_as_compile(self, tmp_path):
        from flextree_tpu.obs.stepclock import StepSpanClock

        clock = StepSpanClock(compute_floor_us=100.0)
        clock.set_plan([("b", _prov())])
        assert clock.observe_step(0, 0.01) is None  # the compiling call
        assert clock.observe_step(1, 0.01) is not None
        clock.set_plan([("b", _prov(8192))])  # re-compile: drop again
        assert clock.observe_step(2, 0.01) is None
        assert clock.dropped_first == 2

    def test_events_carry_pairing_keys_and_breakdowns(self, tmp_path):
        from flextree_tpu.obs.stepclock import StepSpanClock

        with flight_recorder(tmp_path, rank=0):
            clock = StepSpanClock(compute_floor_us=1000.0, fingerprint="fp")
            clock.set_plan([("b0", _prov(4096)), ("b1", _prov(8192))])
            clock.observe_step(0, 0.002)
            clock.observe_step(1, 0.002)  # 2000us: comm = 1000us
        events, _ = read_dir(str(tmp_path))
        step_evs = [e for e in events if e["kind"] == "step_measured"]
        buck_evs = [e for e in events if e["kind"] == "bucket_measured"]
        assert len(step_evs) == 1 and len(buck_evs) == 2
        assert step_evs[0]["comm_us"] == pytest.approx(1000.0, rel=0.01)
        for ev in buck_evs:
            assert ev["per_step"] is True and ev["apportioned"] is True
            assert ev["topo"] == {"dp": "8"} and ev["world"] == {"dp": 8}
            assert isinstance(ev["predicted"], dict)
            assert ev["fingerprint"] == "fp"
        # equal predictions -> equal apportioned shares
        assert buck_evs[0]["measured_us"] == pytest.approx(
            buck_evs[1]["measured_us"]
        )
        assert sum(e["measured_us"] for e in buck_evs) == pytest.approx(
            1000.0, rel=0.01
        )

    def test_provisional_floor_tracks_quietest_step(self):
        from flextree_tpu.obs.stepclock import StepSpanClock

        clock = StepSpanClock()  # no configured floor
        clock.set_plan([("b", _prov())])  # predicted_us = 41
        clock.observe_step(0, 0.001)
        assert clock.floor_us is None  # compile dropped: no evidence yet
        clock.observe_step(1, 0.002)
        clock.observe_step(2, 0.001)
        # floor = min(step_us - predicted) = 1000 - 41
        assert clock.floor_us == pytest.approx(1000.0 - 41.0, rel=0.01)


class TestStepMeasuredTimeline:
    def test_per_step_measured_spans_pair_with_plan_spans(self):
        prov = _prov(4096)
        events = [
            {"ts": 1.0, "rank": 0, "seq": 0, "kind": "bucket_planned",
             "name": "ft_bucket0_dp_4096B", **prov},
            {"ts": 2.0, "rank": 0, "seq": 1, "kind": "bucket_measured",
             "name": "ft_bucket0_dp_4096B", "topo": {"dp": "8"},
             "world": {"dp": 8}, "nbytes": 4096, "codec": "f32",
             "sharded": False, "measured_us": 55.0, "predicted_us": 41.0,
             "predicted": prov["predicted"], "per_step": True,
             "apportioned": True, "step": 3},
            {"ts": 3.0, "rank": 0, "seq": 2, "kind": "step_measured",
             "step": 3, "step_us": 2000.0, "floor_us": 1000.0,
             "comm_us": 1000.0, "predicted_us": 41.0, "plan_sig": "ab",
             "n_buckets": 1},
        ]
        doc = merge_events(events)
        assert validate_trace(doc) == []
        plan = [e for e in doc["traceEvents"] if e.get("cat") == "comm-plan"]
        meas = [e for e in doc["traceEvents"]
                if e.get("cat") == "comm-measured"]
        step = [e for e in doc["traceEvents"]
                if e.get("cat") == "step-measured"]
        assert len(plan) == len(meas) == len(step) == 1
        # the pairing: same name, same rank track, measured span carries
        # the prediction + per-phase breakdown in its args
        assert meas[0]["name"] == plan[0]["name"]
        assert meas[0]["pid"] == plan[0]["pid"] == 0
        assert meas[0]["dur"] == pytest.approx(55.0)
        assert meas[0]["args"]["predicted_us"] == 41.0
        assert isinstance(meas[0]["args"]["predicted"], dict)
        assert step[0]["dur"] == pytest.approx(2000.0)

    def test_residual_pairs_tags_step_source_and_breakdown(self):
        prov = _prov(4096)
        events = [
            {"ts": 1.0, "rank": 0, "seq": 0, "kind": "bucket_planned",
             "name": "b", **prov},
            {"ts": 2.0, "rank": 0, "seq": 1, "kind": "bucket_measured",
             "topo": {"dp": "8"}, "world": {"dp": 8}, "nbytes": 4096,
             "codec": "f32", "sharded": False, "measured_us": 55.0,
             "predicted_us": 41.0, "predicted": prov["predicted"],
             "per_step": True},
        ]
        from flextree_tpu.obs.timeline import residual_pairs

        samples, _skipped = residual_pairs(events)
        assert len(samples) == 1
        assert samples[0].source == "step"
        assert samples[0].phases == {
            "fixed": pytest.approx(31.0),
            "bytes": pytest.approx(10.0),
            "codec": pytest.approx(0.0),
        }


class TestPrometheusExposition:
    def test_counters_gauges_histograms(self):
        from flextree_tpu.obs.metrics import (
            MetricsRegistry,
            prometheus_exposition,
        )

        reg = MetricsRegistry()
        reg.counter("serve.finished").inc(3)
        reg.gauge("serve.free_blocks").set(17)
        h = reg.windowed_histogram(
            "serve.ttft_ms", buckets=(1.0, 10.0, 100.0), interval_s=1.0,
            intervals=4,
        )
        for v in (0.5, 5.0, 50.0, 50.0):
            h.observe(v, now=100.0)
        text = prometheus_exposition({"0": reg.snapshot()})
        assert "# TYPE flextree_serve_finished counter" in text
        assert 'flextree_serve_finished{rank="0"} 3' in text
        assert 'flextree_serve_free_blocks{rank="0"} 17' in text
        assert "# TYPE flextree_serve_ttft_ms histogram" in text
        # cumulative buckets, not per-bucket counts
        assert 'flextree_serve_ttft_ms_bucket{rank="0",le="1.0"} 1' in text
        assert 'flextree_serve_ttft_ms_bucket{rank="0",le="10.0"} 2' in text
        assert 'flextree_serve_ttft_ms_bucket{rank="0",le="100.0"} 4' in text
        assert 'flextree_serve_ttft_ms_bucket{rank="0",le="+Inf"} 4' in text
        assert 'flextree_serve_ttft_ms_count{rank="0"} 4' in text
        # the windowed SLO view is scrapeable as a gauge
        assert "flextree_serve_ttft_ms_window_count" in text

    def test_name_sanitization(self):
        from flextree_tpu.obs.metrics import _prom_name

        assert _prom_name("serve.ttft_ms") == "flextree_serve_ttft_ms"
        assert _prom_name("a-b/c d") == "flextree_a_b_c_d"

    def test_metrics_cli_prom(self, tmp_path, capsys):
        from flextree_tpu.obs.__main__ import main
        from flextree_tpu.obs.metrics import MetricsRegistry

        reg = MetricsRegistry()
        reg.counter("train.steps").inc(5)
        (tmp_path / "metrics_0.json").write_text(json.dumps(reg.snapshot()))
        assert main(["metrics", str(tmp_path), "--prom"]) == 0
        out = capsys.readouterr().out
        assert 'flextree_train_steps{rank="0"} 5' in out
        assert main(["metrics", str(tmp_path)]) == 0
        assert "train.steps" in capsys.readouterr().out
        empty = tmp_path / "empty"
        empty.mkdir()
        assert main(["metrics", str(empty), "--prom"]) == 1


class TestResidualsCLIFilters:
    def _write_events(self, dir, fingerprint, spec="8", sizes=(4096, 65536)):
        os.makedirs(dir, exist_ok=True)
        with open(os.path.join(dir, "flight_0.jsonl"), "w") as f:
            for i, nb in enumerate(sizes):
                pred = {
                    "latency_us": 30.0, "bandwidth_us": nb / 1000.0,
                    "reduce_us": nb / 4000.0, "control_us": 1.0,
                    "codec_us": 0.0,
                }
                ev = {
                    "ts": float(i), "rank": 0, "seq": i,
                    "kind": "bucket_measured", "topo": {"dp": spec},
                    "world": {"dp": 8}, "nbytes": nb, "codec": "f32",
                    "sharded": False, "measured_us": sum(pred.values()) * 2,
                    "predicted_us": sum(pred.values()), "predicted": pred,
                    "fingerprint": fingerprint,
                }
                f.write(json.dumps(ev) + "\n")

    def test_json_and_fingerprint_filter(self, tmp_path, capsys):
        from flextree_tpu.obs.__main__ import main

        self._write_events(str(tmp_path), "fpA")
        assert main(["residuals", str(tmp_path), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert len(doc["samples"]) == 2
        assert doc["samples"][0]["phases"]["fixed"] == pytest.approx(31.0)
        assert main(
            ["residuals", str(tmp_path), "--fingerprint", "nope", "--json"]
        ) == 0
        assert json.loads(capsys.readouterr().out)["samples"] == []

    def test_table_has_phase_columns(self, tmp_path, capsys):
        from flextree_tpu.obs.__main__ import main

        self._write_events(str(tmp_path), "fpA")
        assert main(["residuals", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "phases f/b/c" in out and "drift" in out

    def test_fleet_pools_across_dirs(self, tmp_path, capsys):
        from flextree_tpu.obs.__main__ import main

        # each run alone is one shape at two sizes (refuses to fit);
        # pooled across shapes the phase fit answers
        sizes = (4096, 65536, 1 << 20)
        self._write_events(str(tmp_path / "r0"), "fp", spec="8", sizes=sizes)
        self._write_events(str(tmp_path / "r1"), "fp", spec="4,2",
                           sizes=sizes)
        rc = main(["fleet", str(tmp_path / "r0"), str(tmp_path / "r1"),
                   "--json"])
        doc = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert doc["pooled"]["fp"]["condition"] is not None
        assert doc["pooled"]["fp"]["samples"] == 6
        assert doc["pooled"]["fp"]["runs"] == 2

    def test_fleet_fit_out_persists_calibration(self, tmp_path, capsys):
        from flextree_tpu.obs.__main__ import main

        sizes = (4096, 65536, 1 << 20)
        self._write_events(str(tmp_path / "r0"), "fp", spec="8", sizes=sizes)
        self._write_events(str(tmp_path / "r1"), "fp", spec="4,2",
                           sizes=sizes)
        out_path = tmp_path / "CAL.json"
        rc = main([
            "fleet", str(tmp_path / "r0"), str(tmp_path / "r1"),
            "--fit-out", str(out_path), "--backend", "cpu", "--json",
        ])
        assert rc == 0
        capsys.readouterr()
        doc = json.loads(out_path.read_text())
        assert doc["cpu"]["source"] == "feedback"
        assert doc["cpu"]["fingerprint"] == "fp"
        assert doc["cpu"]["meta"]["fleet"]["samples"] == 6
