"""The RPC front door: framing, taxonomy, dedup, deadlines, routing.

The cross-process serving contract, provable without a real cluster:

- **wire framing** (in-memory socketpair): roundtrip, torn/partial
  reads, CRC-trailer mismatch, oversized-frame refusal, and interleaved
  out-of-order responses multiplexed on one socket;
- **typed error taxonomy**: ``FT_RPC_TIMEOUT`` / ``FT_RPC_CONN_REFUSED``
  / ``FT_RPC_TORN_FRAME`` / ``FT_RPC_SHED`` pinned exactly the way
  ``FT_INIT_*`` is pinned in ``test_launch.py`` — these strings are the
  cross-process API and may not drift;
- **replica server** (real engine, in-process threads): idempotency
  dedup (a retried rid never re-executes), deadline refusal before
  execution, backlog shedding, SIGTERM drain refusals, torn-frame
  injection caught by the client CRC;
- **front door**: the arrival stamp written once at intake (injectable
  clock — TTFT includes queue + retry time), exponential backoff on the
  typed failures, circuit-breaker strike-out, intake shedding, hedging
  around a black-holed replica with first-result-wins, and the
  Prometheus export carrying per-replica windowed TTFT-p99 gauges plus
  the retry/hedge/shed/drain counters.

The kill-chaos floors (SIGKILL mid-decode, SIGSTOP stragglers, real
processes) live in ``tools/rpc_chaos.py`` → ``RPC_CHAOS.json``; this
file is the fast tier-1 gate underneath them.
"""

import socket
import struct
import threading
import time

import jax
import numpy as np
import pytest

from flextree_tpu.models.transformer import TransformerConfig, init_params
from flextree_tpu.runtime.ctrlfile import write_control_json
from flextree_tpu.serving import (
    BatcherConfig,
    FrontDoor,
    FrontDoorConfig,
    PagedCacheConfig,
    ReplicaClient,
    ReplicaConfig,
    ReplicaServer,
    RpcConnection,
    RpcConnRefused,
    RpcError,
    RpcShed,
    RpcTimeout,
    RpcTornFrame,
    ServingEngine,
)
from flextree_tpu.serving import frontdoor as frontdoor_mod
from flextree_tpu.serving.replica_main import ENDPOINT_FMT
from flextree_tpu.serving.rpc import (
    MAX_FRAME_BYTES,
    decode_frame_payload,
    encode_frame,
    recv_frame,
    send_frame,
)

# ---------------------------------------------------------------------------
# framing (no cluster, no jax compute: an in-memory socketpair)
# ---------------------------------------------------------------------------


class TestFraming:
    def test_roundtrip(self):
        a, b = socket.socketpair()
        try:
            send_frame(a, {"kind": "ping", "x": [1, 2, 3]})
            got = recv_frame(b)
            assert got == {"kind": "ping", "x": [1, 2, 3]}
        finally:
            a.close()
            b.close()

    def test_encode_decode_inverse(self):
        raw = encode_frame({"a": 1})
        (length,) = struct.unpack(">I", raw[:4])
        assert length == len(raw) - 4
        assert decode_frame_payload(raw[4:]) == {"a": 1}

    def test_torn_partial_read_is_typed(self):
        """A frame whose sender dies mid-payload is FT_RPC_TORN_FRAME,
        never a hang and never a half-parsed message."""
        a, b = socket.socketpair()
        try:
            raw = encode_frame({"kind": "generate", "rid": 1})
            a.sendall(raw[: len(raw) // 2])
            a.close()  # EOF mid-frame
            with pytest.raises(RpcTornFrame):
                recv_frame(b)
        finally:
            b.close()

    def test_crc_mismatch_refused(self):
        """One flipped body byte under an intact length header: the CRC
        trailer is the only defense, and it must fire."""
        raw = bytearray(encode_frame({"kind": "result", "tokens": [7, 8]}))
        raw[10] ^= 0xFF
        a, b = socket.socketpair()
        try:
            a.sendall(bytes(raw))
            with pytest.raises(RpcTornFrame) as ei:
                recv_frame(b)
            assert "FT_RPC_TORN_FRAME" in str(ei.value)
        finally:
            a.close()
            b.close()

    def test_oversized_frame_refused_before_read(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack(">I", MAX_FRAME_BYTES + 1))
            with pytest.raises(RpcTornFrame):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_zero_length_refused(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack(">I", 0))
            with pytest.raises(RpcTornFrame):
                recv_frame(b)
        finally:
            a.close()
            b.close()

    def test_eof_at_boundary_is_conn_refused(self):
        """A clean close between frames is the peer going away (conn
        refused), not a torn frame — the retry policy differs."""
        a, b = socket.socketpair()
        a.close()
        try:
            with pytest.raises(RpcConnRefused):
                recv_frame(b)
        finally:
            b.close()

    def test_trailer_mismatch_wrong_len(self):
        import json

        body = b'{"kind": "x"}\n'
        trailer = json.dumps({"len": 999, "crc32": "00000000"}).encode()
        with pytest.raises(RpcTornFrame):
            decode_frame_payload(body + trailer + b"\n")

    def test_interleaved_responses_one_socket(self):
        """Two calls multiplexed on one connection, answered in REVERSE
        order — each waiter gets its own reply by correlation id."""
        a, b = socket.socketpair()

        def server():
            try:
                first = recv_frame(b)
                second = recv_frame(b)
                send_frame(b, {"corr": second["corr"], "echo": second["v"]})
                send_frame(b, {"corr": first["corr"], "echo": first["v"]})
            except RpcError:
                pass

        t = threading.Thread(target=server, daemon=True)
        t.start()
        conn = RpcConnection(a)
        results = {}

        def call(v):
            results[v] = conn.call({"v": v}, timeout_s=5.0)

        t1 = threading.Thread(target=call, args=("one",), daemon=True)
        t1.start()
        time.sleep(0.05)  # order the sends: "one" first on the wire
        call("two")
        t1.join(timeout=5.0)
        t.join(timeout=5.0)
        assert results["one"]["echo"] == "one"
        assert results["two"]["echo"] == "two"
        conn.close()
        b.close()

    def test_torn_frame_fails_all_waiters(self):
        """A framing violation kills the connection: every outstanding
        call fails with the same typed error (a byte stream cannot be
        re-synchronized past a tear)."""
        a, b = socket.socketpair()
        conn = RpcConnection(a)
        errs = []

        def call():
            try:
                conn.call({"kind": "generate"}, timeout_s=5.0)
            except RpcError as e:
                errs.append(e)

        threads = [
            threading.Thread(target=call, daemon=True) for _ in range(2)
        ]
        for t in threads:
            t.start()
        time.sleep(0.05)
        raw = bytearray(encode_frame({"corr": 0}))
        raw[8] ^= 0xFF
        b.sendall(bytes(raw))
        for t in threads:
            t.join(timeout=5.0)
        assert len(errs) == 2
        assert all(isinstance(e, RpcTornFrame) for e in errs)
        assert isinstance(conn.dead, RpcTornFrame)
        with pytest.raises(RpcTornFrame):
            conn.call({"kind": "ping"}, timeout_s=1.0)
        conn.close()
        b.close()

    def test_call_timeout(self):
        a, b = socket.socketpair()
        conn = RpcConnection(a)
        try:
            with pytest.raises(RpcTimeout):
                conn.call({"kind": "ping"}, timeout_s=0.05)
        finally:
            conn.close()
            b.close()


# ---------------------------------------------------------------------------
# the taxonomy, pinned (the cross-process API surface)
# ---------------------------------------------------------------------------


class TestRpcErrorTaxonomy:
    """Mirror of test_launch.py's TestBringupErrorTaxonomy: these code
    strings travel on the wire and into artifacts — they may not drift."""

    def test_codes_pinned(self):
        assert RpcTimeout.code == "FT_RPC_TIMEOUT"
        assert RpcConnRefused.code == "FT_RPC_CONN_REFUSED"
        assert RpcTornFrame.code == "FT_RPC_TORN_FRAME"
        assert RpcShed.code == "FT_RPC_SHED"

    def test_hierarchy(self):
        for cls in (RpcTimeout, RpcConnRefused, RpcTornFrame, RpcShed):
            assert issubclass(cls, RpcError)
        assert issubclass(RpcError, RuntimeError)

    def test_str_leads_with_code(self):
        assert str(RpcTimeout("late")).startswith("FT_RPC_TIMEOUT")
        assert str(RpcShed()) == "FT_RPC_SHED"


# ---------------------------------------------------------------------------
# replica server semantics (real engine, in-process threads)
# ---------------------------------------------------------------------------

CFG = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64
)
PCFG = PagedCacheConfig(num_blocks=17, block_size=8, blocks_per_seq=4)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _server(params, dir, rank=0, **rkw):
    eng = ServingEngine(
        params, CFG, PCFG, BatcherConfig(slots=2), fused=False
    )
    srv = ReplicaServer(eng, ReplicaConfig(rank, str(dir), **rkw))
    return srv.start()


def _wait_dispatched(fd, rank, timeout_s) -> bool:
    """True once the front door has an attempt outstanding on ``rank``."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with fd._lock:
            client = fd.clients.get(rank)
        if client is not None:
            with client._lock:
                if client.outstanding > 0:
                    return True
        time.sleep(0.005)
    return False


def _dial(srv) -> RpcConnection:
    return RpcConnection.connect("127.0.0.1", srv.port, timeout_s=2.0)


class TestReplicaServer:
    def test_ping_and_endpoint_file(self, params, tmp_path):
        srv = _server(params, tmp_path)
        try:
            assert (tmp_path / ENDPOINT_FMT.format(rank=0)).exists()
            conn = _dial(srv)
            assert conn.call({"kind": "ping"}, timeout_s=2.0)["ok"]
            conn.close()
        finally:
            srv.stop()

    def test_idempotent_dedup_single_execution(self, params, tmp_path):
        """The exactly-once core: two attempts for one rid (a retry or a
        hedge twin) produce identical tokens from ONE execution."""
        srv = _server(params, tmp_path)
        conn = _dial(srv)
        try:
            payload = {
                "kind": "generate", "rid": 7, "prompt": [1, 2, 3, 4],
                "max_new_tokens": 4,
            }
            replies = {}

            def call(attempt):
                replies[attempt] = conn.call(
                    dict(payload, attempt=attempt), timeout_s=30.0
                )

            ts = [
                threading.Thread(target=call, args=(i,), daemon=True)
                for i in range(2)
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join(timeout=30.0)
            assert replies[0]["ok"] and replies[1]["ok"]
            assert replies[0]["tokens"] == replies[1]["tokens"]
            eng = srv.engine
            # one execution: submitted once, deduped at least once
            assert eng.metrics.counter("serve.submitted").value == 1
            assert eng.metrics.counter("serve.dedup_hits").value >= 1
            # and a third, late attempt answers from the completed store
            again = conn.call(dict(payload, attempt=9), timeout_s=5.0)
            assert again["tokens"] == replies[0]["tokens"]
        finally:
            conn.close()
            srv.stop()

    def test_expired_deadline_refused_before_execution(
        self, params, tmp_path
    ):
        """Deadline propagation: a request whose budget is already spent
        is refused with FT_RPC_TIMEOUT, never executed."""
        srv = _server(params, tmp_path)
        conn = _dial(srv)
        try:
            reply = conn.call(
                {
                    "kind": "generate", "rid": 1, "prompt": [1, 2],
                    "max_new_tokens": 4, "deadline_in_s": -0.5,
                },
                timeout_s=5.0,
            )
            assert reply["ok"] is False
            assert reply["code"] == "FT_RPC_TIMEOUT"
            eng = srv.engine
            assert eng.metrics.counter("serve.submitted").value == 0
            assert eng.metrics.counter("serve.deadline_refused").value == 1
        finally:
            conn.close()
            srv.stop()

    def test_backlog_shed(self, params, tmp_path):
        srv = _server(params, tmp_path, max_pending=0)
        conn = _dial(srv)
        try:
            reply = conn.call(
                {
                    "kind": "generate", "rid": 2, "prompt": [1],
                    "max_new_tokens": 2,
                },
                timeout_s=5.0,
            )
            assert reply["ok"] is False and reply["code"] == "FT_RPC_SHED"
            assert srv.engine.metrics.counter("serve.shed").value == 1
        finally:
            conn.close()
            srv.stop()

    def test_sigterm_drain_refuses_inflight(
        self, params, tmp_path, monkeypatch
    ):
        """Drain answers in-flight requests with a drain refusal (the
        front door re-queues them) instead of dropping them silently."""
        monkeypatch.setenv("FT_RPC_DECODE_SLEEP", "0.05")
        srv = _server(params, tmp_path)
        conn = _dial(srv)
        try:
            reply = {}

            def call():
                reply["r"] = conn.call(
                    {
                        "kind": "generate", "rid": 3, "prompt": [1, 2, 3],
                        "max_new_tokens": 24,
                    },
                    timeout_s=30.0,
                )

            t = threading.Thread(target=call, daemon=True)
            t.start()
            deadline = time.monotonic() + 10.0
            while (
                not srv.engine.metrics.counter("serve.submitted").value
                and time.monotonic() < deadline
            ):
                time.sleep(0.01)
            srv.initiate_drain()
            t.join(timeout=10.0)
            assert reply["r"].get("drain") is True
            assert srv.drained.wait(5.0)
            assert (
                srv.engine.metrics.counter("serve.drain_refusals").value >= 1
            )
            # post-drain arrivals are refused too
        finally:
            conn.close()
            srv.stop()

    def test_torn_frame_injection_caught_by_client(
        self, params, tmp_path, monkeypatch
    ):
        """FT_RPC_TEAR_EVERY=1 corrupts every response body under an
        intact length header — only the CRC trailer stands between the
        tear and a silently corrupt result, and it must catch it."""
        monkeypatch.setenv("FT_RPC_TEAR_EVERY", "1")
        srv = _server(params, tmp_path)
        conn = _dial(srv)
        try:
            with pytest.raises(RpcTornFrame):
                conn.call({"kind": "ping"}, timeout_s=5.0)
        finally:
            conn.close()
            srv.stop()


# ---------------------------------------------------------------------------
# front door: stamping, retries, breaker, shed, hedging, export
# ---------------------------------------------------------------------------


class _FakeReplica:
    """A scripted replica process stand-in (no engine, no jax): publishes
    a real endpoint file and answers per ``behavior(payload) -> reply``;
    ``behavior`` returning None black-holes the request (SIGSTOP twin)."""

    def __init__(self, dir: str, rank: int, behavior):
        self.rank = rank
        self.behavior = behavior
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.bind(("127.0.0.1", 0))
        self._listener.listen(8)
        self._listener.settimeout(0.2)
        self.port = self._listener.getsockname()[1]
        self._stop = threading.Event()
        self._conns = []
        path = f"{dir}/" + ENDPOINT_FMT.format(rank=rank)
        write_control_json(
            dir, path,
            {"rank": rank, "pid": 10_000 + rank, "host": "127.0.0.1",
             "port": self.port, "wall": time.time()},
        )
        self._thread = threading.Thread(target=self._accept, daemon=True)
        self._thread.start()

    def _accept(self):
        while not self._stop.is_set():
            try:
                conn, _ = self._listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            self._conns.append(conn)
            threading.Thread(
                target=self._serve, args=(conn,), daemon=True
            ).start()

    def _serve(self, conn):
        while not self._stop.is_set():
            try:
                payload = recv_frame(conn)
            except RpcError:
                return
            reply = self.behavior(payload)
            if reply is None:
                continue  # black hole
            try:
                send_frame(conn, dict(reply, corr=payload.get("corr")))
            except RpcError:
                return

    def stop(self):
        self._stop.set()
        self._listener.close()
        for c in self._conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass


def _ok_reply(rank):
    def behavior(payload):
        return {
            "ok": True, "rid": payload["rid"], "rank": rank,
            "tokens": [1, 2, 3], "ttft_s": 0.001, "decode_s": 0.0,
        }

    return behavior


class TestFrontDoor:
    def test_arrival_stamped_once_ttft_includes_retry_time(
        self, tmp_path, monkeypatch
    ):
        """The satellite contract, on an injectable clock: arrival is
        written exactly once at intake, and the delivered TTFT spans
        intake -> winning attempt's send (queue + retries) PLUS the
        replica-side queue-to-first-token time."""
        clock = {"t": 100.0}
        monkeypatch.setattr(frontdoor_mod, "_now", lambda: clock["t"])
        fd = FrontDoor(str(tmp_path), FrontDoorConfig(dispatchers=0))
        try:
            fd._arrival.setdefault(5, frontdoor_mod._now())
            clock["t"] = 103.0
            fd._arrival.setdefault(5, frontdoor_mod._now())  # a re-route
            assert fd._arrival[5] == 100.0  # stamped ONCE
            client = ReplicaClient(0, fd.cfg)
            fd._deliver(
                5, {"rid": 5, "rank": 0, "tokens": [9], "ttft_s": 0.25},
                client, send_mono=104.0, hedged=False,
            )
            # 4s of front-door queue/retries + 0.25s replica TTFT
            assert fd.completed[5].ttft_s == pytest.approx(4.25)
        finally:
            fd.close()

    def test_submit_stamps_arrival_once(self, tmp_path, monkeypatch):
        times = iter([10.0, 20.0, 30.0])
        monkeypatch.setattr(frontdoor_mod, "_now", lambda: next(times))
        fd = FrontDoor(str(tmp_path), FrontDoorConfig(dispatchers=0))
        try:
            fd.submit(1, [1, 2], 4)
            with fd._lock:
                fd._inflight.discard(1)  # simulate the dispatch cycle
            fd.submit(1, [1, 2], 4)  # a re-submit keeps the first stamp
            assert fd._arrival[1] == 10.0
        finally:
            fd.close()

    def test_intake_shed_accounted(self, tmp_path):
        fd = FrontDoor(
            str(tmp_path),
            FrontDoorConfig(dispatchers=0, shed_outstanding=0),
        )
        try:
            assert fd.submit(42, [1], 2) is False
            assert fd.shed_rids == [42]
            assert fd.metrics.counter("serve.shed").value == 1
        finally:
            fd.close()

    def test_retry_backoff_then_strikeout(self, tmp_path, monkeypatch):
        """Connect-refused attempts retry with exponential backoff and
        strike the breaker open; the rid fails with a typed code."""
        sleeps = []
        monkeypatch.setattr(
            frontdoor_mod, "_sleep", lambda s: sleeps.append(s)
        )
        # an endpoint nobody listens on: reserve a port, then close it
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()
        write_control_json(
            str(tmp_path), str(tmp_path / ENDPOINT_FMT.format(rank=0)),
            {"rank": 0, "pid": 1, "host": "127.0.0.1", "port": dead_port,
             "wall": time.time()},
        )
        cfg = FrontDoorConfig(
            dispatchers=1, max_attempts=2, breaker_strikes=2,
            breaker_cooldown_s=30.0,
            request_timeout_s=5.0, backoff_base_s=0.05, backoff_cap_s=0.2,
            max_hedges=0,
        )
        fd = FrontDoor(str(tmp_path), cfg).start()
        try:
            fd.submit(9, [1, 2], 4)
            deadline = time.monotonic() + 10.0
            while 9 not in fd.failed and time.monotonic() < deadline:
                time.sleep(0.01)
            assert fd.failed[9] in ("FT_RPC_RETRIES", "FT_RPC_TIMEOUT")
            assert fd.metrics.counter("serve.retries").value >= 2
            assert fd.metrics.counter("serve.breaker_opens").value >= 1
            assert fd.clients[0].breaker_open(frontdoor_mod._now())
            # backoff grew (exponential, capped)
            growing = [s for s in sleeps if s > 0]
            assert any(b > a for a, b in zip(growing, growing[1:]))
        finally:
            fd.close()

    def test_hedge_around_black_hole(self, tmp_path):
        """Rank 0 black-holes (a SIGSTOP straggler's signature); after
        the windowed-p99 hedge delay the twin attempt on rank 1 wins —
        without waiting out the primary's full attempt timeout."""
        stalled = _FakeReplica(str(tmp_path), 0, lambda p: None)
        healthy = _FakeReplica(str(tmp_path), 1, _ok_reply(1))
        cfg = FrontDoorConfig(
            dispatchers=1, attempt_timeout_s=20.0, request_timeout_s=30.0,
            hedge_min_samples=4, hedge_floor_s=0.05, max_hedges=1,
        )
        fd = FrontDoor(str(tmp_path), cfg).start()
        try:
            # prime the hedge trigger: recent attempts were ~10ms
            for _ in range(8):
                fd.metrics.histogram("serve.attempt_ms").observe(10.0)
            assert fd._hedge_delay_s() is not None
            t0 = time.monotonic()
            fd.submit(1, [1, 2, 3], 3)
            assert fd.wait_idle(timeout_s=15.0)
            elapsed = time.monotonic() - t0
            res = fd.completed[1]
            assert res.hedged and res.rank == 1
            assert list(res.tokens) == [1, 2, 3]
            assert fd.metrics.counter("serve.hedges").value == 1
            # the whole point: far faster than the 20s attempt timeout
            assert elapsed < 10.0
        finally:
            fd.close()
            stalled.stop()
            healthy.stop()

    def test_no_hedge_when_disabled(self, tmp_path):
        fd = FrontDoor(
            str(tmp_path), FrontDoorConfig(dispatchers=0, max_hedges=0)
        )
        try:
            for _ in range(20):
                fd.metrics.histogram("serve.attempt_ms").observe(10.0)
            assert fd._hedge_delay_s() is None
        finally:
            fd.close()

    def test_drain_reroutes_to_survivor(self, tmp_path):
        """A drain refusal is a re-route, not a failure: the request
        completes on the survivor and serve.drains counts the hop."""
        draining = _FakeReplica(
            str(tmp_path), 0,
            lambda p: {"ok": False, "drain": True, "rid": p["rid"]},
        )
        survivor = _FakeReplica(str(tmp_path), 1, _ok_reply(1))
        # make rank 0 the preferred first hop (least outstanding, lowest
        # rank) so the drain path actually executes
        fd = FrontDoor(
            str(tmp_path), FrontDoorConfig(dispatchers=1, max_hedges=0)
        ).start()
        try:
            fd.submit(4, [1], 3)
            assert fd.wait_idle(timeout_s=15.0)
            assert 4 in fd.completed
            assert fd.metrics.counter("serve.drains").value >= 1
        finally:
            fd.close()
            draining.stop()
            survivor.stop()

    def test_prometheus_export_per_replica_slo(self, tmp_path):
        """Satellite 6: per-replica windowed TTFT-p99 gauges and the
        retry/hedge/shed/drain counters, through the same exposition
        ``obs metrics DIR --prom`` renders."""
        fd = FrontDoor(str(tmp_path), FrontDoorConfig(dispatchers=0))
        try:
            client = ReplicaClient(0, fd.cfg)
            fd.clients[0] = client
            for v in (5.0, 7.0, 9.0):
                client.registry.histogram("serve.ttft_ms").observe(v)
                fd.metrics.histogram("serve.ttft_ms").observe(v)
            for name in (
                "serve.retries", "serve.hedges", "serve.shed",
                "serve.drains",
            ):
                fd.metrics.counter(name).inc()
            text = fd.prometheus()
            assert (
                'flextree_serve_ttft_ms_window_p99{rank="fd_00000"}' in text
            )
            assert (
                'flextree_serve_ttft_ms_window_p99{rank="frontdoor"}' in text
            )
            for name in (
                "serve_retries", "serve_hedges", "serve_shed",
                "serve_drains",
            ):
                assert f'flextree_{name}{{rank="frontdoor"}} 1' in text
            # and the on-disk export lands where `obs metrics` globs
            paths = fd.write_metrics(str(tmp_path))
            names = {p.rsplit("/", 1)[-1] for p in paths}
            assert "metrics_frontdoor.json" in names
            assert "metrics_fd_00000.json" in names
        finally:
            fd.close()

    def test_end_to_end_exactly_once_with_kill(self, params, tmp_path):
        """Two real in-process replica servers; one stops mid-run.  All
        requests complete exactly once, tokens bitwise vs the engine
        oracle (the full chaos version with SIGKILL on real processes
        lives in tools/rpc_chaos.py)."""
        from flextree_tpu.models.generate import generate

        srv0 = _server(params, tmp_path, rank=0)
        srv1 = _server(params, tmp_path, rank=1)
        cfg = FrontDoorConfig(
            dispatchers=2, max_hedges=0, request_timeout_s=60.0,
            attempt_timeout_s=30.0,
        )
        fd = FrontDoor(str(tmp_path), cfg).start()
        rng = np.random.default_rng(3)
        prompts = {
            i: rng.integers(0, CFG.vocab_size, (6,)).astype(np.int32)
            for i in range(4)
        }
        try:
            for rid, p in prompts.items():
                assert fd.submit(rid, p, 4)
            # yank one replica once it holds work in flight: its
            # connections die and the front door re-routes to the survivor
            assert _wait_dispatched(fd, rank=1, timeout_s=60.0)
            srv1.stop()
            assert fd.wait_idle(timeout_s=90.0)
            assert fd.failed == {}
            assert sorted(fd.completed) == sorted(prompts)
            for rid, p in prompts.items():
                oracle = np.asarray(
                    generate(params, p[None], CFG, max_new_tokens=4)
                )[0]
                assert np.array_equal(fd.completed[rid].tokens, oracle)
            # exactly-once: no duplicate deliveries even with re-routes
            assert (
                fd.metrics.counter("serve.duplicate_results").value == 0
            )
        finally:
            fd.close()
            srv0.stop()
            srv1.stop()
