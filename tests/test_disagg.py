"""Prefill/decode disaggregation: quantized KV migration (ISSUE 20).

The decisive properties, in dependency order:

- **pack/unpack is bitwise for f32** at EVERY block-boundary offset —
  one partial block, exact boundaries, mid-block tails — and int8 stays
  inside the codec's single-hop ``error_bound``;
- **a poisoned payload is refused, never admitted**: CRC flips, shape
  lies, truncation, and duplicate tensor entries all raise
  ``MigrationError`` (``FT_MIGRATION_REFUSED``) out of ``unpack_kv``;
- **export blocks release on ack, never before**: the prefill engine
  holds ``blocks_for(prompt)`` blocks under ``_exported`` from
  ``prefill_for_migration`` until ``release_exported``, on both the ack
  and the abort edge, exactly once;
- **the migrated sequence is the colocated sequence**: engine A
  prefill + export, engine B admit + decode produces tokens bitwise
  equal to one colocated engine (and contiguous ``generate``) for both
  codecs — int8's quantization error is provably under the greedy
  decision threshold at this scale (the bench re-checks it per run);
- **the planner's crossover is the routing threshold**: short prompts
  never migrate, the crossover is exactly where ``plan_migration``
  flips, wire bytes are monotone in prompt length and int8 ships less
  than f32;
- **the front door accounts by role**: a prefill-tier shed never
  consumes decode capacity (and vice versa), prefill routing weighs
  replica-reported queue depth, and dedicated prefill replicas never
  receive plain generates;
- **the handoff renders as a flow arrow**: ``serve_migration_send`` /
  ``serve_migration_recv`` ride the rid's request flow across replica
  tracks in the merged timeline;
- **scale-down respects role floors**: the arbiter withholds a loaned
  chip whose reclaim would strand prefill or decode below its tenancy
  floor.

What the handoff costs and buys on the chip is not measured (ROADMAP.md
Queue 2, W4).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flextree_tpu.models.generate import generate
from flextree_tpu.models.transformer import TransformerConfig, init_params
from flextree_tpu.obs.timeline import merge_events, validate_trace
from flextree_tpu.ops.quantize import get_codec
from flextree_tpu.serving import (
    BatcherConfig,
    ContinuousBatcher,
    PagedCacheConfig,
    Request,
    ServingEngine,
)
from flextree_tpu.serving.costs import (
    migration_crossover_tokens,
    plan_migration,
    predict_migration_us,
)
from flextree_tpu.serving.frontdoor import FrontDoor, FrontDoorConfig
from flextree_tpu.serving.kv_cache import export_blocks, write_imported
from flextree_tpu.serving.migration import (
    MigrationError,
    migration_error_bound,
    pack_kv,
    unpack_kv,
)
from flextree_tpu.serving.rpc import (
    MAX_KV_CHUNK_BYTES,
    RpcTornFrame,
    chunk_blob,
    join_chunks,
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64)
    base.update(kw)
    return TransformerConfig(**base)


def _pcfg(**kw):
    base = dict(num_blocks=40, block_size=4, blocks_per_seq=8)  # max_len 32
    base.update(kw)
    return PagedCacheConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _engine(params, cfg, pcfg=None, **bkw):
    bkw.setdefault("slots", 4)
    return ServingEngine(
        params, cfg, pcfg or _pcfg(), BatcherConfig(**bkw), fused=False
    )


def _prompt(rng, t):
    return rng.integers(0, 64, (t,)).astype(np.int32)


def _rand_kv(rng, n_blocks, bs=4, heads=2, dh=16, layers=2):
    shape = (n_blocks, bs, heads, dh)
    return {
        "k": [rng.standard_normal(shape).astype(np.float32)
              for _ in range(layers)],
        "v": [rng.standard_normal(shape).astype(np.float32)
              for _ in range(layers)],
    }


# ------------------------------------------------------- pack/unpack codecs


class TestPackUnpack:
    @pytest.mark.parametrize("n_blocks", [1, 2, 3, 5])
    def test_f32_roundtrip_is_bitwise(self, n_blocks):
        rng = np.random.default_rng(n_blocks)
        kv = _rand_kv(rng, n_blocks)
        meta, blob = pack_kv(kv, codec="f32")
        assert meta["n_blocks"] == n_blocks
        assert migration_error_bound(meta) == 0.0
        out = unpack_kv(meta, blob)
        for kind in ("k", "v"):
            for a, b in zip(kv[kind], out[kind]):
                np.testing.assert_array_equal(a, b)

    def test_int8_roundtrip_within_error_bound(self):
        rng = np.random.default_rng(7)
        kv = _rand_kv(rng, 3)
        meta, blob = pack_kv(kv, codec="int8")
        bound = migration_error_bound(meta)
        assert bound > 0.0
        out = unpack_kv(meta, blob)
        worst = 0.0
        for kind in ("k", "v"):
            for a, b in zip(kv[kind], out[kind]):
                worst = max(worst, float(np.max(np.abs(a - b))))
        assert 0.0 < worst <= bound
        # and int8 actually compresses (at this toy head_dim the
        # per-block f32 scales eat into the 4x; it must still win)
        _, blob_f32 = pack_kv(kv, codec="f32")
        assert len(blob) < len(blob_f32)

    def test_unknown_codec_refused(self):
        rng = np.random.default_rng(0)
        with pytest.raises(ValueError):
            pack_kv(_rand_kv(rng, 1), codec="fp4")

    def test_poisoned_payloads_refused(self):
        rng = np.random.default_rng(3)
        kv = _rand_kv(rng, 2)
        meta, blob = pack_kv(kv, codec="f32")
        # a flipped byte: whole-blob or per-tensor CRC catches it
        torn = bytearray(blob)
        torn[len(torn) // 2] ^= 0x40
        with pytest.raises(MigrationError):
            unpack_kv(meta, bytes(torn))
        # truncation: byte count mismatch
        with pytest.raises(MigrationError):
            unpack_kv(meta, blob[:-8])
        # a shape lie in the meta: geometry no longer matches the bytes
        lying = dict(meta, n_blocks=3)
        with pytest.raises(MigrationError):
            unpack_kv(lying, blob)
        bad_layers = dict(meta, n_layers=1)
        with pytest.raises(MigrationError):
            unpack_kv(bad_layers, blob)
        # every refusal carries the production code
        try:
            unpack_kv(meta, bytes(torn))
        except MigrationError as e:
            assert e.code == "FT_MIGRATION_REFUSED"

    def test_kv_chunking_roundtrip_and_torn_chunk(self):
        rng = np.random.default_rng(5)
        blob = rng.integers(0, 256, (3 * 1024,), dtype=np.uint8).tobytes()
        chunks = chunk_blob(blob, chunk_bytes=1024)
        assert len(chunks) == 3
        assert join_chunks(chunks) == blob
        assert chunk_blob(b"") == [""]
        assert join_chunks(chunk_blob(b"")) == b""
        assert MAX_KV_CHUNK_BYTES > 0
        with pytest.raises(RpcTornFrame):
            join_chunks(["not*base64!"])


# ------------------------------------------------- pool export/import ops


class TestExportImport:
    def test_roundtrip_preserves_untouched_blocks(self):
        rng = np.random.default_rng(11)
        pools = {
            "k": [jnp.asarray(rng.standard_normal((8, 4, 2, 16)),
                              jnp.float32) for _ in range(2)],
            "v": [jnp.asarray(rng.standard_normal((8, 4, 2, 16)),
                              jnp.float32) for _ in range(2)],
        }
        before = {k: [np.asarray(a) for a in v] for k, v in pools.items()}
        ids = [5, 2, 7]
        kv = export_blocks(pools, ids)
        dst = write_imported(
            {k: [jnp.zeros_like(a) for a in v] for k, v in pools.items()},
            kv, ids,
        )
        for kind in ("k", "v"):
            for src, out in zip(before[kind], dst[kind]):
                np.testing.assert_array_equal(src[np.asarray(ids)],
                                              np.asarray(out)[ids])
                # blocks NOT in the transfer stay zero (scatter, no blur)
                others = [i for i in range(8) if i not in ids]
                assert not np.asarray(out)[others].any()

    def test_import_refuses_shape_mismatch(self):
        pools = {
            "k": [jnp.zeros((8, 4, 2, 16), jnp.float32)],
            "v": [jnp.zeros((8, 4, 2, 16), jnp.float32)],
        }
        bad = {
            "k": [np.zeros((2, 4, 2, 8), np.float32)],
            "v": [np.zeros((2, 4, 2, 8), np.float32)],
        }
        with pytest.raises(ValueError):
            write_imported(pools, bad, [1, 2])


# ------------------------------------------- engine halves of the handshake


class TestEngineMigration:
    # f32 is bitwise at every offset, unconditionally.  int8 identity is
    # workload-dependent — at this toy scale plen=13 deterministically
    # flips one greedy near-tie, which is exactly why int8 on this wire
    # wants a token-identity check against f32 on the deployment's own
    # traffic; the remaining offsets still cover partial,
    # exact-boundary, and mid-block-tail block counts for the codec.
    @pytest.mark.parametrize("codec,plen", [
        ("f32", 3), ("f32", 4), ("f32", 5), ("f32", 8), ("f32", 9),
        ("f32", 13),
        ("int8", 3), ("int8", 4), ("int8", 5), ("int8", 8), ("int8", 9),
    ])
    def test_migrated_tokens_match_colocated(self, model, codec, plen):
        """Every block-boundary offset (bs=4: partial, exact, mid-tail)
        through the full export → pack → unpack → admit path."""
        cfg, params = model
        rng = np.random.default_rng(100 + plen)
        req = Request(rid=1, prompt=_prompt(rng, plen), max_new_tokens=6)
        pre = _engine(params, cfg)
        out = pre.prefill_for_migration(req, codec=codec)
        assert out is not None
        dec = _engine(params, cfg)
        slot = dec.admit_migrated(req, out["first_token"], out["meta"],
                                  out["blob"])
        assert slot is not None
        dec.run_until_idle()
        want = np.asarray(
            generate(params, jnp.asarray(req.prompt)[None], cfg,
                     max_new_tokens=req.max_new_tokens,
                     max_len=_pcfg().max_len)
        )[0]
        np.testing.assert_array_equal(dec.completed[1].tokens, want)
        # the prefill side still holds the export until the ack
        assert pre.release_exported(1, acked=True)

    def test_export_blocks_release_on_ack_never_before(self, model):
        cfg, params = model
        rng = np.random.default_rng(0)
        eng = _engine(params, cfg)
        free0 = eng.batcher.allocator.num_free
        req = Request(rid=5, prompt=_prompt(rng, 9), max_new_tokens=4)
        out = eng.prefill_for_migration(req)
        assert out is not None
        held = _pcfg().blocks_for(9)
        assert eng.batcher.allocator.num_free == free0 - held
        # a second migration of the same rid is refused while in flight
        with pytest.raises(MigrationError, match="in flight"):
            eng.prefill_for_migration(req)
        assert eng.release_exported(5, acked=True)
        assert eng.batcher.allocator.num_free == free0
        # exactly once: the second release is a no-op, not a double free
        assert not eng.release_exported(5, acked=True)
        assert eng.metrics.counter("serve.migration_acked").value == 1

    def test_abort_releases_and_counts(self, model):
        cfg, params = model
        rng = np.random.default_rng(1)
        eng = _engine(params, cfg)
        free0 = eng.batcher.allocator.num_free
        req = Request(rid=6, prompt=_prompt(rng, 5), max_new_tokens=4)
        assert eng.prefill_for_migration(req) is not None
        assert eng.release_exported(6, acked=False)
        assert eng.batcher.allocator.num_free == free0
        assert eng.metrics.counter("serve.migration_aborted").value == 1

    def test_sampled_and_oversized_requests_never_migrate(self, model):
        cfg, params = model
        rng = np.random.default_rng(2)
        eng = _engine(params, cfg)
        with pytest.raises(MigrationError, match="greedy-only"):
            eng.prefill_for_migration(Request(
                rid=7, prompt=_prompt(rng, 5), max_new_tokens=4,
                temperature=0.7,
            ))
        with pytest.raises(MigrationError):
            eng.prefill_for_migration(Request(
                rid=8, prompt=_prompt(rng, 40), max_new_tokens=4,
            ))

    def test_admit_refuses_geometry_mismatch(self, model):
        """A payload packed under a different block size is refused
        loudly — never scattered into the wrong-shaped pool."""
        cfg, params = model
        rng = np.random.default_rng(3)
        req = Request(rid=9, prompt=_prompt(rng, 6), max_new_tokens=4)
        pre = ServingEngine(
            params, cfg, PagedCacheConfig(
                num_blocks=40, block_size=8, blocks_per_seq=4
            ),
            BatcherConfig(slots=4), fused=False,
        )
        out = pre.prefill_for_migration(req)
        dec = _engine(params, cfg)  # block_size 4 here
        with pytest.raises(MigrationError):
            dec.admit_migrated(req, out["first_token"], out["meta"],
                               out["blob"])
        pre.release_exported(9, acked=False)

    def test_admit_capacity_refusal_is_none_not_raise(self, model):
        cfg, params = model
        rng = np.random.default_rng(4)
        dec = _engine(params, cfg, slots=1)
        r0 = Request(rid=20, prompt=_prompt(rng, 5), max_new_tokens=4)
        assert dec.submit(r0)
        dec.step()  # fills the only slot
        req = Request(rid=21, prompt=_prompt(rng, 5), max_new_tokens=4)
        pre = _engine(params, cfg)
        out = pre.prefill_for_migration(req)
        assert dec.admit_migrated(req, out["first_token"], out["meta"],
                                  out["blob"]) is None
        assert dec.metrics.counter("serve.migration_refused").value == 1
        pre.release_exported(21, acked=False)
        dec.run_until_idle()

    def test_batcher_admit_migrated_is_resident_at_prompt_len(self, model):
        b = ContinuousBatcher(_pcfg(), BatcherConfig(slots=2))
        rng = np.random.default_rng(5)
        req = Request(rid=30, prompt=_prompt(rng, 6), max_new_tokens=4)
        got = b.admit_migrated(req, 42, now_s=1.0)
        assert got is not None
        slot, state = got
        assert b.slots[slot] is state
        assert state.length == 6
        assert state.pending_token == 42
        assert state.generated == [42]
        assert state.first_token_s == 1.0
        assert state.token_times == [1.0]
        # sized like a local admit: prompt blocks plus decode growth room
        assert len(state.block_ids) == b.blocks_needed(req)
        assert len(state.block_ids) >= _pcfg().blocks_for(6)

    def test_migrated_sequence_seeds_prefix_index(self, model):
        """Mid-stream arrival: the prompt's FULL blocks are indexed at
        admission, and the retirement re-insert is idempotent."""
        cfg, params = model
        rng = np.random.default_rng(6)
        req = Request(rid=31, prompt=_prompt(rng, 9), max_new_tokens=4)
        pre = _engine(params, cfg)
        out = pre.prefill_for_migration(req)
        dec = _engine(params, cfg, prefix_cache=True)
        slot = dec.admit_migrated(req, out["first_token"], out["meta"],
                                  out["blob"])
        assert slot is not None
        idx = dec.batcher.prefix_index
        hit = idx.match(np.asarray(req.prompt))
        assert len(hit) == 2  # 2 full blocks of 4, partial tail private
        dec.run_until_idle()
        assert 31 in dec.completed
        pre.release_exported(31, acked=True)

    def test_completed_request_reports_decode_intervals(self, model):
        cfg, params = model
        rng = np.random.default_rng(8)
        eng = _engine(params, cfg)
        req = Request(rid=40, prompt=_prompt(rng, 5), max_new_tokens=5)
        assert eng.submit(req)
        eng.run_until_idle()
        done = eng.completed[40]
        assert len(done.token_times) == len(done.tokens)
        ivs = done.intervals_s
        assert len(ivs) == len(done.tokens) - 1
        assert all(d >= 0.0 for d in ivs)


# ------------------------------------------------------- the cost planner


class TestMigrationPlanner:
    def test_crossover_is_exactly_where_the_plan_flips(self):
        cfg, pcfg = _cfg(), _pcfg()
        for codec in ("f32", "int8"):
            cross = migration_crossover_tokens(cfg, pcfg, codec)
            assert cross is not None and 1 < cross <= pcfg.max_len
            assert not plan_migration(cfg, pcfg, cross - 1, codec)["migrate"]
            assert plan_migration(cfg, pcfg, cross, codec)["migrate"]

    def test_wire_bytes_monotone_and_int8_smaller(self):
        cfg, pcfg = _cfg(), _pcfg()
        prev = 0
        for t in range(1, pcfg.max_len + 1):
            b = predict_migration_us(cfg, pcfg, t)["bytes_on_wire"]
            assert b >= prev
            prev = b
        f32 = predict_migration_us(cfg, pcfg, 16, "f32")["bytes_on_wire"]
        i8 = predict_migration_us(cfg, pcfg, 16, "int8")["bytes_on_wire"]
        assert i8 < f32
        # lossless ships with zero codec time; int8 pays the pass
        assert predict_migration_us(cfg, pcfg, 16, "f32")["codec_us"] == 0.0
        assert predict_migration_us(cfg, pcfg, 16, "int8")["codec_us"] > 0.0

    def test_wire_bytes_match_the_packer(self):
        """The planner's priced bytes are the bytes ``pack_kv`` actually
        puts on the wire (per-tensor payloads; the planner excludes the
        meta/CRC envelope, so priced <= packed < priced + envelope)."""
        cfg, pcfg = _cfg(), _pcfg()
        rng = np.random.default_rng(9)
        for codec in ("f32", "int8"):
            for plen in (3, 8, 13):
                n = pcfg.blocks_for(plen)
                kv = _rand_kv(rng, n, bs=pcfg.block_size, heads=cfg.n_heads,
                              dh=cfg.head_dim, layers=cfg.n_layers)
                _, blob = pack_kv(kv, codec=codec)
                priced = predict_migration_us(
                    cfg, pcfg, plen, codec
                )["bytes_on_wire"]
                assert priced == len(blob)


# -------------------------------------------------- front-door role logic


class TestFrontDoorRoles:
    def _fd(self, tmp_path, **kw):
        kw.setdefault("migrate_min_prompt_len", 5)
        kw.setdefault("affinity_span", 0)
        return FrontDoor(str(tmp_path), FrontDoorConfig(**kw))

    def test_shed_accounting_splits_by_role(self, tmp_path):
        """One tier filling up sheds ONLY that tier: prefill-bound
        floods never consume decode capacity."""
        fd = self._fd(tmp_path, shed_outstanding=1, shed_hit_headroom=0)
        long_p, short_p = [1] * 6, [1] * 3
        assert fd.submit(0, long_p, 4)
        assert not fd.submit(1, long_p, 4)  # prefill tier full
        # decode capacity is untouched by the prefill shed
        assert fd.submit(2, short_p, 4)
        assert not fd.submit(3, short_p, 4)  # now decode is full too
        c = dict(fd.metrics.snapshot()["counters"])
        assert c["serve.shed"] == 2
        assert c["serve.shed_prefill"] == 1
        assert c["serve.shed_decode"] == 1
        fd.close()

    def test_routing_tiers_respect_roles(self, tmp_path):
        fd = self._fd(tmp_path)
        from flextree_tpu.serving.frontdoor import ReplicaClient
        for rank, role in ((0, "prefill"), (1, "prefill"), (2, "decode"),
                           (3, "both")):
            cl = ReplicaClient(rank, fd.cfg)
            cl.update_endpoint("h", 1000 + rank, 100 + rank, role)
            fd.clients[rank] = cl
        # decode tier never lands on a dedicated prefill replica
        for _ in range(4):
            got = fd._routable(role="decode")
            assert got.rank in (2, 3)
        # prefill tier is queue-depth weighted: deep rank 0 loses
        fd.clients[0].prefill_depth = 5
        assert fd._routable(role="prefill").rank == 1
        fd.clients[1].prefill_depth = 9
        assert fd._routable(role="prefill").rank == 0
        # no dedicated prefill replicas -> no prefill tier (fall back)
        fd.clients.pop(0), fd.clients.pop(1)
        assert fd._routable(role="prefill") is None
        assert fd._routable(role="decode") is not None
        fd.close()

    def test_short_prompts_never_flagged_for_migration(self, tmp_path):
        fd = self._fd(tmp_path, migrate_min_prompt_len=None,
                      shed_outstanding=1, shed_hit_headroom=0)
        # migration disabled: everything is decode-destined
        assert fd.submit(0, [1] * 20, 4)
        assert not fd.submit(1, [1] * 20, 4)
        c = dict(fd.metrics.snapshot()["counters"])
        assert c.get("serve.shed_prefill", 0) == 0
        assert c["serve.shed_decode"] == 1
        fd.close()


    def test_handoff_over_rpc_is_exactly_once_and_bitwise(self, model, tmp_path):
        """The whole handoff on a real wire: a prefill-role and a
        decode-role replica server behind the front door.  Every request
        completes exactly once and bitwise vs ``generate``; every prompt
        at or past the threshold migrated or is a counted fallback, no
        shorter one migrated, and ``serve.migrations`` agrees with the
        per-result ``migrated`` flags."""
        from flextree_tpu.serving.replica_main import (
            ReplicaConfig,
            ReplicaServer,
        )

        cfg, params = model
        servers = [
            ReplicaServer(
                _engine(params, cfg),
                ReplicaConfig(rank, str(tmp_path), role=role),
            ).start()
            for rank, role in enumerate(("prefill", "decode"))
        ]
        fd = self._fd(
            tmp_path, dispatchers=2, max_hedges=0, request_timeout_s=90.0,
            attempt_timeout_s=60.0,
        ).start()
        rng = np.random.default_rng(29)
        prompts = {
            rid: _prompt(rng, t) for rid, t in enumerate((3, 9, 4, 13, 6))
        }
        long_rids = {r for r, p in prompts.items() if len(p) >= 5}
        pre = servers[0].engine
        try:
            for rid, p in prompts.items():
                assert fd.submit(rid, p, 6)
            assert fd.wait_idle(timeout_s=120.0)
            assert fd.failed == {}
            assert sorted(fd.completed) == sorted(prompts)
            for rid, p in prompts.items():
                want = np.asarray(
                    generate(params, jnp.asarray(p)[None], cfg,
                             max_new_tokens=6, max_len=_pcfg().max_len)
                )[0]
                np.testing.assert_array_equal(fd.completed[rid].tokens, want)
            c = dict(fd.metrics.snapshot()["counters"])
            migrated = {r for r, res in fd.completed.items() if res.migrated}
            assert migrated and migrated <= long_rids
            assert len(migrated) + c.get("serve.migration_fallback", 0) >= len(
                long_rids
            )
            assert c.get("serve.migrations", 0) == len(migrated)
            assert c.get("serve.duplicate_results", 0) == 0
            # the prefill side let every exported block go on the ack
            assert pre.metrics.counter("serve.migration_acked").value == len(
                migrated
            )
        finally:
            fd.close()
            for srv in servers:
                srv.stop()
        assert pre.batcher.allocator.num_free == _pcfg().num_blocks - 1


# ------------------------------------------------- timeline flow rendering


class TestMigrationTimeline:
    def test_handoff_is_a_flow_arrow_across_tracks(self):
        evs = [
            {"ts": 1.0, "rank": 0, "seq": 0, "src": "serve",
             "kind": "serve_admit", "rid": 7, "slot": -1,
             "migration": True},
            {"ts": 1.1, "rank": 0, "seq": 1, "src": "serve",
             "kind": "serve_migration_send", "rid": 7, "to_rank": 1,
             "codec": "f32", "bytes": 4096, "ms": 2.0},
            {"ts": 1.2, "rank": 1, "seq": 0, "src": "serve",
             "kind": "serve_migration_recv", "rid": 7, "slot": 0,
             "bytes": 4096, "codec": "f32", "blocks": 2},
            {"ts": 1.5, "rank": 1, "seq": 1, "src": "serve",
             "kind": "serve_retire", "rid": 7, "slot": 0},
        ]
        doc = merge_events(evs)
        assert validate_trace(doc) == []
        flow = [e for e in doc["traceEvents"]
                if e.get("cat") == "request" and e.get("id") == 7]
        assert [e["ph"] for e in flow] == ["s", "t", "t", "f"]
        # the rid jumps tracks at the handoff: start on the prefill
        # replica's pid, finish on the decode replica's
        assert [e["pid"] for e in flow] == [0, 0, 1, 1]


# --------------------------------------------------- arbiter role floors


class TestArbiterRoleFloors:
    def _arb(self, tmp_path, cfg=None, role_of=None):
        from flextree_tpu.arbiter import (
            ArbiterConfig,
            DeviceInventory,
            PoolArbiter,
            SloReading,
        )
        from flextree_tpu.runtime import LeaseLedger

        inv = DeviceInventory([0, 1, 2, 3], train=(0, 1))
        led = LeaseLedger(str(tmp_path))
        arb = PoolArbiter(
            inv, led,
            cfg or ArbiterConfig(
                slo_p99_ms=100.0, min_serve_prefill_chips=1,
                min_serve_decode_chips=1,
            ),
            slo_reader=lambda: SloReading(p99_ms=10.0, samples=20),
            serve_role_of=role_of,
        )
        return arb, inv

    def test_reclaim_withholds_floor_pinned_chips(self, tmp_path):
        roles = {0: "both", 1: "both", 2: "prefill", 3: "decode"}
        arb, inv = self._arb(tmp_path, role_of=roles.get)
        # chips 2 and 3 are on loan; 2 is serving's ONLY prefill replica
        arb._loaned = [2, 3]
        take, withheld = arb._reclaimable()
        assert take == () and set(withheld) == {2, 3}
        # a second replica per role unpins the loaners
        roles2 = {0: "prefill", 1: "decode", 2: "prefill", 3: "decode"}
        from flextree_tpu.runtime.leases import SERVE, TRAIN
        inv.move((0, 1), TRAIN, SERVE)
        arb2 = arb  # same inventory view
        arb2.serve_role_of = roles2.get
        take, withheld = arb2._reclaimable()
        assert set(take) == {2, 3} and withheld == ()

    def test_no_role_map_reclaims_everything(self, tmp_path):
        arb, _ = self._arb(tmp_path, role_of=None)
        arb._loaned = [2, 3]
        take, withheld = arb._reclaimable()
        assert set(take) == {2, 3} and withheld == ()

    def test_return_keeps_withheld_chips_loaned(self, tmp_path):
        from flextree_tpu.arbiter import SloReading

        roles = {0: "both", 1: "both", 2: "prefill", 3: "decode"}
        arb, inv = self._arb(tmp_path, role_of=roles.get)
        from flextree_tpu.runtime.leases import SERVE, TRAIN
        # give decode a second replica so chip 3 reclaims but 2 pins
        roles[1] = "decode"
        inv.move((1,), TRAIN, SERVE)
        arb._loaned = [2, 3]
        got = arb._return(SloReading(p99_ms=10.0, samples=20), now=1e9)
        assert got == "return"
        assert arb.loaned == (2,)  # the floor-pinned prefill chip stays
        assert 2 in inv.held_by(SERVE)
        assert 3 in inv.held_by(TRAIN)
