"""Serving subsystem: paged KV cache, continuous batcher, elastic pool.

The decisive properties, in dependency order:

- **allocator**: exhaustion / free / reuse / double-free are exact — a
  silently double-freed block would hand one page to two sequences;
- **paged == contiguous, bitwise**: the gather → ragged decode → scatter
  step over block tables produces exactly the tokens the contiguous-cache
  ``generate`` produces, for greedy AND sampled requests, through ragged
  joins (a fresh prefill entering a batch of mid-decode sequences), and
  regardless of what the null block holds;
- **admission/retirement state machine**: block reservation is
  all-or-nothing, head-of-line FIFO, bounded by the join-at-step prefill
  budget; retirement frees every block immediately;
- **elastic pool**: a dead replica (hang, crash, or silent heartbeat
  death — the latter driven by the injectable ``_wall`` clock) drains its
  in-flight requests to survivors and the pool finishes everything,
  degraded instead of failed;
- **on-demand admission + preemption** (PR 11): prompt-blocks-only
  admission grows per block boundary, keeps more sequences resident than
  reservation at equal pool memory, and mid-decode exhaustion preempts
  the newest sequence (swap-out or recompute) with resume that continues
  to exactly ``generate``'s tokens — including a resume that lands
  mid-block, and through the replica pool's drain/re-route.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flextree_tpu.models.generate import generate, prefill
from flextree_tpu.models.transformer import TransformerConfig, init_params
from flextree_tpu.serving import (
    NULL_BLOCK,
    BatcherConfig,
    BlockAllocator,
    CacheExhausted,
    ContinuousBatcher,
    PagedCacheConfig,
    PoolConfig,
    ReplicaPool,
    Request,
    ServingEngine,
    gather_seq,
    init_pools,
    paged_decode_step,
    write_prefill,
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    base.update(kw)
    return TransformerConfig(**base)


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _pcfg(**kw):
    base = dict(num_blocks=32, block_size=8, blocks_per_seq=6)  # max_len 48
    base.update(kw)
    return PagedCacheConfig(**base)


def _prompt(rng, t):
    return rng.integers(0, 64, (t,)).astype(np.int32)


# ---------------------------------------------------------------- allocator


def test_allocator_exhaustion_is_all_or_nothing():
    a = BlockAllocator(num_blocks=5)  # 4 allocatable (block 0 reserved)
    assert a.num_free == 4
    got = a.alloc(3)
    assert len(got) == 3 and NULL_BLOCK not in got
    with pytest.raises(CacheExhausted, match="FT_CACHE_EXHAUSTED"):
        a.alloc(2)
    assert a.num_free == 1  # the failed alloc took nothing


def test_allocator_free_reuse_and_double_free():
    a = BlockAllocator(num_blocks=6)
    x = a.alloc(5)
    assert a.num_free == 0
    a.free(x[:2])
    assert a.num_free == 2
    y = a.alloc(2)
    assert set(y) == set(x[:2])  # LIFO reuse of just-freed blocks
    with pytest.raises(ValueError, match="duplicate"):
        a.free(y + y[:1])  # one call, overlapping ids: loud, takes nothing
    assert a.num_free == 0
    # precise double-free: free once is fine, twice is loud
    a.free(y)
    with pytest.raises(ValueError, match="not allocated"):
        a.free(y)


def test_allocator_never_hands_out_null_block():
    a = BlockAllocator(num_blocks=8)
    assert NULL_BLOCK not in a.alloc(7)
    with pytest.raises(ValueError):
        BlockAllocator(num_blocks=1)
    with pytest.raises(ValueError):
        a.free([NULL_BLOCK])


def test_allocator_churn_property():
    """Random alloc/free interleavings (the on-demand allocator's real
    life): the null block is never handed out, no block is ever owned
    twice, and the free list never acquires duplicates or foreign ids —
    across 200 seeded episodes of mixed traffic."""
    rng = np.random.default_rng(42)
    a = BlockAllocator(num_blocks=17)  # 16 allocatable
    held: list = []  # lists of blocks, freed in random order/groups
    for step in range(200):
        # invariants, every step
        free = set(a._free)
        owned = set(a._allocated)
        assert NULL_BLOCK not in free and NULL_BLOCK not in owned
        assert len(a._free) == len(free), "free list acquired duplicates"
        assert not (free & owned), "a block is both free and allocated"
        assert free | owned == set(range(1, 17)), "foreign or lost ids"
        if held and (rng.random() < 0.45 or a.num_free == 0):
            grp = held.pop(rng.integers(len(held)))
            # split the group: partial frees interleave with allocs
            cut = int(rng.integers(len(grp) + 1))
            if cut:
                a.free(grp[:cut])
            if grp[cut:]:
                held.append(grp[cut:])
        else:
            want = int(rng.integers(1, 5))
            if want > a.num_free:
                with pytest.raises(CacheExhausted):
                    a.alloc(want)
            else:
                got = a.alloc(want)
                assert len(set(got)) == len(got), "double-allocated"
                assert NULL_BLOCK not in got
                held.append(got)
    for grp in held:
        a.free(grp)
    assert a.num_free == 16


def test_allocator_free_rejects_foreign_ids():
    a = BlockAllocator(num_blocks=6)
    got = a.alloc(2)
    with pytest.raises(ValueError, match="not allocated"):
        a.free(got + [99])  # foreign id: loud, and the call takes nothing
    assert a.num_free == 3


def test_paged_cache_config_validation():
    assert _pcfg().max_len == 48
    assert _pcfg().blocks_for(1) == 1
    assert _pcfg().blocks_for(8) == 1
    assert _pcfg().blocks_for(9) == 2
    with pytest.raises(ValueError):
        PagedCacheConfig(num_blocks=1)
    with pytest.raises(ValueError):
        PagedCacheConfig(num_blocks=4, block_size=0)


# ------------------------------------------------- gather/scatter equivalence


def test_write_prefill_gather_roundtrip_bitwise(model):
    """Prefill K/V scattered into pool blocks gathers back bitwise."""
    cfg, params = model
    pcfg = _pcfg()
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(_prompt(rng, 13))[None]
    _, cache = prefill(params, prompt, cfg, max_len=pcfg.max_len)
    blocks = BlockAllocator(pcfg.num_blocks).alloc(pcfg.blocks_for(13))
    pools = write_prefill(init_pools(cfg, pcfg), cache, blocks)
    view = gather_seq(pools, blocks, length=13)
    for l in range(cfg.n_layers):
        np.testing.assert_array_equal(
            np.asarray(view["k"][l]), np.asarray(cache["k"][l][0, :13])
        )
        np.testing.assert_array_equal(
            np.asarray(view["v"][l]), np.asarray(cache["v"][l][0, :13])
        )


def test_null_block_content_is_invisible(model):
    """The bitwise contract's load-bearing property: whatever the null
    block holds sits beyond every causal bound, where the mask drives its
    softmax weight to exactly 0.0 — logits AND scattered K/V must be
    bitwise identical under a poisoned null block."""
    cfg, params = model
    pcfg = _pcfg(num_blocks=8)
    rng = np.random.default_rng(1)
    prompt = jnp.asarray(_prompt(rng, 11))[None]
    _, cache = prefill(params, prompt, cfg, max_len=pcfg.max_len)
    blocks = BlockAllocator(pcfg.num_blocks).alloc(pcfg.blocks_for(11 + 1))
    tables = np.full((1, pcfg.blocks_per_seq), NULL_BLOCK, np.int32)
    tables[0, : len(blocks)] = blocks
    lengths = np.asarray([11], np.int32)
    tokens = np.asarray([7], np.int32)

    outs = []
    for poison in (False, True):
        pools = write_prefill(init_pools(cfg, pcfg), cache, blocks)
        if poison:
            for kind in ("k", "v"):
                pools[kind] = [
                    p.at[NULL_BLOCK].set(1e30) for p in pools[kind]
                ]
        outs.append(paged_decode_step(
            params, pools, tables, lengths, tokens, cfg
        ))
    np.testing.assert_array_equal(np.asarray(outs[0][0]), np.asarray(outs[1][0]))
    for l in range(cfg.n_layers):
        np.testing.assert_array_equal(
            np.asarray(outs[0][1]["k"][l][1:]), np.asarray(outs[1][1]["k"][l][1:])
        )


# --------------------------------------------------- engine bitwise contract


def test_engine_greedy_bitwise_matches_generate_ragged_joins(model):
    """The acceptance floor, in-suite: staggered ragged requests through
    one shared pool produce exactly generate()'s tokens per request."""
    cfg, params = model
    pcfg = _pcfg()
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=3))
    rng = np.random.default_rng(2)
    reqs = [
        Request(rid=i, prompt=_prompt(rng, t), max_new_tokens=m)
        for i, (t, m) in enumerate([(5, 6), (9, 4), (13, 8), (7, 5), (11, 7)])
    ]
    # stagger: 3 up front (fill every slot), the rest join mid-decode
    for r in reqs[:3]:
        assert eng.submit(r)
    eng.step()
    for r in reqs[3:]:
        assert eng.submit(r)
    eng.run_until_idle()
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=r.max_new_tokens, max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(eng.completed[r.rid].tokens, want)
    # every reserved block came back
    assert eng.batcher.allocator.num_free == pcfg.num_blocks - 1


def test_engine_sampled_request_matches_generate_key_schedule(model):
    cfg, params = model
    pcfg = _pcfg()
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=2))
    rng = np.random.default_rng(3)
    prompt = _prompt(rng, 6)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8,
                       temperature=0.8, top_k=4, seed=17))
    eng.run_until_idle()
    want = np.asarray(
        generate(params, jnp.asarray(prompt)[None], cfg, max_new_tokens=8,
                 max_len=pcfg.max_len, temperature=0.8, top_k=4,
                 key=jax.random.PRNGKey(17))
    )[0]
    np.testing.assert_array_equal(eng.completed[0].tokens, want)


# ------------------------------------------- the pick: ids cross, not logits


def _rows_fetched(eng):
    return eng.report()["counters"].get("serve.logits_rows_fetched", 0)


def _spy_on_picks(eng):
    """Every (logits, ids) pair the engine's pick program saw, as numpy."""
    seen, pick = [], eng._greedy_ids

    def spy(logits):
        ids = pick(logits)
        seen.append((np.asarray(logits), np.asarray(ids)))
        return ids

    eng._greedy_ids = spy
    return seen


def test_all_greedy_run_fetches_no_logits_row(model):
    """Greedy tokens are the host argmax's, bit for bit: every id the
    device picked is ``np.argmax`` of the same logits (what the engine
    fetched and took before), the completed tokens are ``generate``'s, and
    no logits row crossed to the host."""
    cfg, params = model
    pcfg = _pcfg()
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=3))
    seen = _spy_on_picks(eng)
    rng = np.random.default_rng(21)
    reqs = [
        Request(rid=i, prompt=_prompt(rng, t), max_new_tokens=m)
        for i, (t, m) in enumerate([(5, 6), (9, 4), (13, 8), (7, 1)])
    ]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=r.max_new_tokens, max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(eng.completed[r.rid].tokens, want)
    assert _rows_fetched(eng) == 0
    # one pick a prefill over (1, V), one a decode round over (S, V)
    shapes = [logits.shape for logits, _ in seen]
    assert shapes.count((1, cfg.vocab_size)) == len(reqs)
    assert shapes.count((3, cfg.vocab_size)) == eng.decode_steps > 0
    for logits, ids in seen:
        assert ids.dtype == np.int32
        np.testing.assert_array_equal(ids, np.argmax(logits, axis=-1))


def test_mixed_batch_fetches_only_the_sampled_rows(model):
    """Greedy beside ``temperature > 0`` requests in one batch: a sampled
    one reproduces ``generate(key=PRNGKey(seed))``, a greedy one the
    argmax, and exactly one logits row crossed for each sampled token."""
    cfg, params = model
    pcfg = _pcfg()
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=4))
    rng = np.random.default_rng(22)
    knobs = [
        dict(), dict(temperature=0.8, top_k=4, seed=17), dict(),
        dict(temperature=1.3, seed=5), dict(temperature=0.5, top_k=2, seed=9),
    ]
    reqs = [
        Request(rid=i, prompt=_prompt(rng, t), max_new_tokens=m, **kw)
        for i, ((t, m), kw) in enumerate(
            zip([(5, 6), (9, 4), (13, 8), (7, 5), (6, 3)], knobs))
    ]
    for r in reqs[:4]:
        assert eng.submit(r)
    eng.step()
    assert _rows_fetched(eng) == 2 + 2  # two first tokens, two decoded
    assert eng.submit(reqs[4])  # joins a batch in mid-decode
    eng.run_until_idle()
    for r, kw in zip(reqs, knobs):
        key = jax.random.PRNGKey(kw["seed"]) if kw else None
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=r.max_new_tokens, max_len=pcfg.max_len,
                     temperature=r.temperature, top_k=r.top_k, key=key)
        )[0]
        np.testing.assert_array_equal(eng.completed[r.rid].tokens, want)
    assert _rows_fetched(eng) == sum(
        r.max_new_tokens for r, kw in zip(reqs, knobs) if kw)


@pytest.mark.parametrize("rows", [
    [[1.0, 3.0, 3.0, 2.0]],  # a tie at the maximum: the lower index
    [[0.0, 0.0, 0.0, 0.0], [-1.0, -1.0, -2.0, -1.0]],  # all equal; ties below 0
    [[-np.inf, 5.0, 5.0, -np.inf], [7.0, 7.0, 7.0, 7.5]],  # masked ends; no tie
], ids=["tie", "all-equal", "masked"])
def test_greedy_pick_takes_the_lowest_index_of_tied_maxima(rows):
    from flextree_tpu.serving.engine import greedy_ids

    logits = np.asarray(rows, np.float32)
    ids = np.asarray(jax.jit(greedy_ids)(logits))
    assert ids.dtype == np.int32 and ids.shape == (len(rows),)
    np.testing.assert_array_equal(ids, np.argmax(logits, axis=-1))


def test_tied_logits_through_the_engine_pick_the_lowest_id(model):
    """All-zero embeddings make every logit equal: each token is id 0."""
    cfg, params = model
    flat = dict(params, embed=jnp.zeros_like(params["embed"]))
    eng = ServingEngine(flat, cfg, _pcfg(), BatcherConfig(slots=2))
    assert eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                              max_new_tokens=4))
    eng.run_until_idle()
    np.testing.assert_array_equal(eng.completed[0].tokens, np.zeros(4, np.int32))


def test_benchmark_reference_check_runs_the_engines_own_programs():
    """``benchmarks/lib/serve_closed.py::check_against_reference`` calls
    ``engine._prefill``, ``_write`` and ``_decode`` and reads logits ROWS
    from the first and the last: they keep those signatures and stay the
    programs ``step()`` runs, so the check proves what is timed."""
    from benchmarks.lib import harness, serve_closed

    cell = harness.load_cell("pythia-6.9b.chat-closed-c32")
    harness.apply_rehearsal(cell)
    engine, cfg = serve_closed.build_engine(cell, 4)
    t = cell.traffic
    calls = {"_prefill": 0, "_write": 0, "_decode": 0}

    def counted(name):
        fn = getattr(engine, name)

        def call(*args):
            calls[name] += 1
            return fn(*args)

        setattr(engine, name, call)

    for name in calls:
        counted(name)
    check = serve_closed.check_against_reference(
        engine, cfg, 4, t["check_prompt"], t["check_steps"], t["check_blocks"])
    assert check["ok"], check
    assert calls == {"_prefill": 1, "_write": 1, "_decode": t["check_steps"]}
    # and a round of the closed loop goes through the same three
    loop = serve_closed.ClosedLoop(engine, t, 4, cfg.vocab_size)
    loop.issue()
    loop.round()
    # its own round, and the next where it was dispatched ahead
    ahead = engine.report()["counters"]["serve.rounds_ahead"]
    assert calls == {"_prefill": 2, "_write": 2,
                     "_decode": t["check_steps"] + 1 + ahead}
    assert loop.rounds[-1][2:4] == (2, 1)  # a first token and a decoded one


def test_pick_program_is_not_counted_as_the_decode_program(model):
    """``kernels.decode_roofline`` finds the decode program by name and
    divides its time by its run count: the pick program's name must match
    neither pattern, or the count doubles."""
    import os
    import re

    from benchmarks.lib import harness

    match = re.compile(harness._read_json(os.path.join(
        harness.ROOT, "metrics", "kernels.decode_roofline.json"))["args"]["match"])
    cfg, params = model
    pcfg = _pcfg()
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=2))

    def module_name(lowered):
        return re.search(r"module @(\S+)", lowered.as_text())[1]

    pick = module_name(
        eng._greedy_ids.lower(jnp.zeros((2, cfg.vocab_size), jnp.float32)))
    assert pick == "jit_greedy_ids" and not match.search(pick)
    decode = module_name(eng._decode.lower(
        params, eng.pools, np.zeros((2, pcfg.blocks_per_seq), np.int32),
        np.zeros((2,), np.int32), np.zeros((2,), np.int32)))
    assert match.search(decode)  # the same look finds the decode program


def test_engine_sampled_without_seed_rejected_at_submit(model):
    """Discovered mid-prefill this would wedge the slot (blocks reserved,
    no sampler key) — so it must be refused BEFORE admission."""
    cfg, params = model
    eng = ServingEngine(params, cfg, _pcfg(), BatcherConfig(slots=1))
    assert not eng.submit(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                                  max_new_tokens=2, temperature=1.0))
    assert "seed" in eng.batcher.rejected[0][1]
    assert eng.idle


def test_engine_stop_token_retires_and_frees(model):
    cfg, params = model
    pcfg = _pcfg()
    rng = np.random.default_rng(4)
    prompt = _prompt(rng, 7)
    free_run = np.asarray(
        generate(params, jnp.asarray(prompt)[None], cfg, max_new_tokens=8,
                 max_len=pcfg.max_len)
    )[0]
    stop_tok = int(free_run[2])
    first = int(np.argmax(free_run == stop_tok))
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=2))
    eng.submit(Request(rid=9, prompt=prompt, max_new_tokens=8,
                       stop_tokens=(stop_tok,)))
    eng.run_until_idle()
    np.testing.assert_array_equal(
        eng.completed[9].tokens, free_run[: first + 1]
    )
    assert eng.batcher.allocator.num_free == pcfg.num_blocks - 1


def test_engine_bf16_paged_matches_generate():
    cfg = _cfg(dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pcfg = _pcfg()
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=2))
    rng = np.random.default_rng(5)
    reqs = [Request(rid=i, prompt=_prompt(rng, t), max_new_tokens=4)
            for i, t in enumerate([6, 10])]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=4, max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(eng.completed[r.rid].tokens, want)


def test_engine_oversized_request_rejected_not_queued(model):
    cfg, params = model
    pcfg = _pcfg()  # max_len 48
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=1))
    assert not eng.submit(Request(rid=0, prompt=np.arange(40, dtype=np.int32),
                                  max_new_tokens=20))
    assert eng.batcher.rejected and eng.idle


def test_engine_capacity_pressure_completes_all(model):
    """More concurrent demand than the pool holds: admission waits for
    retirements, everything still finishes, blocks never go negative."""
    cfg, params = model
    pcfg = _pcfg(num_blocks=9)  # 8 allocatable; each request needs 2-3
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=4))
    rng = np.random.default_rng(6)
    reqs = [Request(rid=i, prompt=_prompt(rng, 9), max_new_tokens=6)
            for i in range(7)]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    assert sorted(eng.completed) == list(range(7))
    assert eng.batcher.allocator.num_free == 8


# ----------------------------------------------- batcher state machine (pure)


def test_admission_reserves_all_or_nothing():
    pcfg = _pcfg(num_blocks=6)  # 5 allocatable
    b = ContinuousBatcher(pcfg, BatcherConfig(slots=4))
    # needs ceil((17+15)/8) = 4 blocks
    b.submit(Request(rid=0, prompt=np.zeros(17, np.int32), max_new_tokens=15))
    # needs 3 blocks — must NOT jump the queue when 0 admits first
    b.submit(Request(rid=1, prompt=np.zeros(9, np.int32), max_new_tokens=9))
    admitted = b.try_admit()
    assert [s.rid for _, s in admitted] == [0]
    assert b.allocator.num_free == 1  # 4 reserved up front
    # head-of-line: rid 1 waits even though a slot is free
    assert b.try_admit() == []
    assert [r.rid for r in b.queue] == [1]
    # retirement frees everything and admits the waiter
    b.slots[admitted[0][0]].done = True
    assert [s.rid for _, s in b.retire_ready()] == [0]
    assert b.allocator.num_free == 5
    assert [s.rid for _, s in b.try_admit()] == [1]


def test_admission_prefill_token_budget_joins_at_step():
    pcfg = _pcfg(num_blocks=32)
    b = ContinuousBatcher(
        pcfg, BatcherConfig(slots=4, max_prefill_tokens_per_step=10)
    )
    for i, t in enumerate([8, 8, 8]):
        b.submit(Request(rid=i, prompt=np.zeros(t, np.int32), max_new_tokens=4))
    # one 8-token prefill fits the 10-token budget; the second would blow it
    assert [s.rid for _, s in b.try_admit()] == [0]
    assert [s.rid for _, s in b.try_admit()] == [1]  # next step admits more
    # a prompt longer than the whole budget still admits when it is first
    b2 = ContinuousBatcher(
        pcfg, BatcherConfig(slots=2, max_prefill_tokens_per_step=4)
    )
    b2.submit(Request(rid=9, prompt=np.zeros(8, np.int32), max_new_tokens=4))
    assert [s.rid for _, s in b2.try_admit()] == [9]


def test_batch_arrays_masks_inactive_slots():
    pcfg = _pcfg()
    b = ContinuousBatcher(pcfg, BatcherConfig(slots=3))
    b.submit(Request(rid=0, prompt=np.zeros(9, np.int32), max_new_tokens=4))
    [(slot, state)] = b.try_admit()
    b.record_first_token(slot, 42, now_s=1.0)
    tables, lengths, tokens, active = b.batch_arrays()
    assert active.tolist() == [i == slot for i in range(3)]
    assert lengths[slot] == 9 and tokens[slot] == 42
    other = [i for i in range(3) if i != slot]
    assert (tables[other] == NULL_BLOCK).all()
    assert (lengths[other] == 0).all()
    # decode advances length and re-arms the pending token
    b.record_decode_token(slot, 7, now_s=2.0)
    assert b.slots[slot].length == 10
    assert b.slots[slot].generated == [42, 7]
    # max_new reached after 4 tokens
    b.record_decode_token(slot, 8, now_s=3.0)
    b.record_decode_token(slot, 9, now_s=4.0)
    assert b.slots[slot].done and b.slots[slot].done_s == 4.0


# -------------------------------------------- on-demand admission/preemption


def test_ondemand_admits_on_prompt_blocks_only():
    pcfg = _pcfg(num_blocks=8)  # 7 allocatable
    b = ContinuousBatcher(
        pcfg, BatcherConfig(slots=4, admission="ondemand")
    )
    # reservation would need ceil((9+30)/8) = 5 blocks each: one admits.
    # on-demand needs ceil(9/8) = 2: three admit concurrently.
    for i in range(3):
        assert b.submit(Request(rid=i, prompt=np.zeros(9, np.int32),
                                max_new_tokens=30))
    admitted = b.try_admit()
    assert [s.rid for _, s in admitted] == [0, 1, 2]
    assert b.allocator.num_free == 1  # 3 x 2 prompt blocks
    # the same traffic under reservation: head-of-line blocks after one
    br = ContinuousBatcher(pcfg, BatcherConfig(slots=4, admission="reserve"))
    for i in range(3):
        br.submit(Request(rid=i, prompt=np.zeros(9, np.int32),
                          max_new_tokens=30))
    assert [s.rid for _, s in br.try_admit()] == [0]
    assert br.admit_blocked is not None  # rid 1 blocked on blocks


def test_ondemand_grow_allocates_at_block_boundary():
    pcfg = _pcfg(num_blocks=16)
    b = ContinuousBatcher(pcfg, BatcherConfig(slots=2, admission="ondemand"))
    b.submit(Request(rid=0, prompt=np.zeros(8, np.int32), max_new_tokens=12))
    [(slot, s)] = b.try_admit()
    assert len(s.block_ids) == 1  # exactly the prompt's block
    b.record_first_token(slot, 1, now_s=0.0)
    # length 8 = block boundary: the first decode write needs block 2
    assert b.grow_for_decode() == [slot]
    assert len(s.block_ids) == 2
    # mid-block positions need nothing
    b.record_decode_token(slot, 2, now_s=0.0)  # length 9
    assert b.grow_for_decode() == []
    for _ in range(7):
        b.record_decode_token(slot, 2, now_s=0.0)  # length 16: boundary
    assert b.grow_for_decode() == [slot]
    assert len(s.block_ids) == 3


def test_pick_victim_is_newest_and_never_the_last():
    pcfg = _pcfg(num_blocks=32)
    b = ContinuousBatcher(pcfg, BatcherConfig(slots=3, admission="ondemand"))
    for i in range(2):
        b.submit(Request(rid=i, prompt=np.zeros(4, np.int32),
                         max_new_tokens=4))
    (s0, st0), (s1, st1) = b.try_admit()
    assert st1.admit_seq > st0.admit_seq
    assert b.pick_victim() == s1  # newest
    b.record_first_token(s0, 1, 0.0)
    b.record_first_token(s1, 1, 0.0)
    kv = None
    b.preempt(s1, kv)
    assert b.pick_victim() is None  # one resident: nothing to evict
    assert [p.state.rid for p in b.preempted] == [1]
    assert st1.block_ids == [] and st1.preempts == 1


def test_preempted_resume_has_priority_over_fresh_admissions():
    pcfg = _pcfg(num_blocks=32)
    b = ContinuousBatcher(pcfg, BatcherConfig(slots=2, admission="ondemand"))
    b.submit(Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=8))
    [(slot, st)] = b.try_admit()
    b.record_first_token(slot, 1, 0.0)
    b.preempt(slot, None)
    b.submit(Request(rid=1, prompt=np.zeros(4, np.int32), max_new_tokens=8))
    # fresh admission must refuse while a preempted sequence waits
    assert b.try_admit() == []
    [(rslot, rstate, kv)] = b.try_resume()
    assert rstate.rid == 0 and kv is None
    assert len(rstate.block_ids) == rstate.length // pcfg.block_size + 1
    # with the resume done, the fresh request admits
    assert [s.rid for _, s in b.try_admit()] == [1]


def test_submit_rejects_requests_the_pool_can_never_hold():
    pcfg = _pcfg(num_blocks=4)  # 3 allocatable, max_len still 48
    for mode in ("reserve", "ondemand"):
        b = ContinuousBatcher(pcfg, BatcherConfig(slots=2, admission=mode))
        assert not b.submit(
            Request(rid=0, prompt=np.zeros(20, np.int32), max_new_tokens=20)
        )  # needs 5 blocks, pool holds 3: wedge (reserve) or livelock (ondemand)
        assert "pool holds" in b.rejected[-1][1]


@pytest.mark.parametrize("preempt", ["swap", "recompute"])
def test_engine_preemption_resume_matches_generate(model, preempt):
    """Injected exhaustion: a pool too small for the traffic preempts
    mid-decode; every sequence still finishes with exactly generate()'s
    tokens (swap-in restores the exact K/V bytes; recompute replays
    prefill), blocks all return, and the preempt/resume accounting shows
    the machinery actually fired."""
    cfg, params = model
    pcfg = _pcfg(num_blocks=10)  # 9 allocatable blocks
    eng = ServingEngine(
        params, cfg, pcfg,
        BatcherConfig(slots=4, admission="ondemand", preempt=preempt),
    )
    rng = np.random.default_rng(11)
    # prompts of 9 -> length hits boundaries mid-run; 4 resident sequences
    # want up to 4 x ceil((9+20)/8) = 16 blocks against 9: must preempt
    reqs = [Request(rid=i, prompt=_prompt(rng, 9), max_new_tokens=20)
            for i in range(5)]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    snap = eng.metrics.snapshot()["counters"]
    assert snap.get("serve.preempts", 0) >= 1
    assert snap.get("serve.resumes", 0) == snap.get("serve.preempts")
    if preempt == "swap":
        assert snap.get("serve.swap_outs", 0) >= 1
    assert sorted(eng.completed) == list(range(5))
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=20, max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(eng.completed[r.rid].tokens, want)
    assert eng.batcher.allocator.num_free == 9


def test_engine_midblock_swap_resume_is_bit_identical(model):
    """Force a victim whose length is NOT a block multiple, resume it,
    and check its restored K/V bytes equal the swapped bytes exactly —
    the bit-identical-resume contract at the pool level."""
    cfg, params = model
    pcfg = _pcfg(num_blocks=12)
    eng = ServingEngine(
        params, cfg, pcfg,
        BatcherConfig(slots=2, admission="ondemand", preempt="swap"),
    )
    rng = np.random.default_rng(12)
    req = Request(rid=0, prompt=_prompt(rng, 9), max_new_tokens=8)
    eng.submit(req)
    eng.step()  # prefill + first decode: length 9, mid-block
    eng.settle()  # the round dispatched ahead, recorded before a read
    state = eng.batcher.slots[0]
    assert state.length % pcfg.block_size != 0
    saved = gather_seq(eng.pools, state.block_ids, length=state.length)
    saved = {k: [np.asarray(x) for x in v] for k, v in saved.items()}
    eng._preempt_slot(0)
    assert eng.batcher.preempted and state.block_ids == []
    [(slot, rstate, kv)] = eng.batcher.try_resume()
    eng._resume_slot(slot, rstate, kv)
    restored = gather_seq(eng.pools, rstate.block_ids, length=rstate.length)
    for l in range(cfg.n_layers):
        np.testing.assert_array_equal(
            np.asarray(restored["k"][l]), saved["k"][l]
        )
        np.testing.assert_array_equal(
            np.asarray(restored["v"][l]), saved["v"][l]
        )
    eng.run_until_idle()
    want = np.asarray(
        generate(params, jnp.asarray(req.prompt)[None], cfg,
                 max_new_tokens=8, max_len=pcfg.max_len)
    )[0]
    np.testing.assert_array_equal(eng.completed[0].tokens, want)


def test_engine_sampled_request_survives_preemption(model):
    """The per-request key schedule is a pure function of the seed:
    eviction and resume must not shift it."""
    cfg, params = model
    pcfg = _pcfg(num_blocks=9)  # 8 allocatable: 3 residents x 3 blocks > 8
    eng = ServingEngine(
        params, cfg, pcfg,
        BatcherConfig(slots=3, admission="ondemand", preempt="swap"),
    )
    rng = np.random.default_rng(13)
    reqs = [
        Request(rid=i, prompt=_prompt(rng, 9), max_new_tokens=16,
                temperature=0.7, top_k=8, seed=100 + i)
        for i in range(4)
    ]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    assert eng.metrics.counter("serve.preempts").value >= 1
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=16, max_len=pcfg.max_len,
                     temperature=0.7, top_k=8,
                     key=jax.random.PRNGKey(r.seed))
        )[0]
        np.testing.assert_array_equal(eng.completed[r.rid].tokens, want)


def test_engine_gather_path_still_bitwise(model):
    """The oracle must stay covered now that fused is the default: an
    explicit fused=False engine reproduces generate() bitwise."""
    cfg, params = model
    pcfg = _pcfg()
    eng = ServingEngine(params, cfg, pcfg, BatcherConfig(slots=2),
                        fused=False)
    rng = np.random.default_rng(14)
    reqs = [Request(rid=i, prompt=_prompt(rng, t), max_new_tokens=6)
            for i, t in enumerate([5, 11])]
    for r in reqs:
        eng.submit(r)
    eng.run_until_idle()
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=6, max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(eng.completed[r.rid].tokens, want)


def test_engine_report_carries_cache_pressure_metrics(model):
    cfg, params = model
    eng = ServingEngine(
        params, cfg, _pcfg(num_blocks=10),
        BatcherConfig(slots=4, admission="ondemand"),
    )
    rng = np.random.default_rng(15)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=_prompt(rng, 9), max_new_tokens=20))
    eng.run_until_idle()
    rep = eng.report()
    assert "serve.free_blocks" in rep["gauges"]
    assert "serve.active_blocks" in rep["gauges"]
    assert rep["gauges"]["serve.active_blocks"] == 0  # all retired
    occ = rep["histograms"]["serve.cache_occupancy"]
    assert occ["count"] == eng.steps and 0.0 < occ["max"] <= 1.0
    assert rep["counters"]["serve.preempts"] >= 1


def test_pool_drain_reroutes_preempted_sequences(model, tmp_path):
    """A replica dying WITH a parked preempted sequence must re-route it
    like any other in-flight request — the exactly-once machinery covers
    the preempted queue too."""
    cfg, params = model
    pcfg = _pcfg(num_blocks=10)
    engines = [
        ServingEngine(params, cfg, pcfg,
                      BatcherConfig(slots=3, admission="ondemand"))
        for _ in range(2)
    ]
    pool = ReplicaPool(
        engines,
        PoolConfig(heartbeat_dir=str(tmp_path / "hb"), step_timeout_s=120.0,
                   lease_s=30.0, max_suspect_strikes=2),
    )
    rng = np.random.default_rng(16)
    reqs = [Request(rid=200 + i, prompt=_prompt(rng, 9), max_new_tokens=20)
            for i in range(6)]
    for r in reqs:
        pool.submit(r)
    # run until replica 1 has actually preempted something, then kill it
    for _ in range(40):
        pool.step()
        if engines[1].batcher.preempted:
            break
    assert engines[1].batcher.preempted, "scenario did not reach preemption"
    parked = [p.state.rid for p in engines[1].batcher.preempted]
    pool.kill(1, mode="raise")
    rep = pool.run_until_idle()
    assert rep["completed"] == 6 and rep["degraded"]
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=20, max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(pool.completed[r.rid].tokens, want)
    assert all(rid in pool.completed for rid in parked)
    pool.shutdown()


# ----------------------------------------------------------- elastic pool


def _mk_pool(model, tmp_path, n=2, **cfg_kw):
    cfg, params = model
    pcfg = _pcfg(num_blocks=24)
    engines = [
        ServingEngine(params, cfg, pcfg, BatcherConfig(slots=2))
        for _ in range(n)
    ]
    # the default watchdog deadline is deliberately generous: pool tests
    # step UNWARMED engines, and a prefill/decode compile landing inside
    # a tight deadline on a loaded host strikes out a healthy replica (a
    # flake observed at 5 s).  Tests of the hang path pass their own
    # step_timeout_s and warm their engines first.
    kw = dict(heartbeat_dir=str(tmp_path / "hb"), step_timeout_s=120.0,
              lease_s=30.0, max_suspect_strikes=2)
    kw.update(cfg_kw)
    return ReplicaPool(engines, PoolConfig(**kw)), pcfg


def _reqs(n, seed=7):
    rng = np.random.default_rng(seed)
    return [Request(rid=100 + i, prompt=_prompt(rng, 5 + i), max_new_tokens=5)
            for i in range(n)]


def test_pool_routes_balanced_and_completes(model, tmp_path):
    pool, pcfg = _mk_pool(model, tmp_path)
    cfg, params = model
    reqs = _reqs(6)
    for r in reqs:
        pool.submit(r)
    pool.step()
    loads = [len(r.assigned) for r in pool.replicas]
    assert sorted(loads) == [3, 3]
    rep = pool.run_until_idle()
    assert rep["completed"] == 6 and not rep["degraded"]
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=5, max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(pool.completed[r.rid].tokens, want)
    pool.shutdown()


def test_pool_rejected_request_is_recorded_not_lost(model, tmp_path):
    """A request a replica refuses (oversized for its pool) must surface
    in the POOL report — a silently vanished request is the one outcome
    a serving layer may never have."""
    pool, pcfg = _mk_pool(model, tmp_path)
    good = _reqs(2)
    for r in good:
        pool.submit(r)
    pool.submit(Request(rid=999, prompt=np.zeros(40, np.int32),
                        max_new_tokens=20))  # > max_len 48
    rep = pool.run_until_idle()
    assert rep["completed"] == 2
    assert [rid for rid, _ in rep["rejected"]] == [999]
    pool.shutdown()


def test_pool_reroute_preserves_original_arrival_stamp(model, tmp_path,
                                                       monkeypatch):
    """TTFT of a re-routed request must include the time it sat on the
    dead replica: arrival is stamped once, at pool intake."""
    from flextree_tpu.serving import engine as eng_mod

    t = {"now": 100.0}
    monkeypatch.setattr(eng_mod, "_now", lambda: t["now"])
    pool, _ = _mk_pool(model, tmp_path)
    reqs = _reqs(4)
    for r in reqs:
        pool.submit(r)
    t["now"] = 101.0
    pool.step()
    t["now"] = 105.0  # the doomed replica holds them for 4 "seconds"
    pool.kill(1, mode="raise")
    rep = pool.run_until_idle()
    assert rep["completed"] == 4 and rep["reroutes"] > 0
    # every completion's TTFT counts from the ORIGINAL intake at t=100
    for done in pool.completed.values():
        assert done.arrival_s == 100.0
        assert done.ttft_s >= 0
    rerouted_ttfts = [d.ttft_s for d in pool.completed.values()
                      if d.first_token_s >= 105.0]
    assert rerouted_ttfts and all(x >= 5.0 for x in rerouted_ttfts)
    pool.shutdown()


def test_pool_crash_kill_drains_and_reroutes(model, tmp_path):
    pool, _ = _mk_pool(model, tmp_path)
    reqs = _reqs(6)
    for r in reqs:
        pool.submit(r)
    pool.step()
    pool.kill(1, mode="raise")
    rep = pool.run_until_idle()
    assert rep["completed"] == 6
    assert rep["degraded"] and rep["alive"] == 1 and rep["reroutes"] > 0
    pool.shutdown()


def test_pool_silent_death_confirmed_by_lease_wall_clock(model, tmp_path, monkeypatch):
    """The membership verdict end-to-end on the injectable clock: a
    replica whose heartbeat dies silently (engine still stepping) is
    drained once its lease expires — no watchdog strike involved."""
    from flextree_tpu.runtime import supervisor as sup_mod

    t = {"now": 1000.0}
    monkeypatch.setattr(sup_mod, "_wall", lambda: t["now"])
    pool, _ = _mk_pool(model, tmp_path, lease_s=3.0, straggler_s=1.0)
    reqs = _reqs(4)
    for r in reqs:
        pool.submit(r)
    pool.step()
    pool.kill(0, mode="silent")
    # inside the lease: still counted alive
    pool.step()
    assert len(pool.alive_replicas) == 2
    # jump the clock past the lease; survivors re-beat at the new time
    t["now"] += 10.0
    pool.replicas[1].supervisor.beat_now()
    pool.step()
    assert [r.rank for r in pool.alive_replicas] == [1]
    rep = pool.run_until_idle()
    assert rep["completed"] == 4 and rep["degraded"] and rep["reroutes"] > 0
    pool.shutdown()


def test_pool_hang_kill_watchdog_converts_to_drain(model, tmp_path):
    pool, _ = _mk_pool(model, tmp_path, step_timeout_s=0.5,
                       max_suspect_strikes=3)
    cfg, params = model
    for r in pool.replicas:  # compiles must not eat the deadline
        r.engine.warmup([5, 6, 7, 8])
    reqs = _reqs(4)
    for r in reqs:
        pool.submit(r)
    pool.step()
    pool.kill(0, mode="hang")
    rep = pool.run_until_idle()
    assert rep["completed"] == 4 and rep["degraded"] and rep["reroutes"] > 0
    pool.shutdown()


def test_pool_results_are_exactly_once(model, tmp_path):
    """A drained request recomputes on a survivor; the pool records one
    result per rid and greedy recompute is bit-identical."""
    pool, pcfg = _mk_pool(model, tmp_path)
    cfg, params = model
    reqs = _reqs(6)
    for r in reqs:
        pool.submit(r)
    for _ in range(3):
        pool.step()
    pool.kill(1, mode="raise")
    rep = pool.run_until_idle()
    assert rep["completed"] == 6 == len(set(pool.completed))
    for r in reqs:
        want = np.asarray(
            generate(params, jnp.asarray(r.prompt)[None], cfg,
                     max_new_tokens=5, max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(pool.completed[r.rid].tokens, want)
    pool.shutdown()


def test_engine_timestamps_on_injected_clock(model, monkeypatch):
    from flextree_tpu.serving import engine as eng_mod

    t = {"now": 0.0}

    def fake_now():
        t["now"] += 0.5
        return t["now"]

    monkeypatch.setattr(eng_mod, "_now", fake_now)
    cfg, params = model
    eng = ServingEngine(params, cfg, _pcfg(), BatcherConfig(slots=1))
    rng = np.random.default_rng(8)
    eng.submit(Request(rid=0, prompt=_prompt(rng, 5), max_new_tokens=3))
    eng.run_until_idle()
    done = eng.completed[0]
    assert done.arrival_s < done.first_token_s < done.done_s
    assert done.ttft_s > 0 and done.per_token_s > 0
    assert done.n_tokens == 3


# ------------------------------------ serving-side feedback (ISSUE 15)


def test_decode_cost_estimate_is_positive_and_split(model):
    from flextree_tpu.serving.costs import (
        predict_decode_round_us,
        predict_prefill_us,
    )

    cfg, _params = model
    pcfg = _pcfg()
    pred = predict_decode_round_us(cfg, pcfg, n_active=3, max_len=24)
    assert pred["predicted_us"] > 0
    assert pred["predicted_us"] == pytest.approx(
        pred["compute_us"] + pred["bytes_us"]
    )
    # empty round costs nothing; longer frontiers cost more
    assert predict_decode_round_us(cfg, pcfg, 0, 24)["predicted_us"] == 0.0
    longer = predict_decode_round_us(cfg, pcfg, 3, 48)
    assert longer["predicted_us"] > pred["predicted_us"]
    assert predict_prefill_us(cfg, 16) > predict_prefill_us(cfg, 4)
