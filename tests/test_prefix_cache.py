"""Cross-request prefix caching: refcounted COW blocks, the radix index,
suffix-only prefill, and prefix-affinity routing.

The decisive properties, in dependency order:

- **refcounted allocator**: retain/release bookkeeping is exact — the
  free list regains a block only at refcount 0, ``free`` of a shared
  block is loud, ``fork_block`` never aliases a live shared block — and
  the 200-episode churn property holds across random
  alloc/retain/release/fork/free interleavings;
- **radix index**: block-granularity matching (FULL blocks only — the
  partial tail is always private), first-writer-wins insertion with
  adoption retains, LRU eviction that never touches an entry a live
  sequence still holds, and deterministic keying (two replicas fed the
  same requests build identical key paths);
- **suffix-only prefill is bitwise**: ``prefill_suffix`` over a cached
  prefix reproduces the full prefill's last-token logits AND its suffix
  cache rows exactly — wherever XLA sums in one order for both (see
  ``_pv_row_class``; a stated tolerance only across that boundary);
- **the warm engine is the cold engine**: with the prefix cache on,
  every completed request's tokens are bitwise-identical to a cold
  engine and to contiguous ``generate`` — through COW divergence
  mid-block, full-prompt hits, poisoned unreferenced pool blocks,
  sampled requests, and preemption/swap of shared-prefix sequences —
  and every block drains back to the free list at the end;
- **the front door prefers warmth**: prefix-affinity routing picks the
  replica that last served a first-block hash, but never overrides
  health, breaker state, or drain avoidance — a draining affinity
  target re-routes the request to a cold replica which still answers
  bitwise.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flextree_tpu.models.generate import generate, prefill, prefill_suffix
from flextree_tpu.models.transformer import TransformerConfig, init_params
from flextree_tpu.serving import (
    NULL_BLOCK,
    BatcherConfig,
    BlockAllocator,
    CacheExhausted,
    ContinuousBatcher,
    PagedCacheConfig,
    PrefixIndex,
    PrefixIndexError,
    Request,
    ServingEngine,
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


def _warm_engine(params, cfg, pcfg, **bkw):
    bkw.setdefault("slots", 4)
    return ServingEngine(
        params, cfg, pcfg, BatcherConfig(prefix_cache=True, **bkw),
        fused=False,
    )


def _oracle(params, cfg, pcfg, req, **gen_kw):
    return np.asarray(
        generate(params, jnp.asarray(req.prompt)[None], cfg,
                 max_new_tokens=req.max_new_tokens, max_len=pcfg.max_len,
                 **gen_kw)
    )[0]


# ------------------------------------------------------ refcounted allocator


def test_retain_release_returns_block_only_at_zero():
    a = BlockAllocator(num_blocks=6)
    got = a.alloc(2)
    assert all(a.refcount(b) == 1 for b in got)
    a.retain(got)
    assert all(a.refcount(b) == 2 for b in got)
    a.release(got)
    assert a.num_free == 3  # still held once: nothing regained
    a.release(got)
    assert a.num_free == 5
    assert all(a.refcount(b) == 0 for b in got)


def test_release_and_retain_are_loud_on_misuse():
    a = BlockAllocator(num_blocks=6)
    got = a.alloc(1)
    with pytest.raises(ValueError, match="not allocated"):
        a.retain([99])
    with pytest.raises(ValueError, match="duplicate"):
        a.release(got + got)
    a.release(got)
    with pytest.raises(ValueError, match="double release or foreign"):
        a.release(got)


def test_free_of_shared_block_is_loud():
    """``free`` keeps its exclusive-ownership meaning: freeing a block
    someone else still holds is the corruption refcounts exist to stop."""
    a = BlockAllocator(num_blocks=6)
    got = a.alloc(1)
    a.retain(got)
    with pytest.raises(ValueError, match="use release"):
        a.free(got)
    a.release(got)
    a.free(got)  # now exclusive: the historical path still works
    assert a.num_free == 5


def test_fork_block_requires_a_shared_source():
    a = BlockAllocator(num_blocks=6)
    got = a.alloc(1)
    with pytest.raises(ValueError, match="not shared"):
        a.fork_block(got[0])
    a.retain(got)
    twin = a.fork_block(got[0])
    assert twin != got[0] and a.refcount(twin) == 1
    with pytest.raises(ValueError, match="not allocated"):
        a.fork_block(99)


def test_allocator_refcounted_churn_property():
    """Satellite 4: the churn property test, extended to refcounted
    interleavings.  Random alloc/retain/release/fork/free traffic across
    200 seeded episodes against a model of holder counts: the free list
    never acquires duplicates, refcounts match the model exactly, a
    refcount-0 block is never held, and a COW fork never aliases a live
    shared block."""
    rng = np.random.default_rng(1234)
    a = BlockAllocator(num_blocks=17)  # 16 allocatable
    holders: dict[int, int] = {}  # model: block -> holder count
    for step in range(200):
        free = set(a._free)
        assert NULL_BLOCK not in free
        assert len(a._free) == len(free), "free list acquired duplicates"
        assert set(a._allocated) == set(holders), "ownership drifted"
        assert not (free & set(holders)), "a held block is on the free list"
        assert free | set(holders) == set(range(1, 17)), "foreign/lost ids"
        for b, n in holders.items():
            assert a.refcount(b) == n, f"refcount drift on block {b}"
            assert n >= 1, "model holds a refcount-0 block"
        op = rng.random()
        held = list(holders)
        if op < 0.35 or (op < 0.75 and not held):
            want = int(rng.integers(1, 4))
            if want > a.num_free:
                with pytest.raises(CacheExhausted):
                    a.alloc(want)
            else:
                got = a.alloc(want)
                assert len(set(got)) == len(got)
                assert not (set(got) & set(holders)), (
                    "alloc aliased a live block"
                )
                for b in got:
                    holders[b] = 1
        elif op < 0.55:
            b = held[rng.integers(len(held))]
            a.retain([b])
            holders[b] += 1
        elif op < 0.85:
            b = held[rng.integers(len(held))]
            a.release([b])
            holders[b] -= 1
            if holders[b] == 0:
                del holders[b]
        elif op < 0.95:
            shared = [b for b, n in holders.items() if n >= 2]
            if shared and a.num_free:
                src = shared[rng.integers(len(shared))]
                twin = a.fork_block(src)
                assert twin not in holders, "fork aliased a live block"
                holders[twin] = 1
        else:
            exclusive = [b for b, n in holders.items() if n == 1]
            if exclusive:
                b = exclusive[rng.integers(len(exclusive))]
                a.free([b])
                del holders[b]
    for b in list(holders):
        while holders[b]:
            a.release([b])
            holders[b] -= 1
    assert a.num_free == 16


# ------------------------------------------------------------- radix index


def test_index_match_full_blocks_only():
    a = BlockAllocator(num_blocks=10)
    idx = PrefixIndex(block_size=4, allocator=a)
    toks = np.arange(10, dtype=np.int32)  # 2 full blocks + partial tail
    got = a.alloc(2)
    assert idx.insert(toks, got) == 2
    idx.check()
    assert idx.match(toks) == got
    assert idx.match(toks[:7]) == got[:1]  # 7 tokens: one FULL block
    assert idx.match(toks[:3]) == []  # under a block: nothing cacheable
    # divergence inside the second block stops the walk after the first
    other = toks.copy()
    other[6] = 63
    assert idx.match(other) == got[:1]
    # insertion retained: releasing the sequence's refs keeps them alive
    a.release(got)
    assert a.num_free == 7 and all(a.refcount(b) == 1 for b in got)


def test_index_insert_is_loud_on_misuse():
    a = BlockAllocator(num_blocks=10)
    idx = PrefixIndex(block_size=4, allocator=a)
    got = a.alloc(3)
    with pytest.raises(PrefixIndexError, match="tokens"):
        idx.insert(np.arange(8, dtype=np.int32), got)  # 3 blocks, 8 toks
    idx.insert(np.arange(8, dtype=np.int32), got[:2])
    with pytest.raises(PrefixIndexError, match="already indexed"):
        # same BLOCK under a different prefix: one block, one owner chain
        idx.insert(np.arange(50, 58, dtype=np.int32), got[:1])
    idx.check()


def test_index_lru_eviction_spares_live_holders():
    a = BlockAllocator(num_blocks=10)
    idx = PrefixIndex(block_size=4, allocator=a)
    cold = a.alloc(1)
    warm = a.alloc(1)
    held = a.alloc(1)
    idx.insert(np.arange(0, 4, dtype=np.int32), cold)
    idx.insert(np.arange(10, 14, dtype=np.int32), warm)
    idx.insert(np.arange(20, 24, dtype=np.int32), held)
    a.release(cold + warm)  # index is now their only holder
    # "held" keeps its sequence reference: refcount 2, not evictable
    assert idx.match(np.arange(10, 14, dtype=np.int32)) == warm  # touch
    assert idx.evict(1) == 1  # takes the LRU evictable: cold
    assert a.refcount(cold[0]) == 0
    assert a.refcount(warm[0]) == 1
    assert idx.evict(5) == 1  # only warm left evictable; held survives
    assert idx.size == 1 and a.refcount(held[0]) == 2
    idx.check()


def test_index_eviction_is_leaves_first():
    """Evicting an interior node would orphan reachable children: the
    LRU order must yield the chain tail before its parent."""
    a = BlockAllocator(num_blocks=10)
    idx = PrefixIndex(block_size=2, allocator=a)
    got = a.alloc(3)
    idx.insert(np.arange(6, dtype=np.int32), got)  # one 3-deep chain
    a.release(got)
    assert idx.evict(1) == 1
    assert a.refcount(got[2]) == 0, "leaf should fall first"
    assert idx.match(np.arange(6, dtype=np.int32)) == got[:2]
    idx.check()


def test_index_keying_is_deterministic_across_replicas():
    """Two indexes fed the same prompts build identical KEY paths even
    when their allocators hand out different block ids — the contract
    prefix-affinity routing rests on."""
    prompts = [np.arange(8, dtype=np.int32),
               np.arange(4, 12, dtype=np.int32),
               np.arange(8, dtype=np.int32)]  # duplicate: first wins
    paths = []
    for skew in (0, 3):
        a = BlockAllocator(num_blocks=16)
        if skew:
            a.alloc(skew)  # shift the id sequence between "replicas"
        idx = PrefixIndex(block_size=4, allocator=a)
        for p in prompts:
            idx.insert(p, a.alloc(len(p) // 4))
        paths.append(idx.key_paths())
    assert paths[0] == paths[1]


def test_index_clear_releases_everything():
    a = BlockAllocator(num_blocks=10)
    idx = PrefixIndex(block_size=4, allocator=a)
    got = a.alloc(2)
    idx.insert(np.arange(8, dtype=np.int32), got)
    a.release(got)
    assert idx.clear() == 2
    assert a.num_free == 9 and idx.size == 0


# ----------------------------------------------------- suffix-only prefill


def _pv_row_class(rows: int) -> int:
    """XLA:CPU (jaxlib 0.9.0) lowers ``cached_attention``'s P·V
    contraction — ``einsum("bhqk,bkhd->bqhd")``, a batched dot over the
    ``max_len`` cached positions — with one of three accumulation orders,
    chosen by the number of query rows: 1, 2..16, or 17 and up (probed by
    slicing the rows of one einsum: the same row's result changes exactly
    at those two boundaries and nowhere else up to 40)."""
    return 0 if rows == 1 else 1 if rows <= 16 else 2


@pytest.mark.parametrize("c,s", [(8, 5), (16, 8), (24, 2)])
def test_prefill_suffix_matches_full_prefill(model, c, s):
    """The tentpole's core, at the kernel level: suffix prefill over a
    cached prefix reproduces the full prefill's last-token logits AND
    every suffix cache row — bitwise when the full prefill (c + s rows)
    and the suffix (s rows) fall in the same P·V row class, which is the
    claim the cache was built on.  Across a class boundary the two sum
    the same 48 products per output in a different order, so the claim
    is re-pinned to a tolerance THERE ONLY: 1e-5 is ~100 f32 ulps at the
    logits' magnitude (observed 6e-7) — room for two layers of
    reassociated sums and the vocab projection, and five orders below
    what a wrong RoPE offset or mask does to a logit (O(1))."""
    cfg, params = model
    rng = np.random.default_rng(7)
    toks = _prompt(rng, c + s)
    want_logits, want_cache = prefill(params, toks[None], cfg, max_len=48)
    prefix = {
        "k": [np.asarray(k[:, :c]) for k in want_cache["k"]],
        "v": [np.asarray(v[:, :c]) for v in want_cache["v"]],
    }
    got_logits, got_cache = prefill_suffix(
        params, toks[None, c:], prefix, cfg, max_len=48
    )
    if _pv_row_class(c + s) == _pv_row_class(s):
        check = np.testing.assert_array_equal
    else:
        check = lambda a, b: np.testing.assert_allclose(  # noqa: E731
            a, b, rtol=1e-5, atol=1e-5
        )
    check(np.asarray(got_logits), np.asarray(want_logits))
    for l in range(cfg.n_layers):
        for kind in ("k", "v"):
            check(
                np.asarray(got_cache[kind][l][:, : c + s]),
                np.asarray(want_cache[kind][l][:, : c + s]),
            )


def test_prefill_suffix_rejects_empty_suffix_and_overflow(model):
    cfg, params = model
    prefix = {
        "k": [np.zeros((1, 8, 4, 8), np.float32)] * cfg.n_layers,
        "v": [np.zeros((1, 8, 4, 8), np.float32)] * cfg.n_layers,
    }
    with pytest.raises(ValueError, match="at least one suffix token"):
        prefill_suffix(params, np.zeros((1, 0), np.int32), prefix, cfg,
                       max_len=48)
    with pytest.raises(ValueError, match="exceeds max_len"):
        prefill_suffix(params, np.zeros((1, 48), np.int32), prefix, cfg,
                       max_len=48)


# --------------------------------------------------------- the warm engine


@pytest.mark.parametrize("shared", [True, False], ids=["shared", "unique"])
def test_warm_engine_bitwise_equals_cold_and_generate(model, shared):
    """The certification oracle: a shared-system-prompt workload through
    a warm-index engine produces BITWISE the tokens of a cold engine and
    of contiguous generate — and the warm engine actually hit (including
    one COW full-prompt hit) and drains every block.  The negative
    control: prompts that share nothing are as bitwise and as leak-free,
    and the index invents no sharing — no hit, no fork, no token saved."""
    cfg, params = model
    pcfg = _pcfg()
    rng = np.random.default_rng(0)
    sysp = _prompt(rng, 32)  # 4 full blocks at block_size 8
    suffixes = [5, 9, 3, 10, 0, 7]  # 0: the bare prompt — the COW case
    reqs = [
        Request(
            rid=i,
            prompt=np.concatenate(
                [sysp if shared else _prompt(rng, 32), _prompt(rng, k)]
            ),
            max_new_tokens=6,
        )
        for i, k in enumerate(suffixes)
    ]

    def run(prefix_cache):
        eng = ServingEngine(
            params, cfg, pcfg,
            BatcherConfig(slots=4, prefix_cache=prefix_cache), fused=False,
        )
        for r in reqs:
            assert eng.submit(r)
        eng.run_until_idle()
        return eng

    warm, cold = run(True), run(False)
    for r in reqs:
        want = _oracle(params, cfg, pcfg, r)
        np.testing.assert_array_equal(warm.completed[r.rid].tokens, want)
        np.testing.assert_array_equal(cold.completed[r.rid].tokens, want)
    snap = warm.report()
    if shared:
        assert snap["counters"]["serve.prefix_hits"] >= 1
        assert snap["counters"]["serve.prefix_cow"] >= 1
        assert snap["counters"]["serve.cached_tokens_saved"] >= 32
        assert 0.0 < snap["gauges"]["serve.prefix_hit_rate"] <= 1.0
    else:
        for name in ("prefix_hits", "prefix_cow", "cached_tokens_saved"):
            assert snap["counters"].get(f"serve.{name}", 0) == 0
        assert snap["gauges"]["serve.prefix_hit_rate"] == 0.0
    # no leaked blocks: dropping the index's references drains the pool
    warm.batcher.prefix_index.check()
    assert warm.release_prefix_cache() > 0
    assert warm.batcher.allocator.num_free == pcfg.num_blocks - 1
    # the cold engine never consulted an index
    assert "serve.prefix_hits" not in cold.report()["counters"]


def test_warm_hit_ignores_poisoned_unreferenced_blocks(model):
    """Poison-the-pool invariance: after the index is warm, garbage in
    every FREE block must not reach a cache-hit request's output — the
    suffix prefill gathers only the blocks the radix chain names."""
    cfg, params = model
    pcfg = _pcfg()
    rng = np.random.default_rng(21)
    sysp = _prompt(rng, 32)
    eng = _warm_engine(params, cfg, pcfg)
    seed_req = Request(rid=0, prompt=np.concatenate([sysp, _prompt(rng, 4)]),
                       max_new_tokens=4)
    assert eng.submit(seed_req)
    eng.run_until_idle()
    poison_ids = np.asarray(sorted(eng.batcher.allocator._free), np.int32)
    for l in range(cfg.n_layers):
        eng.pools["k"][l] = eng.pools["k"][l].at[poison_ids].set(1e9)
        eng.pools["v"][l] = eng.pools["v"][l].at[poison_ids].set(1e9)
    hit = Request(rid=1, prompt=np.concatenate([sysp, _prompt(rng, 9)]),
                  max_new_tokens=5)
    assert eng.submit(hit)
    eng.run_until_idle()
    assert eng.report()["counters"]["serve.prefix_hits"] >= 1
    np.testing.assert_array_equal(
        eng.completed[1].tokens, _oracle(params, cfg, pcfg, hit)
    )


def test_cow_divergence_leaves_shared_bytes_untouched(model):
    """COW certification: a full-prompt hit forks the final shared block
    instead of writing into it, and a mid-block divergent prompt shares
    only the agreeing FULL blocks — in both cases every byte of every
    shared block is identical before and after, and outputs are bitwise."""
    cfg, params = model
    pcfg = _pcfg()
    rng = np.random.default_rng(5)
    sysp = _prompt(rng, 32)
    eng = _warm_engine(params, cfg, pcfg, slots=2)
    assert eng.submit(Request(rid=0, prompt=sysp, max_new_tokens=4))
    eng.run_until_idle()
    shared_ids = np.asarray(
        eng.batcher.prefix_index.match(sysp), np.int32
    )
    assert len(shared_ids) == 4
    before = [np.asarray(eng.pools["k"][l][shared_ids])
              for l in range(cfg.n_layers)]
    # the COW case: the exact prompt again — every block matched, the
    # last one forked (its tail positions must be re-derived in a
    # private copy, never written in place)
    again = Request(rid=1, prompt=sysp, max_new_tokens=6)
    # the mid-block divergence case: same first 31 tokens, different last
    div = sysp.copy()
    div[-1] = (div[-1] + 1) % 64
    diverged = Request(rid=2, prompt=div, max_new_tokens=6)
    for r in (again, diverged):
        assert eng.submit(r)
    eng.run_until_idle()
    snap = eng.report()
    assert snap["counters"]["serve.prefix_cow"] >= 1
    for l in range(cfg.n_layers):
        np.testing.assert_array_equal(
            np.asarray(eng.pools["k"][l][shared_ids]), before[l]
        )
    for r in (again, diverged):
        np.testing.assert_array_equal(
            eng.completed[r.rid].tokens, _oracle(params, cfg, pcfg, r)
        )


def test_sampled_shared_prefix_survives_preemption_and_swap(model):
    """Shared-prefix sequences through on-demand admission + swap
    preemption, SAMPLED: eviction releases the shared blocks (the index
    keeps them), resume is all-private, and the key schedule still lands
    every request exactly on generate(key)."""
    cfg, params = model
    pcfg = _pcfg(num_blocks=10, blocks_per_seq=6)
    eng = ServingEngine(
        params, cfg, pcfg,
        BatcherConfig(slots=3, prefix_cache=True, admission="ondemand",
                      preempt="swap"),
        fused=False,
    )
    rng = np.random.default_rng(13)
    sysp = _prompt(rng, 16)  # 2 shared full blocks
    reqs = [
        Request(rid=i, prompt=np.concatenate([sysp, _prompt(rng, 3)]),
                max_new_tokens=12, temperature=0.7, top_k=8, seed=100 + i)
        for i in range(4)
    ]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    assert eng.metrics.counter("serve.preempts").value >= 1
    assert eng.report()["counters"]["serve.prefix_hits"] >= 1
    for r in reqs:
        want = _oracle(params, cfg, pcfg, r, temperature=0.7, top_k=8,
                       key=jax.random.PRNGKey(r.seed))
        np.testing.assert_array_equal(eng.completed[r.rid].tokens, want)
    eng.release_prefix_cache()
    assert eng.batcher.allocator.num_free == pcfg.num_blocks - 1


def test_admission_charges_suffix_only_and_evicts_under_pressure(model):
    """Batcher-level tentpole semantics: a cache hit charges the prefill
    budget for the SUFFIX alone, and pool pressure evicts refcount-1
    index entries instead of refusing admission."""
    cfg, params = model
    pcfg = _pcfg(num_blocks=10, blocks_per_seq=6)  # 9 allocatable
    rng = np.random.default_rng(3)
    sysp = _prompt(rng, 32)
    eng = _warm_engine(params, cfg, pcfg, slots=1)
    assert eng.submit(Request(rid=0, prompt=sysp, max_new_tokens=4))
    eng.run_until_idle()
    assert eng.batcher.prefix_index.size == 4
    # suffix-only budget: a 37-token prompt under a 16-token prefill
    # budget admits ONLY because 32 of its tokens are cached
    b = ContinuousBatcher(
        pcfg,
        BatcherConfig(slots=1, max_prefill_tokens_per_step=16,
                      prefix_cache=True),
    )
    b.prefix_index = eng.batcher.prefix_index
    b.allocator = eng.batcher.allocator
    b.prefix_index.allocator = b.allocator
    long = Request(rid=1, prompt=np.concatenate([sysp, _prompt(rng, 5)]),
                   max_new_tokens=4)
    assert b.submit(long)
    admitted = b.try_admit()
    assert len(admitted) == 1
    state = admitted[0][1]
    assert state.cached_tokens == 32 and state.shared_blocks == 4
    # while rid 1 shares the index's blocks, they have live holders:
    # eviction must refuse them even under direct pressure
    assert b.prefix_index.evict(4) == 0
    b.preempt(0)
    b.preempted.clear()  # drop the parked sequence; blocks were released
    # pool pressure: a unique prompt needing more than the free list
    # holds forces LRU eviction of the now-idle index tail
    free_before = b.allocator.num_free
    unique = Request(rid=2, prompt=_prompt(rng, 42), max_new_tokens=4)
    need = b.blocks_needed(unique)
    assert need > free_before  # the pressure is real
    assert b.submit(unique)
    assert len(b.try_admit()) == 1
    assert b.prefix_index.evictions >= need - free_before


def test_engine_warmup_compiles_suffix_buckets(model):
    cfg, params = model
    eng = _warm_engine(params, cfg, _pcfg(), slots=2)
    eng.warmup([8], (), suffix_buckets=[(8, 4), (30, 2)])  # incl. COW shape
    with pytest.raises(ValueError, match="suffix bucket"):
        eng.warmup([], (), suffix_buckets=[(0, 4)])
    with pytest.raises(ValueError, match="suffix bucket"):
        eng.warmup([], (), suffix_buckets=[(8, 0)])


def test_prefix_events_and_prom_export(model, tmp_path):
    """Satellite 3: hit/evict flight events land on the serve lane of the
    merged timeline, and the windowed hit-rate gauge plus the counters
    travel through the prometheus exposition ``obs metrics --prom``
    renders."""
    from flextree_tpu.obs import flight_recorder
    from flextree_tpu.obs.metrics import prometheus_exposition
    from flextree_tpu.obs.timeline import merge_events, read_dir

    cfg, params = model
    pcfg = _pcfg()
    rng = np.random.default_rng(11)
    sysp = _prompt(rng, 32)
    eng = _warm_engine(params, cfg, pcfg, slots=2)
    with flight_recorder(tmp_path, rank=0):
        for i, k in enumerate([4, 6]):
            assert eng.submit(Request(
                rid=i, prompt=np.concatenate([sysp, _prompt(rng, k)]),
                max_new_tokens=4,
            ))
            eng.run_until_idle()
        eng.batcher.prefix_index.evict(1)
    events, _ = read_dir(str(tmp_path))
    hits = [e for e in events if e["kind"] == "serve_prefix_hit"]
    assert hits and hits[0]["cached_tokens"] == 32
    assert any(e["kind"] == "serve_prefix_evict" for e in events)
    trace = merge_events(events)
    by_name = {t["name"]: t for t in trace["traceEvents"] if "name" in t}
    assert by_name["serve_prefix_hit"]["cat"] == "serve"
    assert by_name["serve_prefix_evict"]["cat"] == "serve"
    prefills = [e for e in events if e["kind"] == "serve_prefill"]
    assert {e["cached_tokens"] for e in prefills} == {0, 32}
    text = prometheus_exposition({"replica": eng.metrics.snapshot()})
    assert "flextree_serve_prefix_hits" in text
    assert "flextree_serve_prefix_hit_rate" in text
    assert "flextree_serve_cached_tokens_saved" in text


def test_predict_prefill_us_prices_cache_hits(model):
    """Satellite 1: cached tokens pay neither their dense FLOPs nor
    their attention rows — but the suffix still attends over the full
    prefix, so a hit is cheaper than a cold suffix-length prompt is NOT
    (t² − c² > (t − c)²)."""
    from flextree_tpu.serving.costs import predict_prefill_us

    cfg, _ = model
    full = predict_prefill_us(cfg, 32)
    hit = predict_prefill_us(cfg, 32, cached_tokens=24)
    assert 0 < hit < full
    assert hit > predict_prefill_us(cfg, 8)  # the t²−c² tail is real
    # monotone in cached_tokens, and clamped to t−1 (the last token
    # always runs for its logits)
    prev = full
    for c in (8, 16, 24, 31, 31_000):
        cur = predict_prefill_us(cfg, 32, cached_tokens=c)
        assert cur <= prev
        prev = cur
    assert prev == predict_prefill_us(cfg, 32, cached_tokens=31)


# ------------------------------------------------- prefix-affinity routing


def test_frontdoor_affinity_prefers_last_server_within_safe_set(tmp_path):
    """Affinity is a tiebreak inside the healthy tier, never a way past
    health/breaker/exclusion: the preferred rank wins over the load
    balance, but an excluded or breaker-open preference falls back to
    least-outstanding and counts the miss."""
    from flextree_tpu.serving import FrontDoor, FrontDoorConfig
    from flextree_tpu.serving.frontdoor import ReplicaClient

    fd = FrontDoor(str(tmp_path), FrontDoorConfig(dispatchers=0))
    try:
        for rank, outstanding in ((0, 0), (1, 5)):
            c = ReplicaClient(rank, fd.cfg)
            c.update_endpoint("127.0.0.1", 1, 1)
            c.outstanding = outstanding
            fd.clients[rank] = c
        assert fd._routable().rank == 0  # plain least-outstanding
        assert fd._routable(prefer=1).rank == 1  # affinity beats load
        assert fd.metrics.counter("serve.affinity_routed").value == 1
        # exclusion (a drain refusal) overrides the preference
        assert fd._routable(exclude={1}, prefer=1).rank == 0
        assert fd.metrics.counter("serve.affinity_miss").value == 1
        # an open breaker does too
        fd.clients[1].open_until = time.monotonic() + 60.0
        assert fd._routable(prefer=1).rank == 0
        assert fd.metrics.counter("serve.affinity_miss").value == 2
    finally:
        fd.close()


def test_frontdoor_records_affinity_and_short_prompts_opt_out(tmp_path):
    from flextree_tpu.serving import FrontDoor, FrontDoorConfig

    fd = FrontDoor(str(tmp_path), FrontDoorConfig(dispatchers=0,
                                                  affinity_span=4))
    try:
        assert fd.submit(1, np.arange(8, dtype=np.int32), 2)
        assert 1 in fd._rid_phash
        # a prompt no longer than the span cannot share a FULL block
        assert fd.submit(2, np.arange(4, dtype=np.int32), 2)
        assert 2 not in fd._rid_phash
        fd._deliver(
            1, {"tokens": [1], "ttft_s": 0.0, "rank": 7}, fd.clients
            .setdefault(7, __import__(
                "flextree_tpu.serving.frontdoor", fromlist=["ReplicaClient"]
            ).ReplicaClient(7, fd.cfg)),
            time.monotonic(), False,
        )
        phash = __import__("zlib").crc32(
            np.arange(4, dtype=np.int32).tobytes()
        )
        assert fd._affinity[phash] == 7
        assert 1 not in fd._rid_phash  # consumed on delivery
    finally:
        fd.close()


def test_drain_reroutes_cache_hit_to_cold_replica(model, tmp_path):
    """The certification's routing leg, on real in-process servers: the
    affinity target (warm index) starts draining, the front door
    re-routes the cache-hit request to the COLD replica, and the answer
    is still bitwise — warmth is a latency property, never a correctness
    one."""
    from flextree_tpu.serving import (
        FrontDoor,
        FrontDoorConfig,
        ReplicaConfig,
        ReplicaServer,
    )

    cfg, params = model
    pcfg = _pcfg()
    rng = np.random.default_rng(9)
    sysp = _prompt(rng, 32)
    servers = [
        ReplicaServer(
            _warm_engine(params, cfg, pcfg, slots=2),
            ReplicaConfig(rank, str(tmp_path)),
        ).start()
        for rank in (0, 1)
    ]
    fd = FrontDoor(
        str(tmp_path),
        FrontDoorConfig(dispatchers=1, max_hedges=0,
                        request_timeout_s=60.0, attempt_timeout_s=30.0),
    ).start()
    try:
        p0 = np.concatenate([sysp, _prompt(rng, 4)])
        assert fd.submit(1, p0, 4)
        assert fd.wait_idle(timeout_s=60.0)
        warm_rank = fd.completed[1].rank
        # the replica that owns the warm index leaves the pool
        servers[warm_rank].initiate_drain()
        p1 = np.concatenate([sysp, _prompt(rng, 7)])
        assert fd.submit(2, p1, 4)
        assert fd.wait_idle(timeout_s=60.0)
        assert fd.failed == {}
        assert fd.completed[2].rank == 1 - warm_rank
        want = np.asarray(
            generate(params, p1[None], cfg, max_new_tokens=4,
                     max_len=pcfg.max_len)
        )[0]
        np.testing.assert_array_equal(fd.completed[2].tokens, want)
    finally:
        fd.close()
        for s in servers:
            s.stop()
