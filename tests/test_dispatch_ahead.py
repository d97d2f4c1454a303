"""Dispatch ahead: a step dispatches the next decode round before it
fetches its own round's ids, when the host already knows that round
(``ContinuousBatcher.continues``), and feeds it those ids as they lie on
the device.  The tokens are those of the engine that never does, step by
step; the path disengages wherever the next round is not known yet; and a
round in flight is delivered before anything reads or moves a slot, or
dropped with its replica.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flextree_tpu.models import TransformerConfig, generate, init_params
from flextree_tpu.serving import (
    BatcherConfig,
    PagedCacheConfig,
    PoolConfig,
    ReplicaPool,
    Request,
    ServingEngine,
)
from flextree_tpu.serving.batcher import ContinuousBatcher

CFG = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
PCFG = PagedCacheConfig(num_blocks=32, block_size=8, blocks_per_seq=6)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _prompt(seed, n, vocab=64):
    return np.random.default_rng(seed).integers(0, vocab, (n,)).astype(np.int32)


def _dense(params, slots=3, pcfg=PCFG, **bcfg):
    return ServingEngine(params, CFG, pcfg, BatcherConfig(slots=slots, **bcfg))


def _block(name):
    from tests.test_laguna import tiny as laguna_tiny
    from tests.test_olmo_hybrid import tiny as olmo_tiny

    config = {"olmo": olmo_tiny, "laguna": laguna_tiny}[name]()
    return ServingEngine.from_config(
        config, PagedCacheConfig(num_blocks=40, block_size=4, blocks_per_seq=8),
        BatcherConfig(slots=3), seed=3)


def _generated(eng):
    return [None if s is None else list(s.generated) for s in eng.batcher.slots]


def _ahead(eng):
    return eng.report()["counters"]["serve.rounds_ahead"]


def _want(params, req, pcfg=PCFG):
    return np.asarray(generate(
        params, jnp.asarray(req.prompt)[None], CFG,
        max_new_tokens=req.max_new_tokens, max_len=pcfg.max_len))[0]


# ---------------------------------------------- token for token the same


@pytest.mark.parametrize("block", ["dense", "olmo", "laguna"])
def test_rounds_dispatched_ahead_give_the_tokens_of_the_engine_that_waits(
    params, block, monkeypatch
):
    """The same requests through two engines of one model, one of them
    never dispatching ahead: after every step the same counts and the same
    tokens in every slot, and the same completions — for the dense block,
    a block that holds a state a slot, and one that routes experts."""
    if block == "dense":
        fast, slow = _dense(params), _dense(params)
        vocab = CFG.vocab_size
    else:
        fast, slow = _block(block), _block(block)
        vocab = fast.cfg.vocab_size
    monkeypatch.setattr(slow.batcher, "continues", lambda slots: False)
    # three slots, four requests: the fourth waits for the first to retire
    reqs = [Request(rid=i, prompt=_prompt(i, t, vocab), max_new_tokens=m)
            for i, (t, m) in enumerate([(5, 9), (7, 4), (3, 6), (6, 5)])]
    for eng in (fast, slow):
        for r in reqs:
            assert eng.submit(r)
    steps = 0
    while not slow.idle:
        assert fast.step() == slow.step()
        assert _generated(fast) == _generated(slow)
        steps += 1
    assert fast.idle and steps > 0
    assert sorted(fast.completed) == sorted(slow.completed) == [0, 1, 2, 3]
    for rid, done in slow.completed.items():
        np.testing.assert_array_equal(fast.completed[rid].tokens, done.tokens)
    assert _ahead(fast) > 0 and _ahead(slow) == 0
    assert fast.decode_steps == slow.decode_steps


def test_completions_are_generates_with_finishers_in_one_round(params):
    """Greedy requests of mixed lengths, two of them finishing in the same
    round: every completion is ``generate``'s, and rounds went ahead."""
    eng = _dense(params, slots=4)
    reqs = [Request(rid=i, prompt=_prompt(10 + i, t), max_new_tokens=m)
            for i, (t, m) in enumerate([(5, 6), (9, 6), (13, 8), (7, 2),
                                        (4, 11)])]
    for r in reqs:
        assert eng.submit(r)
    finished = []
    while not eng.idle:
        finished.append(eng.step()["finished"])
    assert max(finished) >= 2  # rids 0 and 1 retire in one round
    for r in reqs:
        np.testing.assert_array_equal(eng.completed[r.rid].tokens,
                                      _want(params, r))
    assert _ahead(eng) > 0


def test_a_round_fed_the_picks_ids_compiles_nothing_after_the_warmup(params):
    """The warm-up runs the decode program on host tokens alone; a round
    dispatched ahead takes the pick's ids as they lie on the device, and
    reaches the same executable: the rounds after the warm-up compile
    nothing (what the benchmark counts as ``entry.compiles_in_window``)."""
    import jax.monitoring

    eng = _dense(params)
    reqs = [Request(rid=i, prompt=_prompt(40 + i, 6), max_new_tokens=7)
            for i in range(3)]
    eng.warmup([6], [PCFG.blocks_for(6 + 7)])
    compiles = []

    def on(name, duration, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        for r in reqs:
            assert eng.submit(r)
        eng.run_until_idle()
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert _ahead(eng) > 0 and compiles == []


# ------------------------------------------------------- when it disengages


def test_a_sampled_request_keeps_the_rounds_it_is_in_waiting(params):
    eng = _dense(params)
    assert eng.submit(Request(rid=0, prompt=_prompt(0, 5), max_new_tokens=8))
    assert eng.submit(Request(rid=1, prompt=_prompt(1, 6), max_new_tokens=4,
                              temperature=0.7, top_k=5, seed=2))
    while not eng.idle:
        sampled = any(s is not None and s.request.temperature > 0
                      and not s.done for s in eng.batcher.slots)
        eng.step()
        # no round went ahead while the sampled request was in it
        assert eng._ahead is None or not sampled
    assert _ahead(eng) > 0  # the greedy one went on alone


def test_a_request_with_stop_tokens_never_goes_ahead(params):
    eng = _dense(params)
    req = Request(rid=0, prompt=_prompt(3, 5), max_new_tokens=8,
                  stop_tokens=(63,))
    assert eng.submit(req)
    eng.run_until_idle()
    assert _ahead(eng) == 0
    assert eng.completed[0].n_tokens >= 1


def test_an_ondemand_block_boundary_waits_for_the_block(params):
    """Under on-demand admission a 7-token prompt holds one block of 8:
    the round after the first writes position 8, in a block not held yet,
    so the first step waits; the step after grows the block and goes on."""
    eng = _dense(params, admission="ondemand")
    req = Request(rid=0, prompt=_prompt(4, 7), max_new_tokens=6)
    assert eng.submit(req)
    eng.step()
    assert eng._ahead is None and eng.batcher.slots[0].length == 8
    eng.step()
    assert eng._ahead is not None
    eng.run_until_idle()
    np.testing.assert_array_equal(eng.completed[0].tokens, _want(params, req))


def test_a_pending_resume_or_admission_keeps_the_round_waiting():
    """The batcher's own check: a queued request with a free slot, and a
    preempted sequence waiting to resume, each make the next round
    unknown."""
    batcher = ContinuousBatcher(PCFG, BatcherConfig(slots=3))
    for rid in range(2):
        assert batcher.submit(Request(rid=rid, prompt=_prompt(rid, 5),
                                      max_new_tokens=6))
    admitted = batcher.try_admit()
    for slot, _ in admitted:
        batcher.record_first_token(slot, 1, 0.0)
    slots = batcher.active_slots()
    assert batcher.continues(slots)
    assert batcher.submit(Request(rid=9, prompt=_prompt(9, 5), max_new_tokens=6))
    assert not batcher.continues(slots)  # rid 9 would join the next round
    batcher.queue.clear()
    assert batcher.continues(slots)
    batcher.preempt(slots[-1])
    assert not batcher.continues(slots[:-1])  # it waits to resume
    assert not batcher.continues(slots)  # and is no longer resident


def test_an_admission_joins_the_next_round_not_the_one_in_flight(params):
    """A request that arrives while a round is in flight is admitted and
    prefilled behind it; the step delivers the round in flight alone and
    goes ahead no further, and the next step's round holds both."""
    eng = _dense(params, slots=2)
    first = Request(rid=0, prompt=_prompt(5, 6), max_new_tokens=9)
    assert eng.submit(first)
    eng.step()
    assert eng._ahead is not None
    late = Request(rid=1, prompt=_prompt(6, 5), max_new_tokens=5)
    assert eng.submit(late)
    out = eng.step()
    assert (out["admitted"], out["decoded"]) == (1, 1)
    assert eng._ahead is None
    assert len(eng.batcher.slots[1].generated) == 1  # its first token only
    assert eng.step()["decoded"] == 2
    assert eng._ahead is not None  # both go on
    eng.run_until_idle()
    for r in (first, late):
        np.testing.assert_array_equal(eng.completed[r.rid].tokens,
                                      _want(params, r))


def test_a_preemption_settles_the_round_in_flight_first(params):
    """On-demand admission on a pool too small for every sequence: rounds
    go ahead between boundaries, preemptions come at the boundaries, and
    every completion is ``generate``'s."""
    pcfg = PagedCacheConfig(num_blocks=10, block_size=8, blocks_per_seq=6)
    eng = _dense(params, slots=4, pcfg=pcfg, admission="ondemand",
                 preempt="swap")
    reqs = [Request(rid=i, prompt=_prompt(20 + i, 9), max_new_tokens=20)
            for i in range(5)]
    for r in reqs:
        assert eng.submit(r)
    eng.run_until_idle()
    counters = eng.report()["counters"]
    assert counters["serve.preempts"] >= 1 and counters["serve.rounds_ahead"] > 0
    for r in reqs:
        np.testing.assert_array_equal(eng.completed[r.rid].tokens,
                                      _want(params, r, pcfg))


def test_a_slot_preempted_with_a_round_in_flight_keeps_that_rounds_token(
    params
):
    """A preemption that meets a round in flight (an arrival admitted
    between two steps can force one) delivers that round first: the
    evicted sequence swaps out with the round's token recorded, resumes,
    and completes as ``generate`` does."""
    eng = _dense(params, slots=2, admission="ondemand", preempt="swap")
    reqs = [Request(rid=i, prompt=_prompt(50 + i, 5), max_new_tokens=8)
            for i in range(2)]
    for r in reqs:
        assert eng.submit(r)
    eng.step()
    assert eng._ahead is not None
    before = len(eng.batcher.slots[1].generated)
    eng._preempt_slot(1)
    assert eng._ahead.delivered and eng.batcher.slots[1] is None
    [parked] = eng.batcher.preempted
    assert len(parked.state.generated) == before + 1
    eng.run_until_idle()
    for r in reqs:
        np.testing.assert_array_equal(eng.completed[r.rid].tokens,
                                      _want(params, r))


# --------------------------------------------------- a replica's round in flight


def test_a_replica_killed_with_a_round_in_flight_returns_each_request_once(
    params, tmp_path
):
    engines = [_dense(params, slots=2) for _ in range(2)]
    pool = ReplicaPool(engines, PoolConfig(heartbeat_dir=str(tmp_path),
                                           step_timeout_s=30.0))
    reqs = [Request(rid=10 + i, prompt=_prompt(30 + i, 5 + i),
                    max_new_tokens=8) for i in range(4)]
    try:
        for r in reqs:
            pool.submit(r)
        pool.step()
        assert engines[1]._ahead is not None  # killed with a round in flight
        pool.kill(1, mode="raise")
        report = pool.run_until_idle()
    finally:
        pool.shutdown()
    assert report["completed"] == 4 and report["degraded"]
    assert sorted(pool.completed) == [r.rid for r in reqs]
    for r in reqs:
        np.testing.assert_array_equal(pool.completed[r.rid].tokens,
                                      _want(params, r))
    # the dead replica delivered its first round only: the one in flight
    # was dropped with it, and the survivor recomputed both requests
    assert engines[1].decode_steps == 1 and engines[1]._ahead is not None
