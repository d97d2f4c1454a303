"""Dispatch ahead: a step dispatches the next decode round before it
fetches its own round's ids, when the host already knows that round
(``ContinuousBatcher.continues``), and feeds it those ids as they lie on
the device.  The tokens are those of the engine that never does, step by
step; the path disengages wherever the next round is not known yet; and a
round in flight is delivered before anything reads or moves a slot, or
dropped with its replica.  A diffusion pass goes ahead by the same path,
its kind's half of ``continues`` slot by slot, fed the pick's fold of its
reveal into the next pass's open blocks on the device.
"""

import dataclasses

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


def _sdar(slots=4):
    from tests.test_sdar_moe import engine as sdar_engine, tiny as sdar_tiny

    return sdar_engine(sdar_tiny(), slots=slots)


def _engine(block, params, slots=3):
    """An engine of one model: the dense block, one that holds a state a
    slot (``olmo``), one that routes experts (``laguna``), and the diffusion
    block (``sdar``, whose rounds are passes)."""
    if block == "dense":
        return _dense(params, slots=slots)
    if block == "sdar":
        return _sdar(slots=slots)
    return _block(block)


def _vocab(eng):
    """The ids a prompt may hold: the model's, below a diffusion block's
    mask id."""
    diffusion = eng.batcher.diffusion
    return eng.cfg.vocab_size if diffusion is None else diffusion.mask_id


def _slots(eng):
    """Each slot's length, tokens and open block (a diffusion block's)."""
    return [None if s is None else (s.length, list(s.generated),
                                    s.block and dataclasses.astuple(s.block))
            for s in eng.batcher.slots]


# ---------------------------------------------- token for token the same


#: step -> (prompt length, max_new_tokens) submitted before it.  One token a
#: slot: three slots, four requests, all at the start (the fourth waits for
#: the first to retire).  The diffusion block: whole blocks and prompt tails
#: of 1 to 3, admissions mid-stream, and (for four slots) a pass that holds
#: all three phases and one that finishes two requests
_ROUNDS = {0: [(5, 9), (7, 4), (3, 6), (6, 5)]}
_PASSES = {0: [(8, 12), (10, 8), (9, 4)], 1: [(12, 8)], 2: [(7, 8)],
           6: [(11, 4), (6, 8)], 9: [(16, 8)]}


@pytest.mark.parametrize("block", ["dense", "olmo", "laguna", "sdar"])
def test_rounds_dispatched_ahead_give_the_tokens_of_the_engine_that_waits(
    params, block, monkeypatch
):
    """The same requests through two engines of one model, one of them
    never dispatching ahead: the same completions, bit for bit — for the
    dense block, a block that holds a state a slot, one that routes
    experts, and the diffusion block.  With every request in at the start
    the two move in lockstep: after every step the same counts and the same
    tokens in every slot.  (A request that arrives while a round is in
    flight joins the round after it, so the two engines' steps differ
    there.)  After each round dispatched ahead, the host's own arrays once
    the round before it is recorded are the ones it predicted, and what it
    took on the device (the ids, or a pass's open blocks, the pick's fold,
    commit passes included) the host's."""
    traffic = _PASSES if block == "sdar" else _ROUNDS
    fast = _engine(block, params, slots=4 if block == "sdar" else 3)
    slow = _engine(block, params, slots=4 if block == "sdar" else 3)
    monkeypatch.setattr(slow.batcher, "continues", lambda slots: False)
    predicted, phases, max_new = [], [], []
    dispatch, record = fast._dispatch, fast.batcher.record_block_pass

    def dispatch_spy(rid, active, arrays, prev=None):
        if prev is not None:
            predicted.append((arrays, prev.feed))
        return dispatch(rid, active, arrays, prev)

    def record_spy(slots, ids, revealed, now):
        phases.append({fast.batcher.slots[i].block.phase for i in slots})
        return record(slots, ids, revealed, now)

    monkeypatch.setattr(fast, "_dispatch", dispatch_spy)
    monkeypatch.setattr(fast.batcher, "record_block_pass", record_spy)
    lockstep = list(traffic) == [0]
    rid, step, finished = 0, 0, []
    while step == 0 or not (fast.idle and slow.idle):
        for t, m in traffic.get(step, []):
            req = Request(rid=rid, prompt=_prompt(rid, t, _vocab(fast)),
                          max_new_tokens=m)
            assert fast.submit(req) and slow.submit(req)
            max_new.append(m)
            rid += 1
        waited = None if slow.idle else slow.step()
        if fast.idle:
            step += 1
            continue
        before = len(predicted)
        out = fast.step()
        finished.append(out["finished"])
        if lockstep:
            assert out == waited
            assert _generated(fast) == _generated(slow)
        if len(predicted) > before:
            host, feed = predicted[-1]
            now = fast.batcher.batch_arrays()
            active, fed = now[-1], iter(feed)
            for want, got in zip(host, now):
                if want is None:  # what the round took on the device
                    np.testing.assert_array_equal(
                        np.asarray(next(fed))[active], got[active])
                else:
                    np.testing.assert_array_equal(want, got)
        step += 1
    assert rid == sum(len(reqs) for reqs in traffic.values())
    assert sorted(fast.completed) == sorted(slow.completed) == list(range(rid))
    for r, done in slow.completed.items():
        np.testing.assert_array_equal(fast.completed[r].tokens, done.tokens)
        assert fast.completed[r].n_tokens == done.n_tokens == max_new[r]
    assert _ahead(fast) == len(predicted) > 0 and _ahead(slow) == 0
    if lockstep:
        assert fast.decode_steps == slow.decode_steps
    else:
        # what the traffic was for: a pass of all three phases, a commit
        # pass folded ahead, two finishers in one round
        assert {0, 1, 2} in phases and max(finished) >= 2
        assert any(arrays[5].any() for arrays, _ in predicted)


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


@pytest.mark.parametrize("block, n, prompt_len, max_new, seed",
                         [("dense", 3, 6, 7, 40), ("sdar", 4, 10, 8, 0)])
def test_a_round_fed_the_picks_ids_compiles_nothing_after_the_warmup(
    params, block, n, prompt_len, max_new, seed
):
    """The warm-up runs the round's programs on host arrays, then on what
    the pick hands the round after it as it lies on the device (the ids, or
    a pass's folded open blocks): the rounds after the warm-up, synchronous
    and dispatched ahead, compile nothing (what the benchmark counts as
    ``entry.compiles_in_window``)."""
    import jax.monitoring

    eng = _engine(block, params)
    reqs = [Request(rid=i, prompt=_prompt(seed + i, prompt_len, _vocab(eng)),
                    max_new_tokens=max_new) for i in range(n)]
    eng.warmup([prompt_len], [eng.pcfg.blocks_for(prompt_len + max_new)])
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
    assert sorted(eng.completed) == list(range(n))


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


# ------------------------------------------------------- the diffusion pass


def test_the_picks_fold_is_what_the_host_records_and_its_reveal_is_todays():
    """The pick a pass runs, against the reveal alone (``unmask_greedy_ids``
    on three arguments, as it was before the fold), on logits with exact
    and one-ulp near ties: the same ids and revealed places, bit for bit;
    and its folded open blocks are those ``record_block_pass`` leaves on
    the host, pass after pass, commit passes and prompt tails included."""
    from flextree_tpu.serving.batcher import BlockDiffusion
    from flextree_tpu.serving.engine import unmask_greedy_ids

    diff = BlockDiffusion(4, 2, 90)
    batcher = ContinuousBatcher(PCFG, BatcherConfig(slots=4), diff)
    pick = jax.jit(unmask_greedy_ids, static_argnames="mask_id")
    reveal_alone = jax.jit(unmask_greedy_ids)
    rng = np.random.default_rng(7)
    arrivals = {0: [(8, 12), (10, 8)], 1: [(7, 12)], 2: [(9, 16)]}
    commits = 0
    for n in range(14):
        for t, m in arrivals.get(n, []):
            assert batcher.submit(Request(rid=100 * n + t, prompt=_prompt(n, t, 90),
                                          max_new_tokens=m))
        batcher.try_admit()
        slots = batcher.active_slots()
        _, _, tokens, masked, reveal, commit, _ = batcher.batch_arrays()
        logits = rng.normal(size=(4, 4, 96)).astype(np.float32)
        logits[:, 1] = logits[:, 0]  # places 0 and 1 tie exactly
        logits[:, 3] = np.nextafter(logits[:, 2], np.inf)  # a hair apart
        ids, revealed, nxt_tokens, nxt_masked = (np.asarray(a) for a in pick(
            logits, masked, reveal, tokens, commit, mask_id=90))
        want_ids, want_revealed = reveal_alone(logits, masked, reveal)
        np.testing.assert_array_equal(ids, want_ids)
        np.testing.assert_array_equal(revealed, want_revealed)
        commits += int(commit.sum())
        batcher.record_block_pass(slots, ids, revealed, float(n))
        _, _, tokens, masked, _, _, still = batcher.batch_arrays()
        np.testing.assert_array_equal(nxt_tokens[still], tokens[still])
        np.testing.assert_array_equal(nxt_masked[still], masked[still])
        batcher.retire_ready()
    assert commits >= 4


def test_the_next_pass_waits_where_the_host_cannot_know_it():
    """``continues`` is False for a request with stop tokens, a slot
    whose pass delivers its last block, a queued request with a free slot,
    and a preempted sequence; True otherwise."""
    from flextree_tpu.serving.batcher import BlockDiffusion

    def batcher_with(*reqs, slots=3):
        b = ContinuousBatcher(PCFG, BatcherConfig(slots=slots),
                              BlockDiffusion(4, 2, 90))
        for r in reqs:
            assert b.submit(r)
        b.try_admit()
        return b

    def req(rid, m=8, **kw):
        return Request(rid=rid, prompt=_prompt(rid, 8, 90), max_new_tokens=m, **kw)

    b = batcher_with(req(0), req(1))
    assert b.continues(b.active_slots())
    assert not b.continues(b.active_slots()[:1])  # not the slots there
    # stop tokens
    b = batcher_with(req(0), req(1, stop_tokens=(5,)))
    assert not b.continues(b.active_slots())
    # a pass that delivers a request's last block: a 4-token answer is one
    # block, delivered by its second denoising pass
    b = batcher_with(req(0), req(1, m=4))
    slots = b.active_slots()
    assert b.continues(slots)  # phase 0 delivers nothing
    b.slots[slots[1]].block.phase = 1
    assert not b.continues(slots)
    b.slots[slots[1]].request = req(1, m=8)  # one block of two: goes on
    assert b.continues(slots)
    # a queued request with a free slot, and none free
    b = batcher_with(req(0), req(1))
    assert b.submit(req(2))
    assert b.queue and not b.continues(b.active_slots())
    b = batcher_with(req(0), req(1), slots=2)
    assert b.submit(req(2))
    assert b.queue and b.continues(b.active_slots())
    # a preempted sequence waiting to resume
    b = batcher_with(req(0), req(1))
    slots = b.active_slots()
    b.preempt(slots[-1])
    assert not b.continues(slots[:-1])


@pytest.mark.parametrize("block", ["dense", "sdar"])
def test_settle_delivers_a_pass_in_flight_exactly_once(params, block):
    """A round (a pass) dispatched ahead, settled outside a step: its
    tokens (a pass's reveal) are recorded once (a second settle and the
    step that owns it record nothing more), the step still counts what it
    delivered, and every completion is the waiting engine's."""
    fast, slow = _engine(block, params, slots=2), _engine(block, params, slots=2)
    slow.batcher.continues = lambda slots: False
    for eng in (fast, slow):
        for rid in range(2):
            assert eng.submit(Request(rid=rid, prompt=_prompt(rid, 9, _vocab(eng)),
                                      max_new_tokens=8))
    assert fast.step() == slow.step()
    assert fast._ahead is not None and not fast._ahead.delivered
    counter = "serve.tokens_revealed" if block == "sdar" else "serve.decode_tokens"
    recorded = fast.report()["counters"][counter]
    fast.settle()
    assert fast._ahead.delivered and fast.decode_steps == 2
    counters = fast.report()["counters"]
    assert counters[counter] > recorded
    fast.settle()
    assert fast.decode_steps == 2
    assert fast.report()["counters"] == counters
    assert fast.step() == slow.step()
    assert fast.decode_steps == slow.decode_steps == 2
    assert _slots(fast) == _slots(slow)
    fast.run_until_idle()
    slow.run_until_idle()
    for rid in range(2):
        np.testing.assert_array_equal(fast.completed[rid].tokens,
                                      slow.completed[rid].tokens)


def test_the_spans_and_the_counter_count_the_passes_dispatched_ahead():
    """Each pass dispatched ahead opens ``ft.engine.decode_dispatch`` with
    ``ahead`` 1 and the round of the step after the one that dispatched it,
    its ``ft.engine.decode_fetch`` carries that id once, the step that
    owns it counts its slots under ``ahead`` on ``ft.engine.bookkeeping``,
    and ``serve.rounds_ahead`` counts the passes."""
    from flextree_tpu.obs import flight_recorder

    eng = _sdar(slots=3)
    for rid in range(4):
        assert eng.submit(Request(rid=rid, prompt=_prompt(rid, 7 + rid, _vocab(eng)),
                                  max_new_tokens=8))
    with flight_recorder(None) as rec:
        eng.run_until_idle()

    def spans(name):
        return [e for e in rec.events if e["kind"] == "span" and e["name"] == name]

    dispatched = spans("ft.engine.decode_dispatch")
    books = spans("ft.engine.bookkeeping")
    fetched = [e["round"] for e in spans("ft.engine.decode_fetch")]
    ahead = [e for e in dispatched if e["ahead"] == 1]
    assert ahead and len(ahead) == _ahead(eng)
    assert sorted(e["round"] for e in dispatched) == sorted(fetched)
    assert len(set(fetched)) == len(fetched) == eng.decode_steps
    by_round = {e["round"]: e for e in books}
    for e in ahead:
        # dispatched by the step before its own, for the same slots
        assert by_round[e["round"] - 1]["ahead"] == e["active"]
        assert by_round[e["round"]]["decoded"] == e["active"]
    assert sum(e["ahead"] for e in books) == sum(e["active"] for e in ahead)
