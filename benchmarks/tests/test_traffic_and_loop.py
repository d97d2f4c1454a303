"""The traffic generator and the closed loop's determinism."""

import collections
import json
import os

import pytest

from benchmarks.lib import harness, traffic as T

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def chat():
    return T.load("chat-closed-c32")


def test_every_seed_deals_the_same_deck_in_another_order():
    a = T.request_deck(chat(), 2**31 + 7)
    assert T.request_deck(chat(), 2**31 + 7) == a
    b = T.request_deck(chat(), 2**31 + 8)
    assert collections.Counter(a["cards"]) == collections.Counter(b["cards"])
    assert a["cards"] != b["cards"]  # the same multiset in another order
    assert sorted(a["opening"]) == sorted(b["opening"])
    assert len(a["cards"]) == 200
    # 0.3 * 0.4 * 200 = 24 cards of (64, 128); 0.15 * 0.3 * 200 = 9 of (512, 64)
    count = collections.Counter(a["cards"])
    assert count[(64, 128)] == 24 and count[(512, 64)] == 9


def test_the_opening_meets_every_shape_the_cell_uses():
    t = chat()
    deck = T.request_deck(t, 3)
    assert sorted(deck["opening"]) == sorted(
        (p, m) for p in t["prompt_lens"] for m in t["max_new"])
    # 4 prefill shapes and 9 reservation sizes, all longest within max_len
    blocks = {-(-(p + m) // t["block_size"]) for p, m in deck["opening"]}
    assert len(blocks) == 9 and max(blocks) == t["blocks_per_seq"]
    assert t["check_blocks"] in blocks and t["check_prompt"] in t["prompt_lens"]
    assert T.request_size(deck, 0) == deck["opening"][0]
    assert T.request_size(deck, 12) == deck["cards"][0]
    assert T.request_size(deck, 12 + 200) == deck["cards"][0]


def test_stratified_counts_are_exact_or_largest_remainder():
    assert T.stratified_counts([0.3, 0.4, 0.3], 10) == [3, 4, 3]
    assert sum(T.stratified_counts([1, 1, 1], 10)) == 10


def test_prompts_come_from_the_seed_alone():
    a = T.prompt_tokens(2**31 + 7, 5, 64, 50432)
    assert (a == T.prompt_tokens(2**31 + 7, 5, 64, 50432)).all()
    assert (a != T.prompt_tokens(2**31 + 8, 5, 64, 50432)).any()
    assert a.dtype.name == "int32" and 0 <= a.min() and a.max() < 50432


@pytest.fixture(scope="module")
def tiny_cell():
    cell = harness.load_cell("pythia-6.9b.chat-closed-c32")
    harness.apply_rehearsal(cell)
    return cell


def run_loop(cell, seed, rounds):
    from benchmarks.lib import serve_closed as S

    engine, cfg = S.build_engine(cell, seed)
    loop = S.ClosedLoop(engine, cell.traffic, seed, cfg.vocab_size)
    for _ in range(cell.traffic["clients"]):
        loop.issue()
    for _ in range(rounds):
        loop.round()
    schedule = [(r[2], r[3], r[4]) for r in loop.rounds]  # tokens, decoded, live
    done = {c.rid: c.tokens.tolist() for c in engine.completed.values()}
    return schedule, done, loop


def test_closed_loop_schedule_is_a_function_of_the_seed(tiny_cell):
    a_sched, a_done, loop = run_loop(tiny_cell, 11, 60)
    b_sched, b_done, _ = run_loop(tiny_cell, 11, 60)
    assert a_sched == b_sched  # same admissions and token counts, round by round
    assert a_done == b_done  # and the same tokens
    assert len(a_done) > tiny_cell.traffic["clients"]  # slots turned over
    assert all(len(t) == loop.sizes[rid][1] for rid, t in a_done.items())
    assert loop.rejected == 0
    # another seed: other weights and token ids, the same sizes in another
    # order, so another schedule
    c_sched, c_done, c_loop = run_loop(tiny_cell, 12, 60)
    assert c_sched != a_sched and c_done != a_done
    n = min(loop.issued, c_loop.issued)
    first = len(loop.deck["opening"])
    assert collections.Counter(loop.sizes[r] for r in range(first)) == \
        collections.Counter(c_loop.sizes[r] for r in range(first))
    assert n > first


def test_engine_agrees_with_the_reference_at_a_tiny_width(tiny_cell):
    from benchmarks.lib import serve_closed as S

    engine, cfg = S.build_engine(tiny_cell, 4)
    t = tiny_cell.traffic
    check = S.check_against_reference(
        engine, cfg, 4, t["check_prompt"], t["check_steps"], t["check_blocks"])
    assert check["ok"], check
    # and it is false when the two disagree: the reference is given
    # weights with one matrix zeroed
    import jax.numpy as jnp

    spoiled = dict(engine.params, layers=[
        dict(engine.params["layers"][0],
             wo=jnp.zeros_like(engine.params["layers"][0]["wo"])),
        *engine.params["layers"][1:],
    ])
    bad = S.check_against_reference(
        engine, cfg, 4, t["check_prompt"], t["check_steps"], t["check_blocks"],
        reference_params=spoiled)
    assert not bad["ok"], bad
