"""``obs.span``: the program's own spans, in the flight recorder and in the
profiler's trace at once; where the serving round and the train loop open
them; the named scopes inside the train step; and the benchmark's readers
of both (``benchmarks/readers/spans.py``) on hand-made traces whose
answers are worked by hand.
"""

import glob
import threading
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import xplane as X
from benchmarks.lib.harness import ReaderContext, Run
from benchmarks.readers import spans as S
from flextree_tpu.models.transformer import TransformerConfig, init_params
from flextree_tpu.obs import (
    flight_recorder,
    merge_events,
    record_event,
    span,
    validate_trace,
)
from flextree_tpu.obs import recorder as recorder_mod

ROUND_SPANS = [
    "ft.engine.round", "ft.engine.resume", "ft.batcher.try_admit",
    "ft.engine.prefill", "ft.engine.prefill_dispatch",
    "ft.engine.prefill_fetch_sample", "ft.engine.grow",
    "ft.batcher.batch_arrays", "ft.engine.decode_dispatch",
    "ft.engine.decode_fetch", "ft.engine.sample", "ft.engine.retire",
    "ft.engine.bookkeeping",
]
ROUND_ID_SPANS = [  # the spans of a decode round that carry its ``round``
    "ft.engine.round", "ft.engine.decode_dispatch", "ft.engine.decode_fetch",
    "ft.engine.bookkeeping",
]
STEP_SPANS = [
    "ft.loop.step", "ft.loop.data_wait", "ft.loop.dispatch",
    "ft.loop.guard_fetch", "ft.loop.bookkeeping",
]
PHASE_SCOPES = [
    "ft_embed", "ft_norm", "ft_attn", "ft_mlp", "ft_head", "ft_loss",
    "ft_grad_sync", "ft_grad_clip", "ft_optimizer",
]


def _spans(rec):
    return [e for e in rec.events if e["kind"] == "span"]


# ---------------------------------------------------------------- obs.span


def test_span_records_start_end_ids_and_names_its_parent():
    with flight_recorder(None) as rec:
        with span("ft.t.outer", round=7):
            with span("ft.t.inner", rid=3):
                pass
            with span("ft.t.second"):
                pass
    inner, second, outer = _spans(rec)  # recorded as each closes
    assert (inner["name"], inner["parent"], inner["rid"]) == (
        "ft.t.inner", "ft.t.outer", 3)
    assert (second["name"], second["parent"]) == ("ft.t.second", "ft.t.outer")
    assert (outer["name"], outer["parent"], outer["round"]) == (
        "ft.t.outer", None, 7)
    assert outer["start"] <= inner["start"] <= inner["end"] <= outer["end"]
    assert inner["end"] <= second["start"]


def test_span_parent_is_per_thread():
    inside = threading.Event()
    done = threading.Event()

    def other():
        inside.wait(5)
        with span("ft.t.other_thread"):
            pass
        done.set()

    t = threading.Thread(target=other)
    with flight_recorder(None) as rec:
        t.start()
        with span("ft.t.main"):
            inside.set()
            assert done.wait(5)
        t.join(5)
    assert not t.is_alive()
    by_name = {e["name"]: e for e in _spans(rec)}
    # opened while ft.t.main was open, on another thread: not its child
    assert by_name["ft.t.other_thread"]["parent"] is None
    assert by_name["ft.t.main"]["start"] < by_name["ft.t.other_thread"]["start"]


def test_span_without_a_recorder_records_nothing_and_keeps_no_stack():
    assert recorder_mod.current_recorder() is None
    recorder_mod._SPAN_STACKS.__dict__.pop("stack", None)
    with span("ft.t.off", round=1) as s:
        with span("ft.t.off_inner"):
            pass
    # no recorder: no clock read, no per-thread stack, no event
    assert s._rec is None and not hasattr(s, "_t0")
    assert "stack" not in recorder_mod._SPAN_STACKS.__dict__


def test_span_opened_before_the_recorder_closes_quietly():
    s = span("ft.t.early")
    s.__enter__()
    with flight_recorder(None) as rec:
        s.__exit__(None, None, None)
        with span("ft.t.late"):
            pass
    assert [e["name"] for e in _spans(rec)] == ["ft.t.late"]
    assert _spans(rec)[0]["parent"] is None


def test_span_closes_when_its_body_raises():
    with flight_recorder(None) as rec:
        with pytest.raises(ValueError):
            with span("ft.t.raises"):
                raise ValueError("boom")
        with span("ft.t.after"):
            pass
    names = [(e["name"], e["parent"]) for e in _spans(rec)]
    assert names == [("ft.t.raises", None), ("ft.t.after", None)]


def test_timeline_draws_spans_as_durations():
    with flight_recorder(None) as rec:
        record_event("step_start", step=0)
        with span("ft.t.outer", round=2):
            with span("ft.t.inner"):
                pass
    doc = merge_events(list(rec.events))
    assert validate_trace(doc) == []
    drawn = {e["name"]: e for e in doc["traceEvents"] if e.get("cat") == "span"}
    assert set(drawn) == {"ft.t.outer", "ft.t.inner"}
    outer, inner = drawn["ft.t.outer"], drawn["ft.t.inner"]
    assert outer["ph"] == inner["ph"] == "X"
    assert outer["args"] == {"parent": None, "round": 2}
    assert inner["args"] == {"parent": "ft.t.outer"}
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.2
    assert min(e["ts"] for e in doc["traceEvents"] if "ts" in e) >= 0


@pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")
def test_span_in_the_profile_and_in_the_recorder_agree_in_time(tmp_path):
    """The profiler's clock is the wall clock less the profile's start
    (the ``profile_start_time`` stat of the ``Task Environment`` plane),
    so one constant joins an xplane span to its recorder event."""
    import time

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    with flight_recorder(None) as rec:
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            for i in range(4):
                with span("ft.t.timed", i=i):
                    time.sleep(0.003 * (i + 1))
        finally:
            jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    in_profile = sorted(S._spans_of_file(path), key=lambda e: e.stats["i"])
    in_recorder = sorted(_spans(rec), key=lambda e: e["i"])
    assert [e.name for e in in_profile] == ["ft.t.timed"] * 4
    offsets = []
    for prof, ev in zip(in_profile, in_recorder):
        assert prof.stats["i"] == ev["i"]
        assert prof.dur_ns / 1e6 == pytest.approx(
            (ev["end"] - ev["start"]) * 1e3, abs=1.0)
        offsets.append(ev["start"] * 1e3 - prof.start_ns / 1e6)  # ms
    assert max(offsets) - min(offsets) < 1.0
    starts = [
        v for p in jax.profiler.ProfileData.from_file(path).planes
        for k, v in p.stats if k == "profile_start_time"
    ]
    if starts:  # wall-clock nanoseconds
        assert offsets[0] == pytest.approx(starts[0] / 1e6, abs=1.0)


# ------------------------------------------------------- the serving round


def _engine():
    from flextree_tpu.serving import (
        BatcherConfig, PagedCacheConfig, ServingEngine,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    params = init_params(jax.random.PRNGKey(0), cfg)
    pcfg = PagedCacheConfig(num_blocks=32, block_size=8, blocks_per_seq=6)
    return ServingEngine(params, cfg, pcfg, BatcherConfig(slots=2))


def _request(rid, prompt_len, max_new):
    from flextree_tpu.serving import Request

    prompt = np.random.default_rng(rid).integers(0, 64, (prompt_len,))
    return Request(rid=rid, prompt=prompt.astype(np.int32),
                   max_new_tokens=max_new)


def test_one_engine_round_emits_exactly_its_spans():
    eng = _engine()
    assert eng.submit(_request(11, 6, 4))
    with flight_recorder(None) as rec:
        out = eng.step()
    assert out["admitted"] == 1 and out["decoded"] == 1
    spans = _spans(rec)
    # the request goes on, so the round after is dispatched before this
    # one's ids are fetched: a second dispatch, of round 1
    assert sorted(e["name"] for e in spans) == sorted(
        ROUND_SPANS + ["ft.engine.decode_dispatch"])
    first, ahead = [e for e in spans
                    if e["name"] == "ft.engine.decode_dispatch"]
    assert [(e["round"], e["ahead"], e["active"]) for e in (first, ahead)] \
        == [(0, 0, 1), (1, 1, 1)]
    by_name = {e["name"]: e for e in spans}
    by_name["ft.engine.decode_dispatch"] = first
    children = set(ROUND_SPANS) - {
        "ft.engine.round", "ft.engine.prefill_dispatch",
        "ft.engine.prefill_fetch_sample"}
    assert all(by_name[n]["parent"] == "ft.engine.round" for n in children)
    assert by_name["ft.engine.prefill_dispatch"]["parent"] == "ft.engine.prefill"
    assert by_name["ft.engine.prefill_fetch_sample"]["parent"] == "ft.engine.prefill"
    assert by_name["ft.engine.round"]["round"] == 0
    # one id joins the round's dispatch, fetch, books and the round itself
    # (benchmarks/readers/chain.py joins them by it, never by a stamp)
    assert [by_name[n]["round"] for n in ROUND_ID_SPANS] == [0] * 4
    assert by_name["ft.engine.prefill_dispatch"]["rid"] == 11
    prefill = by_name["ft.engine.prefill"]
    assert (prefill["rid"], prefill["prompt_len"], prefill["cached_tokens"]) == (11, 6, 0)
    book = by_name["ft.engine.bookkeeping"]
    total = eng.pcfg.num_blocks - 1
    assert book["blocks_total"] == total
    assert book["blocks_in_use"] == total - eng.batcher.allocator.num_free > 0
    assert (book["round"], book["decoded"], book["admitted"], book["finished"]) == (0, 1, 1, 0)
    assert book["ahead"] == 1
    # the pick's counts, known before the span opens: one greedy slot
    # decoded, its id picked on the device, no logits row fetched
    sample = by_name["ft.engine.sample"]
    assert (sample["on_device"], sample["rows_fetched"], sample["active"]) == (1, 0, 1)
    # how often the paged kernel engages, fixed when the program was
    # built: on the CPU every attention layer walks the table in the loop
    dispatch = by_name["ft.engine.decode_dispatch"]
    assert (dispatch["attn_layers"], dispatch["attn_kernel_layers"]) \
        == (eng.cfg.n_layers, 0)
    # the children tile the round in the order the work happens
    order = [e["name"] for e in sorted(spans, key=lambda e: e["start"])
             if e["parent"] == "ft.engine.round"]
    assert order == [
        "ft.engine.resume", "ft.batcher.try_admit", "ft.engine.prefill",
        "ft.engine.grow", "ft.batcher.batch_arrays",
        "ft.engine.decode_dispatch", "ft.engine.decode_dispatch",
        "ft.engine.decode_fetch", "ft.engine.sample", "ft.engine.retire",
        "ft.engine.bookkeeping",
    ]
    # and the next round counts on, on all four: the step delivers round
    # 1 and dispatches round 2 ahead
    with flight_recorder(None) as rec:
        assert eng.step()["decoded"] == 1
    by_name = {e["name"]: e for e in _spans(rec)}
    assert [by_name[n]["round"] for n in ROUND_ID_SPANS] == [1, 2, 1, 1]
    assert "ft.engine.prefill_dispatch" not in by_name


def test_a_round_samples_under_one_span_and_later_rounds_count_on():
    eng = _engine()
    for rid in (1, 2):
        assert eng.submit(_request(rid, 5, 3))
    with flight_recorder(None) as rec:
        eng.run_until_idle()
    spans = _spans(rec)
    rounds = [e for e in spans if e["name"] == "ft.engine.round"]
    assert [e["round"] for e in rounds] == list(range(len(rounds)))
    samples = [e for e in spans if e["name"] == "ft.engine.sample"]
    decoding = [e for e in spans if e["name"] == "ft.engine.bookkeeping"
                and e["decoded"] > 0]
    assert len(samples) == len(decoding)  # ONE a round, whatever the slots
    fetches = [e for e in spans if e["name"] == "ft.engine.decode_fetch"]
    assert len(fetches) == len(decoding) == eng.decode_steps
    dispatches = [e for e in spans if e["name"] == "ft.engine.decode_dispatch"]
    assert len(dispatches) == eng.decode_steps
    report = eng.report()
    assert all(
        (e["attn_layers"], e["attn_kernel_layers"])
        == (report["attn_layers"], report["attn_kernel_layers"])
        == (eng.cfg.n_layers, 0)
        for e in dispatches
    )
    assert [e["active"] for e in samples] == [e["decoded"] for e in decoding]
    assert all(e["on_device"] == e["active"] and e["rows_fetched"] == 0
               for e in samples)
    assert any(e["decoded"] == 2 for e in decoding)
    assert sum(e["finished"] for e in spans
               if e["name"] == "ft.engine.bookkeeping") == 2
    # the round span took the round-time histogram's place
    assert not any(k.endswith("round_ms") for k in eng.report()["histograms"])


def test_a_sampled_slot_is_counted_as_a_fetched_row():
    from flextree_tpu.serving import Request

    eng = _engine()
    assert eng.submit(_request(1, 5, 4))
    prompt = np.arange(6, dtype=np.int32)
    assert eng.submit(Request(rid=2, prompt=prompt, max_new_tokens=2,
                              temperature=0.7, top_k=3, seed=1))
    with flight_recorder(None) as rec:
        eng.run_until_idle()
    samples = [e for e in _spans(rec) if e["name"] == "ft.engine.sample"]
    # round 0 decodes both; the sampled request is done after it
    assert [(e["on_device"], e["rows_fetched"], e["active"]) for e in samples] \
        == [(1, 1, 2), (1, 0, 1), (1, 0, 1)]
    # the counter adds the row its first token took in the prefill
    rows = eng.report()["counters"]["serve.logits_rows_fetched"]
    assert rows == 1 + sum(e["rows_fetched"] for e in samples) == 2


def test_a_round_dispatched_ahead_opens_before_the_last_ones_fetch_closes():
    """Two greedy requests of 4 tokens: rounds 0 and 1 leave both going on,
    so each step dispatches the next round before it fetches its own ids.
    That dispatch opens inside the step, after the step's own dispatch and
    before its fetch closes; every span carries its own round's id;
    ``active`` and ``ahead`` on the dispatch, and ``ahead`` (the slots whose
    next round was already dispatched) on the books, say so."""
    eng = _engine()
    for rid in (1, 2):
        assert eng.submit(_request(rid, 5, 4))
    with flight_recorder(None) as rec:
        eng.run_until_idle()
    spans = _spans(rec)

    def by_round(name):
        return {e["round"]: e for e in spans if e["name"] == name}

    rounds, dispatch, fetch, books = (by_round(n) for n in ROUND_ID_SPANS)
    assert sorted(rounds) == sorted(dispatch) == sorted(fetch) \
        == sorted(books) == [0, 1, 2]
    for n in (0, 1):
        # round n+1 is dispatched in step n, after round n was, before
        # round n's fetch closes; its own fetch is the next step's
        ahead = dispatch[n + 1]
        assert dispatch[n]["end"] <= ahead["start"]
        assert rounds[n]["start"] <= ahead["start"] < fetch[n]["end"] \
            <= rounds[n]["end"] <= fetch[n + 1]["start"]
    assert [(dispatch[n]["ahead"], dispatch[n]["active"]) for n in range(3)] \
        == [(0, 2), (1, 2), (1, 2)]
    assert [(books[n]["ahead"], books[n]["decoded"]) for n in range(3)] \
        == [(2, 2), (2, 2), (0, 2)]
    assert eng.report()["counters"]["serve.rounds_ahead"] == 2


def test_prefill_prediction_is_built_only_for_a_recorder(monkeypatch):
    """Tracing off costs no tracing work: the cost model's prediction is
    an argument of the ``serve_prefill`` event and of nothing else."""
    from flextree_tpu.serving import costs

    calls = []
    real = costs.predict_prefill_us
    monkeypatch.setattr(
        costs, "predict_prefill_us",
        lambda *a, **k: calls.append(1) or real(*a, **k))
    eng = _engine()
    assert eng.submit(_request(1, 6, 2))
    eng.step()
    assert calls == []
    assert eng.submit(_request(2, 6, 2))
    with flight_recorder(None) as rec:
        eng.step()
    assert calls == [1]
    [ev] = [e for e in rec.events if e["kind"] == "serve_prefill"]
    assert ev["rid"] == 2 and ev["predicted_us"] > 0 and ev["measured_us"] > 0


def test_a_decode_round_prices_nothing_and_reports_no_residual(monkeypatch):
    """Between ``ft.engine.sample`` and ``ft.engine.retire`` the device
    waits: the round's prediction, its histogram and its event went (PR
    36); neither the report nor a recorder sees them, recorder or not."""
    from flextree_tpu.serving import costs

    calls = []
    monkeypatch.setattr(
        costs, "predict_decode_round_us", lambda *a, **k: calls.append(1))
    eng = _engine()
    assert eng.submit(_request(1, 6, 3))
    eng.step()
    with flight_recorder(None) as rec:
        eng.run_until_idle()
    assert calls == []
    kinds = {e["kind"] for e in rec.events}
    assert "serve_decode" in kinds
    assert not [k for k in kinds if k.endswith("_measured")]
    histograms = eng.report()["histograms"]
    assert "serve.cache_occupancy" in histograms
    assert not [h for h in histograms if "residual" in h]


def _block_config(block):
    if block == "dense":
        return None
    from tests.test_kimi_linear import tiny as kimi_tiny
    from tests.test_laguna import tiny as laguna_tiny
    from tests.test_olmo_hybrid import tiny as olmo_tiny
    from tests.test_pangu_ultra_moe import tiny as pangu_tiny

    return {"laguna": laguna_tiny, "pangu": pangu_tiny,
            "kimi": kimi_tiny, "olmo": olmo_tiny}[block]()


#: what ``ft.engine.decode_dispatch`` and ``engine.report()`` say of each
#: block's decode program on the CPU (no Pallas kernel runs there):
#: (attention layers that read a paged pool, layers that hold a state a
#: slot, parts a position, parts a slot)
BLOCK_COUNTS = {
    "dense": (2, 0, {"k", "v"}, set()),
    "laguna": (None, 0, {"k", "v"}, set()),
    "pangu": (None, 0, {"ckv"}, set()),
    "kimi": (2, 6, {"ckv"}, {"s", "conv"}),
    "olmo": (2, 6, {"k", "v"}, {"s", "conv"}),
}


@pytest.mark.parametrize("block", list(BLOCK_COUNTS))
def test_every_blocks_dispatch_span_counts_its_layers_and_what_it_keeps(block):
    """One round of each block under a recorder: the dispatch span carries
    the program's own counts (attention layers and state layers, none of
    either on a Pallas kernel on the CPU; the bytes a slot and a position
    hold), the report says the same, and the engine holds the parts the
    block's layout names: a state beside plain K and V rows in the
    Olmo-Hybrid block, beside one latent row in the Kimi-Linear one."""
    from flextree_tpu.serving import (
        BatcherConfig, PagedCacheConfig, ServingEngine, costs,
    )

    config = _block_config(block)
    if config is None:
        eng = _engine()
    else:
        eng = ServingEngine.from_config(
            config, PagedCacheConfig(num_blocks=40, block_size=4,
                                     blocks_per_seq=8),
            BatcherConfig(slots=2), seed=3)
    attn, held, paged, slot = BLOCK_COUNTS[block]
    assert set(eng.pools) == paged and set(eng.state) == slot
    assert eng.submit(_request(1, 5, 3))
    with flight_recorder(None) as rec:
        eng.step()
    # the round and the next, dispatched ahead: both say the same
    dispatch, ahead = [e for e in _spans(rec)
                       if e["name"] == "ft.engine.decode_dispatch"]
    assert (dispatch["ahead"], ahead["ahead"]) == (0, 1)
    report = eng.report()
    for key in ("attn_layers", "attn_kernel_layers", "state_layers",
                "state_kernel_layers", "state_bytes_per_slot",
                "cache_bytes_per_position"):
        assert dispatch[key] == ahead[key] == report[key], key
    assert dispatch["attn_layers"] == (attn or eng.cfg.n_layers)
    assert dispatch["state_layers"] == held
    assert dispatch["attn_kernel_layers"] == dispatch["state_kernel_layers"] == 0
    assert dispatch["state_bytes_per_slot"] == costs.state_bytes_per_slot(eng.cfg)
    assert (dispatch["state_bytes_per_slot"] > 0) == bool(slot)
    assert dispatch["cache_bytes_per_position"] == \
        costs.cache_bytes_per_position(eng.cfg) > 0


@pytest.mark.parametrize("block", ["dense", "laguna", "pangu", "kimi", "olmo"])
def test_every_blocks_decode_program_is_named_paged_decode(block):
    """A profile names a program after the function ``jax.jit`` was
    handed (``jit_<name>``): a ``functools.partial`` has none and read
    ``jit__unknown``.  Every block's decode program holds ``paged_decode``,
    where the metric files and ``readers/chain.py`` find it, and the pick
    and prefill programs keep the names they are found by."""
    import re

    from flextree_tpu.serving import (
        BatcherConfig, PagedCacheConfig, ServingEngine,
    )

    config = _block_config(block)
    if config is None:
        eng = _engine()
    else:
        eng = ServingEngine.from_config(
            config, PagedCacheConfig(num_blocks=40, block_size=4,
                                     blocks_per_seq=8),
            BatcherConfig(slots=2), seed=3)
    tables, lengths, tokens, _ = eng.batcher.batch_arrays()
    carried = (eng.state,) if eng.state else ()
    text = eng._decode.lower(
        eng.params, eng.pools, tables, lengths, tokens, *carried).as_text()
    [name] = re.findall(r"module @(\w+)", text)
    assert name.startswith("jit_") and "paged_decode" in name
    assert "unknown" not in name
    assert bool(carried) == (block in ("kimi", "olmo"))
    logits = jax.ShapeDtypeStruct((2, 128), jnp.float32)
    assert "module @jit_greedy_ids" in eng._greedy_ids.lower(logits).as_text()
    assert eng._prefill.__name__ == "prefill_program"


# ---------------------------------------------------------- the train loop


def _toy_fit(num_steps, **kw):
    from flextree_tpu.parallel.loop import FitConfig, fit

    class Data:
        def batch_at(self, step):
            t = np.full((2, 4), float(step + 1))
            return t, t

    def step_fn(state, tokens, targets):
        s = int(np.asarray(state["step"]))
        return ({"step": np.int64(s + 1), "w": np.asarray(state["w"]) - 1.0},
                {"loss": 0.5})

    return fit(
        {"step": np.int64(0), "w": np.zeros(2)}, step_fn, Data(),
        FitConfig(num_steps=num_steps, log_every=0, prefetch=0), **kw)


def test_three_fit_steps_emit_the_loop_spans():
    with flight_recorder(None) as rec:
        result = _toy_fit(3)
    assert result.steps_run == 3
    spans = _spans(rec)
    assert sorted(e["name"] for e in spans) == sorted(STEP_SPANS * 3)
    steps = [e for e in spans if e["name"] == "ft.loop.step"]
    assert [e["step"] for e in steps] == [0, 1, 2]
    assert all(e["parent"] is None for e in steps)
    assert all(e["parent"] == "ft.loop.step" for e in spans
               if e["name"] != "ft.loop.step")
    first = [e["name"] for e in sorted(spans, key=lambda e: e["start"])][:5]
    assert first == STEP_SPANS
    # the events the benchmark reads are still there, one a step
    kinds = [e["kind"] for e in rec.events]
    assert kinds.count("step_start") == kinds.count("step_end") == 3
    assert kinds[0] == "fit_start" and kinds[-1] == "fit_end"


def test_supervised_fit_ticks_under_bookkeeping_and_dispatches_once_a_step():
    from flextree_tpu.parallel.loop import Supervision

    with flight_recorder(None) as rec:
        _toy_fit(2, supervision=Supervision(
            membership=lambda: {0: "healthy"}, configured_world=1))
    names = [e["name"] for e in _spans(rec)]
    assert names.count("ft.loop.dispatch") == 2
    # the supervisor's ticks before the step and the bookkeeping after it
    assert names.count("ft.loop.bookkeeping") == 4
    assert names.count("ft.loop.step") == 2


# ------------------------------------------------ scopes inside the step


@pytest.fixture(scope="module")
def lowered_step_paths():
    """The ``op_name`` path of every operation of a lowered (2,2,2) train
    step with clipping on."""
    import re

    from flextree_tpu.parallel.train import (
        TrainConfig, init_train_state, make_mesh_nd, make_train_step,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
    tc = TrainConfig(grad_clip_norm=1.0)
    state = init_train_state(jax.random.PRNGKey(0), cfg, tc, mesh=mesh)
    tok = jnp.zeros((4, 16), jnp.int32)
    text = make_train_step(mesh, cfg, tc).lower(state, tok, tok).as_text(
        debug_info=True)
    return sorted(set(re.findall(r'loc\("([^"]*ft_[^"]*)"', text)))


@pytest.mark.parametrize("scope", PHASE_SCOPES)
def test_lowered_train_step_holds_the_scope(lowered_step_paths, scope):
    import re

    token = re.compile(rf"\b{scope}\b")
    assert any(token.search(p) for p in lowered_step_paths), scope


@pytest.mark.parametrize(
    "scope", ["ft_embed", "ft_norm", "ft_attn", "ft_mlp", "ft_head", "ft_loss"])
def test_scope_survives_into_the_backward_pass(lowered_step_paths, scope):
    assert any(f"transpose(jvp({scope}))" in p for p in lowered_step_paths)


def test_phase_scopes_never_nest(lowered_step_paths):
    """One phase a path: the shares by scope then add up, and the scopes
    the collectives bring (``ft_bucket*``, ``ft_rs_stage*``) sit INSIDE a
    phase."""
    import re

    phases = re.compile(r"\b(" + "|".join(PHASE_SCOPES) + r")\b")
    for path in lowered_step_paths:
        assert len(set(phases.findall(path))) == 1, path
    assert any("ft_grad_sync" in p and "ft_bucket" in p
               for p in lowered_step_paths)


# ------------------------------------------------------------ the readers

E = X.Event


def _ctx(host_events, ops, window=(0.0, 1000.0), trace_dir=None,
         modules=()):
    planes = [X.Plane("/host:CPU", [X.Line("python3", host_events)])]
    if ops is not None:
        planes.append(X.Plane("/device:TPU:0", [
            X.Line("XLA Ops", ops), X.Line("XLA Modules", list(modules))]))
    run = Run(True, 0, 0, {}, {}, 0.0, trace_dir)
    cell = types.SimpleNamespace(name="toy")
    return ReaderContext(cell, run, {}, X.Trace(planes), window)


def _round_trace():
    """Two rounds of 400 ns inside a 1000 ns window.  Chip 0 is busy
    [50,150] [230,300] [450,560] [640,700] [900,1000]: idle 650."""
    host = [
        E("bench_window", 0, 1000),
        E("ft.engine.round", 0, 400, {"round": 0}),
        E("ft.engine.decode_fetch", 100, 100),  # 100..200
        E("ft.engine.sample", 200, 100),  # 200..300
        E("ft.engine.bookkeeping", 300, 100, {"blocks_in_use": 12, "blocks_total": 32}),
        E("ft.engine.round", 420, 400, {"round": 1}),
        E("ft.engine.prefill", 430, 20),
        E("ft.engine.decode_fetch", 500, 100),  # 500..600
        E("ft.engine.sample", 600, 150),  # 600..750
        E("ft.engine.bookkeeping", 750, 70, {"blocks_in_use": 16, "blocks_total": 32}),
    ]
    ops = [
        E("%fusion.1 = f32[4]{0} fusion(", 50, 100,
          {"tf_op": "jit(step)/ft_mlp/dot_general"}),
        E("%fusion.2 = f32[4]{0} fusion(", 230, 70,
          {"tf_op": "jit(step)/transpose(jvp(ft_mlp))/dot_general"}),
        E("%fusion.3 = f32[4]{0} fusion(", 450, 110,
          {"tf_op": "jit(step)/ft_grad_sync/ft_bucket0_dp_128B/psum"}),
        E("%fusion.4 = f32[4]{0} fusion(", 640, 60, {"tf_op": "jit(step)/add"}),
        E("%while.5 = f32[4]{0} while(", 900, 100,
          {"tf_op": "jit(step)/ft_optimizer/while"}),
        E("%fusion.6 = f32[4]{0} fusion(", 920, 30,
          {"tf_op": "jit(step)/ft_optimizer/mul"}),
    ]
    return host, ops


def test_innermost_segments_tile_the_spans():
    host, _ = _round_trace()
    spans = sorted((e for e in host if e.name.startswith("ft.")),
                   key=lambda e: (e.start_ns, -e.dur_ns))
    segs = S.innermost_segments(spans)
    assert segs[:5] == [
        (0, 100, "ft.engine.round"), (100, 200, "ft.engine.decode_fetch"),
        (200, 300, "ft.engine.sample"), (300, 400, "ft.engine.bookkeeping"),
        (420, 430, "ft.engine.round"),
    ]
    assert all(a < b for a, b, _ in segs)
    assert all(p[1] <= q[0] for p, q in zip(segs, segs[1:]))
    assert sum(b - a for a, b, _ in segs) == 400 + 400


def test_idle_gap_crossing_two_spans_is_split_by_overlap():
    host, ops = _round_trace()
    ctx = _ctx(host, ops)
    # the gap [150,230] crosses decode_fetch (150..200) and sample (200..230)
    per = "ft.engine.round"
    fetch = S.idle_ms_per(ctx, ["ft.engine.decode_fetch"], per)
    sample = S.idle_ms_per(ctx, ["ft.engine.sample"], per)
    # fetch: [150,200] + [560,600]; sample: [200,230] + [600,640] + [700,750]
    assert fetch * 2 * 1e6 == pytest.approx(50 + 40)
    assert sample * 2 * 1e6 == pytest.approx(30 + 40 + 50)


def test_idle_by_span_sums_to_the_idle_time():
    host, ops = _round_trace()
    ctx = _ctx(host, ops)
    per = "ft.engine.round"
    names = sorted({e.name for e in host if e.name.startswith("ft.")})
    parts = [S.idle_ms_per(ctx, [n], per) for n in names]
    outside = S.idle_ms_per(ctx, [], per)
    # outside every ft. span: [400,420] between the rounds, [820,900] after
    assert outside * 2 * 1e6 == pytest.approx(20 + 80)
    busy_ns = X.busy_seconds(ctx.trace, ctx.window) * 1e9
    assert busy_ns == pytest.approx(100 + 70 + 110 + 60 + 100)
    assert (sum(parts) + outside) * 2 * 1e6 == pytest.approx(1000 - busy_ns)


def test_span_median_and_the_rounds_that_admitted():
    host, ops = _round_trace()
    ctx = _ctx(host, ops)
    assert S.span_ms_p50(ctx, "ft.engine.sample") * 1e6 == pytest.approx(125)
    # only round 1 holds a prefill
    assert S.span_ms_p50(
        ctx, "ft.engine.sample", within="ft.engine.round",
        having="ft.engine.prefill") * 1e6 == pytest.approx(150)
    assert S.span_ms_p50(ctx, "ft.engine.nowhere") is None
    assert S.count_ratio_p50(
        ctx, "ft.engine.bookkeeping", "blocks_in_use", "blocks_total"
    ) == pytest.approx(100 * (12 / 32 + 16 / 32) / 2)
    assert S.count_ratio_p50(ctx, "ft.engine.round", "blocks_in_use",
                             "blocks_total") is None


def test_scope_shares_forward_transposed_nested_and_unscoped():
    host, ops = _round_trace()
    ctx = _ctx(host, ops)
    busy = 100 + 70 + 110 + 60 + 100
    assert S.scope_share(ctx, ["ft_mlp"]) == pytest.approx(100 * 170 / busy)
    # the bucket's scope sits inside ft_grad_sync
    assert S.scope_share(ctx, ["ft_grad_sync"]) == pytest.approx(100 * 110 / busy)
    # a loop and its body: own times, nothing counted twice
    assert S.scope_share(ctx, ["ft_optimizer", "ft_grad_clip"]) == pytest.approx(
        100 * 100 / busy)
    assert S.scope_share(ctx, []) == pytest.approx(100 * 60 / busy)
    assert S.scope_share(ctx, ["ft_mlp_other"]) == 0.0


def _pb(num, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    if isinstance(value, int):
        return varint(num << 3) + varint(value)
    if isinstance(value, str):
        value = value.encode()
    return varint(num << 3 | 2) + varint(len(value)) + value


def _xspace(ops_by_plane):
    """An ``.xplane.pb`` holding only what ``op_paths`` reads: per plane,
    the stat names and each operation's metadata record with its
    ``tf_op`` (as a string, or for the last one as a reference to a stat
    record of that name)."""
    space = b""
    for plane, ops in ops_by_plane.items():
        body = _pb(1, 1) + _pb(2, plane) + _pb(3, _pb(2, "XLA Ops"))
        body += _pb(5, _pb(1, 7) + _pb(2, _pb(1, 7) + _pb(2, "tf_op")))
        body += _pb(5, _pb(1, 9) + _pb(2, _pb(1, 9) + _pb(2, "flops")))
        for i, (text, path) in enumerate(ops.items(), start=1):
            stats = _pb(5, _pb(1, 9) + _pb(4, 1234))
            if path is not None and i == len(ops):
                body += _pb(5, _pb(1, 100 + i) + _pb(
                    2, _pb(1, 100 + i) + _pb(2, path)))
                stats += _pb(5, _pb(1, 7) + _pb(7, 100 + i))
            elif path is not None:
                stats += _pb(5, _pb(1, 7) + _pb(5, path))
            body += _pb(4, _pb(1, i) + _pb(
                2, _pb(1, i) + _pb(2, text) + _pb(4, "shown") + stats))
        space += _pb(1, body)
    return space


def test_op_paths_reads_the_metadata_table_and_joins_by_hlo_text(tmp_path):
    """On the chip the scope path is no stat of the event but the
    ``tf_op`` of its metadata record, which ``ProfileData`` does not hand
    out: read from the file, joined by the HLO text."""
    _, ops = _round_trace()
    table = {o.name: o.stats["tf_op"] for o in ops}
    table[ops[3].name] = None  # an operation the compiler put in: no path
    where = tmp_path / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(_xspace({
        "/host:CPU": {"%not_a_device_op": "jit(f)/ft_mlp/mul"},
        "/device:TPU:0": table,
    }))
    got = S.op_paths(str(where / "host.xplane.pb"))
    assert got == {k: v for k, v in table.items() if v is not None}
    host, _ = _round_trace()
    bare = [E(o.name, o.start_ns, o.dur_ns) for o in ops]  # as xplane.load leaves them
    ctx = _ctx(host, bare, trace_dir=str(tmp_path))
    busy = 100 + 70 + 110 + 60 + 100
    assert S.scope_share(ctx, ["ft_mlp"]) == pytest.approx(100 * 170 / busy)
    assert S.scope_share(ctx, ["ft_optimizer"]) == pytest.approx(100 * 100 / busy)
    assert S.scope_share(ctx, []) == pytest.approx(100 * 60 / busy)


def _metric_file(name):
    import os

    from benchmarks.lib import harness

    return harness._read_json(
        os.path.join(harness.ROOT, "metrics", f"{name}.json"))


def test_device_pick_share_is_the_median_share_of_slots_picked_on_device():
    """``engine.device_pick_share`` as its metric file reads it, on rounds
    whose ``ft.engine.sample`` spans carry the pick's counts."""
    meta = _metric_file("engine.device_pick_share")
    assert meta["reader"] == "spans:count_ratio_p50"

    def rounds(counts):
        host = [E("bench_window", 0, 1000)] + [
            E("ft.engine.sample", 100 * i, 50,
              {"on_device": on, "rows_fetched": act - on, "active": act})
            for i, (on, act) in enumerate(counts)
        ]
        return S.count_ratio_p50(_ctx(host, None), **meta["args"])

    assert rounds([(32, 32)] * 3) == pytest.approx(100.0)
    assert rounds([(32, 32), (24, 32), (31, 32)]) == pytest.approx(100 * 31 / 32)
    assert rounds([(0, 2), (1, 2)]) == pytest.approx(25.0)  # 0 is a count too
    # a parent commit's spans carry no counts: nothing to read, no error
    bare = [E("bench_window", 0, 1000), E("ft.engine.sample", 100, 50)]
    assert S.count_ratio_p50(_ctx(bare, None), **meta["args"]) is None


def test_paged_kernel_share_is_the_share_of_layers_that_run_the_kernel():
    """``kernels.paged_kernel_share`` as its metric file reads it: the two
    counts every ``ft.engine.decode_dispatch`` span carries."""
    meta = _metric_file("kernels.paged_kernel_share")
    assert meta["reader"] == "spans:count_ratio_p50"

    def rounds(took, layers, n=3):
        host = [E("bench_window", 0, 1000)] + [
            E("ft.engine.decode_dispatch", 100 * i, 50,
              {"attn_layers": layers, "attn_kernel_layers": took})
            for i in range(n)
        ]
        return S.count_ratio_p50(_ctx(host, None), **meta["args"])

    assert rounds(8, 8) == pytest.approx(100.0)  # the dense cell on a TPU
    assert rounds(0, 8) == pytest.approx(0.0)  # the CPU: the loop, counted
    assert rounds(3, 5) == pytest.approx(60.0)  # a layer kind kept in the loop
    # a parent commit's span carries no counts: left out, no error
    bare = [E("bench_window", 0, 1000), E("ft.engine.decode_dispatch", 100, 50)]
    assert S.count_ratio_p50(_ctx(bare, None), **meta["args"]) is None


def test_lagged_fetch_share_is_the_share_of_verdicts_read_one_step_late():
    """``loop.lagged_fetch_share`` as its metric file reads it: the two
    counts every ``ft.loop.guard_fetch`` span carries."""
    meta = _metric_file("loop.lagged_fetch_share")
    assert meta["reader"] == "spans:count_ratio_p50"

    def steps(lagged):
        host = [E("bench_window", 0, 1000)] + [
            E("ft.loop.guard_fetch", 100 * i, 50, {"lagged": lag, "steps": 1})
            for i, lag in enumerate(lagged)
        ]
        return S.count_ratio_p50(_ctx(host, None), **meta["args"])

    # a window of lagged fetches and the drain before fit returns
    assert steps([1] * 9 + [0]) == pytest.approx(100.0)
    assert steps([0] * 4) == pytest.approx(0.0)  # a step the host guards
    # a parent commit's span carries no counts: left out, no error
    bare = [E("bench_window", 0, 1000), E("ft.loop.guard_fetch", 100, 50)]
    assert S.count_ratio_p50(_ctx(bare, None), **meta["args"]) is None


@pytest.mark.parametrize("verdict,lagged", [(True, [1, 1, 0]), (False, [0, 0, 0])])
def test_guard_fetch_says_whether_it_lagged(verdict, lagged):
    """The counts ``fit`` opens ``ft.loop.guard_fetch`` with: a step that
    reports ``applied`` is read one step late (and last, at the drain, on
    time); a step that does not is fetched before the next dispatch."""
    from flextree_tpu.parallel.loop import FitConfig, fit

    class Data:
        def batch_at(self, step):
            t = np.full((2, 4), float(step + 1))
            return t, t

    def step_fn(state, tokens, targets):
        s = int(np.asarray(state["step"]))
        metrics = {"loss": 0.5, **({"applied": True} if verdict else {})}
        return {"step": np.int64(s + 1), "w": np.asarray(state["w"]) - 1.0}, metrics

    with flight_recorder(None) as rec:
        fit({"step": np.int64(0), "w": np.zeros(2)}, step_fn, Data(),
            FitConfig(num_steps=3, log_every=0, prefetch=0))
    fetches = [e for e in _spans(rec) if e["name"] == "ft.loop.guard_fetch"]
    assert [(e["lagged"], e["steps"]) for e in fetches] == [(n, 1) for n in lagged]


@pytest.mark.filterwarnings("ignore:builtin type:DeprecationWarning")
def test_device_pick_share_reads_the_engines_own_spans(tmp_path):
    """The same reader over a real profile of a mixed batch: the counts
    the engine opens ``ft.engine.sample`` with are the stats it finds."""
    from flextree_tpu.serving import Request

    eng = _engine()
    assert eng.submit(_request(1, 5, 4))
    assert eng.submit(Request(rid=2, prompt=np.arange(6, dtype=np.int32),
                              max_new_tokens=2, temperature=0.7, seed=1))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        eng.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    # no trace in the neutral form: the reader takes the spans from the file
    run = Run(True, 0, 0, {}, {}, 0.0, str(tmp_path))
    ctx = ReaderContext(types.SimpleNamespace(name="toy"), run, {}, None,
                        (0.0, float("inf")))
    meta = _metric_file("engine.device_pick_share")
    # rounds of (1 of 2), (1 of 1), (1 of 1) slots picked on the device
    assert S.count_ratio_p50(ctx, **meta["args"]) == pytest.approx(100.0)
    shares = sorted(
        float(s.stats["on_device"]) / float(s.stats["active"])
        for s in S._named(ctx, "ft.engine.sample"))
    assert shares == [0.5, 1.0, 1.0]


# ------------------------------------------- the chain between two rounds
#
# Six decode rounds, every stamp a whole number of nanoseconds (so that a
# shifted plane's differences are exact).  Round k's decode program starts
# LAUNCH[k] after its dispatch opens; its pick program ends 15.05 ms later
# and its fetch closes RETURN[k] after that; HOST_GAP[k] later round k+1's
# dispatch opens, OUTSIDE[k] of it outside any round.  Rounds 2 and 5 admit
# a request first, so the pairs are (0,1), (2,3), (3,4).

MS = 1_000_000
LAUNCH = [400_000, 500_000, 450_000, 600_000, 420_000, 480_000]
RETURN = [700_000, 650_000, 800_000, 750_000, 900_000, 720_000]
HOST_GAP = [900_000, 5_000_000, 1_100_000, 1_000_000, 6_000_000]
OUTSIDE = [200_000, 300_000, 250_000, 150_000, 300_000]
ADMITS = (2, 5)  # the rounds that open with a prefill
PROGRAM_NS = 15_050_000  # decode program's first operation -> pick's last


def _chain_ctx(host_late=0, host_prefills=True, device_prefills=True,
               drop_host=(), drop_device=(), ids=True, decode="paged_decode"):
    """The six rounds as a profile would hold them, the host plane's stamps
    ``host_late`` ns later than true time.  ``drop_host`` / ``drop_device``:
    rounds missing from one plane; ``ids=False``: a parent commit's spans."""
    rid = (lambda k: {"round": k + 100}) if ids else (lambda k: {})
    host, ops, modules = [], [], []
    dispatch = 1 * MS  # round 0's dispatch opens, true time
    ends = []
    for k in range(6):
        start = dispatch + LAUNCH[k]  # the decode program's first operation
        pick_end = start + PROGRAM_NS
        fetch_close = pick_end + RETURN[k]
        ends.append(fetch_close)
        pre = 0 if k == 0 else (HOST_GAP[k - 1] - OUTSIDE[k - 1]) // 2
        post = 50_000 if k == 5 else HOST_GAP[k] - OUTSIDE[k] - (
            HOST_GAP[k] - OUTSIDE[k]) // 2
        if k not in drop_host:
            host += [
                E("ft.engine.round", dispatch - pre,
                  fetch_close + post - (dispatch - pre), rid(k)),
                E("ft.batcher.batch_arrays", dispatch - 60_000, 50_000),
                E("ft.engine.decode_dispatch", dispatch, 620_000, rid(k)),
                E("ft.engine.decode_fetch", dispatch + 650_000,
                  fetch_close - dispatch - 650_000, rid(k)),
                E("ft.engine.sample", fetch_close + 10_000, 100_000),
                E("ft.engine.bookkeeping", fetch_close + post - 40_000,
                  30_000, rid(k)),
            ]
            if k in ADMITS and host_prefills:
                host.append(E("ft.engine.prefill", dispatch - pre + 20_000,
                              pre - 100_000, {"rid": k}))
        if k not in drop_device:
            if k in ADMITS and device_prefills:
                at = dispatch - pre + 200_000
                modules += [E("jit_prefill_program(7)", at, 900_000),
                            E("jit_greedy_ids(9)", at + 1 * MS, 20_000)]
                ops += [E("%fusion.7 = f32[4]{0} fusion(", at + 900, 890_000),
                        E("%fusion.9 = s32[1]{0} fusion(", at + 1 * MS + 900,
                          10_000)]
            modules += [
                E(f"jit_{decode}_program(3)", start - 900, 14_999_800),
                E("jit_greedy_ids(5)", start + 15_000_000 - 900, 52_000),
            ]
            ops += [  # back to back but for 2 us before the pick's
                E("%fusion.1 = f32[4]{0} fusion(", start, 10 * MS),
                E("%while.2 = f32[4]{0} while(", start + 10 * MS, 4_998_000),
                E("%fusion.3 = f32[4]{0} fusion(", start + 10_200_000,
                  100_000),
                E("%fusion.5 = s32[2]{0} fusion(", start + 15_000_000,
                  50_000),
            ]
        if k < 5:
            dispatch = fetch_close + HOST_GAP[k]
    window = (0 + host_late, ends[-1] + 100_000 + host_late)
    host = [E("bench_window", 0, ends[-1] + 100_000)] + host
    host = [E(e.name, e.start_ns + host_late, e.dur_ns, e.stats) for e in host]
    return _ctx(host, ops, window, modules=modules)


def _chain_metric(ctx, name):
    """A chain metric as its metric file reads it, in ns (whole numbers in
    the hand-made trace, so equalities below are exact)."""
    from benchmarks.readers import chain

    meta = _metric_file(name)
    module, fn = meta["reader"].split(":")
    assert module == "chain"
    value = getattr(chain, fn)(ctx, **meta["args"])
    return None if value is None else round(value * 1e6, 3)


GAP_METRICS = ["engine.gap_device_ms_p50", "engine.gap_host_ms_p50",
               "engine.gap_crossing_ms_p50", "engine.gap_outside_ms_p50"]
CLOCK_METRICS = ["trace.launch_min_ms", "trace.return_min_ms"]
# worked by hand over the pairs (0,1), (2,3), (3,4): the host's gaps
# 0.9, 1.1, 1.0; the crossings RETURN[n] + LAUNCH[n+1] = 1.2, 1.4, 1.17;
# the device's their sums 2.1, 2.5, 2.17: medians taken pair by pair, so
# the crossing's is NOT the device's less the host's
WRITTEN = {
    "engine.gap_device_ms_p50": 2_170_000,
    "engine.gap_host_ms_p50": 1_000_000,
    "engine.gap_crossing_ms_p50": 1_200_000,
    "engine.gap_outside_ms_p50": 200_000,
    "trace.launch_min_ms": 400_000,  # round 0
    "trace.return_min_ms": 650_000,  # round 1
}


@pytest.mark.parametrize("name", GAP_METRICS + CLOCK_METRICS)
def test_chain_metric_reads_the_written_value(name):
    assert _chain_metric(_chain_ctx(), name) == WRITTEN[name]


@pytest.mark.parametrize("seen_by", ["host", "device"])
def test_a_pair_with_a_prefill_is_skipped_whichever_plane_shows_it(seen_by):
    """The prefill span on the host, or any program between the pick and
    the next decode program on the device: either takes the pair out."""
    from benchmarks.readers import chain

    ctx = _chain_ctx(host_prefills=seen_by == "host",
                     device_prefills=seen_by == "device")
    ids = [(a[0].round_id, b[0].round_id) for a, b in chain.pairs(ctx)]
    assert ids == [(100, 101), (102, 103), (103, 104)]
    assert [_chain_metric(ctx, n) for n in GAP_METRICS] \
        == [WRITTEN[n] for n in GAP_METRICS]
    # with no prefill on either plane all five gaps count: the medians move
    bare = _chain_ctx(host_prefills=False, device_prefills=False)
    assert len(chain.pairs(bare)) == 5
    assert _chain_metric(bare, "engine.gap_host_ms_p50") == 1_100_000


@pytest.mark.parametrize("shift_ms", [-1.5, -0.5, 0.5, 1.5])
def test_a_shift_of_the_host_plane_moves_only_what_crosses_the_clocks(shift_ms):
    """The test the reader exists for.  The profiler joins the host's and
    the device's clocks once a session, to about a millisecond: with the
    host plane recorded ``shift`` later, every ``engine.gap_*`` reads the
    same to the nanosecond (each is a difference on ONE clock, or a
    difference of two such differences), the two ``trace.*`` readings move
    by exactly the shift, in opposite directions, their sum unmoved, and
    the old reading (chip 0's idle time laid over ``ft.engine.decode_fetch``,
    ``engine.idle_fetch_ms``) moves with the clocks, on the same code."""
    shift = int(shift_ms * MS)
    base, moved = _chain_ctx(), _chain_ctx(host_late=shift)
    for name in GAP_METRICS:
        assert _chain_metric(moved, name) == _chain_metric(base, name) \
            == WRITTEN[name]
    launch = _chain_metric(moved, "trace.launch_min_ms")
    back = _chain_metric(moved, "trace.return_min_ms")
    assert launch == WRITTEN["trace.launch_min_ms"] - shift
    assert back == WRITTEN["trace.return_min_ms"] + shift
    # a floor under the crossing, whatever the offset
    assert launch + back == 1_050_000 <= WRITTEN["engine.gap_crossing_ms_p50"]
    # one of them negative: the clocks are shown apart, by at least that
    assert (launch < 0) == (shift > 400_000)
    assert (back < 0) == (shift < -650_000)
    old = _metric_file("engine.idle_fetch_ms")
    assert old["reader"] == "spans:idle_ms_per"
    before = S.idle_ms_per(base, **old["args"])
    after = S.idle_ms_per(moved, **old["args"])
    # on true clocks a round's fetch covers the 2 us before its pick
    # program and RETURN[k] of the gap after it; on shifted ones, what the
    # shift lays under it
    assert before * 6 * 1e6 == pytest.approx(sum(RETURN) + 6 * 2_000)
    assert abs(after - before) > 0.3 * abs(shift_ms)


@pytest.mark.parametrize("planes", [
    {"drop_device": (4, 5)}, {"drop_host": (0, 1)}, {"drop_device": (0,)},
    {"drop_device": range(6)}, {"drop_host": range(6)}, {"ids": False},
    {"decode": "unknown"},
])
def test_rounds_that_do_not_join_give_nothing(planes):
    """Counts that differ by more than the one round an edge of the profile
    may cut (and a cut the causal order rules out: the device's FIRST round
    missing while the host holds its dispatch), a plane with no round at
    all, a parent commit's spans without the id, a decode program no name
    finds: every chain metric is None, none guesses."""
    ctx = _chain_ctx(**planes)
    if planes == {"drop_device": (0,)}:
        # joined, by the rule, one round off: the clock readings show it
        # (a fetch that closed a round before its pick program ended),
        # which is what they are for
        assert _chain_metric(ctx, "trace.return_min_ms") < -10 * MS
        return
    assert [_chain_metric(ctx, n) for n in GAP_METRICS + CLOCK_METRICS] \
        == [None] * 6


@pytest.mark.parametrize("planes,pairs", [
    ({"drop_host": (0,)}, [(102, 103), (103, 104)]),
    ({"drop_device": (5,)}, [(100, 101), (102, 103), (103, 104)]),
])
def test_a_round_cut_by_an_edge_of_the_profile_is_dropped(planes, pairs):
    """A dispatch comes before its program: the device's first round may
    lack its dispatch (it opened before the profile did), the host's last
    its program.  One such round is dropped there and the rest join."""
    from benchmarks.readers import chain

    ctx = _chain_ctx(**planes)
    assert [(a[0].round_id, b[0].round_id)
            for a, b in chain.pairs(ctx)] == pairs
    for (ha, da), (hb, db) in chain.pairs(ctx):
        n = ha.round_id - 100
        assert db.decode_start - da.pick_end \
            == HOST_GAP[n] + RETURN[n] + LAUNCH[n + 1]


def test_readers_return_none_where_there_is_nothing_to_read():
    host, ops = _round_trace()
    # a rehearsal: host spans, no device plane
    cpu = _ctx(host, None)
    assert S.idle_ms_per(cpu, [], "ft.engine.round") is None
    assert S.scope_share(cpu, ["ft_mlp"]) is None
    assert S.span_ms_p50(cpu, "ft.engine.sample") is not None
    # a parent commit: a device plane, no program span and no scope
    bare = [E(o.name, o.start_ns, o.dur_ns) for o in ops]
    parent = _ctx([E("bench_window", 0, 1000)], bare)
    assert S.idle_ms_per(parent, ["ft.engine.sample"], "ft.engine.round") is None
    assert S.span_ms_p50(parent, "ft.engine.sample") is None
    assert S.scope_share(parent, []) is None
    assert S.scope_share(parent, ["ft_mlp"]) is None


def test_new_metric_files_name_readers_that_exist():
    """Every per-layer entry of BENCHMARK.json that reads spans has its
    metric file, and the file's arguments fit the reader's signature."""
    import inspect

    from benchmarks.lib import harness

    bench = harness.load_benchmark()
    from benchmarks.readers import chain

    modules = {"spans": S, "chain": chain}
    seen = {"spans": 0, "chain": 0}
    for entry in bench["per_layer"]:
        meta = _metric_file(entry["name"])
        module, name = meta["reader"].split(":")
        if module not in modules:
            continue
        seen[module] += 1
        fn = getattr(modules[module], name)
        inspect.signature(fn).bind(None, **meta.get("args", {}))
        assert entry["source"] in ("program_span", "program_counter", "device_trace")
    # PR 36: four more medians of a span's duration, and the chain's six;
    # PR 37: the expert layers that run the grouped kernel
    # PR 38: two more scope shares (`attn.gdn_proj_share`, `attn.gdn_core_share`)
    # the diffusion cell's two more (`attn.block_share`, `engine.unmask_share`)
    assert seen == {"spans": 39, "chain": 6}


def test_the_chains_entries_are_the_serving_cells_and_move_the_rate():
    from benchmarks.lib import harness

    bench = harness.load_benchmark()
    serving = [w["name"] for w in bench["workloads"]
               if "closed" in w["traffic"]]
    assert len(serving) == 6  # the diffusion block's cell joins every list
    first = [e["name"] for e in bench["per_layer"]].index(GAP_METRICS[0])
    added = bench["per_layer"][first:first + 10]
    assert [e["name"] for e in added] == GAP_METRICS + [
        "engine.dispatch_ms_p50", "engine.retire_ms_p50",
        "engine.bookkeeping_ms_p50", "batcher.batch_arrays_ms_p50",
    ] + CLOCK_METRICS
    for e in added:
        assert (e["workloads"], e["moves"], e["unit"], e["better"]) \
            == (serving, "serve_tokens_per_s", "ms", "lower")
