"""The training step owns its state (ISSUE 33): the NaN guard is made
INSIDE the built step (``metrics["applied"]``; a refused update leaves
every state leaf as it was), the state is donated, and ``fit`` reads a
step's verdict while the next step runs.

A poisoned batch here is one out-of-range target: the loss of that token
is NaN (``take_along_axis`` fills) on a state that is finite, as after a
transient anomaly.
"""

import contextlib
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import own_copy

from flextree_tpu.models.moe import MoEConfig
from flextree_tpu.models.transformer import TransformerConfig
from flextree_tpu.obs import flight_recorder
from flextree_tpu.parallel import loop as loop_mod
from flextree_tpu.parallel import train as train_mod
from flextree_tpu.parallel.loop import FitConfig, Supervision, TrainingDiverged, fit
from flextree_tpu.parallel.moe_train import (
    init_moe_train_state, make_mesh_moe, make_moe_train_step,
)
from flextree_tpu.parallel.pipeline import (
    init_pipeline_train_state, make_mesh_4d, make_pipeline_train_step,
)
from flextree_tpu.parallel.train import (
    TrainConfig, init_train_state, make_mesh_3d, make_train_step,
)
from flextree_tpu.runtime import BackgroundSaver
from flextree_tpu.utils.checkpoint import latest_checkpoint, restore_train_state

VOCAB = 64
DENSE = TransformerConfig(
    vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=1, d_ff=64)
MOE = MoEConfig(
    vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2, d_ff=64,
    n_experts=4, top_k=1, moe_every=2)


def _batch(poisoned=False, batch=4, seq=32, seed=0):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, VOCAB, (batch, seq)).astype(np.int32)
    tgt = rng.integers(0, VOCAB, (batch, seq)).astype(np.int32)
    if poisoned:
        tgt[0, 0] = VOCAB + 1000
    return jnp.asarray(tok), jnp.asarray(tgt)


def _dense(**tc):
    tc = TrainConfig(lr=1e-2, **tc)
    mesh = make_mesh_3d(8, (2, 2, 2))
    return (
        lambda: make_train_step(mesh, DENSE, tc),
        lambda: init_train_state(jax.random.PRNGKey(0), DENSE, tc, mesh=mesh),
    )


def _moe(**tc):
    tc = TrainConfig(lr=1e-2, **tc)
    mesh = make_mesh_moe(8, (1, 2, 2, 2))
    return (
        lambda: make_moe_train_step(mesh, MOE, tc),
        lambda: init_moe_train_state(jax.random.PRNGKey(0), MOE, tc, mesh=mesh),
    )


def _pipeline(**tc):
    tc = TrainConfig(lr=1e-2, **tc)
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    mesh = make_mesh_4d(8, (1, 2, 2, 2))
    return (
        lambda: make_pipeline_train_step(mesh, cfg, tc, n_microbatches=2),
        lambda: init_pipeline_train_state(
            jax.random.PRNGKey(0), cfg, tc, mesh=mesh),
    )


# every builder, and every kind of leaf the update writes: the moments, the
# error-feedback residual of a lossy codec, the ZeRO shards, and the master
# copy and re-gathered parameters of a lossy ZeRO step
FAMILIES = {
    "dense": lambda: _dense(),
    "dense-clipped": lambda: _dense(grad_clip_norm=0.5),
    "dense-int8": lambda: _dense(codec="int8"),
    "dense-zero": lambda: _dense(shard_optimizer=True),
    "dense-zero-int8-clipped": lambda: _dense(
        shard_optimizer=True, codec="int8", grad_clip_norm=0.5),
    "moe": lambda: _moe(),
    "pipeline": lambda: _pipeline(),
}


@contextlib.contextmanager
def unguarded():
    """Build and trace a step WITHOUT the guard: the update takes no
    verdict and keeps nothing back (the parent's arithmetic)."""
    elem = train_mod.adamw_elem

    def plain(p, g, mu, nu, t, lr, train_cfg, ok=None):
        return elem(p, g, mu, nu, t, lr, train_cfg, None)

    with mock.patch.object(train_mod, "adamw_elem", plain), \
            mock.patch.object(
                train_mod, "keep_if_refused", lambda ok, new, old: new):
        yield


def _bytes(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(jax.device_get(tree))]


def _assert_same_bits(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for path, x, y in zip(
        [p for p, _ in jax.tree_util.tree_leaves_with_path(a)],
        _bytes(a), _bytes(b),
    ):
        assert x == y, jax.tree_util.keystr(path)


# ------------------------------------------------- (a) the guard in the step


@pytest.mark.parametrize("family", list(FAMILIES))
def test_a_poisoned_step_returns_its_state_and_a_clean_one_the_plain_update(
    family,
):
    build, init = FAMILIES[family]()
    step = build()
    tok, tgt = _batch()
    # one clean step first: moments, residuals and shards are nonzero
    state, m = step(init(), tok, tgt)
    assert bool(m["applied"]) and np.isfinite(float(m["loss"]))
    before = jax.device_get(state)

    refused, m_bad = step(own_copy(state), *_batch(poisoned=True))
    assert not bool(m_bad["applied"])
    assert not np.isfinite(float(m_bad["loss"]))
    assert int(refused["step"]) == int(before["step"]) + 1
    refused, kept = dict(refused), dict(before)
    refused.pop("step"), kept.pop("step")
    _assert_same_bits(refused, kept)

    # a clean step: the bits of the update with no guard in it
    guarded, m_ok = step(own_copy(state), tok, tgt)
    with unguarded():
        plain, m_plain = build()(state, tok, tgt)
    assert bool(m_ok["applied"])
    assert np.asarray(m_ok["loss"]).tobytes() == np.asarray(m_plain["loss"]).tobytes()
    _assert_same_bits(guarded, plain)
    # and it did move
    assert _bytes(guarded["params"]) != _bytes(before["params"])


def test_a_built_step_donates_its_state_and_places_an_unplaced_one():
    build, _ = _dense()
    step = build()
    tok, tgt = _batch()
    # init_train_state without a mesh: everything on one device
    state = init_train_state(jax.random.PRNGKey(0), DENSE)
    # no "donated buffers were not usable" (pytest.ini: warnings are errors):
    # the call places what it is given, then every leaf has a buffer to take
    out, _ = step(state, tok, tgt)
    shardings = [x.sharding for x in jax.tree.leaves(out)]
    out2, _ = step(out, tok, tgt)
    assert all(x.is_deleted() for x in jax.tree.leaves(out))
    assert [x.sharding for x in jax.tree.leaves(out2)] == shardings


# ------------------------------------ (b) fit over a built step, with poison


class _Data:
    """Step-addressed batches; the steps in ``poison`` are poisoned once
    (the set is consumed, as a transient anomaly would be)."""

    def __init__(self, poison=()):
        self.poison = set(poison)

    def batch_at(self, step):
        bad = step in self.poison
        self.poison.discard(step)
        return _batch(poisoned=bad, seed=step)


@pytest.fixture(scope="module")
def dense_steps():
    """(built step, the parent's step): the second is built with no guard
    in it and gives no verdict, so ``fit`` guards it on the host as the
    parent's loop did; it is handed copies, for it donates too."""
    build, init = _dense()
    step = build()
    with unguarded():
        bare = build()
        bare(init(), *_batch())  # trace it while the guard is out

    def parents(state, tok, tgt):
        new, metrics = bare(own_copy(state), tok, tgt)
        metrics = dict(metrics)
        metrics.pop("applied")
        return new, metrics

    return step, parents, init


def _fit(step, init, tmp_path, name, **kw):
    with flight_recorder(None) as rec:
        res = fit(init(), step, _Data(kw.pop("poison")),
                  FitConfig(ckpt_dir=str(tmp_path / name), prefetch=0, **kw))
    return res, [dict(e) for e in rec.events]


def test_fit_skips_a_poisoned_step_as_the_parents_loop_did(dense_steps, tmp_path):
    step, parents, init = dense_steps
    kw = dict(num_steps=8, log_every=1, ckpt_every=100, poison={3})
    new, ev_new = _fit(step, init, tmp_path, "new", **kw)
    old, ev_old = _fit(parents, init, tmp_path, "old", **kw)
    assert new.report.skipped_steps == old.report.skipped_steps == [3]
    assert new.report.anomalies == old.report.anomalies == 1
    assert new.steps_run == old.steps_run == 8
    assert new.losses == old.losses  # every logged loss, float for float
    assert [s for s, _ in new.losses] == [1, 2, 3, 5, 6, 7, 8]
    _assert_same_bits(new.state, old.state)
    skips = lambda ev: [(e["step"], e["streak"]) for e in ev if e["kind"] == "nan_skip"]
    assert skips(ev_new) == skips(ev_old) == [(3, 1)]


def test_a_burst_rewinds_to_the_same_checkpoint_as_the_parents_loop(
    dense_steps, tmp_path,
):
    step, parents, init = dense_steps
    kw = dict(num_steps=12, log_every=4, ckpt_every=4, max_bad_steps=3,
              max_rewinds=2, poison={4, 5, 6})
    new, ev_new = _fit(step, init, tmp_path, "new", **kw)
    old, ev_old = _fit(parents, init, tmp_path, "old", **kw)
    assert new.report.rewinds == old.report.rewinds == 1
    assert new.report.skipped_steps == old.report.skipped_steps == [4, 5, 6]
    rewound = lambda ev: [e["step"] for e in ev if e["kind"] == "nan_rewind"]
    assert rewound(ev_new) == rewound(ev_old) == [4]
    assert new.losses == old.losses
    _assert_same_bits(new.state, old.state)
    # the replay was clean: the undisturbed run's parameters
    clean, _ = _fit(step, init, tmp_path, "clean", num_steps=12, log_every=4,
                    ckpt_every=100, poison=())
    _assert_same_bits(new.state, clean.state)


def test_a_poisoned_last_step_is_read_before_the_run_ends(dense_steps, tmp_path):
    step, _, init = dense_steps
    res, events = _fit(step, init, tmp_path, "last", num_steps=4, log_every=1,
                       ckpt_every=100, poison={3})
    assert res.report.skipped_steps == [3] and res.steps_run == 4
    assert [s for s, _ in res.losses] == [1, 2, 3]
    kinds = [e["kind"] for e in events]
    assert kinds.index("nan_skip") < kinds.index("fit_end")
    # the refused step's state is not checkpointed as a step's result: the
    # final save holds it (step 4), and it is the state of step 3
    assert int(res.state["step"]) == 4


def test_divergence_with_nothing_to_rewind_to_raises_one_step_late(dense_steps):
    step, _, init = dense_steps
    with pytest.raises(TrainingDiverged, match="no checkpoint"):
        fit(init(), step, _Data({0, 1, 2}),
            FitConfig(num_steps=8, log_every=0, max_bad_steps=3, prefetch=0))


# ------------------- (c) the order of dispatch and fetch, with a recording step


class _Recorded:
    """A scalar that says when the host reads it."""

    def __init__(self, log, what, value):
        self.log, self.what, self.value = log, what, value

    def __array__(self, dtype=None, copy=None):
        self.log.append(self.what)
        return np.asarray(self.value, dtype=dtype)

    def __float__(self):
        return float(np.asarray(self))


def _recording_step(log, refuse=(), verdict=True):
    """The toy linear model of tests/test_chaos.py that guards itself:
    every read of its metrics is recorded."""

    def step_fn(state, tokens, targets):
        s = int(np.asarray(state["step"]))
        log.append(("dispatch", s))
        ok = s not in refuse
        w = np.asarray(state["w"]) - (0.01 * float(tokens.mean()) if ok else 0.0)
        metrics = {"loss": _Recorded(log, ("loss", s), 0.5 if ok else np.nan)}
        if verdict:
            metrics["applied"] = _Recorded(log, ("fetch", s), ok)
        return {"step": np.int64(s + 1), "w": w}, metrics

    return step_fn


class _ToyData:
    def batch_at(self, step):
        t = np.full((2, 4), float(step + 1))
        return t, t


def _w0():
    return {"step": np.int64(0), "w": np.zeros(4)}


def _guard_fetches(rec):
    return [(e["lagged"], e["steps"]) for e in rec.events
            if e["kind"] == "span" and e["name"] == "ft.loop.guard_fetch"]


def test_fit_dispatches_the_next_step_before_it_reads_a_verdict():
    log = []
    with flight_recorder(None) as rec:
        res = fit(_w0(), _recording_step(log, refuse={2}), _ToyData(),
                  FitConfig(num_steps=5, log_every=0, prefetch=0))
    assert log == [
        ("dispatch", 0),
        ("dispatch", 1), ("fetch", 0),
        ("dispatch", 2), ("fetch", 1),
        ("dispatch", 3), ("fetch", 2),
        ("dispatch", 4), ("fetch", 3),
        ("fetch", 4),  # the drain before the run ends
    ]
    assert _guard_fetches(rec) == [(1, 1)] * 4 + [(0, 1)]
    assert res.report.skipped_steps == [2] and res.steps_run == 5
    np.testing.assert_allclose(
        res.state["w"], -0.01 * sum(s + 1 for s in (0, 1, 3, 4)) * np.ones(4))
    # the events the benchmark reads: one step_start a step, fit_end last
    kinds = [e["kind"] for e in rec.events]
    assert kinds.count("step_start") == 5 and kinds[-1] == "fit_end"


def test_fit_drains_before_a_checkpoint_and_saves_no_refused_step(
    tmp_path, monkeypatch,
):
    log = []
    save = loop_mod.save_train_state

    def recording_save(ckpt_dir, state, **kw):
        log.append(("save", int(np.asarray(state["step"]))))
        return save(ckpt_dir, state, **kw)

    monkeypatch.setattr(loop_mod, "save_train_state", recording_save)
    fit(_w0(), _recording_step(log, refuse={3}), _ToyData(),
        FitConfig(num_steps=6, log_every=0, prefetch=0,
                  ckpt_dir=str(tmp_path), ckpt_every=2))
    assert log == [
        ("dispatch", 0),
        ("dispatch", 1), ("fetch", 0), ("fetch", 1), ("save", 2),
        ("dispatch", 2),
        # step 3 ends on a checkpoint boundary and was refused: no save
        ("dispatch", 3), ("fetch", 2), ("fetch", 3),
        ("dispatch", 4),
        ("dispatch", 5), ("fetch", 4), ("fetch", 5), ("save", 6),
        ("save", 6),  # the run's last, synchronous save
    ]


def test_logged_losses_are_read_one_step_late_too():
    log = []
    res = fit(_w0(), _recording_step(log), _ToyData(),
              FitConfig(num_steps=3, log_every=1, prefetch=0))
    assert [s for s, _ in res.losses] == [1, 2, 3]
    assert log.index(("loss", 0)) > log.index(("dispatch", 1))
    assert log.index(("loss", 1)) > log.index(("dispatch", 2))


def test_nan_guard_off_reads_no_verdict_of_a_step_that_guards_itself():
    log = []
    res = fit(_w0(), _recording_step(log, refuse={1}), _ToyData(),
              FitConfig(num_steps=3, log_every=0, prefetch=0, nan_guard=False))
    assert [x for x in log if x[0] != "dispatch"] == []
    # the step still refused its bad update; fit counted nothing
    assert res.report.anomalies == 0 and res.report.skipped_steps == []
    np.testing.assert_allclose(res.state["w"], -0.01 * (1 + 3) * np.ones(4))


def test_a_materialised_step_gives_its_verdict_at_once():
    """The watchdog materialises a step on purpose: nothing stays in
    flight, so the verdict read is the step's own."""
    log = []
    with flight_recorder(None) as rec:
        fit(_w0(), _recording_step(log), _ToyData(),
            FitConfig(num_steps=3, log_every=0, prefetch=0),
            supervision=Supervision(step_timeout_s=30.0))
    assert log == [("dispatch", 0), ("fetch", 0), ("dispatch", 1),
                   ("fetch", 1), ("dispatch", 2), ("fetch", 2)]
    assert _guard_fetches(rec) == [(0, 1)] * 3


def test_a_supervised_step_that_is_not_materialised_still_lags(tmp_path):
    """The trainer's default supervision (a preemption guard alone)
    materialises nothing: the verdict is read one step late, and the
    preemption save drains first."""

    class Preempt:
        def __init__(self, at):
            self.at, self.polls = at, 0

        @property
        def preempted(self):
            self.polls += 1
            return self.polls > self.at

    log = []
    res = fit(_w0(), _recording_step(log), _ToyData(),
              FitConfig(num_steps=8, log_every=0, prefetch=0,
                        ckpt_dir=str(tmp_path), ckpt_every=100),
              supervision=Supervision(preemption=Preempt(3)))
    assert log == [("dispatch", 0), ("dispatch", 1), ("fetch", 0),
                   ("dispatch", 2), ("fetch", 1), ("fetch", 2)]
    assert res.report.preempted_at == 3
    assert int(restore_train_state(str(tmp_path))["step"]) == 3


def test_the_background_saver_never_holds_a_donated_buffer(tmp_path):
    build, init = _dense()
    ck = str(tmp_path / "ck")
    saver = BackgroundSaver(ck, max_to_keep=8)
    try:
        res = fit(init(), build(), _Data(),
                  FitConfig(num_steps=6, log_every=0, prefetch=0,
                            ckpt_dir=ck, ckpt_every=1, max_to_keep=8),
                  supervision=Supervision(background_saver=saver))
    finally:
        saver.close()
    assert saver.errors == []
    assert res.report.background_saves >= 1
    assert latest_checkpoint(ck)
    restored = restore_train_state(ck)
    assert int(restored["step"]) == 6
    _assert_same_bits(restored["params"], res.state["params"])


def test_own_buffers_copies_only_what_the_state_holds():
    state = {"a": jnp.ones(3), "step": jnp.zeros((), jnp.int32)}
    fresh = jnp.full(3, 2.0)
    packed = {"a": state["a"], "b": fresh, "n": np.ones(2)}
    owned = loop_mod._own_buffers(packed, state)
    assert owned["a"] is not state["a"] and owned["b"] is fresh
    assert owned["n"] is packed["n"]
    state["a"].delete()
    assert loop_mod._consumed(state)
    np.testing.assert_array_equal(np.asarray(owned["a"]), np.ones(3))


# ---------------------------- (d) a step with no verdict: the host's guard


def test_a_step_that_gives_no_verdict_is_guarded_by_the_host_as_before():
    log = []
    with flight_recorder(None) as rec:
        res = fit(_w0(), _recording_step(log, verdict=False), _ToyData(),
                  FitConfig(num_steps=3, log_every=0, prefetch=0))
    # its loss is fetched BEFORE the next dispatch
    assert log == [("dispatch", 0), ("loss", 0), ("dispatch", 1), ("loss", 1),
                   ("dispatch", 2), ("loss", 2)]
    assert _guard_fetches(rec) == [(0, 1)] * 3
    assert res.steps_run == 3
