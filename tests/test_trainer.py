"""Data pipeline + training loop: determinism, prefetch, exact resume.

The decisive property composes the whole stack: interrupting a run at any
checkpoint and resuming must produce exactly the parameters of a
straight-through run — data addressing, step accounting, checkpointing,
and the train step all have to agree.
"""

import jax
import numpy as np
import pytest

from conftest import own_copy

pytestmark = pytest.mark.slow  # multi-minute train-step tests (fast subset: -m 'not slow')

from flextree_tpu.data import LMDataset, prefetch, synthetic_tokens
from flextree_tpu.models.transformer import TransformerConfig
from flextree_tpu.parallel.loop import FitConfig, fit
from flextree_tpu.parallel.train import (
    TrainConfig,
    init_train_state,
    make_mesh_3d,
    make_train_step,
    state_specs,
)


# ------------------------------------------------------------------- data


def test_synthetic_tokens_deterministic_and_in_range():
    a = synthetic_tokens(1000, 64, seed=3)
    b = synthetic_tokens(1000, 64, seed=3)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < 64
    assert len(np.unique(a)) > 10  # a walk, not a constant


def test_dataset_batch_addressing_deterministic():
    ds = LMDataset(synthetic_tokens(10_000, 64), batch=4, seq_len=32, seed=1)
    t1, y1 = ds.batch_at(7)
    t2, y2 = ds.batch_at(7)
    np.testing.assert_array_equal(t1, t2)
    assert t1.shape == (4, 32) and y1.shape == (4, 32)
    # targets are the next token of the same window
    np.testing.assert_array_equal(t1[:, 1:], y1[:, :-1])


def test_dataset_epoch_covers_all_windows_once():
    # token value == position, so a window's first token IS its start
    ds = LMDataset(np.arange(0, 1000, dtype=np.int32), batch=2, seq_len=10, seed=0)
    starts = set()
    for step in range(ds.batches_per_epoch):
        toks, _ = ds.batch_at(step)
        for row in toks:
            assert int(row[0]) % ds.seq_len == 0  # aligned window start
            starts.add(int(row[0]))
    # every visited window distinct within the epoch
    assert len(starts) == ds.batches_per_epoch * 2


def test_dataset_epochs_reshuffle():
    ds = LMDataset(synthetic_tokens(10_000, 64), batch=4, seq_len=32, seed=1)
    e0 = ds.batch_at(0)[0]
    e1 = ds.batch_at(ds.batches_per_epoch)[0]
    assert not np.array_equal(e0, e1)


def test_dataset_validates_sizes():
    with pytest.raises(ValueError, match="windows"):
        LMDataset(np.zeros(50, np.int32), batch=8, seq_len=32)
    with pytest.raises(ValueError, match="1-D"):
        LMDataset(np.zeros((4, 4), np.int32), batch=1, seq_len=2)


def test_prefetch_preserves_order_and_raises():
    got = list(prefetch(iter(range(10)), size=3))
    assert got == list(range(10))

    def bad():
        yield 1
        raise RuntimeError("boom")

    it = prefetch(bad(), size=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="boom"):
        next(it)


# ------------------------------------------------------------------ fit


def _setup(tmp_path=None):
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    mesh = make_mesh_3d(8, (2, 2, 2))
    step = make_train_step(mesh, cfg, TrainConfig(lr=3e-3))
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    ds = LMDataset(synthetic_tokens(20_000, 64), batch=8, seq_len=32, seed=0)
    return cfg, mesh, step, state, ds


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def test_fit_runs_and_loss_decreases(tmp_path):
    cfg, mesh, step, state, ds = _setup()
    res = fit(state, step, ds, FitConfig(num_steps=12, log_every=4))
    assert res.steps_run == 12
    assert res.losses[-1][1] < res.losses[0][1]


def test_fit_resume_is_exact(tmp_path):
    cfg, mesh, step, state, ds = _setup()

    straight = fit(
        own_copy(state), step, ds, FitConfig(num_steps=8, log_every=4)
    )

    ck = str(tmp_path / "ck")
    half = fit(
        own_copy(state), step, ds,
        FitConfig(num_steps=4, ckpt_dir=ck, ckpt_every=4, log_every=4),
    )
    assert half.steps_run == 4
    resumed = fit(
        state, step, ds,  # state arg is ignored: restored from ck
        FitConfig(num_steps=8, ckpt_dir=ck, ckpt_every=4, log_every=4),
        mesh=mesh,
        state_specs=state_specs(cfg),
    )
    assert resumed.resumed_from == 4
    assert resumed.steps_run == 4
    for a, b in zip(_leaves(straight.state), _leaves(resumed.state)):
        np.testing.assert_array_equal(a, b)


def test_fit_completed_run_resumes_to_noop(tmp_path):
    cfg, mesh, step, state, ds = _setup()
    ck = str(tmp_path / "ck")
    fit(state, step, ds, FitConfig(num_steps=4, ckpt_dir=ck, ckpt_every=4))
    again = fit(
        state, step, ds,
        FitConfig(num_steps=4, ckpt_dir=ck, ckpt_every=4),
        mesh=mesh, state_specs=state_specs(cfg),
    )
    assert again.steps_run == 0 and again.resumed_from == 4


# ------------------- multi-stage tree in the production train step


@pytest.mark.parametrize("tree_topo", ["4,2", "2,2,2"])
def test_multistage_grad_sync_matches_psum(tree_topo):
    """The gradient allreduce over an 8-wide dp axis with a real multi-stage
    tree must produce the same training step as native psum sync — the
    FlexTree production path (``mpi_mod.hpp:953-1111`` as the host
    framework's gradient sync), not a side-door demo."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    mesh = make_mesh_3d(8, (8, 1, 1))  # single 8-wide dp axis
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    ds = LMDataset(synthetic_tokens(20_000, 64), batch=8, seq_len=32, seed=0)
    tokens, targets = ds.batch_at(0)

    step_psum = make_train_step(mesh, cfg, TrainConfig(lr=3e-3, grad_topo="psum"))
    step_tree = make_train_step(mesh, cfg, TrainConfig(lr=3e-3, grad_topo=tree_topo))

    s_psum, m_psum = step_psum(own_copy(state), tokens, targets)
    s_tree, m_tree = step_tree(state, tokens, targets)
    assert np.isclose(float(m_psum["loss"]), float(m_tree["loss"]), rtol=1e-6)
    for a, b in zip(_leaves(s_psum["params"]), _leaves(s_tree["params"])):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)


# ------------------------------------------------------------------- CLI


def test_trainer_cli_dense(capsys):
    from flextree_tpu.trainer import main

    rc = main([
        "--steps", "4", "--log-every", "2", "--batch", "8",
        "--seq-len", "32", "--d-model", "32", "--d-ff", "64",
        "--corpus-tokens", "20000",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "dense: 4 steps" in out


def test_trainer_cli_zigzag_sp(capsys):
    from flextree_tpu.trainer import main

    rc = main([
        "--steps", "2", "--log-every", "1", "--batch", "8",
        "--seq-len", "32", "--d-model", "32", "--d-ff", "64",
        "--sp-impl", "zigzag", "--mesh", "2,2,2",
        "--corpus-tokens", "20000",
    ])
    assert rc == 0
    assert "dense: 2 steps" in capsys.readouterr().out


def test_trainer_cli_moe(capsys):
    from flextree_tpu.trainer import main

    rc = main([
        "--model", "moe", "--mesh", "1,2,2,2", "--steps", "2",
        "--log-every", "1", "--batch", "8", "--seq-len", "32",
        "--d-model", "32", "--d-ff", "64", "--corpus-tokens", "20000",
    ])
    assert rc == 0
    assert "moe: 2 steps" in capsys.readouterr().out
