"""Pipeline parallelism vs the single-device oracle.

The strongest check is end-to-end: one GPipe train step over a 4-axis mesh
must produce the same loss and the same updated parameters as the plain
dp/sp/tp step (and the single-device step) on identical data — the same
A/B-oracle discipline as everywhere else in the suite (SURVEY §4).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import own_copy

pytestmark = pytest.mark.slow  # multi-minute train-step tests (fast subset: -m 'not slow')

from flextree_tpu.models.transformer import TransformerConfig, init_params
from flextree_tpu.parallel.pipeline import (
    factor_devices_4d,
    init_pipeline_train_state,
    make_mesh_4d,
    make_pipeline_train_step,
    stack_layer_params,
    unstack_layer_params,
)
from flextree_tpu.parallel.train import (
    TrainConfig,
    init_train_state,
    make_mesh_3d,
    make_train_step,
)


def _cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64)
    base.update(kw)
    return TransformerConfig(**base)


def _batch(cfg, b=8, t=32, seed=1):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    return tokens, targets


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def _single_device_reference(cfg, state_key, tokens, targets, train_cfg=TrainConfig()):
    state = init_train_state(jax.random.PRNGKey(state_key), cfg)
    step = make_train_step(make_mesh_3d(1, (1, 1, 1)), cfg, train_cfg)
    return step(state, tokens, targets)


def test_stack_unstack_roundtrip():
    cfg = _cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    back = unstack_layer_params(stack_layer_params(params))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize(
    "shape,microbatches",
    [
        ((1, 2, 2, 2), 2),  # pp=2 with sp and tp alongside
        ((2, 4, 1, 1), 4),  # deep pipeline, dp alongside
        ((1, 8, 1, 1), 2),  # pure pipeline, one layer per stage... n_layers=8
        ((2, 2, 2, 1), 2),
    ],
)
def test_pipeline_step_matches_single_device(shape, microbatches):
    n_layers = 8 if shape[1] == 8 else 4
    cfg = _cfg(n_layers=n_layers)
    tokens, targets = _batch(cfg)
    s1, m1 = _single_device_reference(cfg, 0, tokens, targets)

    mesh = make_mesh_4d(8, shape)
    state = init_pipeline_train_state(jax.random.PRNGKey(0), cfg)
    step = make_pipeline_train_step(mesh, cfg, n_microbatches=microbatches)
    sp_, mp = step(state, tokens, targets)

    np.testing.assert_allclose(float(mp["loss"]), float(m1["loss"]), rtol=1e-5)
    got = _leaves(unstack_layer_params(jax.device_get(sp_["params"])))
    want = _leaves(s1["params"])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_pipeline_pp1_is_grad_accumulation():
    """pp=1 degenerates to plain microbatched training — must still match."""
    cfg = _cfg()
    tokens, targets = _batch(cfg)
    s1, m1 = _single_device_reference(cfg, 0, tokens, targets)
    mesh = make_mesh_4d(8, (8, 1, 1, 1))
    state = init_pipeline_train_state(jax.random.PRNGKey(0), cfg)
    step = make_pipeline_train_step(mesh, cfg, n_microbatches=1)
    sp_, mp = step(state, tokens, targets)
    np.testing.assert_allclose(float(mp["loss"]), float(m1["loss"]), rtol=1e-5)
    for a, b in zip(
        _leaves(unstack_layer_params(jax.device_get(sp_["params"]))),
        _leaves(s1["params"]),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_pipeline_with_tree_grad_topo():
    cfg = _cfg()
    tokens, targets = _batch(cfg)
    mesh = make_mesh_4d(8, (4, 2, 1, 1))
    state = init_pipeline_train_state(jax.random.PRNGKey(0), cfg)
    flat_s, flat_m = make_pipeline_train_step(mesh, cfg, n_microbatches=2)(
        own_copy(state), tokens, targets
    )
    tree_s, tree_m = make_pipeline_train_step(
        mesh, cfg, TrainConfig(grad_topo="2,2"), n_microbatches=2
    )(state, tokens, targets)
    np.testing.assert_allclose(
        float(tree_m["loss"]), float(flat_m["loss"]), rtol=1e-6
    )
    for a, b in zip(_leaves(tree_s["params"]), _leaves(flat_s["params"])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_pipeline_loss_decreases():
    cfg = _cfg()
    tokens, targets = _batch(cfg)
    mesh = make_mesh_4d(8, (1, 2, 2, 2))
    state = init_pipeline_train_state(jax.random.PRNGKey(0), cfg)
    step = make_pipeline_train_step(
        mesh, cfg, TrainConfig(lr=3e-3), n_microbatches=2
    )
    losses = []
    for _ in range(5):
        state, metrics = step(state, tokens, targets)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses


def test_pipeline_rejects_indivisible_layers():
    cfg = _cfg(n_layers=3)
    mesh = make_mesh_4d(8, (4, 2, 1, 1))
    with pytest.raises(ValueError, match="divisible"):
        make_pipeline_train_step(mesh, cfg)


def test_pipeline_rejects_indivisible_microbatch():
    cfg = _cfg()
    tokens, targets = _batch(cfg, b=6)
    mesh = make_mesh_4d(8, (1, 2, 2, 2))
    state = init_pipeline_train_state(jax.random.PRNGKey(0), cfg)
    step = make_pipeline_train_step(mesh, cfg, n_microbatches=4)
    with pytest.raises(ValueError, match="microbatch"):
        step(state, tokens, targets)


def test_factor_devices_4d():
    assert factor_devices_4d(1) == (1, 1, 1, 1)
    assert factor_devices_4d(8) == (1, 2, 2, 2)
    assert factor_devices_4d(16) == (2, 2, 2, 2)
    for n in range(1, 33):
        assert int(np.prod(factor_devices_4d(n))) == n
