"""MoE model + expert parallelism vs the single-device oracle.

Routing is deterministic (greedy argmax, first-come-first-served capacity),
so with capacity high enough that no shard drops tokens, an ep-sharded run
must match the all-experts-local single-device run exactly — the same A/B
oracle discipline as the rest of the suite (SURVEY §4).  Capacity dropping
itself is pinned down directly on ``route_topk``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import own_copy

pytestmark = pytest.mark.slow  # multi-minute train-step tests (fast subset: -m 'not slow')
from jax.sharding import PartitionSpec as P

from flextree_tpu.models.moe import (
    MoEConfig,
    expert_capacity,
    init_moe_params,
    moe_forward,
    moe_param_specs,
    route_topk,
)
from flextree_tpu.parallel.moe_train import (
    factor_devices_moe,
    init_moe_train_state,
    make_mesh_moe,
    make_moe_train_step,
)
from flextree_tpu.parallel.train import TrainConfig


def _cfg(**kw):
    base = dict(
        vocab_size=64,
        d_model=32,
        n_heads=4,
        n_layers=2,
        d_ff=64,
        n_experts=8,
        top_k=2,
        capacity_factor=8.0,  # no drops at test sizes
        router_aux_weight=0.0,
    )
    base.update(kw)
    return MoEConfig(**base)


def _batch(cfg, b=8, t=32, seed=1):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    return tokens, targets


# ----------------------------------------------------------------- routing


def test_route_topk_shapes_and_mass():
    rng = np.random.default_rng(0)
    probs = jax.nn.softmax(
        jnp.asarray(rng.standard_normal((16, 4)).astype(np.float32)), axis=-1
    )
    dispatch, combine = route_topk(probs, k=2, capacity=16)
    assert dispatch.shape == (16, 4, 16)
    # every token dispatched exactly k times (no drops at this capacity)
    np.testing.assert_array_equal(
        np.asarray(dispatch.sum(axis=(1, 2))), np.full(16, 2.0)
    )
    # combine weights normalized over the k picks
    np.testing.assert_allclose(
        np.asarray(combine.sum(axis=(1, 2))), np.ones(16), rtol=1e-6
    )
    # each (expert, slot) holds at most one token
    assert float(dispatch.sum(axis=0).max()) <= 1.0


def test_route_topk_capacity_drops_in_order():
    """All tokens prefer expert 0; only the first C fit."""
    probs = jnp.tile(jnp.asarray([[0.9, 0.1]], jnp.float32), (8, 1))
    dispatch, combine = route_topk(probs, k=1, capacity=3)
    kept = np.asarray(dispatch[:, 0].sum(axis=1))
    np.testing.assert_array_equal(kept, [1, 1, 1, 0, 0, 0, 0, 0])
    # dropped tokens have zero combine mass
    np.testing.assert_array_equal(
        np.asarray(combine.sum(axis=(1, 2)))[3:], np.zeros(5)
    )


def test_route_topk_distinct_experts_per_token():
    rng = np.random.default_rng(1)
    probs = jax.nn.softmax(
        jnp.asarray(rng.standard_normal((32, 8)).astype(np.float32)), axis=-1
    )
    dispatch, _ = route_topk(probs, k=2, capacity=32)
    per_expert = np.asarray(dispatch.sum(axis=2))  # (S, E)
    assert per_expert.max() <= 1.0  # k picks hit k distinct experts


def test_expert_capacity_static():
    cfg = _cfg(capacity_factor=1.0)
    assert expert_capacity(256, cfg) == 256 * 2 // 8
    assert expert_capacity(1, cfg) == 1


# ----------------------------------------------------- forward equivalence


def test_moe_forward_ep_sharded_matches_single_device():
    cfg = _cfg()
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    tokens, _ = _batch(cfg, b=4)
    ref, aux_ref = moe_forward(params, tokens, cfg)

    mesh = jax.make_mesh((4,), ("ep",))
    fn = jax.jit(
        jax.shard_map(
            lambda p, tok: moe_forward(p, tok, cfg, ep_axis="ep")[0],
            mesh=mesh,
            in_specs=(moe_param_specs(cfg, None, "ep"), P("ep", None)),
            out_specs=P("ep", None),
            check_vma=False,
        )
    )
    out = fn(params, tokens)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(out)), np.asarray(ref), atol=2e-4
    )
    assert np.isfinite(float(aux_ref))


def test_moe_forward_full_mesh_matches_single_device():
    """dp x ep x sp x tp all at once, dense layers interleaved (moe_every=2)."""
    cfg = _cfg(n_layers=4, moe_every=2, n_heads=8)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    tokens, _ = _batch(cfg, b=4)
    ref, _ = moe_forward(params, tokens, cfg)

    mesh = jax.make_mesh((2, 2, 2), ("ep", "sp", "tp"))
    fn = jax.jit(
        jax.shard_map(
            lambda p, tok: moe_forward(
                p, tok, cfg, tp_axis="tp", sp_axis="sp", ep_axis="ep"
            )[0],
            mesh=mesh,
            in_specs=(moe_param_specs(cfg, "tp", "ep"), P("ep", "sp")),
            out_specs=P("ep", "sp"),
            check_vma=False,
        )
    )
    out = fn(params, tokens)
    np.testing.assert_allclose(
        np.asarray(jax.device_get(out)), np.asarray(ref), atol=2e-4
    )


def test_moe_layer_rejects_indivisible_experts():
    cfg = _cfg(n_experts=6)
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    tokens, _ = _batch(cfg, b=4)
    mesh = jax.make_mesh((4,), ("ep",))
    with pytest.raises(ValueError, match="divisible"):
        jax.shard_map(
            lambda p, tok: moe_forward(p, tok, cfg, ep_axis="ep")[0],
            mesh=mesh,
            in_specs=(moe_param_specs(cfg, None, None), P("ep", None)),
            out_specs=P("ep", None),
            check_vma=False,
        )(params, tokens)


# ---------------------------------------------------------------- training


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def test_moe_train_step_matches_single_device():
    cfg = _cfg()
    tokens, targets = _batch(cfg)
    state = init_moe_train_state(jax.random.PRNGKey(0), cfg)

    s1, m1 = make_moe_train_step(make_mesh_moe(1, (1, 1, 1, 1)), cfg)(
        own_copy(state), tokens, targets
    )
    s8, m8 = make_moe_train_step(make_mesh_moe(8, (1, 4, 1, 2)), cfg)(
        state, tokens, targets
    )
    np.testing.assert_allclose(float(m8["loss"]), float(m1["loss"]), rtol=1e-4)
    for a, b in zip(_leaves(s8["params"]), _leaves(s1["params"])):
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("shape", [(2, 2, 2, 1), (1, 2, 2, 2), (2, 4, 1, 1)])
def test_moe_train_step_mesh_shapes(shape):
    cfg = _cfg(n_heads=4 if shape[3] == 1 else 8)
    tokens, targets = _batch(cfg)
    state = init_moe_train_state(jax.random.PRNGKey(0), cfg)
    s1, m1 = make_moe_train_step(make_mesh_moe(1, (1, 1, 1, 1)), cfg)(
        own_copy(state), tokens, targets
    )
    s, m = make_moe_train_step(make_mesh_moe(8, shape), cfg)(state, tokens, targets)
    np.testing.assert_allclose(float(m["loss"]), float(m1["loss"]), rtol=1e-4)
    for a, b in zip(_leaves(s["params"]), _leaves(s1["params"])):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_moe_training_loss_decreases_and_aux_reported():
    cfg = _cfg(router_aux_weight=1e-2)
    tokens, targets = _batch(cfg)
    state = init_moe_train_state(jax.random.PRNGKey(0), cfg)
    step = make_moe_train_step(
        make_mesh_moe(8, (1, 4, 1, 2)), cfg, TrainConfig(lr=3e-3)
    )
    losses, auxes = [], []
    for _ in range(5):
        state, metrics = step(state, tokens, targets)
        losses.append(float(metrics["loss"]))
        auxes.append(float(metrics["aux"]))
    assert losses[-1] < losses[0] - 0.2, losses
    assert all(a > 0 for a in auxes), auxes


def test_moe_train_step_with_tree_grad_topo():
    cfg = _cfg()
    tokens, targets = _batch(cfg)
    state = init_moe_train_state(jax.random.PRNGKey(0), cfg)
    mesh = make_mesh_moe(8, (4, 2, 1, 1))
    s_flat, m_flat = make_moe_train_step(mesh, cfg)(
        own_copy(state), tokens, targets
    )
    s_tree, m_tree = make_moe_train_step(mesh, cfg, TrainConfig(grad_topo="2,2"))(
        state, tokens, targets
    )
    np.testing.assert_allclose(float(m_tree["loss"]), float(m_flat["loss"]), rtol=1e-5)
    for a, b in zip(_leaves(s_tree["params"]), _leaves(s_flat["params"])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_moe_train_step_validation():
    cfg = _cfg(n_experts=6)
    with pytest.raises(ValueError, match="divisible"):
        make_moe_train_step(make_mesh_moe(8, (1, 4, 1, 2)), cfg)
    cfg = _cfg(top_k=9)
    with pytest.raises(ValueError, match="top_k"):
        make_moe_train_step(make_mesh_moe(8, (1, 4, 1, 2)), cfg)


def test_factor_devices_moe():
    assert factor_devices_moe(8) == (1, 2, 2, 2)
    for n in range(1, 33):
        assert int(np.prod(factor_devices_moe(n))) == n
