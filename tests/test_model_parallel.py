"""Tests for the model layer and dp/sp/tp parallel composition.

The oracle discipline mirrors the reference's A/B method (its ``--comm-type
mpi`` baseline, ``benchmark.cpp:147-174``): every sharded computation is
checked against an unsharded single-device run of the same math.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import own_copy
from jax.sharding import PartitionSpec as P

from flextree_tpu.models.transformer import (
    TransformerConfig,
    cross_entropy_loss,
    forward,
    init_params,
    param_specs,
)
from flextree_tpu.parallel.ring_attention import (
    attention_reference,
    ring_attention,
)
from flextree_tpu.parallel.train import (
    TrainConfig,
    factor_devices,
    init_train_state,
    make_mesh_3d,
    make_train_step,
)


def _qkv(b=2, t=32, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
        for _ in range(3)
    )


# ---------------------------------------------------------------- ring attn


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(sp, causal):
    mesh = jax.make_mesh((sp,), ("sp",))
    q, k, v = _qkv()
    fn = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
    )
    out = fn(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ring_attention_gradients_match_reference():
    mesh = jax.make_mesh((4,), ("sp",))
    q, k, v = _qkv()
    ring = jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"),
    )
    g_ring = jax.jit(
        jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(), argnums=(0, 1, 2))
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: (attention_reference(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ring_attention_single_device_axis():
    mesh = jax.make_mesh((1,), ("sp",))
    q, k, v = _qkv(t=16)
    fn = jax.jit(
        jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, "sp"),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
    )
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)),
        np.asarray(attention_reference(q, k, v)),
        atol=1e-5,
    )


# ---------------------------------------------------------------- model fwd


def _tiny_cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    base.update(kw)
    return TransformerConfig(**base)


def test_forward_sharded_matches_single_device():
    cfg = _tiny_cfg()
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)

    ref = forward(params, tokens, cfg)

    mesh = jax.make_mesh((4, 2), ("sp", "tp"))
    fn = jax.jit(
        jax.shard_map(
            lambda p, tok: forward(p, tok, cfg, tp_axis="tp", sp_axis="sp"),
            mesh=mesh,
            in_specs=(param_specs(cfg, "tp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            # logits are replicated over tp by our allreduce, but the vma
            # type system can't statically infer that through the
            # psum_scatter/all_gather chain
            check_vma=False,
        )
    )
    out = fn(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_forward_logits_finite_bf16():
    cfg = _tiny_cfg(dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    logits = forward(params, tokens, cfg)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_cross_entropy_loss_uniform_is_log_vocab():
    logits = jnp.zeros((2, 8, 64), jnp.float32)
    targets = jnp.zeros((2, 8), jnp.int32)
    loss, count = cross_entropy_loss(logits, targets)
    assert count == 16
    np.testing.assert_allclose(float(loss) / 16, np.log(64), rtol=1e-6)


# ---------------------------------------------------------------- training


def _batch(cfg, b=4, t=32, seed=1):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32)
    return tokens, targets


def _np_tree(t):
    return jax.tree.map(np.asarray, jax.device_get(t))


@pytest.mark.slow
def test_train_step_8dev_matches_single_device():
    cfg = _tiny_cfg()
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens, targets = _batch(cfg)
    s8, m8 = make_train_step(make_mesh_3d(8, (2, 2, 2)), cfg)(
        own_copy(state), tokens, targets
    )
    s1, m1 = make_train_step(make_mesh_3d(1, (1, 1, 1)), cfg)(state, tokens, targets)
    np.testing.assert_allclose(float(m8["loss"]), float(m1["loss"]), rtol=1e-5)
    p8, p1 = _np_tree(s8["params"]), _np_tree(s1["params"])
    for a, b in zip(jax.tree.leaves(p8), jax.tree.leaves(p1)):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(4, 2, 1), (1, 2, 4), (2, 1, 4), (8, 1, 1)])
def test_train_step_other_mesh_shapes(shape):
    cfg = _tiny_cfg()
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens, targets = _batch(cfg, b=8)
    s1, m1 = make_train_step(make_mesh_3d(1, (1, 1, 1)), cfg)(
        own_copy(state), tokens, targets
    )
    s, m = make_train_step(make_mesh_3d(8, shape), cfg)(state, tokens, targets)
    np.testing.assert_allclose(float(m["loss"]), float(m1["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree.leaves(_np_tree(s["params"])), jax.tree.leaves(_np_tree(s1["params"]))
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_train_step_with_tree_grad_topo():
    """Gradient sync through a 2-stage hierarchical topology, not flat."""
    cfg = _tiny_cfg()
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens, targets = _batch(cfg)
    mesh = make_mesh_3d(8, (4, 1, 2))
    s_flat, m_flat = make_train_step(mesh, cfg)(
        own_copy(state), tokens, targets
    )
    s_tree, m_tree = make_train_step(mesh, cfg, TrainConfig(grad_topo="2,2"))(
        state, tokens, targets
    )
    np.testing.assert_allclose(float(m_tree["loss"]), float(m_flat["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree.leaves(_np_tree(s_tree["params"])),
        jax.tree.leaves(_np_tree(s_flat["params"])),
    ):
        np.testing.assert_allclose(a, b, atol=1e-5)


@pytest.mark.slow
def test_training_loss_decreases():
    cfg = _tiny_cfg()
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens, targets = _batch(cfg)
    step = make_train_step(make_mesh_3d(8, (2, 2, 2)), cfg, TrainConfig(lr=3e-3))
    losses = []
    for _ in range(5):
        state, metrics = step(state, tokens, targets)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses


def test_factor_devices():
    assert factor_devices(1) == (1, 1, 1)
    assert factor_devices(8) == (2, 2, 2)
    assert factor_devices(4) == (2, 2, 1)
    for n in range(1, 33):
        assert np.prod(factor_devices(n)) == n


# ---------------------------------------------------------------- contract


@pytest.mark.slow
def test_graft_entry_contract(monkeypatch):
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[-1] == 8192
    # the driver-facing default also spawns n=12/n=60 child dryruns (+5 min,
    # covered by test_dryrun_non_power_of_two_world); keep this test at n=8
    monkeypatch.setenv("FLEXTREE_DRYRUN_EXTRA", "")
    g.dryrun_multichip(8)


@pytest.mark.slow
def test_dryrun_non_power_of_two_world():
    """The driver-facing extra worlds: one child dryrun
    at n=12 running the grad-sync oracles (tree topologies, lonely shape,
    planner-picked multi-slice sync vs psum) exactly as dryrun_multichip(8)
    spawns it — but scenario-subset so the test stays minutes, not tens."""
    import subprocess
    import sys as _sys

    env = {
        **os.environ,
        "FLEXTREE_DRYRUN_EXTRA": "",
        "FLEXTREE_DRYRUN_SCENARIOS": "tree,multislice",
    }
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [_sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(12)"],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    assert "tree grad sync over 12-wide dp axis, FT_TOPO=11+1" in p.stdout
    assert "multi-slice 2x6 hybrid mesh" in p.stdout
