"""LR schedules and global-norm gradient clipping in the train step.

The clipping oracle is the usual A/B: the sharded step (params tp-sharded,
so the global norm must psum shard square-sums) must match the
single-device step bit-for-tolerance — a wrong norm (over- or
under-counted shards) shifts every parameter update.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import own_copy

from flextree_tpu.models.transformer import TransformerConfig
from flextree_tpu.parallel.train import (
    TrainConfig,
    clip_by_global_norm,
    global_grad_norm,
    init_train_state,
    make_mesh_3d,
    make_train_step,
    schedule_lr,
)


# ------------------------------------------------------------- schedule


def test_schedule_constant():
    cfg = TrainConfig(lr=3e-4)
    for s in (1, 10, 1000):
        assert float(schedule_lr(cfg, jnp.int32(s))) == pytest.approx(3e-4)


def test_schedule_warmup_cosine_shape():
    cfg = TrainConfig(
        lr=1e-3, schedule="warmup_cosine", warmup_steps=10, total_steps=110,
        min_lr_frac=0.1,
    )
    lrs = [float(schedule_lr(cfg, jnp.int32(s))) for s in range(1, 121)]
    # linear ramp: step 5 is half of step 10; peak at warmup end
    assert lrs[4] == pytest.approx(0.5e-3, rel=1e-5)
    assert lrs[9] == pytest.approx(1e-3, rel=1e-5)
    assert max(lrs) == pytest.approx(1e-3, rel=1e-5)
    # monotone decay after warmup, floor at min_lr_frac * lr
    assert all(a >= b - 1e-12 for a, b in zip(lrs[9:], lrs[10:]))
    assert lrs[109] == pytest.approx(0.1e-3, rel=1e-4)
    assert lrs[119] == pytest.approx(0.1e-3, rel=1e-4)  # flat past the end


def test_schedule_validation():
    with pytest.raises(ValueError, match="total_steps"):
        schedule_lr(
            TrainConfig(schedule="warmup_cosine", total_steps=0), jnp.int32(1)
        )
    with pytest.raises(ValueError, match="schedule"):
        schedule_lr(TrainConfig(schedule="nope"), jnp.int32(1))


# ------------------------------------------------------------- clipping


def test_clip_by_global_norm_math():
    g = {"a": jnp.asarray([3.0, 0.0]), "b": jnp.asarray([[4.0]])}
    norm = jnp.sqrt(jnp.float32(25.0))
    clipped = clip_by_global_norm(g, norm, 1.0)
    np.testing.assert_allclose(np.asarray(clipped["a"]), [0.6, 0.0], rtol=1e-6)
    np.testing.assert_allclose(np.asarray(clipped["b"]), [[0.8]], rtol=1e-6)
    # below the clip: untouched
    same = clip_by_global_norm(g, norm, 10.0)
    np.testing.assert_allclose(np.asarray(same["a"]), [3.0, 0.0], rtol=1e-6)


def _cfg():
    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )


def _batch(cfg, b=4, t=32, seed=1):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32),
        jnp.asarray(rng.integers(0, cfg.vocab_size, (b, t)), jnp.int32),
    )


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(2, 2, 2), (2, 1, 4)])
def test_clipped_train_step_matches_single_device(shape):
    """The global norm over tp-sharded grads must equal the unsharded
    norm — a tight clip makes any miscount visible in every parameter."""
    cfg = _cfg()
    tcfg = TrainConfig(lr=1e-2, grad_clip_norm=0.05)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens, targets = _batch(cfg, b=8)
    s8, m8 = make_train_step(make_mesh_3d(8, shape), cfg, tcfg)(
        own_copy(state), tokens, targets
    )
    s1, m1 = make_train_step(make_mesh_3d(1, (1, 1, 1)), cfg, tcfg)(
        state, tokens, targets
    )
    np.testing.assert_allclose(
        float(m8["grad_norm"]), float(m1["grad_norm"]), rtol=1e-5
    )
    # the clip must actually bind for this test to mean anything
    assert float(m1["grad_norm"]) > tcfg.grad_clip_norm
    for a, b in zip(
        jax.tree.leaves(jax.device_get(s8["params"])),
        jax.tree.leaves(jax.device_get(s1["params"])),
    ):
        # atol 1e-4: the norm's f32 reduction order differs (psum of shard
        # sums vs one full sum, ~1e-7 relative) and AdamW's first-step
        # g/sqrt(g^2) normalization amplifies ulp-level grad differences;
        # a miscounted norm (e.g. a shard double-count) is ~sqrt(2) off
        # and fails both this and the grad_norm assert above by orders
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_grad_norm_counts_tp_shards_once():
    """Unit check of the spec-aware norm: a tp-sharded leaf sums across
    shards; a replicated leaf is counted once (not axis-size times)."""
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = jax.make_mesh((4,), ("tp",))
    g_sharded = jnp.arange(8, dtype=jnp.float32)  # sharded over tp: 2/dev
    g_repl = jnp.asarray([2.0])

    def f(gs, gr):
        return global_grad_norm({"s": gs, "r": gr}, {"s": P("tp"), "r": P()})

    out = jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=(P("tp"), P()), out_specs=P(),
            check_vma=False,
        )
    )(g_sharded, g_repl)
    expect = np.sqrt(np.sum(np.arange(8.0) ** 2) + 4.0)
    np.testing.assert_allclose(float(out), expect, rtol=1e-6)


@pytest.mark.slow
def test_clipped_pipeline_step_matches_single_device():
    """pp stage-stacked params: each device holds its stage's slice, so the
    spec-aware norm must psum over pp (and tp) exactly once."""
    from flextree_tpu.parallel.pipeline import (
        init_pipeline_train_state,
        make_mesh_4d,
        make_pipeline_train_step,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64
    )
    tcfg = TrainConfig(lr=1e-2, grad_clip_norm=0.05)
    state = init_pipeline_train_state(jax.random.PRNGKey(0), cfg)
    tokens, targets = _batch(cfg, b=8)
    s8, m8 = make_pipeline_train_step(
        make_mesh_4d(8, (1, 2, 2, 2)), cfg, tcfg, n_microbatches=2
    )(own_copy(state), tokens, targets)
    s1, m1 = make_pipeline_train_step(
        make_mesh_4d(1, (1, 1, 1, 1)), cfg, tcfg, n_microbatches=2
    )(state, tokens, targets)
    np.testing.assert_allclose(
        float(m8["grad_norm"]), float(m1["grad_norm"]), rtol=1e-5
    )
    assert float(m1["grad_norm"]) > tcfg.grad_clip_norm
    for a, b in zip(
        jax.tree.leaves(jax.device_get(s8["params"])),
        jax.tree.leaves(jax.device_get(s1["params"])),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


@pytest.mark.slow
def test_clipped_moe_step_matches_single_device():
    """ep expert-sharded params join the norm once per expert shard."""
    from flextree_tpu.models.moe import MoEConfig
    from flextree_tpu.parallel.moe_train import (
        init_moe_train_state,
        make_mesh_moe,
        make_moe_train_step,
    )

    cfg = MoEConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        n_experts=4, top_k=2, capacity_factor=4.0,
    )
    # eps=1e-3: the tight clip scales grads ~30x down, pushing near-zero
    # elements into AdamW's g/(|g|+eps) sign regime where MoE's inherent
    # ~1e-4 routing-reorder noise flips update signs; a larger eps keeps
    # the update Lipschitz so the equivalence comparison stays meaningful
    tcfg = TrainConfig(lr=1e-2, grad_clip_norm=0.05, eps=1e-3)
    state = init_moe_train_state(jax.random.PRNGKey(0), cfg)
    tokens, targets = _batch(cfg, b=8)
    s8, m8 = make_moe_train_step(
        make_mesh_moe(8, (1, 2, 2, 2)), cfg, tcfg
    )(own_copy(state), tokens, targets)
    s1, m1 = make_moe_train_step(
        make_mesh_moe(1, (1, 1, 1, 1)), cfg, tcfg
    )(state, tokens, targets)
    # MoE's sharded dispatch reorders the routed sums (~1e-4 relative in
    # its own equivalence tests, tests/test_moe.py) — a shard miscount
    # would be ~sqrt(2) off, orders beyond this band
    np.testing.assert_allclose(
        float(m8["grad_norm"]), float(m1["grad_norm"]), rtol=1e-3
    )
    assert float(m1["grad_norm"]) > tcfg.grad_clip_norm
    for a, b in zip(
        jax.tree.leaves(jax.device_get(s8["params"])),
        jax.tree.leaves(jax.device_get(s1["params"])),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.slow
def test_warmup_cosine_through_train_step():
    """The schedule reaches the jitted update: with warmup, step 1's
    update is smaller than the same step at constant lr."""
    cfg = _cfg()
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    tokens, targets = _batch(cfg)
    mesh = make_mesh_3d(1, (1, 1, 1))
    s_w, _ = make_train_step(
        mesh, cfg,
        TrainConfig(lr=1e-2, schedule="warmup_cosine", warmup_steps=10,
                    total_steps=100),
    )(own_copy(state), tokens, targets)
    s_c, _ = make_train_step(mesh, cfg, TrainConfig(lr=1e-2))(
        own_copy(state), tokens, targets
    )
    d_w = sum(
        float(jnp.abs(a - b).sum())
        for a, b in zip(
            jax.tree.leaves(s_w["params"]), jax.tree.leaves(state["params"])
        )
    )
    d_c = sum(
        float(jnp.abs(a - b).sum())
        for a, b in zip(
            jax.tree.leaves(s_c["params"]), jax.tree.leaves(state["params"])
        )
    )
    assert d_w < 0.2 * d_c  # step 1 of 10-step warmup: ~10% of constant
