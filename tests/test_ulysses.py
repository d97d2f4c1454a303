"""Ulysses all-to-all sequence parallelism vs the single-device oracle.

Same A/B discipline as the ring-attention tests (the reference's
``--comm-type mpi`` oracle method, ``benchmark.cpp:147-174``): every sharded
result must match the unsharded full-matrix attention.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from flextree_tpu.models.transformer import (
    TransformerConfig,
    forward,
    init_params,
    param_specs,
)
from flextree_tpu.parallel.ring_attention import attention_reference
from flextree_tpu.parallel.ulysses import (
    heads_to_seq,
    seq_to_heads,
    ulysses_attention,
)


def _qkv(b=2, t=32, h=8, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
        for _ in range(3)
    )


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(sp, causal):
    mesh = jax.make_mesh((sp,), ("sp",))
    q, k, v = _qkv()
    fn = jax.jit(
        jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=causal),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
    )
    out = fn(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_ulysses_gradients_match_reference():
    mesh = jax.make_mesh((4,), ("sp",))
    q, k, v = _qkv()
    uly = jax.shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, "sp", causal=True),
        mesh=mesh,
        in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"),
    )
    g_uly = jax.jit(
        jax.grad(lambda q, k, v: (uly(q, k, v) ** 2).sum(), argnums=(0, 1, 2))
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: (attention_reference(q, k, v, causal=True) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_seq_to_heads_roundtrip_and_layout():
    mesh = jax.make_mesh((4,), ("sp",))
    x = jnp.arange(2 * 32 * 8 * 4, dtype=jnp.float32).reshape(2, 32, 8, 4)

    def body(x):
        g = seq_to_heads(x, "sp")
        # head-sharded view: full sequence, h/n heads
        assert g.shape == (2, 32, 2, 4)
        return heads_to_seq(g, "sp")

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),), out_specs=P(None, "sp")
        )
    )
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x))


def test_seq_to_heads_gathers_global_sequence():
    """After the re-shard every device must hold the full global sequence."""
    mesh = jax.make_mesh((4,), ("sp",))
    # encode the global position in the value so the layout is observable
    x = jnp.broadcast_to(
        jnp.arange(16, dtype=jnp.float32)[None, :, None, None], (1, 16, 4, 2)
    )

    def body(x):
        g = seq_to_heads(x, "sp")
        return g[..., 0:1, 0]  # (B, T_global, 1)

    fn = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P(None, "sp"),), out_specs=P(None, None, "sp")
        )
    )
    out = np.asarray(fn(x))  # (1, 16, 4): per-device copies stacked on axis 2
    for dev in range(4):
        np.testing.assert_array_equal(out[0, :, dev], np.arange(16))


def test_ulysses_rejects_indivisible_heads():
    mesh = jax.make_mesh((4,), ("sp",))
    q, k, v = _qkv(h=6)
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(
            jax.shard_map(
                lambda q, k, v: ulysses_attention(q, k, v, "sp"),
                mesh=mesh,
                in_specs=(P(None, "sp"),) * 3,
                out_specs=P(None, "sp"),
            )
        )(q, k, v)


def test_ulysses_single_device_axis():
    mesh = jax.make_mesh((1,), ("sp",))
    q, k, v = _qkv(t=16)
    fn = jax.jit(
        jax.shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "sp"),
            mesh=mesh,
            in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"),
        )
    )
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)), np.asarray(attention_reference(q, k, v)), atol=1e-5
    )


# ------------------------------------------------------------- model switch


def test_forward_ulysses_matches_single_device():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=8, n_layers=2, d_ff=64, sp_impl="ulysses"
    )
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
            check_vma=False,
        )
    )
    out = fn(params, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_forward_unknown_sp_impl_raises():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64, sp_impl="nope"
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    mesh = jax.make_mesh((2,), ("sp",))
    with pytest.raises(ValueError, match="sp_impl"):
        jax.shard_map(
            lambda p, tok: forward(p, tok, cfg, sp_axis="sp"),
            mesh=mesh,
            in_specs=(param_specs(cfg, None), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )(params, tokens)


def test_train_step_ulysses_matches_single_device():
    from conftest import own_copy
    from flextree_tpu.parallel.train import (
        init_train_state,
        make_mesh_3d,
        make_train_step,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=8, n_layers=2, d_ff=64, sp_impl="ulysses"
    )
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32)
    s8, m8 = make_train_step(make_mesh_3d(8, (2, 2, 2)), cfg)(
        own_copy(state), tokens, targets
    )
    s1, m1 = make_train_step(make_mesh_3d(1, (1, 1, 1)), cfg)(state, tokens, targets)
    np.testing.assert_allclose(float(m8["loss"]), float(m1["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree.leaves(jax.device_get(s8["params"])),
        jax.tree.leaves(jax.device_get(s1["params"])),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ulysses_forwards_flash_kwargs_to_inner_attention():
    """ADVICE r5: tuned flash opts must reach the inner local_attention —
    pinned via the reference impl, which rejects them with local_attention's
    own TypeError (an unforwarded kwarg would die at ulysses' signature
    with a different message)."""
    mesh = jax.make_mesh((4,), ("sp",))
    q, k, v = _qkv()
    fn = jax.shard_map(
        lambda q, k, v: ulysses_attention(
            q, k, v, "sp", impl="reference", block_q=64
        ),
        mesh=mesh,
        in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"),
    )
    with pytest.raises(TypeError, match="no flash kwargs"):
        fn(q, k, v)


def test_attn_opts_require_flash_impl():
    """ADVICE r5: attn_opts with a non-flash attn_impl used to be silently
    dropped — a tuned config running with library defaults.  Now it raises."""
    cfg = TransformerConfig(
        vocab_size=16, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        attn_impl="reference", attn_opts=(("block_q", 64),),
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="attn_impl='flash'"):
        forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
