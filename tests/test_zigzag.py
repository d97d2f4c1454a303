"""Zigzag (load-balanced) ring attention vs the single-device oracle.

Same discipline as test_model_parallel's ring tests: every sharded
computation is checked against an unsharded run of the same math
(the reference's --comm-type A/B method, benchmark.cpp:147-174).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import own_copy
from jax.sharding import PartitionSpec as P

from flextree_tpu.parallel.ring_attention import attention_reference
from flextree_tpu.parallel.zigzag import (
    zigzag_merge,
    zigzag_ring_attention,
    zigzag_split,
)


def _qkv(b=2, t=48, h=4, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.standard_normal((b, t, h, d)).astype(np.float32))
        for _ in range(3)
    )


def _shard_fn(fn, sp, in_specs, out_specs):
    mesh = jax.make_mesh((sp,), ("sp",))
    return jax.jit(
        jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                      check_vma=False)
    )


# ---------------------------------------------------------------- layout


@pytest.mark.parametrize("sp", [2, 3, 4, 8])
def test_zigzag_split_places_chunk_pairs(sp):
    """Device i must end up with global chunks (i, 2n-1-i)."""
    t = 4 * sp  # 2 chunks of 2 per device
    x = jnp.arange(t, dtype=jnp.float32).reshape(1, t, 1, 1)
    split = _shard_fn(
        lambda a: zigzag_split(a, "sp"), sp, (P(None, "sp"),), P(None, "sp")
    )(x)
    got = np.asarray(split).reshape(t)
    c = t // (2 * sp)
    expect = []
    for i in range(sp):
        expect.extend(range(i * c, (i + 1) * c))  # early chunk i
        g = 2 * sp - 1 - i
        expect.extend(range(g * c, (g + 1) * c))  # late chunk 2n-1-i
    np.testing.assert_array_equal(got, np.asarray(expect, np.float32))


@pytest.mark.parametrize("sp", [2, 3, 4, 8])
def test_zigzag_roundtrip(sp):
    q, _, _ = _qkv(t=8 * sp)
    rt = _shard_fn(
        lambda a: zigzag_merge(zigzag_split(a, "sp"), "sp"),
        sp, (P(None, "sp"),), P(None, "sp"),
    )(q)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(q))


def test_zigzag_rejects_odd_local_length():
    with pytest.raises(ValueError, match="even"):
        _shard_fn(
            lambda a: zigzag_split(a, "sp"), 2, (P(None, "sp"),), P(None, "sp")
        )(jnp.ones((1, 6, 1, 1)))  # 3 per device


# ------------------------------------------------------------- attention


@pytest.mark.parametrize("sp", [2, 3, 4, 8])
@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_zigzag_attention_matches_reference(sp, layout):
    q, k, v = _qkv(t=8 * sp)

    def fn(q, k, v):
        if layout == "zigzag":
            q, k, v = (zigzag_split(a, "sp") for a in (q, k, v))
        out = zigzag_ring_attention(
            q, k, v, "sp", layout=layout, impl="reference"
        )
        if layout == "zigzag":
            out = zigzag_merge(out, "sp")
        return out

    out = _shard_fn(fn, sp, (P(None, "sp"),) * 3, P(None, "sp"))(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("sp", [2, 4])
def test_zigzag_flash_matches_reference_impl(sp):
    q, k, v = _qkv(t=8 * sp)
    out = _shard_fn(
        lambda q, k, v: zigzag_ring_attention(q, k, v, "sp", impl="flash"),
        sp, (P(None, "sp"),) * 3, P(None, "sp"),
    )(q, k, v)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_zigzag_single_device_axis_odd_length():
    """n == 1 takes the plain-attention path, so odd lengths are fine."""
    q, k, v = _qkv(t=15)
    out = _shard_fn(
        lambda q, k, v: zigzag_ring_attention(q, k, v, "sp", impl="reference"),
        1, (P(None, "sp"),) * 3, P(None, "sp"),
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(attention_reference(q, k, v, causal=True)),
        atol=1e-5,
    )


def test_zigzag_single_device_axis():
    q, k, v = _qkv(t=16)
    out = _shard_fn(
        lambda q, k, v: zigzag_ring_attention(q, k, v, "sp", impl="reference"),
        1, (P(None, "sp"),) * 3, P(None, "sp"),
    )(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(attention_reference(q, k, v, causal=True)),
        atol=1e-5,
    )


@pytest.mark.slow
def test_zigzag_gradients_match_reference():
    sp = 4
    q, k, v = _qkv(t=8 * sp)
    zig = _shard_fn(
        lambda q, k, v: zigzag_ring_attention(q, k, v, "sp", impl="reference"),
        sp, (P(None, "sp"),) * 3, P(None, "sp"),
    )
    g_zig = jax.jit(
        jax.grad(lambda q, k, v: (zig(q, k, v) ** 2).sum(), argnums=(0, 1, 2))
    )(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: (
            attention_reference(q, k, v, causal=True) ** 2
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_zig, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("n", [2, 3, 4, 8, 12])
def test_zigzag_schedule_is_balanced(n):
    """The load-balance claim, checked against the IMPLEMENTATION's own
    branch selection (``hop_branches``, the function the kernel's
    ``lax.switch`` consumes): at every hop every device executes exactly 2
    non-masked chunk-pair attentions (1 static late-vs-early full hop + 1
    switch hop; the diagonal hop fires both switches as causal
    half-blocks).  Contrast: the contiguous causal ring's per-device
    visible-hop totals spread 1..n — the imbalance zigzag removes."""
    from flextree_tpu.parallel.zigzag import hop_branches

    for i in range(n):          # device
        for s in range(n):      # hop
            src = (i - s) % n
            br_e, br_l = (int(b) for b in hop_branches(src, i))
            work = 1            # static late-q vs visiting-early-k hop
            work += int(br_e != 2) + int(br_l != 2)  # non-masked switches
            expect = 3 if src == i else 2
            assert work == expect, (n, i, s, br_e, br_l)
            # diagonal iff src == idx, on both switches
            assert (br_e == 0) == (src == i) and (br_l == 0) == (src == i)
    # contrast: contiguous causal ring — device i sees src <= i only, so
    # per-device totals range 1..n (the imbalance)
    totals = [
        sum(1 for s in range(n) if (i - s) % n <= i) for i in range(n)
    ]
    assert min(totals) == 1 and max(totals) == n


# ------------------------------------------------------------- model switch


def test_forward_zigzag_matches_single_device():
    from flextree_tpu.models.transformer import (
        TransformerConfig,
        forward,
        init_params,
        param_specs,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        sp_impl="zigzag",
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


@pytest.mark.slow
def test_train_step_zigzag_matches_single_device():
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.parallel.train import (
        init_train_state,
        make_mesh_3d,
        make_train_step,
    )

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        sp_impl="zigzag",
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


def test_zigzag_rejects_bad_args():
    q, k, v = _qkv(t=16)
    with pytest.raises(ValueError, match="layout"):
        _shard_fn(
            lambda q, k, v: zigzag_ring_attention(q, k, v, "sp", layout="x"),
            2, (P(None, "sp"),) * 3, P(None, "sp"),
        )(q, k, v)
    with pytest.raises(ValueError, match="impl"):
        _shard_fn(
            lambda q, k, v: zigzag_ring_attention(q, k, v, "sp", impl="x"),
            2, (P(None, "sp"),) * 3, P(None, "sp"),
        )(q, k, v)


def test_zigzag_critical_path_closed_form():
    """The README's throughput claim, as accounting:
    per-hop critical path (max over devices of visible work, since the
    hop's ppermute is a lockstep barrier) summed over hops gives
    plain/zigzag = 2 - 1/n exactly, with total executed work identical —
    derived from the kernels' own branch predicates by
    ``tools/zigzag_accounting.py`` (artifact: ZIGZAG_ACCOUNTING.json)."""
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "tools", "zigzag_accounting.py",
    )
    spec = importlib.util.spec_from_file_location("zigzag_accounting", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    for n in (2, 4, 8, 16):
        t = mod.schedule_tables(n)
        assert t["total_work_equal"], t
        assert t["critical_path_ratio"] == t["closed_form_ratio"] == round(
            2.0 - 1.0 / n, 4
        ), t
        # zigzag rows are flat (perfect balance); plain rows are not (n>2)
        for row in t["zigzag_per_hop_units"]:
            assert len(set(row)) == 1, row
