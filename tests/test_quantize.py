"""Wire codecs + compressed collectives (ISSUE 5 tentpole).

Three contracts, each machine-checked here and in the bench driver:

1. **Identity**: the ``f32`` codec is bitwise-identical to the
   uncompressed allreduce — by value across every topology family x tail
   x chunking, and structurally (the compiled HLO is the same program).
2. **Bound**: ``int8``/``bf16`` results stay inside
   ``Codec.error_bound`` (the documented contract) on every schedule,
   and every rank holds bit-identical results (replica consistency —
   a quantized sync that lets replicas drift corrupts training).
3. **Error feedback**: with the EF residual carried across steps, the
   running mean of a repeated-constant-gradient sync converges to the
   exact gradient at ~1/N (stochastic rounding is keyed off the step
   counter, so this test is fully deterministic).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flextree_tpu.ops.quantize import (
    CODECS,
    decode_int8,
    encode_int8,
    get_codec,
)
from flextree_tpu.parallel.allreduce import allreduce
from flextree_tpu.parallel.compressed import compressed_allreduce
from flextree_tpu.parallel.mesh import flat_mesh
from flextree_tpu.schedule.stages import LonelyTopology, Topology

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)

N = 8
TOPOS = ["8", "4,2", "2,2,2", "1", "7+1", "3,2+2"]
SIZES = [4096, 4100, 777]  # divisible / +tail / odd+tail


def _run(fn, x, extra=None):
    mesh = flat_mesh(N, "ft")
    in_specs = (P("ft"),) if extra is None else (P("ft"), P())
    f = lambda row, *a: fn(row[0], *a)[None]
    jf = jax.jit(
        jax.shard_map(
            f, mesh=mesh, in_specs=in_specs, out_specs=P("ft"), check_vma=False
        )
    )
    args = (x,) if extra is None else (x, extra)
    return np.asarray(jax.block_until_ready(jf(*args)))


def _bound_args(topo_spec):
    t = Topology.resolve(N, topo_spec)
    if isinstance(t, LonelyTopology):
        return t.tree.widths, t.lonely
    return t.widths, 0


# ------------------------------------------------------------ codec units


class TestCodecUnits:
    def test_registry(self):
        assert set(CODECS) == {"f32", "bf16", "int8"}
        assert not get_codec("f32").lossy
        assert get_codec(None).name == "f32"
        assert get_codec(get_codec("int8")).name == "int8"
        with pytest.raises(ValueError, match="unsupported codec"):
            get_codec("fp4")

    def test_int8_roundtrip_error_within_one_step(self):
        rng = np.random.default_rng(0)
        v = jnp.asarray(rng.standard_normal(5000).astype(np.float32) * 7)
        q, s = encode_int8(v, step=3)
        out = decode_int8(q, s, v.shape[0])
        # stochastic rounding: error strictly under one quantization step,
        # per block (scale = block amax / 127)
        blocks = np.asarray(jnp.pad(v, (0, q.shape[0] - v.shape[0]))).reshape(-1, 1024)
        scales = np.abs(blocks).max(axis=1) / 127.0
        err = np.abs(np.asarray(out) - np.asarray(v)).reshape(-1)
        per_elem_bound = np.repeat(scales, 1024)[: v.shape[0]] + 1e-7
        assert (err <= per_elem_bound).all()

    def test_int8_deterministic_in_step(self):
        v = jnp.asarray(np.random.default_rng(1).standard_normal(2048), jnp.float32)
        q1, _ = encode_int8(v, step=5)
        q2, _ = encode_int8(v, step=5)
        q3, _ = encode_int8(v, step=6)
        assert np.array_equal(np.asarray(q1), np.asarray(q2))
        assert not np.array_equal(np.asarray(q1), np.asarray(q3))

    def test_zeros_and_pad_are_exact(self):
        v = jnp.zeros(1500, jnp.float32)  # non-block-aligned, all zero
        q, s = encode_int8(v, step=0)
        assert np.asarray(q).max() == 0
        out = decode_int8(q, s, 1500)
        assert out.shape == (1500,) and not np.asarray(out).any()

    def test_roundtrip_maps(self):
        v = jnp.asarray(np.random.default_rng(2).standard_normal(1000), jnp.float32)
        assert np.array_equal(
            np.asarray(get_codec("f32").roundtrip(v)), np.asarray(v)
        )
        bf = get_codec("bf16").roundtrip(v)
        assert bf.dtype == v.dtype
        assert np.abs(np.asarray(bf) - np.asarray(v)).max() <= np.abs(
            np.asarray(v)
        ).max() * 2**-8

    def test_error_bound_hops(self):
        c = get_codec("int8")
        assert c.hops_for(8, (4, 2)) == 3  # 2 RS stages + 1 AG encode
        assert c.hops_for(8, (1,)) == 8  # ring: 7 folds + 1 AG encode
        assert c.hops_for(8, (7,), lonely=1) == 4  # buddy + RS + AG + restore
        assert get_codec("f32").error_bound(10.0, 8, (4, 2)) == 0.0
        assert c.error_bound(1.0, 8, (4, 2)) == pytest.approx(3 * 8 / 127.0)


# ----------------------------------------------- identity codec == allreduce


class TestIdentityCodec:
    @pytest.mark.parametrize("topo", TOPOS)
    @pytest.mark.parametrize("size", SIZES)
    def test_bitwise_identical(self, topo, size):
        x = jnp.asarray(
            np.random.default_rng(0).standard_normal((N, size)).astype(np.float32)
        )
        a = _run(lambda v: compressed_allreduce(v, "ft", topo=topo, codec="f32"), x)
        b = _run(lambda v: allreduce(v, "ft", topo=topo), x)
        assert a.tobytes() == b.tobytes()

    @pytest.mark.parametrize("chunks", [2, 3])
    def test_bitwise_identical_chunked(self, chunks):
        x = jnp.asarray(
            np.random.default_rng(1).standard_normal((N, 4096)).astype(np.float32)
        )
        a = _run(
            lambda v: compressed_allreduce(
                v, "ft", topo="4,2", codec="f32", chunks=chunks
            ),
            x,
        )
        b = _run(lambda v: allreduce(v, "ft", topo="4,2", chunks=chunks), x)
        assert a.tobytes() == b.tobytes()

    def test_compiles_identically(self):
        """Structural half of the identity contract: with the f32 codec
        the compressed entrypoint compiles to the SAME program as the
        plain allreduce (modulo op-name metadata) — the codec layer adds
        literally nothing to the uncompressed path."""
        from conftest import strip_hlo_debug

        mesh = flat_mesh(N, "ft")

        def lower(fn):
            f = lambda row: fn(row[0])[None]
            jf = jax.jit(
                jax.shard_map(
                    f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"),
                    check_vma=False,
                )
            )
            return jf.lower(jnp.zeros((N, 4096), jnp.float32)).compile().as_text()

        plain = strip_hlo_debug(lower(lambda v: allreduce(v, "ft", topo="4,2")))
        compressed = strip_hlo_debug(
            lower(lambda v: compressed_allreduce(v, "ft", topo="4,2", codec="f32"))
        )
        assert plain == compressed

    def test_residual_is_zero(self):
        x = jnp.asarray(
            np.random.default_rng(2).standard_normal((N, 512)).astype(np.float32)
        )

        def f(v):
            out, res = compressed_allreduce(
                v, "ft", topo="8", codec="f32", return_residual=True
            )
            return jnp.stack([out, res])

        out = _run(f, x)
        assert not out[:, 1].any()


# ------------------------------------------------------- lossy codec bounds


class TestLossyCodecs:
    @pytest.mark.parametrize("codec", ["int8", "bf16"])
    @pytest.mark.parametrize("topo", TOPOS)
    @pytest.mark.parametrize("size", [4096, 777])
    def test_within_documented_bound(self, codec, topo, size):
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((N, size)).astype(np.float32) * 3)
        out = _run(
            lambda v: compressed_allreduce(v, "ft", topo=topo, codec=codec, step=5),
            x,
        )
        exact = np.asarray(x).astype(np.float64).sum(axis=0)
        widths, lonely = _bound_args(topo)
        bound = get_codec(codec).error_bound(
            float(np.abs(np.asarray(x)).max()), N, widths, lonely
        )
        err = np.abs(out - exact[None]).max()
        assert err <= bound + 1e-5, f"{codec}/{topo}: {err} > {bound}"

    @pytest.mark.parametrize("topo", ["4,2", "1", "7+1"])
    def test_replica_consistency(self, topo):
        """Every rank must hold bit-identical results — replicas that
        drift under a lossy sync silently fork the model."""
        x = jnp.asarray(
            np.random.default_rng(8).standard_normal((N, 2048)).astype(np.float32)
        )
        out = _run(
            lambda v: compressed_allreduce(v, "ft", topo=topo, codec="int8", step=1),
            x,
        )
        for r in range(1, N):
            assert out[0].tobytes() == out[r].tobytes()

    def test_chunked_int8_within_bound(self):
        x = jnp.asarray(
            np.random.default_rng(9).standard_normal((N, 4096)).astype(np.float32)
        )
        out = _run(
            lambda v: compressed_allreduce(
                v, "ft", topo="4,2", codec="int8", chunks=3, step=2
            ),
            x,
        )
        exact = np.asarray(x).astype(np.float64).sum(axis=0)
        bound = get_codec("int8").error_bound(
            float(np.abs(np.asarray(x)).max()), N, (4, 2)
        )
        assert np.abs(out - exact[None]).max() <= bound + 1e-5

    def test_step_changes_rounding(self):
        """Different step counters must draw different stochastic
        rounding — that decorrelation over time is what makes the
        long-run average converge (and it must come from the step
        counter, not from RNG in the trace)."""
        x = jnp.asarray(
            np.random.default_rng(10).standard_normal((N, 2048)).astype(np.float32)
        )
        f = lambda v, s: compressed_allreduce(
            v, "ft", topo="8", codec="int8", step=s
        )
        a = _run(f, x, extra=jnp.int32(3))
        b = _run(f, x, extra=jnp.int32(3))
        c = _run(f, x, extra=jnp.int32(4))
        assert a.tobytes() == b.tobytes()  # deterministic in step
        assert a.tobytes() != c.tobytes()  # decorrelated across steps


# ------------------------------------------------------------ error feedback


class TestErrorFeedback:
    def test_constant_gradient_running_mean_converges(self):
        """The EF contract: sync ``g + e`` compressed, carry ``e' = input
        - C(input)``; the input quantization telescopes exactly and the
        per-hop requantization is unbiased (stochastic rounding keyed off
        the step), so the running mean of the synced gradient converges
        to the exact ``n * g`` at ~1/N.  Deterministic: same steps, same
        bits, every run."""
        rng = np.random.default_rng(3)
        g = rng.standard_normal(2048).astype(np.float32)
        exact = N * g.astype(np.float64)
        bound = get_codec("int8").error_bound(float(np.abs(g).max()), N, (N,))

        def f(v, s):
            out, res = compressed_allreduce(
                v, "ft", topo="8", codec="int8", step=s, return_residual=True
            )
            return jnp.stack([out, res])

        e = np.zeros_like(g)
        acc = np.zeros_like(exact)
        errs = {}
        for step in range(1, 25):
            x = jnp.asarray(np.tile(g + e, (N, 1)))
            out = _run(f, x, extra=jnp.int32(step))
            acc += out[0, 0].astype(np.float64)
            e = out[0, 1]
            errs[step] = np.abs(acc / step - exact).max()
            # the residual never accumulates beyond one quantization step
            assert np.abs(e).max() <= float(np.abs(g + e).max()) / 127.0 + 1e-6
        # single-shot error is within the bound; the running mean shrinks
        # ~1/N below it (measured 0.23 -> 0.0095 over 24 steps; margins 2x)
        assert errs[1] <= bound + 1e-5
        assert errs[24] < errs[1] / 8
        assert errs[24] < bound / 10

    def test_train_state_carries_ef(self):
        from flextree_tpu.models.transformer import TransformerConfig
        from flextree_tpu.parallel.train import (
            TrainConfig,
            init_train_state,
            make_mesh_nd,
            make_train_step,
            state_specs,
        )

        model_cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
        )
        mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
        tc = TrainConfig(codec="int8")
        state = init_train_state(jax.random.PRNGKey(0), model_cfg, tc)
        assert "ef" in state and "ef" in state_specs(model_cfg, "tp", tc)
        step = make_train_step(mesh, model_cfg, tc)
        tok = jnp.asarray(
            np.random.default_rng(0).integers(0, 64, (4, 32)), jnp.int32
        )
        s1, m1 = jax.block_until_ready(step(state, tok, tok))
        s2, m2 = jax.block_until_ready(step(s1, tok, tok))
        # the residual is live (nonzero) and the step trains
        assert any(np.asarray(l).any() for l in jax.tree.leaves(s2["ef"]))
        assert float(m2["loss"]) < float(m1["loss"])
        # identity codec keeps the historical state layout
        assert "ef" not in init_train_state(
            jax.random.PRNGKey(0), model_cfg, TrainConfig()
        )


# ------------------------------------------------------- sync integration


class TestCompressedSync:
    def test_bucketed_lossy_sync_within_bound_with_residuals(self):
        from flextree_tpu.parallel.train import resolve_axis_topos, sync_grads

        mesh = flat_mesh(N, "dp")
        topos = resolve_axis_topos(mesh, ("dp",), None)
        rng = np.random.default_rng(4)
        tree = {
            f"leaf{i}": jnp.asarray(
                rng.standard_normal((N, 1000 + 7 * i)).astype(np.float32)
            )
            for i in range(5)
        }
        dev_specs = {k: P() for k in tree}
        io_specs = {k: P("dp") for k in tree}

        def make(codec, bucket_bytes, return_residual=False):
            def f(t):
                rows = {k: v[0] for k, v in t.items()}
                out = sync_grads(
                    rows, dev_specs, ("dp",), topos,
                    bucket_bytes=bucket_bytes, codec=codec, step=3,
                    return_residual=return_residual,
                )
                if return_residual:
                    out = {k: jnp.stack([out[0][k], out[1][k]]) for k in rows}
                    return {k: v[None] for k, v in out.items()}
                return {k: v[None] for k, v in out.items()}

            return jax.jit(
                jax.shard_map(
                    f, mesh=mesh, in_specs=(io_specs,), out_specs=io_specs,
                    check_vma=False,
                )
            )

        exact = jax.block_until_ready(make("f32", 0)(tree))
        for bucket_bytes in (0, None):  # per-leaf and bucketed lossy paths
            got = jax.block_until_ready(
                make("int8", bucket_bytes, return_residual=True)(tree)
            )
            for k in tree:
                amax = float(np.abs(np.asarray(tree[k])).max())
                bound = get_codec("int8").error_bound(amax, N, (N,)) + 1e-5
                err = np.abs(
                    np.asarray(got[k])[0, 0].astype(np.float64)
                    - np.asarray(exact[k])[0].astype(np.float64)
                ).max()
                assert err <= bound, (k, bucket_bytes, err, bound)
                # residuals returned and bounded by one quantization step
                res = np.asarray(got[k])[0, 1]
                assert np.abs(res).max() <= amax / 127.0 + 1e-6

    def test_codec_aware_bucket_sizing(self):
        """choose_bucket_bytes must see the codec: cheaper wire bytes
        shift the launch-vs-bytes argmin toward fewer, larger buckets."""
        from flextree_tpu.planner.choose import choose_bucket_bytes

        t = Topology(8, (4, 2))
        plain = choose_bucket_bytes(64 << 20, t, n_leaves=64)
        compressed = choose_bucket_bytes(64 << 20, t, n_leaves=64, codec="int8")
        assert compressed >= plain
