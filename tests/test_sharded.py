"""ZeRO-1 sharded-optimizer path (PR 7): the split-collective seam and
the sharded train step.

Contracts pinned here:

1. **Shard layout** (``schedule.blocks.owned_block``): a permutation of
   ``range(N)`` for tree/ring shapes (buddy-mirrored for lonely), and the
   block the real ``reduce_scatter`` actually leaves on each rank.
2. **The seam**: ``all_gather(reduce_scatter(x)) == allreduce(x)``
   BITWISE for the identity codec across flat/tree/ring/lonely and
   non-divisible counts; within the documented codec bound for bf16/int8
   with bit-identical replicas.
3. **The sharded step**: loss + updated params bitwise-equal to the
   replicated step for f32 across dense/pipeline/MoE (and composed with
   the readiness-ordered overlap), with per-rank moment shards that
   consolidate back to exactly the replicated moments.
4. **Error feedback on the sharded wire**: the running mean of a
   repeated-constant-gradient reduce-scatter∘all-gather round converges
   to exact, same as the fused compressed path.
5. **Plan-cache hygiene**: sharded and replicated autotune plans never
   alias (the cache key grows a sharding component).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from flextree_tpu.ops.quantize import get_codec
from flextree_tpu.parallel.allreduce import all_gather, allreduce, reduce_scatter
from flextree_tpu.parallel.mesh import flat_mesh
from flextree_tpu.schedule.blocks import owned_block, shard_layout
from flextree_tpu.schedule.stages import Topology

N = 8

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)

TOPOS = ["8", "4,2", "2,2,2", "1"]
LONELY = ["3,2+1", "6+1"]


def _run(fn, x, n=N):
    mesh = flat_mesh(n, "ft")
    return np.asarray(
        jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"),
                check_vma=False,
            )
        )(x)
    )


def _leaves_bytes(tree):
    return b"".join(np.asarray(l).tobytes() for l in jax.tree.leaves(tree))


# ------------------------------------------------------------ shard layout


class TestShardLayout:
    @pytest.mark.parametrize("spec", TOPOS + ["2,4"])
    def test_partition(self, spec):
        lay = shard_layout(Topology.resolve(N, spec))
        assert sorted(lay) == list(range(N))

    def test_lonely_mirror(self):
        lay = shard_layout(Topology.resolve(7, "3,2+1"))
        assert sorted(lay[:6]) == list(range(6))  # tree ranks partition
        assert lay[6] == lay[0]  # lonely rank mirrors buddy 0

    @pytest.mark.parametrize("spec", TOPOS + ["2,4"])
    def test_matches_real_reduce_scatter(self, spec):
        """The contract is about the REAL collective: rank r's
        reduce_scatter output is block ``owned_block(topo, r)`` of the
        exact sum."""
        rng = np.random.default_rng(1)
        data = rng.standard_normal((N, N * 6)).astype(np.float32)
        out = _run(lambda r: reduce_scatter(r[0], "ft", topo=spec)[None],
                   jnp.asarray(data))
        blocks = data.sum(0).reshape(N, 6)
        topo = Topology.resolve(N, spec)
        for r in range(N):
            np.testing.assert_allclose(
                out[r], blocks[owned_block(topo, r)], rtol=1e-5, atol=1e-5
            )


# ------------------------------------------------------------------ seam


class TestSeam:
    @pytest.mark.parametrize("spec", TOPOS)
    @pytest.mark.parametrize("count", [64, 35, 5])
    def test_bitwise_identity_codec(self, spec, count):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((N, count)).astype(np.float32))
        ar = _run(lambda r: allreduce(r[0], "ft", topo=spec)[None], x)
        seam = _run(
            lambda r: all_gather(
                reduce_scatter(r[0], "ft", topo=spec), "ft", topo=spec,
                out_shape=r[0].shape,
            )[None],
            x,
        )
        assert ar.tobytes() == seam.tobytes()

    @pytest.mark.parametrize("spec", LONELY)
    @pytest.mark.parametrize("count", [66, 35])
    def test_bitwise_lonely(self, spec, count):
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.standard_normal((7, count)).astype(np.float32))
        ar = _run(lambda r: allreduce(r[0], "ft", topo=spec)[None], x, n=7)
        seam = _run(
            lambda r: all_gather(
                reduce_scatter(r[0], "ft", topo=spec), "ft", topo=spec,
                out_shape=r[0].shape,
            )[None],
            x, n=7,
        )
        assert ar.tobytes() == seam.tobytes()

    @pytest.mark.parametrize("codec", ["bf16", "int8"])
    @pytest.mark.parametrize("spec", TOPOS + LONELY)
    def test_lossy_bounded_and_replica_consistent(self, codec, spec):
        n = 7 if "+" in spec else N
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((n, 2048)).astype(np.float32) * 2)
        out = _run(
            lambda r: all_gather(
                reduce_scatter(r[0], "ft", topo=spec, codec=codec, step=3),
                "ft", topo=spec, out_shape=r[0].shape, codec=codec, step=3,
            )[None],
            x, n=n,
        )
        exact = np.asarray(x).astype(np.float64).sum(axis=0)
        if "+" in spec:
            widths = Topology.resolve(n, spec).tree.widths
            lonely = 1
        else:
            widths = Topology.resolve(n, spec).widths
            lonely = 0
        # the split round quantizes both wires plus the lonely ship hop:
        # one allreduce bound plus two extra single-encode events covers it
        amax = float(np.abs(np.asarray(x)).max())
        step = 1.0 / 127.0 if codec == "int8" else 2.0 ** -8
        bound = get_codec(codec).error_bound(amax, n, widths, lonely)
        bound += 2 * n * amax * step
        err = np.abs(out[0].astype(np.float64) - exact).max()
        assert err <= bound + 1e-5, f"{codec}/{spec}: {err} > {bound}"
        for r in range(1, n):
            assert out[r].tobytes() == out[0].tobytes()

    def test_all_gather_rejects_bad_shard(self):
        x = jnp.zeros((N, 10), jnp.float32)
        with pytest.raises(ValueError, match="does not match"):
            _run(
                lambda r: all_gather(
                    r[0], "ft", topo="8", out_shape=(999,)
                )[None],
                x,
            )


# ----------------------------------------------------------- sharded step


def _dense_cfg():
    from flextree_tpu.models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )


class TestShardedStep:
    @pytest.mark.parametrize("topo", [None, "2,2,2", {"dp": "1"}])
    def test_dense_bitwise_vs_replicated(self, topo):
        from flextree_tpu.parallel.train import (
            TrainConfig,
            init_train_state,
            make_mesh_nd,
            make_train_step,
        )

        mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        outs = {}
        for name, tc in (
            ("rep", TrainConfig(grad_topo=topo)),
            ("sh", TrainConfig(grad_topo=topo, shard_optimizer=True)),
        ):
            st = init_train_state(jax.random.PRNGKey(0), _dense_cfg(), tc, mesh=mesh)
            step = make_train_step(mesh, _dense_cfg(), tc)
            for _ in range(3):
                st, m = step(st, tok, tok)
            outs[name] = (st, float(m["loss"]))
        assert outs["rep"][1] == outs["sh"][1]
        assert _leaves_bytes(outs["rep"][0]["params"]) == _leaves_bytes(
            outs["sh"][0]["params"]
        )

    def test_dense_overlap_composition_bitwise(self):
        from flextree_tpu.parallel.train import (
            TrainConfig,
            init_train_state,
            make_mesh_nd,
            make_train_step,
        )

        mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        outs = {}
        for name, kw in (
            ("rep", dict()),
            ("sh_ovl", dict(shard_optimizer=True, overlap=True)),
        ):
            tc = TrainConfig(**kw)
            st = init_train_state(jax.random.PRNGKey(0), _dense_cfg(), tc, mesh=mesh)
            step = make_train_step(mesh, _dense_cfg(), tc)
            for _ in range(2):
                st, _ = step(st, tok, tok)
            outs[name] = st
        assert _leaves_bytes(outs["rep"]["params"]) == _leaves_bytes(
            outs["sh_ovl"]["params"]
        )

    def test_pipeline_bitwise_vs_replicated(self):
        from flextree_tpu.parallel.pipeline import (
            init_pipeline_train_state,
            make_mesh_4d,
            make_pipeline_train_step,
        )
        from flextree_tpu.parallel.train import TrainConfig

        mesh = make_mesh_4d(8, (1, 2, 2, 2))
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
        outs = {}
        for name, tc in (
            ("rep", TrainConfig()),
            ("sh", TrainConfig(shard_optimizer=True)),
        ):
            st = init_pipeline_train_state(
                jax.random.PRNGKey(0), _dense_cfg(), tc, mesh=mesh
            )
            step = make_pipeline_train_step(mesh, _dense_cfg(), tc, n_microbatches=2)
            for _ in range(2):
                st, m = step(st, tok, tok)
            outs[name] = (st, float(m["loss"]))
        assert outs["rep"][1] == outs["sh"][1]
        assert _leaves_bytes(outs["rep"][0]["params"]) == _leaves_bytes(
            outs["sh"][0]["params"]
        )

    def test_moe_bitwise_vs_replicated(self):
        from flextree_tpu.models.moe import MoEConfig
        from flextree_tpu.parallel.moe_train import (
            init_moe_train_state,
            make_mesh_moe,
            make_moe_train_step,
        )
        from flextree_tpu.parallel.train import TrainConfig

        cfg = MoEConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            n_experts=4, top_k=1, moe_every=2,
        )
        mesh = make_mesh_moe(8, (1, 2, 2, 2))
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 64)
        outs = {}
        for name, tc in (
            ("rep", TrainConfig()),
            ("sh", TrainConfig(shard_optimizer=True)),
        ):
            st = init_moe_train_state(jax.random.PRNGKey(0), cfg, tc, mesh=mesh)
            step = make_moe_train_step(mesh, cfg, tc)
            for _ in range(2):
                st, m = step(st, tok, tok)
            outs[name] = (st, float(m["loss"]))
        assert outs["rep"][1] == outs["sh"][1]
        assert _leaves_bytes(outs["rep"][0]["params"]) == _leaves_bytes(
            outs["sh"][0]["params"]
        )

    def test_moments_consolidate_to_replicated(self):
        """Per-rank moment shards reassemble to EXACTLY the replicated
        path's mu/nu — the strongest form of "the optimizer state is the
        same state, just not duplicated"."""
        from flextree_tpu.models.transformer import init_params, param_specs
        from flextree_tpu.parallel.train import (
            TrainConfig,
            init_train_state,
            make_mesh_nd,
            make_train_step,
            zero_layout_for,
        )
        from flextree_tpu.parallel.zero import make_consolidate_fn, make_reshard_fn

        mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        states = {}
        for name, tc in (
            ("rep", TrainConfig()),
            ("sh", TrainConfig(shard_optimizer=True)),
        ):
            st = init_train_state(jax.random.PRNGKey(0), _dense_cfg(), tc, mesh=mesh)
            step = make_train_step(mesh, _dense_cfg(), tc)
            for _ in range(2):
                st, _ = step(st, tok, tok)
            states[name] = st
        pspecs = param_specs(_dense_cfg(), "tp")
        shapes = jax.eval_shape(
            lambda k: init_params(k, _dense_cfg()), jax.random.PRNGKey(0)
        )
        layout = zero_layout_for(mesh, shapes, pspecs, ("dp", "sp", "tp"))
        cons = make_consolidate_fn(mesh, pspecs, layout, None, False)(states["sh"])
        assert _leaves_bytes(cons["mu"]) == _leaves_bytes(states["rep"]["mu"])
        assert _leaves_bytes(cons["nu"]) == _leaves_bytes(states["rep"]["nu"])
        # and is not duplicated: a rank holds ~1/dp of the moment bytes the
        # replicated layout holds (tails stay replicated, so a hair above)
        def rank_bytes(state):
            return sum(
                leaf.addressable_shards[0].data.nbytes
                for key, sub in state.items() if key.startswith(("mu", "nu"))
                for leaf in jax.tree.leaves(sub)
            )

        ratio = rank_bytes(states["sh"]) / rank_bytes(states["rep"])
        dp = mesh.shape["dp"]
        assert 1.0 / dp - 0.02 <= ratio <= 1.0 / dp + 0.10
        # reshard is the exact inverse: consolidate ∘ reshard is a fixed point
        resh = make_reshard_fn(mesh, pspecs, layout, None, False)(cons)
        cons2 = make_consolidate_fn(mesh, pspecs, layout, None, False)(resh)
        assert _leaves_bytes(cons2) == _leaves_bytes(cons)

    def test_lossy_codec_trains_with_master_and_ef(self):
        from flextree_tpu.parallel.train import (
            TrainConfig,
            init_train_state,
            make_mesh_nd,
            make_train_step,
        )

        mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        tc = TrainConfig(shard_optimizer=True, codec="int8")
        st = init_train_state(jax.random.PRNGKey(0), _dense_cfg(), tc, mesh=mesh)
        assert "master_shard" in st and "ef" in st
        step = make_train_step(mesh, _dense_cfg(), tc)
        losses = []
        for _ in range(3):
            st, m = jax.block_until_ready(step(st, tok, tok))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert all(
            np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(st["params"])
        )
        # the EF residual is live and the master shard is populated
        assert any(np.asarray(l).any() for l in jax.tree.leaves(st["ef"]))
        assert any(
            np.asarray(l).any() for l in jax.tree.leaves(st["master_shard"])
        )

    def test_clipping_close_to_replicated(self):
        from flextree_tpu.parallel.train import (
            TrainConfig,
            init_train_state,
            make_mesh_nd,
            make_train_step,
        )

        mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
        tok = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 64)
        norms = {}
        for name, tc in (
            ("rep", TrainConfig(grad_clip_norm=0.5)),
            ("sh", TrainConfig(grad_clip_norm=0.5, shard_optimizer=True)),
        ):
            st = init_train_state(jax.random.PRNGKey(0), _dense_cfg(), tc, mesh=mesh)
            step = make_train_step(mesh, _dense_cfg(), tc)
            st, m = step(st, tok, tok)
            norms[name] = float(m["grad_norm"])
        # same norm up to summation order (bitwise holds only with clip off)
        assert norms["sh"] == pytest.approx(norms["rep"], rel=1e-5)


# -------------------------------------------------------- EF on the seam


class TestShardedErrorFeedback:
    def test_constant_gradient_running_mean_converges(self):
        """EF on the SPLIT wire: sync ``g + e`` via reduce_scatter (int8,
        wire-exact residual) + all_gather (int8), carry ``e``; the
        running mean of the gathered result converges toward the exact
        ``N * g`` — the same telescoping contract as the fused path."""
        rng = np.random.default_rng(3)
        g = rng.standard_normal(2048).astype(np.float32)
        exact = N * g.astype(np.float64)

        def f(v, s):
            shard, res = reduce_scatter(
                v[0], "ft", topo="8", codec="int8", step=s,
                return_residual=True,
            )
            out = all_gather(
                shard, "ft", topo="8", out_shape=v[0].shape,
                codec="int8", step=s,
            )
            return jnp.stack([out, res])[None]

        mesh = flat_mesh(N, "ft")
        jf = jax.jit(
            jax.shard_map(
                f, mesh=mesh, in_specs=(P("ft"), P()), out_specs=P("ft"),
                check_vma=False,
            )
        )
        e = np.zeros_like(g)
        acc = np.zeros_like(exact)
        errs = {}
        for step in range(1, 25):
            x = jnp.asarray(np.tile(g + e, (N, 1)))
            out = np.asarray(jf(x, jnp.int32(step)))
            acc += out[0, 0].astype(np.float64)
            e = out[0, 1]
            errs[step] = np.abs(acc / step - exact).max()
        assert errs[24] < errs[1] / 4  # the running mean shrinks
        assert np.abs(e).max() <= float(np.abs(g + e).max()) / 127.0 + 1e-6


# ----------------------------------------------------------- plan cache


class TestAutotuneNoAlias:
    def test_sharded_and_replicated_plans_never_alias(self, tmp_path):
        from flextree_tpu.planner.autotune import autotune_plan

        cache = str(tmp_path / "plans.json")
        calls = []

        def timer(cands, n, nbytes, dtype, repeat):
            calls.append(len(cands))
            return [1.0 + i for i in range(len(cands))]

        a = autotune_plan(
            8, 1 << 16, top_k=2, timer=timer, cache_path=cache, sharded=False
        )
        b = autotune_plan(
            8, 1 << 16, top_k=2, timer=timer, cache_path=cache, sharded=True
        )
        # the second call must MISS (different key component) and re-measure
        assert len(calls) == 2
        assert a.source == "measured" and b.source == "measured"
        # and each replays from its own entry afterwards
        a2 = autotune_plan(
            8, 1 << 16, top_k=2, timer=timer, cache_path=cache, sharded=False
        )
        b2 = autotune_plan(
            8, 1 << 16, top_k=2, timer=timer, cache_path=cache, sharded=True
        )
        assert len(calls) == 2  # pure cache hits
        assert a2.source == "cache" and b2.source == "cache"
        assert (a2.widths, a2.codec) == (a.widths, a.codec)
        assert (b2.widths, b2.codec) == (b.widths, b.codec)


# ------------------------------------------------ elastic re-shard (fit)


class TestLiveReshard:
    def test_shrink_without_checkpoint_reshards_live_state(self):
        """A peer dies before any checkpoint exists: the survivors must
        convert the LIVE old-world sharded state through the consolidated
        layout (old world packs, new world re-shards) instead of handing
        old-world shard shapes to the new step."""
        import dataclasses

        from flextree_tpu.models.transformer import init_params, param_specs
        from flextree_tpu.parallel.loop import FitConfig, Supervision, fit
        from flextree_tpu.parallel.train import (
            TrainConfig,
            init_train_state,
            make_mesh_nd,
            make_state_specs,
            make_train_step,
            zero_layout_for,
        )
        from flextree_tpu.parallel.zero import (
            make_consolidate_fn,
            make_reshard_fn,
        )

        cfg = _dense_cfg()
        tc = TrainConfig(shard_optimizer=True)
        axes = ("dp", "sp", "tp")
        pspecs = param_specs(cfg, "tp")
        shapes = jax.eval_shape(
            lambda k: init_params(k, cfg), jax.random.PRNGKey(0)
        )
        packed_specs = make_state_specs(
            pspecs, dataclasses.replace(tc, shard_optimizer=False)
        )

        def build_world(ndev, grad_topo=None):
            tc2 = dataclasses.replace(tc, grad_topo=grad_topo)
            mesh = make_mesh_nd(ndev, (ndev, 1, 1), axes)
            step = make_train_step(mesh, cfg, tc2)
            layout = zero_layout_for(mesh, shapes, pspecs, axes)
            pack = make_consolidate_fn(mesh, pspecs, layout, grad_topo, False)
            unpack = make_reshard_fn(mesh, pspecs, layout, grad_topo, False)
            return mesh, step, pack, unpack

        mesh, step_fn, pack, unpack = build_world(4)

        class _Data:
            def batch_at(self, step):
                tok = (np.arange(4 * 16, dtype=np.int32).reshape(4, 16) + step) % 64
                return tok, tok

        polls = {"n": 0}

        def membership():
            polls["n"] += 1
            dead = "dead" if polls["n"] > 2 else "healthy"
            return {0: "healthy", 1: "healthy", 2: dead}

        def on_shrink(n_alive, plan):
            mesh2, step2, pack2, unpack2 = build_world(
                n_alive, grad_topo=plan.to_ft_topo()
            )
            return step2, mesh2, packed_specs, pack2, unpack2

        state = init_train_state(jax.random.PRNGKey(0), cfg, tc, mesh=mesh)
        result = fit(
            state, step_fn, _Data(),
            FitConfig(num_steps=5, ckpt_dir=None, log_every=0, prefetch=0),
            mesh=mesh, state_specs=packed_specs,
            supervision=Supervision(
                membership=membership, configured_world=3, on_shrink=on_shrink
            ),
            state_pack=pack, state_unpack=unpack,
        )
        assert result.steps_run == 5
        assert len(result.report.membership_epochs) == 2
        assert result.report.membership_epochs[1]["alive"] == 2
        # the live state was re-carved for the 2-wide world: every shard
        # buffer's global length is now head (n=2 blocks), and finite
        for l in jax.tree.leaves(result.state["mu_shard"]):
            assert np.isfinite(np.asarray(l)).all()
        assert all(
            np.isfinite(np.asarray(l)).all()
            for l in jax.tree.leaves(result.state["params"])
        )


# -------------------------------------------------- split-phase verifier


class TestSplitScheduleVerifier:
    def test_clean_matrix_is_green(self):
        from flextree_tpu.analysis.schedule_check import check_split_schedules

        vs, programs = check_split_schedules()
        assert programs >= 16 and not vs

    def test_tampered_rs_ownership_caught(self):
        from flextree_tpu.analysis.schedule_check import (
            SEND,
            Half,
            build_phase_program,
            check_phase_program,
        )

        topo = Topology(8, (4, 2))
        prog = build_phase_program(topo, "rs", count=64)
        ps = [p for p in prog.posts[0] if p.stage == 1][0]
        for i, h in enumerate(ps.halves):
            if h.kind == SEND:
                ps.halves[i] = Half(SEND, h.peer, ())
                break
        vs = check_phase_program(prog, topo)
        assert any(
            v.kind in ("shard-ownership", "dropped-block", "asymmetric-match")
            for v in vs
        )

    def test_tampered_ag_closure_caught(self):
        from flextree_tpu.analysis.schedule_check import (
            RECV,
            Half,
            build_phase_program,
            check_phase_program,
        )

        topo = Topology(8, (2, 2, 2))
        prog = build_phase_program(topo, "ag", count=64)
        # drop one recv half's blocks: the closure must notice the gap
        for ps in prog.posts[3]:
            for i, h in enumerate(ps.halves):
                if h.kind == RECV:
                    ps.halves[i] = Half(RECV, h.peer, ())
                    break
            break
        vs = check_phase_program(prog, topo)
        assert any(
            v.kind in ("dropped-block", "asymmetric-match") for v in vs
        )


# -------------------------------------------------------- wire accounting


class TestWireBytes:
    def test_sharded_f32_is_exactly_replicated_wire(self):
        from flextree_tpu.analysis.hlo_lint import (
            _lower_sharded_train_step,
            collective_wire_bytes,
        )

        rep = collective_wire_bytes(_lower_sharded_train_step(regather=True))
        sh = collective_wire_bytes(_lower_sharded_train_step())
        assert sh["total"] == pytest.approx(rep["total"])

    def test_sharded_int8_below_ratio_floor(self):
        from flextree_tpu.analysis.hlo_lint import (
            _lower_sharded_train_step,
            collective_wire_bytes,
        )

        rep = collective_wire_bytes(_lower_sharded_train_step(regather=True))
        sh8 = collective_wire_bytes(_lower_sharded_train_step(codec="int8"))
        assert sh8["total"] / rep["total"] <= 0.6
