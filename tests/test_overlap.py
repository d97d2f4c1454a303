"""Readiness-ordered backward/comm overlap: planner boundaries, bitwise
identity, and the serialized-path guard.

The overlap tentpole (``parallel/overlap.py``) may relocate the gradient
sync's collectives — it may never change what they compute.  The
contract pinned here:

- **bitwise identity**: the overlapped step's updated parameters equal
  the serialized twin's (the same program behind a full-backward
  ``optimization_barrier``) bit-for-bit across topologies
  (flat/tree/ring/lonely) x codecs (f32/bf16/int8) x EF on/off x model
  families (dense/pipeline/MoE); for the identity codec they also equal
  the historical production path's (``overlap=False``) — lossy codecs
  quantize per bucket, so only the equal-boundary twin comparison is
  bitwise there (documented in docs/OVERLAP.md);
- **compiled-HLO equality for overlap=False**: turning the feature off
  compiles the exact historical program — the refactor cannot have
  touched the default path;
- **planner boundaries** (``planner.choose.choose_overlap_boundaries``):
  a valid consecutive partition, equalizing comm against the remaining
  hiding budget (no hideable compute -> one launch-amortized bucket;
  ample compute -> early firing), with the wire-serial schedule model
  (``predict_overlap_schedule``) matching a hand simulation;
- **plan-cache hygiene**: overlapped and serialized autotune plans never
  alias one cache entry.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from conftest import own_copy
from flextree_tpu.models.transformer import TransformerConfig
from flextree_tpu.parallel.overlap import (
    OverlapPlan,
    plan_overlap,
    readiness_segments,
)
from flextree_tpu.parallel.train import (
    TrainConfig,
    guarded_adamw,
    jit_step,
    init_train_state,
    make_mesh_nd,
    make_train_step,
    maybe_clip_grads,
    metric_specs,
    resolve_axis_topos,
    state_specs,
    sync_with_feedback,
)
from flextree_tpu.planner.choose import (
    choose_overlap_boundaries,
    overlap_comm_us,
    predict_overlap_schedule,
)
from flextree_tpu.planner.cost_model import LinkParams, TpuCostParams
from flextree_tpu.schedule.stages import Topology
from flextree_tpu.models.transformer import cross_entropy_loss, forward

MODEL = TransformerConfig(
    vocab_size=64, d_model=32, n_heads=4, n_layers=3, d_ff=64
)


def small_data(batch=4, seq=32, vocab=64, seed=0):
    rng = np.random.default_rng(seed)
    toks = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    tgts = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    return toks, tgts


def params_bitwise(a, b):
    return all(
        np.asarray(x).tobytes() == np.asarray(y).tobytes()
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


# ------------------------------------------------------------- planner


class TestChooseOverlapBoundaries:
    PARAMS = TpuCostParams(
        ici=LinkParams(bandwidth_GBps=1.0, latency_us=10.0),
        dcn=LinkParams(bandwidth_GBps=1.0, latency_us=10.0),
        reduce_bw_GBps=10.0, control_us_per_width=0.0, launch_us=20.0,
        bwd_GFLOPs=10.0,
    )
    TOPOS = [Topology.flat(4)]

    def test_partition_is_valid_and_consecutive(self):
        seg_bytes = [1 << 10, 1 << 20, 1 << 20, 1 << 18, 1 << 16]
        seg_us = [100.0, 900.0, 900.0, 400.0, 10.0]
        bounds = choose_overlap_boundaries(
            seg_bytes, seg_us, self.TOPOS, params=self.PARAMS
        )
        flat = [i for b in bounds for i in b]
        assert flat == list(range(len(seg_bytes)))
        for b in bounds:
            assert list(b) == list(range(b[0], b[-1] + 1))

    def test_single_segment(self):
        assert choose_overlap_boundaries(
            [1024], [10.0], self.TOPOS, params=self.PARAMS
        ) == ((0,),)

    def test_empty(self):
        assert choose_overlap_boundaries([], [], self.TOPOS) == ()

    def test_length_mismatch_raises(self):
        with pytest.raises(ValueError, match="segments"):
            choose_overlap_boundaries(
                [1, 2], [1.0], self.TOPOS, params=self.PARAMS
            )

    def test_no_hideable_compute_amortizes_launches(self):
        # zero compute everywhere: nothing can hide, so the argmin folds
        # every segment into ONE bucket — the pure launch-amortization
        # limit (this is exactly the pipeline step's post-scan regime)
        seg_bytes = [1 << 16] * 6
        seg_us = [0.0] * 6
        bounds = choose_overlap_boundaries(
            seg_bytes, seg_us, self.TOPOS, params=self.PARAMS
        )
        assert bounds == (tuple(range(6)),)

    def test_ample_compute_hides_all_but_the_tail(self):
        # compute dwarfs comm: the chooser must NOT serialize everything
        # into one end bucket — its exposure must beat full
        # serialization and be bounded by the tail bucket's own comm
        # (the structurally unhideable part)
        seg_bytes = [1 << 20] * 6
        seg_us = [50_000.0] * 6
        bounds = choose_overlap_boundaries(
            seg_bytes, seg_us, self.TOPOS, params=self.PARAMS
        )
        assert len(bounds) >= 2
        _, exposed = predict_overlap_schedule(
            bounds, seg_bytes, seg_us, self.TOPOS, params=self.PARAMS
        )
        _, exposed_serial = predict_overlap_schedule(
            (tuple(range(6)),), seg_bytes, seg_us, self.TOPOS,
            params=self.PARAMS,
        )
        assert exposed < exposed_serial
        tail_bytes = sum(seg_bytes[i] for i in bounds[-1])
        assert exposed <= overlap_comm_us(
            tail_bytes, self.TOPOS, self.PARAMS
        ) + 1e-6

    def test_schedule_model_matches_hand_simulation(self):
        seg_bytes = [1 << 18, 1 << 18, 1 << 18]
        seg_us = [1000.0, 1000.0, 1000.0]
        bounds = ((0,), (1, 2))
        c0 = overlap_comm_us(seg_bytes[0], self.TOPOS, self.PARAMS)
        c1 = overlap_comm_us(
            seg_bytes[1] + seg_bytes[2], self.TOPOS, self.PARAMS
        )
        # bucket 0 issues at 1000; bucket 1 at 3000 or when the wire
        # frees, whichever is later
        w0 = 1000.0 + c0
        start1 = max(3000.0, w0)
        total_hand = max(3000.0, start1 + c1)
        total, exposed = predict_overlap_schedule(
            bounds, seg_bytes, seg_us, self.TOPOS, params=self.PARAMS
        )
        assert total == pytest.approx(total_hand)
        assert exposed == pytest.approx(total_hand - 3000.0)

    def test_greedy_path_matches_amortization_limits(self):
        # > max_enum_segments routes through the greedy pass, which must
        # keep both exhaustive-path limits: zero hideable compute folds
        # everything into ONE bucket (not one exposed launch per tail
        # segment), and ample compute still fires early
        seg_bytes = [1 << 20] * 14
        assert choose_overlap_boundaries(
            seg_bytes, [0.0] * 14, self.TOPOS, params=self.PARAMS
        ) == (tuple(range(14)),)
        bounds = choose_overlap_boundaries(
            seg_bytes, [50_000.0] * 14, self.TOPOS, params=self.PARAMS
        )
        assert len(bounds) >= 2
        _, exposed = predict_overlap_schedule(
            bounds, seg_bytes, [50_000.0] * 14, self.TOPOS,
            params=self.PARAMS,
        )
        _, exposed_serial = predict_overlap_schedule(
            (tuple(range(14)),), seg_bytes, [50_000.0] * 14, self.TOPOS,
            params=self.PARAMS,
        )
        assert exposed < exposed_serial

    def test_last_bucket_always_exposed(self):
        # even infinite compute before it cannot hide the final bucket:
        # it issues when backward ends
        seg_bytes = [1 << 20, 1 << 20]
        seg_us = [1e9, 1.0]
        bounds = choose_overlap_boundaries(
            seg_bytes, seg_us, self.TOPOS, params=self.PARAMS
        )
        _, exposed = predict_overlap_schedule(
            bounds, seg_bytes, seg_us, self.TOPOS, params=self.PARAMS
        )
        last_bytes = sum(seg_bytes[i] for i in bounds[-1])
        assert exposed >= overlap_comm_us(
            last_bytes, self.TOPOS, self.PARAMS
        ) - 1e-6


class TestPlanOverlap:
    def test_readiness_order_and_partition(self):
        state = jax.eval_shape(
            lambda k: init_train_state(k, MODEL), jax.random.PRNGKey(0)
        )
        segs = readiness_segments(state["params"])
        labels = [s[0] for s in segs]
        assert labels[0] == "head"
        assert labels[-1] == "embed"
        assert labels[1:-1] == [f"layer{i}" for i in reversed(range(3))]

        plan = plan_overlap(
            state["params"], state_specs(MODEL, "tp")["params"],
            ("dp", "sp", "tp"),
            {"dp": Topology.flat(8), "sp": None, "tp": None},
            {"dp": 8, "sp": 1, "tp": 1},
            n_tokens=128, t_local=32, d_model=MODEL.d_model,
        )
        assert isinstance(plan, OverlapPlan)
        assert [i for b in plan.boundaries for i in b] == list(
            range(len(plan.labels))
        )
        assert sum(plan.seg_bytes) == sum(
            l.size * 4 for l in jax.tree.leaves(state["params"])
        )

    def test_single_device_mesh_degenerates(self):
        state = jax.eval_shape(
            lambda k: init_train_state(k, MODEL), jax.random.PRNGKey(0)
        )
        plan = plan_overlap(
            state["params"], state_specs(MODEL, "tp")["params"],
            ("dp", "sp", "tp"), {"dp": None, "sp": None, "tp": None},
            {"dp": 1, "sp": 1, "tp": 1},
            n_tokens=128, t_local=32, d_model=MODEL.d_model,
        )
        assert plan.n_buckets == 1
        assert plan.predicted_exposed_us == 0.0


# ------------------------------------------------- bitwise identity


def run_steps(mesh_shape, train_cfg, model=MODEL):
    """(production, overlapped, twin) final states on one data batch."""
    mesh = make_mesh_nd(
        int(np.prod(mesh_shape)), mesh_shape, ("dp", "sp", "tp")
    )
    toks, tgts = small_data(batch=mesh_shape[0])  # one row per dp rank
    state = init_train_state(jax.random.PRNGKey(0), model, train_cfg)
    cfg_ovl = TrainConfig(
        **{**train_cfg.__dict__, "overlap": True}
    )
    out = {}
    out["prod"], _ = make_train_step(mesh, model, train_cfg)(
        own_copy(state), toks, tgts
    )
    out["ovl"], _ = make_train_step(mesh, model, cfg_ovl)(
        own_copy(state), toks, tgts
    )
    out["twin"], _ = make_train_step(
        mesh, model, cfg_ovl, serialize_overlap=True
    )(state, toks, tgts)
    return jax.block_until_ready(out)


class TestBitwiseIdentityDense:
    @pytest.mark.parametrize(
        "mesh_shape,topo",
        [
            ((2, 2, 2), None),  # flat trees on every axis
            ((8, 1, 1), "4,2"),  # hierarchical tree
            ((8, 1, 1), "1"),  # ring
        ],
    )
    def test_f32_overlap_equals_production_and_twin(self, mesh_shape, topo):
        out = run_steps(mesh_shape, TrainConfig(grad_topo=topo))
        assert params_bitwise(out["ovl"]["params"], out["twin"]["params"])
        assert params_bitwise(out["ovl"]["params"], out["prod"]["params"])

    def test_f32_lonely_topology(self):
        # 7 devices: the planner's executable prime-N escape ("3,2+1")
        out = run_steps((7, 1, 1), TrainConfig(grad_topo="3,2+1"))
        assert params_bitwise(out["ovl"]["params"], out["twin"]["params"])
        assert params_bitwise(out["ovl"]["params"], out["prod"]["params"])

    @pytest.mark.parametrize("codec", ["bf16", "int8"])
    def test_lossy_codec_overlap_equals_twin_with_ef(self, codec):
        # lossy codecs quantize per bucket, so production (different
        # boundaries) is only bounded-close; the equal-boundary twin must
        # be BITWISE — including the carried error-feedback residual
        out = run_steps((2, 2, 2), TrainConfig(codec=codec))
        assert params_bitwise(out["ovl"]["params"], out["twin"]["params"])
        assert params_bitwise(out["ovl"]["ef"], out["twin"]["ef"])
        # and the EF state actually carries mass (the codec really ran)
        assert any(
            float(jnp.abs(l).max()) > 0
            for l in jax.tree.leaves(out["ovl"]["ef"])
        )

    def test_f32_with_clipping_and_chunks(self):
        out = run_steps(
            (2, 2, 2),
            TrainConfig(grad_clip_norm=0.5, grad_chunks=2),
        )
        assert params_bitwise(out["ovl"]["params"], out["twin"]["params"])
        assert params_bitwise(out["ovl"]["params"], out["prod"]["params"])


class TestBitwiseIdentityFamilies:
    def test_pipeline(self):
        from flextree_tpu.parallel.pipeline import (
            init_pipeline_train_state,
            make_mesh_4d,
            make_pipeline_train_step,
        )

        cfg = TransformerConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
        )
        mesh = make_mesh_4d(8, (1, 2, 2, 2))
        toks, tgts = small_data()
        for codec in ("f32", "int8"):
            tc = TrainConfig(codec=codec)
            tc_ovl = TrainConfig(codec=codec, overlap=True)
            state = init_pipeline_train_state(jax.random.PRNGKey(0), cfg, tc)
            prod, _ = make_pipeline_train_step(mesh, cfg, tc)(
                own_copy(state), toks, tgts
            )
            ovl, _ = make_pipeline_train_step(mesh, cfg, tc_ovl)(
                own_copy(state), toks, tgts
            )
            twin, _ = make_pipeline_train_step(
                mesh, cfg, tc_ovl, serialize_overlap=True
            )(state, toks, tgts)
            jax.block_until_ready((prod, ovl, twin))
            assert params_bitwise(ovl["params"], twin["params"])
            if codec == "f32":
                assert params_bitwise(ovl["params"], prod["params"])

    def test_moe(self):
        from flextree_tpu.models.moe import MoEConfig
        from flextree_tpu.parallel.moe_train import (
            init_moe_train_state,
            make_mesh_moe,
            make_moe_train_step,
        )

        cfg = MoEConfig(
            vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            n_experts=4, top_k=1, moe_every=2,
        )
        mesh = make_mesh_moe(8, (1, 2, 2, 2))
        toks, tgts = small_data()
        for codec in ("f32", "int8"):
            tc = TrainConfig(codec=codec)
            tc_ovl = TrainConfig(codec=codec, overlap=True)
            state = init_moe_train_state(jax.random.PRNGKey(0), cfg, tc)
            prod, m_prod = make_moe_train_step(mesh, cfg, tc)(
                own_copy(state), toks, tgts
            )
            ovl, m_ovl = make_moe_train_step(mesh, cfg, tc_ovl)(
                own_copy(state), toks, tgts
            )
            twin, _ = make_moe_train_step(
                mesh, cfg, tc_ovl, serialize_overlap=True
            )(state, toks, tgts)
            jax.block_until_ready((prod, ovl, twin))
            assert params_bitwise(ovl["params"], twin["params"])
            if codec == "f32":
                assert params_bitwise(ovl["params"], prod["params"])
                # the segmented aux accounting reproduces the metrics too
                for key in ("loss", "aux", "total"):
                    assert np.asarray(m_prod[key]).tobytes() == np.asarray(
                        m_ovl[key]
                    ).tobytes()


# --------------------------------------- the serialized-path guard


STRIP = re.compile(r'(metadata=\{[^}]*\}|op_name="[^"]*"|loc\([^)]*\))')


def test_overlap_false_compiles_the_historical_program():
    """``overlap=False`` must be byte-for-byte the historical step: the
    same program as a replica of the pre-overlap device_step built from
    the public train.py pieces (value_and_grad + sync_with_feedback +
    the guarded adamw, the state donated).  If this fails, the refactor
    changed the default path."""
    mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
    train_cfg = TrainConfig(overlap=False)
    sspecs = state_specs(MODEL, "tp", train_cfg)
    data_spec = P("dp", "sp")

    def device_step(state, tokens, targets):
        n_total_tokens = (
            tokens.size
            * lax.axis_size("dp")
            * lax.axis_size("sp")
            * lax.axis_size("tp")
        )

        def local_loss(params):
            logits = forward(
                params, tokens, MODEL, tp_axis="tp", sp_axis="sp"
            )
            loss_sum, _ = cross_entropy_loss(logits, targets)
            return loss_sum / n_total_tokens

        loss, grads = jax.value_and_grad(local_loss)(state["params"])
        topos = resolve_axis_topos(
            mesh, ("dp", "sp", "tp"), train_cfg.grad_topo
        )
        grads, new_ef = sync_with_feedback(
            state, grads, sspecs["params"], ("dp", "sp", "tp"), topos,
            train_cfg,
        )
        global_loss = lax.psum(
            lax.psum(lax.psum(loss, "dp"), "sp"), "tp"
        )
        metrics = {"loss": global_loss}
        grads = maybe_clip_grads(grads, sspecs["params"], train_cfg, metrics)
        new_state = guarded_adamw(state, grads, new_ef, train_cfg, metrics)
        return new_state, metrics

    replica = jit_step(
        jax.shard_map(
            device_step, mesh=mesh, in_specs=(sspecs, data_spec, data_spec),
            out_specs=(sspecs, metric_specs(train_cfg, {"loss": P()})),
            check_vma=False,
        ),
        mesh, sspecs,
    )
    production = make_train_step(mesh, MODEL, train_cfg)

    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, MODEL, train_cfg),
        jax.random.PRNGKey(0),
    )
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    from conftest import strip_hlo_debug

    a = strip_hlo_debug(production.lower(state_sds, tok, tok).compile().as_text())
    b = strip_hlo_debug(replica.lower(state_sds, tok, tok).compile().as_text())
    assert a == b


def test_overlapped_program_differs_and_has_no_barrier():
    """Sanity inverse of the guard: overlap=True produces a different
    program, and only the serialized twin carries the barrier."""
    mesh = make_mesh_nd(8, (8, 1, 1), ("dp", "sp", "tp"))
    tc = TrainConfig(overlap=True)
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, MODEL, tc), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    ovl = make_train_step(mesh, MODEL, tc).lower(
        state_sds, tok, tok
    ).as_text()
    twin = make_train_step(mesh, MODEL, tc, serialize_overlap=True).lower(
        state_sds, tok, tok
    ).as_text()
    plain = make_train_step(mesh, MODEL, TrainConfig()).lower(
        state_sds, tok, tok
    ).as_text()
    assert "optimization_barrier" not in ovl
    assert "optimization_barrier" in twin
    assert STRIP.sub("", ovl) != STRIP.sub("", plain)


def test_span_ledger_records_overlap_buckets():
    from flextree_tpu.utils.profiling import span_ledger

    mesh = make_mesh_nd(8, (8, 1, 1), ("dp", "sp", "tp"))
    tc = TrainConfig(overlap=True)
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, MODEL, tc), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    with span_ledger() as ledger:
        make_train_step(mesh, MODEL, tc).lower(state_sds, tok, tok)
    fired = [n for n in ledger.names if n.startswith("ft_overlap_bucket")]
    assert fired, "no overlap buckets recorded at trace time"
    # every fired-bucket span carries its payload bytes, and together
    # they account every synced gradient byte exactly once
    total = ledger.total_bytes("ft_overlap_bucket")
    expect = sum(
        l.size * 4 for l in jax.tree.leaves(state_sds["params"])
    )
    assert total == expect


def test_autotune_cache_never_aliases_overlap_and_serial(tmp_path):
    from flextree_tpu.planner.autotune import autotune_plan

    cache = str(tmp_path / "plans.json")
    calls = []

    def timer(cands, n, nbytes, dtype, repeat):
        calls.append(len(cands))
        return [0.001 * (i + 1) for i in range(len(cands))]

    a = autotune_plan(
        8, 1 << 16, codecs=("f32",), top_k=2, cache_path=cache, timer=timer,
        overlap=False,
    )
    # same everything except overlap: MUST measure again, not cache-hit
    b = autotune_plan(
        8, 1 << 16, codecs=("f32",), top_k=2, cache_path=cache, timer=timer,
        overlap=True,
    )
    assert len(calls) == 2
    assert a.source == "measured" and b.source == "measured"
    # and each key replays from cache independently
    a2 = autotune_plan(
        8, 1 << 16, codecs=("f32",), top_k=2, cache_path=cache, timer=timer,
        overlap=False,
    )
    b2 = autotune_plan(
        8, 1 << 16, codecs=("f32",), top_k=2, cache_path=cache, timer=timer,
        overlap=True,
    )
    assert len(calls) == 2
    assert a2.source == "cache" and b2.source == "cache"
