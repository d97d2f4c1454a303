"""Golden suite for the schedule IR (ISSUE 8).

Four contracts, in order of importance:

1. **Bitwise identity** — the IR-compiled tree / true-ring / lonely
   collectives are bit-for-bit the legacy executors, value AND compiled
   HLO, across topologies x dtypes x tails x chunks.  (``allreduce``
   routes through ``compile_ir`` below ``FT_IR_ROUTE_MAX``, so this is
   the production path, not a twin.)
2. **New families are correct** — Swing (arXiv:2401.09356) and the
   generalized construction (arXiv:2004.09362) compute exact allreduce
   results on real multi-device meshes at N in {4, 6, 8} (integer-valued
   payloads make float sums associativity-independent), and their
   model-check matrices are clean up to N=16, non-power-of-two Swing
   included.
3. **Verified before compiled** — ``compile_ir`` REFUSES a program with
   seeded violations (corrupted peers, truncated block-maps) and a
   program whose stage list diverged from its family's canonical
   emission.
4. **One source of truth** — the plan views (``send_plan``/``recv_plan``),
   the checker's expansion and the IR emitter agree block-for-block, and
   the ``ir_equivalence`` pass holds the lowered StableHLO to the IR
   stage list (the seeded divergence is caught).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import strip_hlo_debug
from jax.sharding import PartitionSpec as P

from flextree_tpu.analysis.schedule_check import (
    check_ir,
    check_ir_families,
    default_ir_matrix,
    program_from_ir,
)
from flextree_tpu.parallel.allreduce import (
    allreduce,
    lonely_allreduce,
    ring_allreduce,
    tree_allreduce,
)
from flextree_tpu.parallel.mesh import flat_mesh
from flextree_tpu.schedule import ir as sir
from flextree_tpu.schedule.ir import (
    IRFamilySpec,
    IRViolationError,
    compile_ir,
    emit_ir,
    generalized_ir,
    resolve_collective,
    ring_ir,
    swing_ir,
    tree_ir,
)
from flextree_tpu.schedule.plan import recv_plan, send_plan
from flextree_tpu.schedule.stages import LonelyTopology, Topology, TopologyError

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)

def _compiled(fn, x) -> str:
    return strip_hlo_debug(fn.lower(x).compile().as_text())


def _jit_collective(f, n):
    mesh = flat_mesh(n, "ft")
    return jax.jit(
        jax.shard_map(
            lambda row: f(row[0])[None],
            mesh=mesh,
            in_specs=P("ft"),
            out_specs=P("ft"),
            check_vma=False,
        )
    )


def _bitwise_equal(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype != b.dtype or a.shape != b.shape:
        return False
    return np.array_equal(
        a.view(np.uint8).reshape(-1), b.view(np.uint8).reshape(-1)
    )


# ---------------------------------------------------------------- golden


@needs_8_devices
class TestGoldenEquivalence:
    """IR-compiled == legacy, bitwise, value + compiled HLO."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize(
        "topo,count,chunks",
        [
            ("8", 64, 1),
            ("4,2", 64, 1),
            ("4,2", 67, 1),      # sub-N tail rides the dense collective
            ("2,2,2", 96, 1),
            ("4,2", 96, 3),      # chunk-pipelined interleave
            ("2,2,2", 131, 2),   # chunked + tail
            ("8", 7, 1),         # tail-only (count < N)
        ],
    )
    def test_tree_bitwise_and_hlo(self, topo, count, chunks, dtype):
        rng = np.random.default_rng(hash((topo, count, chunks)) % 2**31)
        x = jnp.asarray(
            rng.integers(-8, 8, size=(8, count)), dtype=jnp.dtype(dtype)
        )
        ir_fn = _jit_collective(
            lambda v: allreduce(v, "ft", topo, chunks=chunks), 8
        )
        legacy = _jit_collective(
            lambda v: tree_allreduce(v, "ft", topo, chunks=chunks), 8
        )
        assert _bitwise_equal(ir_fn(x), legacy(x))
        assert _compiled(ir_fn, x) == _compiled(legacy, x)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("count", [64, 67, 5])
    def test_ring_bitwise_and_hlo(self, count, dtype):
        rng = np.random.default_rng(count)
        x = jnp.asarray(
            rng.integers(-8, 8, size=(8, count)), dtype=jnp.dtype(dtype)
        )
        ir_fn = _jit_collective(lambda v: allreduce(v, "ft", "1"), 8)
        legacy = _jit_collective(lambda v: ring_allreduce(v, "ft"), 8)
        assert _bitwise_equal(ir_fn(x), legacy(x))
        assert _compiled(ir_fn, x) == _compiled(legacy, x)

    @pytest.mark.parametrize("topo", ["3,2+2", "7+1"])
    @pytest.mark.parametrize("count", [66, 63, 100])
    def test_lonely_bitwise_and_hlo(self, topo, count):
        rng = np.random.default_rng(count)
        x = jnp.asarray(
            rng.standard_normal((8, count)).astype(np.float32)
        )
        ir_fn = _jit_collective(lambda v: allreduce(v, "ft", topo), 8)
        legacy = _jit_collective(lambda v: lonely_allreduce(v, "ft", topo), 8)
        assert _bitwise_equal(ir_fn(x), legacy(x))
        assert _compiled(ir_fn, x) == _compiled(legacy, x)

    def test_non_sum_op_routes_through_ir_identically(self):
        x = jnp.asarray(
            np.random.default_rng(0).integers(0, 255, size=(8, 32)),
            dtype=jnp.int32,
        )
        ir_fn = _jit_collective(lambda v: allreduce(v, "ft", "4,2", op="bor"), 8)
        legacy = _jit_collective(
            lambda v: tree_allreduce(v, "ft", "4,2", op="bor"), 8
        )
        assert _bitwise_equal(ir_fn(x), legacy(x))


# ------------------------------------------------------------ new families


@needs_8_devices
class TestNewFamiliesExecute:
    @pytest.mark.parametrize("n", [4, 6, 8])
    @pytest.mark.parametrize("count", [64, 67])
    def test_swing_exact_sum(self, n, count):
        rng = np.random.default_rng(n * count)
        x = jnp.asarray(rng.integers(-8, 8, size=(n, count)).astype(np.float32))
        fn = _jit_collective(lambda v: allreduce(v, "ft", "swing"), n)
        out = np.asarray(fn(x))
        want = np.broadcast_to(np.asarray(x).sum(0), out.shape)
        assert np.array_equal(out, want)

    @pytest.mark.parametrize(
        "spec,n",
        [
            ("gen:4,2@1", 8),
            ("gen:4,2@2", 8),
            ("gen:8@7", 8),       # flat-tree message-pattern corner
            ("gen:2,2,2@1", 8),   # recursive halving-doubling corner
            ("gen:2,2@1", 4),
            ("gen:3,2@2", 6),
        ],
    )
    @pytest.mark.parametrize("count", [64, 67])
    def test_generalized_exact_sum(self, spec, n, count):
        rng = np.random.default_rng(hash((spec, count)) % 2**31)
        x = jnp.asarray(rng.integers(-8, 8, size=(n, count)).astype(np.float32))
        fn = _jit_collective(lambda v: allreduce(v, "ft", spec), n)
        out = np.asarray(fn(x))
        want = np.broadcast_to(np.asarray(x).sum(0), out.shape)
        assert np.array_equal(out, want)

    def test_swing_bf16_matches_dense_sum(self):
        # bf16: compare against lax.psum on the same wire dtype — the
        # swing fold order differs, so compare on integer-valued payloads
        n = 8
        x = jnp.asarray(
            np.random.default_rng(1).integers(-4, 4, size=(n, 32))
        ).astype(jnp.bfloat16)
        fn = _jit_collective(lambda v: allreduce(v, "ft", "swing"), n)
        out = np.asarray(fn(x)).astype(np.float32)
        want = np.asarray(x).astype(np.float32).sum(0)
        assert np.array_equal(out, np.broadcast_to(want, out.shape))


# ------------------------------------------------------------ model checks


class TestModelCheckMatrices:
    def test_default_ir_matrix_is_clean(self):
        violations, programs = check_ir_families()
        assert programs == len(default_ir_matrix())
        assert violations == []

    @pytest.mark.parametrize("n", [2, 4, 6, 8, 12, 16, 20])
    def test_swing_clean_any_n(self, n):
        """Power-of-two AND non-power-of-two N: the buddy-folded core
        passes symmetry, deadlock, conservation and span checks."""
        assert check_ir(swing_ir(n, count=n * 16)) == []

    @pytest.mark.parametrize(
        "widths,ports",
        [((4, 2), 1), ((4, 2), 3), ((2, 2, 2), 1), ((8,), 7), ((4, 4), 3), ((16,), 5)],
    )
    def test_generalized_clean(self, widths, ports):
        assert check_ir(generalized_ir(widths, ports)) == []

    def test_tree_ring_lonely_via_ir(self):
        assert check_ir(tree_ir(Topology(8, (4, 2)), count=128, chunks=3)) == []
        assert check_ir(ring_ir(8, count=64)) == []
        assert (
            check_ir(
                sir.lonely_ir(LonelyTopology(8, Topology(6, (3, 2)), 2))
            )
            == []
        )

    def test_swing_reach_partitions(self):
        """The emitter's internal invariant: each step's keep/send block
        sets partition the live set, final ownership is the identity."""
        for n in (4, 8, 16, 32):
            prog = swing_ir(n)
            rs = [s for s in prog.stages if s.phase == "rs"]
            live = {r: set(range(n)) for r in range(n)}
            for st in rs:
                sent = {x.src: set(x.blocks) for x in st.xfers}
                recv = {x.dst: set(x.blocks) for x in st.xfers}
                for r in range(n):
                    assert sent[r] | recv[r] == live[r]
                    assert not sent[r] & recv[r]
                    live[r] = recv[r]
            assert all(live[r] == {r} for r in range(n))

    def test_generalized_max_ports_matches_tree_blockmap(self):
        """ports = w-1 is the flat-tree message pattern: the union of the
        generalized rounds' transfers equals the tree stage's transfers."""
        topo = Topology(8, (4, 2))
        gen = generalized_ir((4, 2), 3, count=64)
        tree = tree_ir(topo, count=64)
        for phase in ("rs", "ag"):
            gen_x = sorted(
                (x.src, x.dst, x.blocks)
                for st in gen.stages
                if st.phase == phase
                for x in st.xfers
            )
            tree_x = sorted(
                (x.src, x.dst, x.blocks)
                for st in tree.stages
                if st.phase == phase
                for x in st.xfers
            )
            assert gen_x == tree_x


# ------------------------------------------------- verified-before-compiled


class TestCompileRefusal:
    def _corrupt_peer(self, prog):
        st = prog.stages[1]
        bad = tuple(
            dataclasses.replace(x, dst=(x.dst + 2) % prog.num_nodes)
            for x in st.xfers
        )
        return dataclasses.replace(
            prog,
            stages=prog.stages[:1]
            + (dataclasses.replace(st, xfers=bad),)
            + prog.stages[2:],
        )

    def test_compile_refuses_seeded_violations(self):
        bad = self._corrupt_peer(swing_ir(8, count=64))
        with pytest.raises(IRViolationError) as ei:
            compile_ir(bad)
        assert ei.value.violations, "refusal must carry the checker findings"

    def test_compile_refuses_truncated_blockmap(self):
        prog = generalized_ir((4, 2), 1, count=64)
        st = prog.stages[0]
        bad_x = tuple(
            dataclasses.replace(x, blocks=x.blocks[:-1]) for x in st.xfers
        )
        bad = dataclasses.replace(
            prog,
            stages=(dataclasses.replace(st, xfers=bad_x),) + prog.stages[1:],
        )
        with pytest.raises(IRViolationError):
            compile_ir(bad)

    def test_compile_refuses_divergent_but_valid_program(self):
        """A program every model check PASSES but whose stage order
        diverged from the canonical emission (chunk phases serialized
        instead of interleaved): only the canonical-twin guard can see
        it, and it must refuse — the lowering realizes the canonical
        interleave, not arbitrary stage orders."""
        prog = tree_ir(Topology(8, (4, 2)), count=128, chunks=2)
        reordered = tuple(
            sorted(
                prog.stages,
                key=lambda s: (s.chunk, s.phase == "ag"),
            )
        )
        assert reordered != prog.stages
        serialized = dataclasses.replace(prog, stages=reordered)
        assert check_ir(serialized) == [], "reorder must stay check-clean"
        with pytest.raises(IRViolationError, match="divergence"):
            compile_ir(serialized)

    def test_compile_refuses_mislabeled_family(self):
        """Another family's stages under a tree label: refused (the model
        check or the twin guard — either way it cannot reach a mesh)."""
        tree = tree_ir(Topology(8, (4, 2)), count=64)
        other = tree_ir(Topology(8, (2, 2, 2)), count=64)
        with pytest.raises(IRViolationError):
            compile_ir(dataclasses.replace(tree, stages=other.stages))

    def test_clean_programs_compile(self):
        for prog in (
            tree_ir(Topology(8, (4, 2))),
            ring_ir(8),
            swing_ir(6),
            generalized_ir((4, 2), 2),
        ):
            assert callable(compile_ir(prog))

    def test_mutation_classes_registered(self):
        from flextree_tpu.analysis.mutation import MUTATIONS

        assert len(MUTATIONS) >= 18
        for cls in ("swing-stride", "genblock-truncate", "ir-divergence"):
            assert cls in MUTATIONS


# -------------------------------------------------------- one source of truth


class TestSingleExpansion:
    def test_plan_views_match_ir_blockmap(self):
        """send_plan/recv_plan are views over the IR emitter: every
        cross-rank op matches the tree IR's stage transfers exactly."""
        topo = Topology(12, (3, 2, 2))
        prog = tree_ir(topo, count=144)
        by_stage = {}
        for st in prog.stages:
            if st.phase != "rs":
                continue
            for x in st.xfers:
                by_stage[(st.index, x.src, x.dst)] = x.blocks
        for r in range(12):
            sp = send_plan(topo, r)
            rp = recv_plan(topo, r)
            for i in range(topo.num_stages):
                for op in sp[i]:
                    if op.peer == r:
                        continue
                    assert by_stage[(i, r, op.peer)] == op.blocks
                for op in rp[i]:
                    if op.peer == r:
                        continue
                    assert by_stage[(i, op.peer, r)] == op.blocks

    def test_program_from_ir_matches_legacy_shape(self):
        from flextree_tpu.analysis.schedule_check import build_program

        prog = build_program(Topology(8, (4, 2)), count=128, chunks=2)
        assert prog.chunks == 2
        assert prog.chunk_spans == [(0, 64), (64, 64)]
        assert all(len(q) == 8 for q in prog.posts.values())
        assert prog.kind == "tree"

    def test_build_program_accepts_ir(self):
        from flextree_tpu.analysis.schedule_check import build_program

        prog = build_program(swing_ir(8, count=64))
        assert prog.kind == "swing"
        assert sorted(prog.posts) == list(range(8))


# ------------------------------------------------------------ ir_equivalence


@needs_8_devices
class TestIrEquivalence:
    def test_all_entrypoints_match(self):
        from flextree_tpu.analysis.ir_equivalence import run_ir_equivalence

        violations, detail = run_ir_equivalence()
        assert violations == []
        assert {"tree_4x2", "swing_8", "gen_4x2_p2"} <= set(detail)

    def test_divergence_is_caught(self):
        from flextree_tpu.analysis.ir_equivalence import lower_ir_divergent

        vs = lower_ir_divergent()
        assert any(v.kind == "ir-equivalence" for v in vs)


# ------------------------------------------------------------------ specs


class TestSpecsAndResolution:
    def test_resolve_legacy_specs_unchanged(self):
        assert isinstance(resolve_collective(8, "4,2"), Topology)
        assert resolve_collective(8, "1").is_ring
        assert isinstance(resolve_collective(7, "3,2+1"), LonelyTopology)

    def test_resolve_ir_specs(self):
        fam = resolve_collective(8, "swing")
        assert isinstance(fam, IRFamilySpec) and fam.family == "swing"
        gen = resolve_collective(8, "gen:4,2@2")
        assert gen.widths == (4, 2) and gen.ports == 2
        with pytest.raises(TopologyError):
            resolve_collective(8, "gen:3,2@1")  # product != n

    def test_spec_round_trip(self):
        for prog in (
            swing_ir(6),
            generalized_ir((4, 2), 2),
            tree_ir(Topology(8, (4, 2))),
            ring_ir(8),
        ):
            spec = prog.spec()
            resolved = resolve_collective(prog.num_nodes, spec)
            re_emitted = emit_ir(resolved, num_nodes=prog.num_nodes)
            assert re_emitted.family == prog.family

    def test_emit_ir_rejects_bad_ports(self):
        with pytest.raises(TopologyError):
            generalized_ir((4, 2), 9)
        with pytest.raises(TopologyError):
            generalized_ir((4, 2), 0)


# ----------------------------------------------------------------- planner


class TestPlannerIntegration:
    def test_default_candidate_set_unchanged(self):
        from flextree_tpu.planner.choose import choose_topology

        plan = choose_topology(8, 1 << 20)
        assert all(c.family == "tree" for c in plan.candidates)

    def test_ir_families_enter_enumeration(self):
        from flextree_tpu.planner.choose import choose_topology

        plan = choose_topology(
            8, 1 << 20, ir_families=("swing", "generalized")
        )
        fams = {c.family for c in plan.candidates}
        assert {"tree", "swing", "generalized"} <= fams
        swing = next(c for c in plan.candidates if c.family == "swing")
        assert swing.total_us > 0
        assert swing.shape_label() == "swing"

    def test_shortlist_offers_ir_rows_and_winner_is_executable(self, tmp_path):
        from flextree_tpu.planner.autotune import analytic_shortlist, autotune_plan

        rows = analytic_shortlist(8, 256, top_k=30)
        assert any(isinstance(r[0], IRFamilySpec) for r in rows)

        def timer(cands, n, nb, dt, rep):
            return [
                0.001
                if isinstance(c[0], IRFamilySpec) and c[0].family == "swing"
                else 0.010
                for c in cands
            ]

        t1 = autotune_plan(
            8, 256, timer=timer, cache_path=str(tmp_path / "p.json"), top_k=30
        )
        assert t1.family == "swing" and t1.to_ft_topo() == "swing"
        # the no-alias guard: the cached entry round-trips as the IR
        # family, never as a legacy widths vector
        t2 = autotune_plan(
            8, 256, timer=timer, cache_path=str(tmp_path / "p.json"), top_k=30
        )
        assert t2.source == "cache" and t2.family == "swing"
        assert isinstance(t2.topology, IRFamilySpec)
        assert isinstance(
            resolve_collective(8, t2.to_ft_topo()), IRFamilySpec
        )

    def test_swing_cost_scales_with_bytes_and_n(self):
        from flextree_tpu.planner.cost_model import swing_cost

        small = swing_cost(8, 1 << 10).total_us
        big = swing_cost(8, 1 << 24).total_us
        assert big > small
        assert swing_cost(16, 1 << 20).total_us > swing_cost(4, 1 << 20).total_us

    def test_generalized_cost_ports_trade_latency(self):
        from flextree_tpu.planner.cost_model import generalized_cost

        serial = generalized_cost((8,), 1, 1 << 20)
        parallel = generalized_cost((8,), 7, 1 << 20)
        assert serial.latency_us > parallel.latency_us
        assert serial.bandwidth_us == pytest.approx(parallel.bandwidth_us)
