"""Tests for the schedule validator (the race-detection analog, SURVEY §5)
and the debug-dump utilities (the FT_DEBUG analog)."""

import pytest

from flextree_tpu.schedule import (
    ScheduleError,
    Topology,
    validate,
    validate_ring,
    validate_topology,
)
from flextree_tpu.utils import debug_dump_schedule, debug_enabled


ALL_SHAPES = [
    (8, (8,)),
    (8, (2, 2, 2)),
    (8, (4, 2)),
    (8, (2, 4)),
    (12, (3, 4)),
    (12, (2, 3, 2)),
    (6, (2, 3)),
    (30, (2, 3, 5)),
    (16, (2, 2, 2, 2)),
    (1, (1,)),
]


class TestValidateTopology:
    @pytest.mark.parametrize("n,widths", ALL_SHAPES)
    def test_valid_shapes_pass(self, n, widths):
        stats = validate(Topology(n, widths))
        assert stats.num_nodes == n
        assert stats.widths == widths

    def test_message_count_matches_topo(self):
        # tree p2p rounds: each rank exchanges with (w-1) peers per stage,
        # twice (both phases) — the 2*sum(wi-1) per-rank step count scaled
        # by N ranks (SURVEY §3.2).
        topo = Topology(8, (4, 2))
        stats = validate_topology(topo)
        assert stats.p2p_messages == 8 * 2 * sum(w - 1 for w in (4, 2))

    @pytest.mark.parametrize("n", [2, 3, 4, 7, 8, 12])
    def test_ring_passes(self, n):
        stats = validate_ring(n)
        assert stats.num_nodes == n

    def test_ring_sentinel_dispatch(self):
        assert validate(Topology.ring(5)).widths == (1,)

    def test_corrupted_plan_caught(self, monkeypatch):
        """Sabotage send_plan and check the partition invariant trips."""
        import importlib

        V = importlib.import_module("flextree_tpu.schedule.validate")
        from flextree_tpu.schedule.plan import Operation, send_plan as real_send

        def bad_send(topo, rank):
            plan = real_send(topo, rank)
            if rank == 0:
                # drop a block from the first op of stage 0
                op = plan[0][0]
                plan[0][0] = Operation(op.peer, op.blocks[1:])
            return plan

        monkeypatch.setattr(V, "send_plan", bad_send)
        with pytest.raises(ScheduleError, match="send set != owned"):
            V.validate_topology(Topology(8, (4, 2)))

    def test_double_count_caught(self, monkeypatch):
        import importlib

        V = importlib.import_module("flextree_tpu.schedule.validate")
        from flextree_tpu.schedule.plan import Operation, send_plan as real_send

        def bad_send(topo, rank):
            plan = real_send(topo, rank)
            if rank == 1:
                a, b = plan[0][0], plan[0][1]
                # peer b also claims one of peer a's blocks
                plan[0][1] = Operation(b.peer, b.blocks + (a.blocks[0],))
            return plan

        monkeypatch.setattr(V, "send_plan", bad_send)
        with pytest.raises(ScheduleError, match="double count"):
            V.validate_topology(Topology(8, (4, 2)))


    def test_recv_overclaim_caught(self, monkeypatch):
        """A recv plan claiming blocks the rank never held must trip the
        plan-derived ownership tracking."""
        import importlib

        V = importlib.import_module("flextree_tpu.schedule.validate")
        from flextree_tpu.schedule.plan import Operation, recv_plan as real_recv

        def bad_recv(topo, rank):
            plan = real_recv(topo, rank)
            if rank == 2:
                # stage 1 suddenly claims a block outside rank 2's chain
                op = plan[1][0]
                foreign = (op.blocks[0] + 1) % topo.num_nodes
                plan[1] = [Operation(o.peer, o.blocks + (foreign,)) for o in plan[1]]
            return plan

        monkeypatch.setattr(V, "recv_plan", bad_recv)
        with pytest.raises(ScheduleError):
            V.validate_topology(Topology(8, (4, 2)))

    def test_large_ring_fast(self):
        """validate_ring must stay polynomial-friendly (plans built once)."""
        import time

        t0 = time.perf_counter()
        validate_ring(256)
        assert time.perf_counter() - t0 < 10.0


class TestDebugDump:
    def test_off_by_default(self, monkeypatch):
        monkeypatch.delenv("FT_DEBUG", raising=False)
        assert not debug_enabled()
        assert debug_dump_schedule(Topology(4, (4,))) is None

    @pytest.mark.parametrize("val", ["0", "false", "no", "off", "  "])
    def test_falsy_values(self, monkeypatch, val):
        monkeypatch.setenv("FT_DEBUG", val)
        assert not debug_enabled()

    def test_env_enables(self, monkeypatch):
        monkeypatch.setenv("FT_DEBUG", "1")
        assert debug_enabled()
        out = debug_dump_schedule(Topology(4, (2, 2)), rank=0)
        assert "node 0" in out and "stage0" in out and "stage1" in out

    def test_force_all_ranks(self, monkeypatch):
        monkeypatch.delenv("FT_DEBUG", raising=False)
        out = debug_dump_schedule(Topology(4, (4,)), force=True)
        assert out.count("plan of node") == 4


class TestNamedScopesCompile:
    def test_allreduce_still_correct_with_scopes(self):
        """Named scopes must not perturb results (smoke over shard_map)."""
        import numpy as np
        import jax.numpy as jnp

        from flextree_tpu.parallel import allreduce_over_mesh, flat_mesh

        mesh = flat_mesh(8)
        x = np.arange(8 * 40, dtype=np.float32).reshape(8, 40)
        out = np.asarray(allreduce_over_mesh(jnp.asarray(x), mesh, topo="4,2"))
        np.testing.assert_allclose(out, np.broadcast_to(x.sum(0), (8, 40)), rtol=1e-6)
