"""Executable lonely-node topologies ("4,2+1" shapes).

The reference conceived lonely nodes (ranks outside the factorized tree,
``mpi_mod.hpp:77``) but shipped the machinery disabled — every call site
commented out, the runtime aborting on product != N
(``mpi_mod.hpp:914-918``) — leaving its planner able only to *advise*
resizing prime worlds (``ChooseWidth.h:16-21``).  These tests pin our
executable realization at all three levels: spec parsing, the NumPy
simulator, and the JAX collective on the 8-vdev mesh vs the psum oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import own_copy
from jax.sharding import PartitionSpec as P

from flextree_tpu.backends import simulate_allreduce
from flextree_tpu.parallel.mesh import allreduce_over_mesh, flat_mesh
from flextree_tpu.schedule.stages import (
    LonelyTopology,
    Topology,
    TopologyError,
    split_lonely_spec,
)


class TestSpec:
    def test_split(self):
        assert split_lonely_spec("4,2+1") == ("4,2", 1)
        assert split_lonely_spec("7+1") == ("7", 1)
        assert split_lonely_spec("3,2 + 2") == ("3,2", 2)
        assert split_lonely_spec("4,2") == ("4,2", 0)

    def test_resolve_roundtrip(self):
        t = Topology.resolve(7, "3,2+1")
        assert isinstance(t, LonelyTopology)
        assert t.tree.widths == (3, 2) and t.lonely == 1
        assert str(t) == "3*2+1"
        assert t.message_steps == t.tree.message_steps + 2
        # env-style via resolve(None) path
        t8 = Topology.resolve(8, "7+1")
        assert t8.tree.widths == (7,) and t8.lonely == 1

    def test_errors(self):
        with pytest.raises(TopologyError):
            Topology.resolve(7, "3,2+2")  # 6 + 2 != 7
        with pytest.raises(TopologyError):
            Topology.resolve(5, "2+3")  # more lonely than buddies
        with pytest.raises(TopologyError):
            Topology.resolve(7, "1+1")  # ring + lonely unsupported
        with pytest.raises(TopologyError):
            Topology.resolve(7, "3,2+x")


class TestSimulator:
    @pytest.mark.parametrize(
        "n,spec",
        [(7, "3,2+1"), (7, "6+1"), (8, "7+1"), (8, "3,2+2"), (5, "2,2+1")],
    )
    @pytest.mark.parametrize("count", [35, 42, 6])
    def test_matches_numpy_sum(self, n, spec, count):
        rng = np.random.default_rng(n * count)
        data = rng.standard_normal((n, count))
        out = simulate_allreduce(data, spec)
        np.testing.assert_allclose(
            out, np.tile(data.sum(0), (n, 1)), rtol=1e-9, atol=1e-9
        )

    def test_matches_numpy_max(self):
        rng = np.random.default_rng(0)
        data = rng.standard_normal((7, 33))
        out = simulate_allreduce(data, "3,2+1", op="max")
        np.testing.assert_array_equal(out, np.tile(data.max(0), (7, 1)))


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
class TestJaxCollective:
    def _run(self, n, spec, count, op="sum", dtype=jnp.float32):
        mesh = flat_mesh(n, "ft")
        rng = np.random.default_rng(count * n)
        data = jnp.asarray(
            rng.integers(-8, 8, (n, count)).astype(np.float64), dtype
        )
        out = allreduce_over_mesh(data, mesh, topo=spec, op=op)
        return np.asarray(jax.device_get(out)), np.asarray(
            jax.device_get(data)
        )

    @pytest.mark.parametrize(
        "n,spec", [(7, "3,2+1"), (8, "7+1"), (8, "3,2+2"), (5, "2,2+1")]
    )
    @pytest.mark.parametrize("count", [64, 37])  # divisible + ragged tail
    def test_matches_psum_semantics(self, n, spec, count):
        got, data = self._run(n, spec, count)
        want = np.tile(data.sum(0), (n, 1))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_non_sum_op(self):
        got, data = self._run(7, "3,2+1", 48, op="min")
        np.testing.assert_array_equal(got, np.tile(data.min(0), (7, 1)))

    def test_int_dtype(self):
        got, data = self._run(8, "7+1", 40, dtype=jnp.int32)
        np.testing.assert_array_equal(
            got, np.tile(data.sum(0).astype(np.int32), (8, 1))
        )

    def test_ft_topo_env(self, monkeypatch):
        monkeypatch.setenv("FT_TOPO", "3,2+1")
        mesh = flat_mesh(7, "ft")
        data = jnp.asarray(np.arange(7 * 12, dtype=np.float32).reshape(7, 12))
        out = np.asarray(
            jax.device_get(allreduce_over_mesh(data, mesh, topo=None))
        )
        want = np.tile(np.asarray(data).sum(0), (7, 1))
        np.testing.assert_allclose(out, want, rtol=1e-6)


class TestPlanner:
    def test_prime_n_has_executable_lonely_candidates(self):
        from flextree_tpu.planner import choose_topology

        plan = choose_topology(7, 1 << 20)
        lonely = [c for c in plan.candidates if c.lonely]
        # every factorization of 6 appears as an executable +1 shape
        assert {c.widths for c in lonely} == {(6,), (2, 3), (3, 2)}
        assert all(c.lonely == 1 for c in lonely)
        # uniform fabric: lonely moves the full payload twice extra, so the
        # flat in-tree shape must still win
        assert plan.widths == (7,)

    def test_lonely_plan_roundtrips_to_runtime(self):
        """A plan whose argmin is a lonely shape must produce an FT_TOPO
        spec the runtime resolves and executes."""
        from flextree_tpu.planner import choose_topology

        plan = choose_topology(7, 1 << 20)
        lonely = next(c for c in plan.candidates if c.lonely)
        # build the spec the summary/ft_topo path would emit for it
        t = LonelyTopology(7, Topology(6, lonely.widths), 1)
        spec = f"{','.join(map(str, lonely.widths))}+1"
        resolved = Topology.resolve(7, spec)
        assert resolved == t
        out = simulate_allreduce(np.ones((7, 12)), spec)
        np.testing.assert_allclose(out, np.full((7, 12), 7.0))

    def test_lonely_cost_adds_buddy_terms(self):
        from flextree_tpu.planner import TpuCostParams, allreduce_cost
        from flextree_tpu.planner.cost_model import lonely_allreduce_cost

        p = TpuCostParams()
        tree = Topology(6, (3, 2))
        base = allreduce_cost(tree, 1 << 20, p)
        lone = lonely_allreduce_cost(tree, 1, 1 << 20, p)
        assert lone.latency_us == base.latency_us + 2 * (p.ici.latency_us + p.launch_us)
        assert lone.bandwidth_us > base.bandwidth_us
        assert lone.reduce_us > base.reduce_us

    def test_summary_prints_lonely_notation(self):
        from flextree_tpu.planner import choose_topology

        s = choose_topology(7, 1 << 20).summary()
        assert "+1" in s  # the reference's PrintTreeStructure notation


def test_validator_accepts_lonely():
    from flextree_tpu.schedule.validate import validate

    t = Topology.resolve(7, "3,2+1")
    stats = validate(t)
    assert stats.num_nodes == 7
    tree_stats = validate(t.tree)
    assert stats.p2p_messages == tree_stats.p2p_messages + 2


def test_phase_apis_lonely_mirror_contract():
    """The split phases support lonely shapes since PR 7: the head splits
    over the m TREE ranks and each lonely rank ends holding a bitwise
    COPY of its buddy's owned block (the mirror contract of
    ``schedule.blocks.owned_block``)."""
    import numpy as np

    from flextree_tpu.parallel import reduce_scatter
    from flextree_tpu.parallel.mesh import flat_mesh
    from flextree_tpu.schedule.blocks import shard_layout
    from jax.sharding import PartitionSpec as P

    mesh = flat_mesh(7, "ft")
    rng = np.random.default_rng(3)
    data = rng.standard_normal((7, 12)).astype(np.float32)  # 12 = 2 per block

    def body(row):
        return reduce_scatter(row[0], "ft", topo="3,2+1")[None]

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("ft"), out_specs=P("ft")))
    out = np.asarray(f(jnp.asarray(data)))
    blocks = data.sum(0).reshape(6, 2)
    lay = shard_layout(Topology.resolve(7, "3,2+1"))
    for r in range(7):
        np.testing.assert_allclose(out[r], blocks[lay[r]], rtol=1e-5, atol=1e-5)
    # the mirror is bitwise: lonely rank 6 holds exactly buddy 0's shard
    assert out[6].tobytes() == out[0].tobytes()


def test_lonely_cost_dcn_buddy_pricing():
    from flextree_tpu.planner import TpuCostParams
    from flextree_tpu.planner.cost_model import lonely_allreduce_cost

    p = TpuCostParams()
    tree = Topology(6, (3, 2))
    ici = lonely_allreduce_cost(tree, 1, 1 << 24, p)
    dcn = lonely_allreduce_cost(tree, 1, 1 << 24, p, buddy_crosses_dcn=True)
    # DCN buddy pricing must be strictly costlier (6 vs 45 GB/s links)
    assert dcn.bandwidth_us > ici.bandwidth_us
    assert dcn.latency_us > ici.latency_us


def test_lonely_shape_can_win_and_native_twin_agrees():
    """A parameter regime where a +1 shape is the argmin — the ring pays
    2(n-1) launches, flat pays width control, and the two-stage lonely
    tree threads between them — and the native C++ twin (ft_choose2)
    agrees on winner, lonely flag, and cost."""
    from flextree_tpu.planner import LinkParams, TpuCostParams, choose_topology
    from flextree_tpu.planner.native import native_available, native_choose_lonely

    p = TpuCostParams(
        ici=LinkParams(1e9, 0.0), dcn=LinkParams(1e9, 0.0),
        reduce_bw_GBps=1e9, control_us_per_width=100.0, launch_us=100.0,
    )
    plan = choose_topology(7, 1 << 10, params=p)
    assert isinstance(plan.topology, LonelyTopology), plan.summary()
    assert plan.to_ft_topo().endswith("+1")
    # the winning spec must execute
    out = simulate_allreduce(np.ones((7, 14)), plan.to_ft_topo())
    np.testing.assert_allclose(out, np.full((7, 14), 7.0))
    if native_available():
        widths, lonely, cost = native_choose_lonely(7, 1 << 10, p)
        assert (widths, lonely) == (plan.widths, 1)
        assert abs(cost - plan.candidates[0].total_us) < 1e-3


@pytest.mark.parametrize("n", [7, 8, 12, 13, 30])
def test_native_choose_matches_python_incl_lonely(n):
    """Twin parity on cost and lonely flag.  Costs, not widths: the argmin
    has exact ties at n=8/12/30 ((2,4)/(4,2) etc.), so shape equality
    would only hold by enumeration-order coincidence — same reasoning as
    tests/test_planner.py's existing cost-parity check."""
    from flextree_tpu.planner import TpuCostParams, choose_topology
    from flextree_tpu.planner.native import native_available, native_choose_lonely

    if not native_available():
        pytest.skip("native library not built")
    widths, lonely, cost = native_choose_lonely(n, 1 << 20, TpuCostParams())
    py = choose_topology(n, 1 << 20, params=TpuCostParams())
    py_lonely = 1 if isinstance(py.topology, LonelyTopology) else 0
    assert lonely == py_lonely
    assert cost == pytest.approx(py.candidates[0].total_us, rel=1e-9)
    # the returned widths must be a VALID shape for this world size
    import math

    assert math.prod(widths) + lonely == n or widths == (1,)


@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
def test_lonely_grad_sync_through_train_step():
    """FT_TOPO=7+1 gradient sync through the production train step matches
    the native-psum sync exactly (the dryrun's part-4 check, pinned in the
    suite)."""
    from flextree_tpu.models.transformer import TransformerConfig
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_3d,
        make_train_step,
    )

    mesh = make_mesh_3d(8, (8, 1, 1))
    cfg = TransformerConfig(
        vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=32,
        dtype=jnp.float32,
    )
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 64, (16, 8)), jnp.int32)
    tgts = jnp.asarray(rng.integers(0, 64, (16, 8)), jnp.int32)
    lone_step = make_train_step(mesh, cfg, TrainConfig(lr=1e-3, grad_topo="7+1"))
    psum_step = make_train_step(mesh, cfg, TrainConfig(lr=1e-3, grad_topo="psum"))
    l_state, l_metrics = lone_step(own_copy(state), toks, tgts)
    p_state, p_metrics = psum_step(state, toks, tgts)
    jax.block_until_ready((l_state, p_state))
    assert abs(float(l_metrics["loss"]) - float(p_metrics["loss"])) < 1e-5
    for a, b in zip(
        jax.tree.leaves(l_state["params"]), jax.tree.leaves(p_state["params"])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
