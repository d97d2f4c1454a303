"""The thesis artifact: a pinned scenario where FlexTree hierarchy WINS.

The reference's reason to exist is that topology choice matters: its cost
model picks multi-stage tree shapes that beat flat/ring on a hierarchical
fabric (``cost_model/CostModel.h:82-119``, ``cost_model/README.md:5-71`` —
the two-level 16-host Ethernet cluster).  The TPU analog of that fabric is
multi-slice: fast ICI inside a slice, slow DCN between slices.  A 1-core
CPU host cannot show the win empirically (no real links), so this test pins
the analytical + structural case end to end:

1. the planner, given the multi-slice mesh, picks a multi-stage ICI-first
   shape — NOT flat, NOT ring;
2. the cost model shows flat and ring losing by >= 2x (they pay full-size
   payloads over DCN; the hierarchy's DCN stage moves only 1/g of the
   bytes);
3. the lowered HLO proves the structural claim: the DCN-crossing stage's
   collectives really operate on a 1/g-size tile with cross-slice
   ``replica_groups``.

See WINS.md for the written analysis these tests pin.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from flextree_tpu.parallel import tree_allreduce
from flextree_tpu.parallel.launch import flatten_mesh, hybrid_mesh, plan_for_mesh
from flextree_tpu.planner import choose_topology
from flextree_tpu.planner.cost_model import (
    TpuCostParams,
    allreduce_cost,
    ring_cost,
)
from flextree_tpu.schedule.stages import Topology

MB = 1 << 20
S_256MB = 256 * MB


def _dcn_bytes_per_chip(widths, mesh_shape, dcn_axes, nbytes):
    """Bytes per chip per phase crossing DCN for an aligned shape (the
    quantity the hierarchy shrinks: stage i moves (w-1)/w * S/gap)."""
    from flextree_tpu.planner.choose import _stage_axes

    axes = _stage_axes(tuple(widths), tuple(mesh_shape))
    assert axes is not None, f"{widths} not aligned on {mesh_shape}"
    total = 0.0
    gap = 1
    for w, ax in zip(widths, axes):
        if ax in dcn_axes:
            total += (w - 1) / w * (nbytes / gap)
        gap *= w
    return total


class TestPlannerPicksHierarchy:
    """Cost-model level: 4 slices x 8 chips (v5e-multislice-shaped), 256 MB."""

    # plan_for_mesh ordering: innermost (ICI) axis first, so the planner
    # sees mesh_shape=(8, 4) with the 4-slice DCN axis LAST (gap 8)
    MESH = (8, 4)
    DCN = (1,)
    N = 32

    def test_planner_pick_is_multistage_ici_first(self):
        plan = choose_topology(
            self.N, S_256MB, mesh_shape=self.MESH, dcn_axes=self.DCN
        )
        assert plan.widths != (self.N,), "planner chose flat — no hierarchy win"
        assert plan.widths != (1,), "planner chose ring"
        assert len(plan.widths) >= 2
        best = plan.candidates[0]
        assert best.torus_aligned, "winner must tile the physical mesh"
        # the ICI axis (size 8) is covered by a prefix of the widths, so
        # every DCN-crossing stage has gap >= 8 and moves <= S/8 per phase
        assert math.prod(plan.widths) == self.N
        prefix = 1
        for w in plan.widths:
            prefix *= w
            if prefix == self.MESH[0]:
                break
        assert prefix == self.MESH[0], (
            f"widths {plan.widths} do not cover the ICI axis first"
        )

    def test_flat_and_ring_lose_by_2x(self):
        plan = choose_topology(
            self.N, S_256MB, mesh_shape=self.MESH, dcn_axes=self.DCN
        )
        best_us = plan.candidates[0].total_us
        flat_us = next(
            c.total_us for c in plan.candidates if c.widths == (self.N,)
        )
        ring_us = next(
            c.total_us for c in plan.candidates if c.widths == (1,)
        )
        assert flat_us >= 2 * best_us, (
            f"flat {flat_us:.0f}us vs best {best_us:.0f}us: margin "
            f"{flat_us / best_us:.2f}x < 2x"
        )
        assert ring_us >= 2 * best_us, (
            f"ring {ring_us:.0f}us vs best {best_us:.0f}us: margin "
            f"{ring_us / best_us:.2f}x < 2x"
        )

    def test_dcn_traffic_shrinks_by_gap_factor(self):
        """The mechanism of the win: the hierarchy's DCN stages move ~1/8
        of the bytes a flat all-axis collective pushes over DCN."""
        plan = choose_topology(
            self.N, S_256MB, mesh_shape=self.MESH, dcn_axes=self.DCN
        )
        win_dcn = _dcn_bytes_per_chip(
            plan.widths, self.MESH, set(self.DCN), S_256MB
        )
        # flat (32,) does not tile (8, 4) -> its one group straddles the
        # slice boundary and the full (N-1)/N payload crosses DCN
        flat_dcn = (self.N - 1) / self.N * S_256MB
        assert win_dcn <= flat_dcn / 7.0, (
            f"winner moves {win_dcn / MB:.1f} MB over DCN vs flat's "
            f"{flat_dcn / MB:.1f} MB — expected >= 7x reduction"
        )

    def test_win_is_robust_across_payloads_and_slices(self):
        """The pick stays hierarchical from 16 MB to 1 GB and for 2..8
        slices — not a knife-edge artifact of one config."""
        for n_slices in (2, 4, 8):
            mesh = (8, n_slices)
            n = 8 * n_slices
            for nbytes in (16 * MB, S_256MB, 1024 * MB):
                plan = choose_topology(
                    n, nbytes, mesh_shape=mesh, dcn_axes=(1,)
                )
                assert plan.widths != (n,) and plan.widths != (1,), (
                    f"hierarchy lost at {n_slices} slices, "
                    f"{nbytes >> 20} MB: picked {plan.widths}"
                )


class TestPlanForMeshHybrid:
    """launch.py bridge: the same win through the hybrid-mesh API at the
    8-device scale the CPU suite can actually instantiate."""

    @pytest.fixture()
    def mesh(self):
        if len(jax.devices()) < 8:
            pytest.skip("needs 8 (virtual) devices")
        return hybrid_mesh(ici_shape=(4,), dcn_shape=(2,))

    def test_plan_for_mesh_picks_ici_then_dcn(self, mesh):
        plan = plan_for_mesh(mesh, S_256MB)
        # 8 devices as 2 slices x 4 chips: the only aligned 2-stage shape
        # with the ICI axis first is (4, 2)
        assert plan.widths == (4, 2), plan.summary()
        best = plan.candidates[0]
        assert best.torus_aligned
        flat_us = next(c.total_us for c in plan.candidates if c.widths == (8,))
        assert flat_us >= 2 * best.total_us

    def test_predicted_margin_matches_dcn_bandwidth_ratio(self, mesh):
        """At 256 MB the bandwidth term dominates, so the flat/hierarchy
        ratio approaches the DCN-traffic ratio x the DCN/ICI bandwidth mix;
        sanity-pin it within broad bounds so constant drift is caught."""
        params = TpuCostParams()
        plan = plan_for_mesh(mesh, S_256MB, params=params)
        flat_us = next(c.total_us for c in plan.candidates if c.widths == (8,))
        ratio = flat_us / plan.candidates[0].total_us
        assert 2.0 <= ratio <= 20.0, f"implausible flat/best ratio {ratio:.1f}"


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 (virtual) devices")
class TestLoweredStructure:
    """HLO: the DCN stage of the planner's pick really moves 1/g of the
    tile, with cross-slice replica_groups — the structural half of the
    win (the part a 1-core host CAN prove)."""

    COUNT = 64  # elements per device

    def _lowered(self, topo):
        mesh = flatten_mesh(hybrid_mesh(ici_shape=(4,), dcn_shape=(2,)))

        def f(row):
            return tree_allreduce(row[0], "ft", topo, op="sum")[None]

        return (
            jax.jit(
                jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"))
            )
            .lower(jnp.zeros((8, self.COUNT), jnp.float32))
            .as_text()
        )

    # reduce_scatter is a region op (its reducer body spans lines); the
    # operand type appears at the region close, ``}) : (tensor<Nxf32>)``
    _RS = re.compile(
        r'"stablehlo\.reduce_scatter"'
        r'.*?replica_groups = dense<(\[\[.*?\]\])>'
        r".*?\}\) : \(tensor<(\d+)xf32>\)",
        re.S,
    )

    def test_dcn_stage_tile_and_groups(self):
        ir = self._lowered((4, 2))
        ops = [(int(m.group(2)), m.group(1)) for m in self._RS.finditer(ir)]
        # per-stage reduce_scatter operand sizes: stage0 (ICI) sees the
        # full 64-element tile; stage1 (DCN) sees 64/4 = 16 elements —
        # the 1/g traffic contract that makes the hierarchy win
        sizes = [s for s, _ in ops]
        assert sizes == [64, 16], f"stage operand sizes {sizes} != [64, 16]"
        # stage-1 groups must pair rank r with r+4 (cross-slice): flattened
        # hybrid order is slice-major, so slice 0 = ranks 0..3
        assert "[0, 4]" in ops[1][1] and "[3, 7]" in ops[1][1], (
            f"DCN stage groups are not cross-slice: {ops[1][1]}"
        )
        # and the ICI stage's groups stay inside a slice
        assert "[0, 1, 2, 3]" in ops[0][1], (
            f"ICI stage groups are not intra-slice: {ops[0][1]}"
        )

    def test_flat_pushes_full_tile_across_slices(self):
        """The losing shape, for contrast: flat's single reduce_scatter
        covers all 8 ranks in one group — the full 64-element tile crosses
        the slice boundary."""
        ir = self._lowered((8,))
        ops = [(int(m.group(2)), m.group(1)) for m in self._RS.finditer(ir)]
        assert len(ops) == 1
        assert ops[0][0] == 64
        assert "[0, 1, 2, 3, 4, 5, 6, 7]" in ops[0][1]
