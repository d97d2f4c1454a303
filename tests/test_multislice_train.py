"""Multi-slice integration: launch -> planner -> production train step.

Composes three individually-tested subsystems end to end:
``parallel/launch.py``'s hybrid DCN x ICI mesh, the DCN-aware planner
(``plan_for_mesh``), and ``parallel/train.py``'s full train step.  A
2-slice x 4-chip virtual system trains data-parallel over all 8 devices;
the planner picks the gradient-sync topology from the mesh's physical
shape (ICI-first ``(4, 2)``, WINS.md), the train step runs it, and the
result must match the native-psum sync bit-for-bit in loss — plus the
lowered HLO must contain exactly the per-axis grouped collectives the plan
promises (intra-slice groups then cross-slice pairs).

This is SURVEY §7's "mapping stage widths to the physical torus" — the
actual novelty of the retarget — exercised through the production path.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import own_copy
from jax.sharding import Mesh

from flextree_tpu.models.transformer import TransformerConfig
from flextree_tpu.parallel.launch import hybrid_mesh, plan_for_mesh
from flextree_tpu.parallel.train import (
    TrainConfig,
    init_train_state,
    make_train_step,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)

CFG = TransformerConfig(
    vocab_size=128, d_model=16, n_heads=2, n_layers=2, d_ff=32,
    dtype=jnp.float32,
)


@pytest.fixture(scope="module")
def setup():
    hmesh = hybrid_mesh(ici_shape=(4,), dcn_shape=(2,))
    # the planner sees the physical shape (ICI innermost) and must pick the
    # ICI-then-DCN hierarchy for large gradients
    plan = plan_for_mesh(hmesh, 256 << 20)
    assert plan.widths == (4, 2), plan.summary()
    # pure-DP training mesh over the SAME device order (slice-major), so
    # stage gaps land on the physical fabric the plan priced: gap-1 stage
    # inside a slice, gap-4 stage across slices
    mesh = Mesh(hmesh.devices.reshape(8, 1, 1), ("dp", "sp", "tp"))
    state = init_train_state(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, CFG.vocab_size, (16, 16)), jnp.int32)
    targets = jnp.asarray(rng.integers(0, CFG.vocab_size, (16, 16)), jnp.int32)
    return mesh, plan, state, tokens, targets


@pytest.mark.slow
def test_planner_picked_tree_sync_matches_psum(setup):
    mesh, plan, state, tokens, targets = setup
    tree_step = make_train_step(
        mesh, CFG, TrainConfig(lr=1e-3, grad_topo={"dp": plan.to_ft_topo()})
    )
    psum_step = make_train_step(mesh, CFG, TrainConfig(lr=1e-3, grad_topo="psum"))
    # the module's one state: every step takes a copy
    t_state, t_metrics = tree_step(own_copy(state), tokens, targets)
    p_state, p_metrics = psum_step(own_copy(state), tokens, targets)
    jax.block_until_ready((t_state, p_state))
    t_loss, p_loss = float(t_metrics["loss"]), float(p_metrics["loss"])
    assert np.isfinite(t_loss)
    assert abs(t_loss - p_loss) <= 1e-5 * max(1.0, abs(p_loss))
    # parameters after the update must agree too (the sync feeds AdamW)
    for tp_, pp_ in zip(
        jax.tree.leaves(t_state["params"]), jax.tree.leaves(p_state["params"])
    ):
        np.testing.assert_allclose(
            np.asarray(tp_), np.asarray(pp_), rtol=1e-5, atol=1e-6
        )


def test_lowered_step_has_per_axis_grouped_collectives(setup):
    mesh, plan, state, tokens, targets = setup
    step = make_train_step(
        mesh, CFG, TrainConfig(lr=1e-3, grad_topo={"dp": plan.to_ft_topo()})
    )
    ir = step.lower(state, tokens, targets).as_text()
    # stage 0: intra-slice groups (ICI); stage 1: cross-slice pairs (DCN)
    intra = r"replica_groups = dense<\[\[0, 1, 2, 3\], \[4, 5, 6, 7\]\]>"
    cross = r"replica_groups = dense<\[\[0, 4\], \[1, 5\], \[2, 6\], \[3, 7\]\]>"
    n_intra = len(re.findall(intra, ir))
    n_cross = len(re.findall(cross, ir))
    assert n_intra > 0, "no intra-slice grouped collectives in the train step"
    assert n_cross > 0, "no cross-slice grouped collectives in the train step"
    # the tree sync must not have degenerated to a flat 8-rank all_reduce
    # (the loss psum is the only legitimate full-axis all_reduce here).
    # Count per-op: a `.*?`+re.S match starting at one all_reduce could run
    # ACROSS a grouped op into a later op's full-axis attribute and
    # miscount (the attribute-spanning regex bug of test_hlo_lowering r2) —
    # so look for the group attribute only within each op's own text, which
    # for stablehlo.all_reduce ends at its reduction-region brace.
    full = [
        m
        for m in re.finditer(r'"?stablehlo\.all_reduce"?[^\n]*', ir)
        if "[[0, 1, 2, 3, 4, 5, 6, 7]]" in m.group(0)
    ]
    # exactly the loss psum: == 1 (not <= 1) also anchors the detector —
    # if an MLIR printer change moved the attribute dict off the op's
    # line, this would go to 0 and flag the regex instead of passing
    # vacuously while a degenerated flat gradient sync slips by
    assert len(full) == 1, f"{len(full)} flat 8-rank all_reduce ops (expect 1)"


def test_psum_oracle_lowering_differs(setup):
    """Sanity on the oracle itself: the psum-sync step must NOT contain the
    grouped two-stage pattern (otherwise the previous test proves nothing)."""
    mesh, plan, state, tokens, targets = setup
    step = make_train_step(mesh, CFG, TrainConfig(lr=1e-3, grad_topo="psum"))
    ir = step.lower(state, tokens, targets).as_text()
    cross = r"replica_groups = dense<\[\[0, 4\], \[1, 5\], \[2, 6\], \[3, 7\]\]>"
    assert not re.findall(cross, ir)
