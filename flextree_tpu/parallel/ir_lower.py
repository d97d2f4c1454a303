"""IR lowering: turn a verified ``schedule.ir.IRProgram`` into the jitted
collective.

``schedule.ir.compile_ir`` is the front door — it model-checks the program
and only then calls :func:`lower_ir` here.  Lowering adds the second
refusal: the program's stage list must equal its family's CANONICAL
emission (``_canonical_twin``), so the object the checker certified is
provably the object that runs — an IR/executable divergence is a compile
error, not a silent re-derivation (and the ``analysis.ir_equivalence``
pass independently re-checks the lowered StableHLO against the stage
list).

Lowering strategies per stage kind (the same calls
``parallel/allreduce.py`` makes today):

- **grouped** stages lower to one XLA grouped collective:
  ``lax.psum_scatter(axis_index_groups=stage.groups, tiled=True)`` for a
  sum reduce-scatter, ``lax.all_gather`` for the gather, and the
  ppermute-ring helpers for non-sum ops or prefix trees (lonely);
- **pair** stages lower to one ``lax.ppermute`` per send-slot: each rank
  gathers its declared block set, permutes, and folds (``sum``) or
  stores (``copy``) the received blocks — this is the generic executor
  the swing and generalized families run through (no per-family JAX
  code at all: the block-map IS the program);
- **ring-step** stages lower ROLLED: the 2(N-1) declarative steps
  compile to two ``fori_loop`` s of one ``ppermute`` each, exactly the
  legacy ring program (O(1) program size in N).

Chunk-pipelined trees: the IR's chunk tags declare the interleaving
(chunk ``c``'s allgather between chunk ``c+1``'s reduce-scatter and its
own); the executor replays that order with chunk sizes derived from the
live buffer (block-maps are size-independent — the program was checked
at a representative count, and every check is count-invariant).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax
import jax

from ..ops.reduce import get_op
from ..schedule import ir as sir
from ..schedule.ir import IRProgram, IRViolationError
from ..schedule.stages import LonelyTopology, Topology
from .allreduce import (
    _chunk_sizes,
    _grouped_allgather_generic,
    _grouped_reduce_scatter_generic,
    _groups_or_none,
    _jnp_fn,
    _small_dense_allreduce,
    _split_main_tail,
    _tree_keeps_shape,
    ring_allreduce,
)

__all__ = ["lower_ir"]


def _canonical_twin(prog: IRProgram) -> IRProgram:
    """Re-emit the program from its own family parameters."""
    if prog.family == "tree":
        return sir.tree_ir(prog.topo, count=prog.count, chunks=prog.chunks)
    if prog.family == "ring":
        return sir.ring_ir(prog.num_nodes, count=prog.count)
    if prog.family == "lonely":
        return sir.lonely_ir(prog.topo, count=prog.count)
    if prog.family == "swing":
        return sir.swing_ir(prog.num_nodes, count=prog.count)
    if prog.family == "generalized":
        return sir.generalized_ir(prog.widths, prog.ports, count=prog.count)
    raise IRViolationError(f"unknown IR family {prog.family!r}")


def _require_canonical(prog: IRProgram) -> None:
    """Refuse a program whose stages diverged from the canonical emission:
    the lowering below realizes exactly the canonical message pattern, so
    running a divergent (even if individually verified) stage list would
    silently execute something other than what was declared."""
    twin = _canonical_twin(prog)
    if prog.stages != twin.stages or prog.scheduled != twin.scheduled:
        raise IRViolationError(
            f"IR/executable divergence: {prog} does not match the canonical "
            f"{prog.family} emission — refusing to lower a stage list the "
            f"executor would not faithfully realize"
        )


# ----------------------------------------------------------- pair stages


def _pair_slots(st: "sir.IRStage"):
    """Split a pair stage's transfers into send-slots: slot ``j`` holds
    every rank's ``j``-th transfer (a generalized round with ``ports=p``
    has ``p`` slots; swing/fold/restore have one).  Every slot is one
    ``ppermute`` with a uniform payload shape."""
    per_src: dict[int, list] = {}
    for x in st.xfers:
        per_src.setdefault(x.src, []).append(x)
    n_slots = max(len(v) for v in per_src.values())
    return [
        [v[j] for v in per_src.values() if len(v) > j] for j in range(n_slots)
    ]


def _pair_block_exchange(blocks_view, axis_name, st, num_nodes, fold_fn):
    """Execute one pair stage on the ``(m, tile)`` block view: per slot,
    gather each rank's declared blocks, ``ppermute``, fold or store at
    the receiver's declared indices.  Ranks outside the permutation
    receive zeros and (for ``copy``) may clobber scratch blocks — they
    are, by construction, ranks whose data is restored afterwards."""
    idx = lax.axis_index(axis_name)
    for slot in _pair_slots(st):
        k = len(slot[0].blocks)
        send_idx = np.zeros((num_nodes, k), dtype=np.int32)
        recv_idx = np.zeros((num_nodes, k), dtype=np.int32)
        perm = []
        for x in slot:
            send_idx[x.src] = x.blocks
            recv_idx[x.dst] = x.blocks
            perm.append((x.src, x.dst))
        my_send = jnp.take(jnp.asarray(send_idx), idx, axis=0)
        payload = jnp.take(blocks_view, my_send, axis=0)
        got = lax.ppermute(payload, axis_name, perm)
        my_recv = jnp.take(jnp.asarray(recv_idx), idx, axis=0)
        if st.combine == sir.SUM:
            cur = jnp.take(blocks_view, my_recv, axis=0)
            blocks_view = blocks_view.at[my_recv].set(fold_fn(cur, got))
        else:
            blocks_view = blocks_view.at[my_recv].set(got)
    return blocks_view


def _pair_family_exec(x, axis_name, prog: IRProgram, rop):
    """The generic executor for pair-stage families (swing, generalized):
    head/tail split over the ``scheduled`` block owners, whole-buffer
    fold/restore hops for the non-power-of-two extras, block-map pair
    exchanges for everything else."""
    if rop.name != "sum":
        raise NotImplementedError(
            f"IR family {prog.family!r} lowers op='sum' only (got {rop.name!r})"
        )
    fn = _jnp_fn(rop)
    m = prog.scheduled
    idx = lax.axis_index(axis_name)
    shape = x.shape
    v = x.reshape(-1)
    head, tail = _split_main_tail(v, m)
    parts = []
    if head is not None:
        tile = head.shape[0] // m
        for st in prog.stages:
            if st.phase == "fold":
                with jax.named_scope(f"ft_{prog.family}_fold"):
                    perm = [(x_.src, x_.dst) for x_ in st.xfers]
                    extras = len(perm)
                    got = lax.ppermute(head, axis_name, perm)
                    head = jnp.where(idx < extras, fn(head, got), head)
            elif st.phase == "restore":
                with jax.named_scope(f"ft_{prog.family}_restore"):
                    perm = [(x_.src, x_.dst) for x_ in st.xfers]
                    got = lax.ppermute(head, axis_name, perm)
                    head = jnp.where(idx >= m, got, head)
            else:
                scope = f"ft_{prog.family}_{st.phase}_stage{st.index}"
                with jax.named_scope(scope):
                    view = head.reshape(m, tile)
                    view = _pair_block_exchange(
                        view, axis_name, st, prog.num_nodes, fn
                    )
                    head = view.reshape(-1)
        parts.append(head)
    if tail is not None:
        parts.append(_small_dense_allreduce(tail, axis_name, rop))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out.reshape(shape)


# ---------------------------------------------------------- tree / lonely


def _tree_rs_from_stages(v, axis_name, stages, topo: Topology, rop):
    """Phase 1 driven by the IR's grouped rs stage rows — the same
    ``psum_scatter``/``ppermute-ring`` calls ``_tree_reduce_scatter``
    makes, with the groups read off the stage records."""
    for st in stages:
        w = topo.widths[st.index]
        with jax.named_scope(f"ft_rs_stage{st.index}_w{w}"):
            if rop.name == "sum":
                v = lax.psum_scatter(
                    v,
                    axis_name,
                    scatter_dimension=0,
                    axis_index_groups=_groups_or_none(topo, st.index),
                    tiled=True,
                )
            else:
                v = _grouped_reduce_scatter_generic(
                    v, axis_name, topo, st.index, rop
                )
    return v


def _tree_ag_from_stages(v, axis_name, stages, topo: Topology):
    for st in stages:
        w = topo.widths[st.index]
        with jax.named_scope(f"ft_ag_stage{st.index}_w{w}"):
            v = lax.all_gather(
                v,
                axis_name,
                axis_index_groups=_groups_or_none(topo, st.index),
                axis=0,
                tiled=True,
            )
    return v


def _tree_exec(x, axis_name, prog: IRProgram, rop):
    """The tree program: the stages on ``x`` in its own shape where its
    leading dimension divides, else chunk-interleaved grouped stages on the
    flat view with a head/tail split — trace-for-trace what
    ``tree_allreduce`` emits (the golden suite holds the compiled HLO
    equal)."""
    topo: Topology = prog.topo
    n = topo.num_nodes
    rs_stages = [s for s in prog.stages if s.phase == "rs" and s.chunk == 0]
    ag_stages = [s for s in prog.stages if s.phase == "ag" and s.chunk == prog.chunks - 1]
    if _tree_keeps_shape(x, n, prog.chunks):
        h = _tree_rs_from_stages(x, axis_name, rs_stages, topo, rop)
        return _tree_ag_from_stages(h, axis_name, ag_stages, topo)
    shape = x.shape
    head, tail = _split_main_tail(x, n)
    parts = []
    if head is not None:
        sizes = _chunk_sizes(head.size, n, prog.chunks)
        if len(sizes) == 1:
            h = _tree_rs_from_stages(head, axis_name, rs_stages, topo, rop)
            parts.append(_tree_ag_from_stages(h, axis_name, ag_stages, topo))
        else:
            pieces, off = [], 0
            for s in sizes:
                pieces.append(head[off : off + s])
                off += s
            outs, scattered = [], None
            for c, piece in enumerate(pieces):
                with jax.named_scope(f"ft_chunk{c}_rs"):
                    cur = _tree_rs_from_stages(
                        piece, axis_name, rs_stages, topo, rop
                    )
                if scattered is not None:
                    with jax.named_scope(f"ft_chunk{c - 1}_ag"):
                        outs.append(
                            _tree_ag_from_stages(
                                scattered, axis_name, ag_stages, topo
                            )
                        )
                scattered = cur
            with jax.named_scope(f"ft_chunk{len(pieces) - 1}_ag"):
                outs.append(
                    _tree_ag_from_stages(scattered, axis_name, ag_stages, topo)
                )
            parts.append(jnp.concatenate(outs))
    if tail is not None:
        parts.append(_small_dense_allreduce(tail, axis_name, rop))
    v = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return v.reshape(shape)


def _lonely_exec(x, axis_name, prog: IRProgram, rop):
    """The lonely program driven off its IR stages: fold hop, prefix-tree
    grouped stages (always the ppermute-ring helpers — XLA's grouped
    collectives cannot cover a rank subset), restore hop — trace-for-
    trace ``lonely_allreduce``."""
    topo: LonelyTopology = prog.topo
    tree, m = topo.tree, topo.tree.num_nodes
    fn = _jnp_fn(rop)
    idx = lax.axis_index(axis_name)
    shape = x.shape
    v = x.reshape(-1)
    head, tail = _split_main_tail(v, m)
    parts = []
    if head is not None:
        for st in prog.stages:
            if st.phase == "fold":
                with jax.named_scope("ft_lonely_fold"):
                    perm = [(x_.src, x_.dst) for x_ in st.xfers]
                    got = lax.ppermute(head, axis_name, perm)
                    head = jnp.where(idx < len(perm), fn(head, got), head)
            elif st.phase == "rs":
                w = tree.widths[st.index]
                with jax.named_scope(f"ft_lonely_rs_stage{st.index}_w{w}"):
                    head = _grouped_reduce_scatter_generic(
                        head, axis_name, tree, st.index, rop
                    )
            elif st.phase == "ag":
                w = tree.widths[st.index]
                with jax.named_scope(f"ft_lonely_ag_stage{st.index}_w{w}"):
                    head = _grouped_allgather_generic(
                        head, axis_name, tree, st.index
                    )
            else:  # restore
                with jax.named_scope("ft_lonely_restore"):
                    perm = [(x_.src, x_.dst) for x_ in st.xfers]
                    got2 = lax.ppermute(head, axis_name, perm)
                    head = jnp.where(idx >= m, got2, head)
        parts.append(head)
    if tail is not None:
        parts.append(_small_dense_allreduce(tail, axis_name, rop))
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    return out.reshape(shape)


# ----------------------------------------------------------------- entry


def lower_ir(prog: IRProgram, op: str = "sum"):
    """Lower a (verified) IR program; returns ``f(x, axis_name) -> x``.

    Call only through ``schedule.ir.compile_ir`` — this function assumes
    the model checks already ran; it re-checks only the canonical-twin
    structural equality (the IR/executable-divergence guard)."""
    _require_canonical(prog)
    rop = get_op(op)

    if prog.family == "tree":
        return lambda x, axis_name: _tree_exec(x, axis_name, prog, rop)
    if prog.family == "lonely":
        return lambda x, axis_name: _lonely_exec(x, axis_name, prog, rop)
    if prog.family == "ring":
        # the 2(N-1) ring-step stages compile ROLLED: two fori_loops of
        # one ppermute each (the canonical-twin check above pinned the
        # declarative walk to the reference block schedule)
        return lambda x, axis_name: ring_allreduce(x, axis_name, op=rop)
    return lambda x, axis_name: _pair_family_exec(x, axis_name, prog, rop)
