"""Pallas TPU kernel: fold W source buffers into one, elementwise.

The TPU rebuild of the reference's local reduction kernels ``reduce_sum`` /
``reduce_band`` (``allreduce_over_mpi/mpi_mod.hpp:246-660``): there, an
OpenMP ``parallel for simd`` over up to 20 sources with a hand-unrolled
switch per source count; here, a single VPU kernel tiled over the payload,
streaming one native 2D ``(rows_tile, 128)`` tile per source HBM->VMEM and
folding it into a VMEM-resident accumulator that is written back once per
output tile.  XLA fuses this pattern well on its own —
the kernel exists because the local reduce is the allreduce's only compute
(SURVEY §3.2 "HOT LOOP") and a hand-tiled kernel both pins the layout and
gives the benchmark a deterministic HBM-bandwidth probe on one chip.

The op set mirrors the ``handle_reduce`` dispatch (``mpi_mod.hpp:825-874``):
sum + the bitwise/lattice family, validated against the same dtype matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.backend import pallas_interpret
from .reduce import get_op

__all__ = ["reduce_stacked", "reduce_stacked_reference"]

_LANE = 128


def _kernel(x_ref, o_ref, *, jnp_name: str, sources_tile: int):
    # Grid is (row_tiles, source_groups) with the source axis fastest; the
    # output block's index map ignores the source axis, so Pallas keeps the
    # tile resident in VMEM across all accumulation steps and writes it
    # back to HBM once.  Each step streams ``sources_tile`` native 2D
    # (rows_tile, 128) tiles (one 3D block) and folds them with a statically
    # unrolled tree before touching the accumulator — fewer grid steps and
    # larger DMAs per step than the sources_tile=1 layout, same (W+1)·L
    # traffic.
    from jax.experimental import pallas as pl

    fn = getattr(jnp, jnp_name)
    j = pl.program_id(1)
    vals = [x_ref[t] for t in range(sources_tile)]
    while len(vals) > 1:  # pairwise: dependency depth log2(st), not st-1
        vals = [
            fn(vals[t], vals[t + 1]) if t + 1 < len(vals) else vals[t]
            for t in range(0, len(vals), 2)
        ]
    acc = vals[0]

    @pl.when(j == 0)
    def _init():
        o_ref[:] = acc

    @pl.when(j != 0)
    def _fold():
        o_ref[:] = fn(o_ref[:], acc)


def reduce_stacked_reference(x: jax.Array, op="sum") -> jax.Array:
    """Pure-jnp oracle: fold ``x[(W, L)]`` over axis 0 with ``op``."""
    rop = get_op(op)
    fn = getattr(jnp, rop.jnp_name)
    acc = x[0]
    for j in range(1, x.shape[0]):
        acc = fn(acc, x[j])
    return acc


@functools.partial(
    jax.jit, static_argnames=("op", "rows_tile", "sources_tile", "interpret")
)
def reduce_stacked(
    x: jax.Array,
    op: str = "sum",
    rows_tile: int = 512,
    sources_tile: int = 1,
    interpret: bool | None = None,
) -> jax.Array:
    """Reduce ``x`` of shape ``(W, L)`` over axis 0 -> ``(L,)`` on the VPU.

    ``L`` is padded internally to a multiple of ``rows_tile * 128`` with the
    op identity (like the schedule layer pads to ``data_size_aligned``,
    ``mpi_mod.hpp:232``).  ``interpret=None`` runs the kernel on a TPU
    and the Pallas interpreter on the CPU (``utils.backend.pallas_interpret``).

    ``sources_tile`` folds that many sources per grid step (a 3D input
    block) — a DMA-granularity/step-count tuning knob with identical
    traffic and results equal up to f32 reassociation (the grouped fold
    changes the reduction order; exact for the bitwise/lattice ops);
    silently clamped to ``gcd(sources_tile, W)`` so any W stays valid.
    """
    from jax.experimental import pallas as pl

    rop = get_op(op)
    rop.check_dtype(x.dtype)
    if x.ndim != 2:
        raise ValueError(f"expected (num_sources, length), got {x.shape}")
    w, length = x.shape
    if w == 1:
        return x[0]
    interpret = pallas_interpret(interpret)
    st = np.gcd(int(sources_tile), w) if sources_tile else 1

    chunk = rows_tile * _LANE
    padded = -(-length // chunk) * chunk
    if padded != length:
        pad_val = rop.identity_for(x.dtype)
        x = jnp.pad(x, ((0, 0), (0, padded - length)), constant_values=pad_val)
    rows = padded // _LANE
    x3 = x.reshape(w, rows, _LANE)

    out = pl.pallas_call(
        functools.partial(_kernel, jnp_name=rop.jnp_name, sources_tile=st),
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), x.dtype),
        grid=(rows // rows_tile, w // st),
        in_specs=[
            pl.BlockSpec((st, rows_tile, _LANE), lambda i, j: (j, i, 0)),
        ],
        out_specs=pl.BlockSpec((rows_tile, _LANE), lambda i, j: (i, 0)),
        interpret=interpret,
    )(x3)
    return out.reshape(padded)[:length]
