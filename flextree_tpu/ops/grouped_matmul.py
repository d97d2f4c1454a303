"""A grouped matmul in row tiles that fit the rows an expert gets in a
decode round.

``lax.ragged_dot`` on XLA:TPU is a Mosaic grouped matmul whose tiles XLA
picks from the ROW COUNT alone: 512 rows for the 1,024 sorted picks of the
state cell's round, of which an expert gets four or five.  A grouped
matmul visits a row tile once for every group with rows in it and pushes
the whole tile through the MXU each time, so there the product is bound by
MXU work on masked rows (PERF.md section 6, PR 37).  :func:`grouped_matmul`
is the same product in row tiles of :data:`ROW_TILE`, for calls whose
static shapes say an expert gets a handful of rows
(:func:`runs_grouped_kernel`):

- the sorted rows in tiles of 32, the visits ``(row tile, group)`` that
  hold a row (:func:`group_visits`) scalar-prefetched, and the grid's
  second axis just as long as there are visits: a group with no row is
  never read, a tile past the last group's rows never touched;
- a weight block that is a group's WHOLE ``K`` by a slab of ``N`` as wide
  as :data:`_BLOCK_BYTES` allows (the whole matrix where it fits: one
  contiguous DMA of a few MB), so that a visit is one product with f32
  accumulation and no accumulator lives across grid steps;
- a row tile revisited for the next group while its output block is still
  in VMEM: the visits of a tile are consecutive, the first writes zeros
  where the group has no row, a later one keeps what is there;
- with ``w_gate`` the gated-SiLU inner product ``silu(x W_gate) * (x W)``,
  both products and the activation on one tile that never leaves VMEM.

Rows of tiles that no group reaches are NOT written (whatever memory
held): a caller selects them away, as ``moe.dropless_experts`` does rows
past the held experts' groups.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils import backend

__all__ = [
    "ROW_TILE", "MAX_ROWS_A_GROUP", "group_visits", "grouped_matmul",
    "grouped_kernel_admits", "runs_grouped_kernel",
]

#: rows of a tile: two sublane tiles of bfloat16.  On the v5e, the state
#: cell's round (1,024 sorted rows, 32 groups of 2304 x 1024): 8, 16 and
#: 32 rows read 0.367, 0.355 and 0.342 ms for gate, up and activation,
#: 0.202, 0.201 and 0.189 for down; 32 is also the least at Laguna's and
#: the latent cell's shapes, and at 128 rows a group 18% under 16
#: (PERF.md section 6, PR 37)
ROW_TILE = 32

#: the most rows a group may get, by a call's static shapes (rows over
#: groups), for the kernel to run: the state cell's round, the largest of
#: the three cells' decode shapes (32, 16 and 5).  NOT a crossover: with
#: this tile the kernel read under ``lax.ragged_dot`` at every shape timed,
#: up to 2,048 rows a group (PERF.md section 6, PR 37); a prefill's
#: products are left as they were, and want a row tile that grows with the
#: rows (ROADMAP S14)
MAX_ROWS_A_GROUP = 32

# the widest slab of N a weight block takes: K x slab x itemsize at or
# under this (each weight double-buffered: four such blocks in VMEM for the
# gated product).  The whole matrix at the state cell's and Laguna's widths
# (4.7 and 6.3 MB), 512 columns of the latent cell's 7,680 rows; on the v5e
# halves of these read the same within 2%, quarters (256 columns there) 6
# to 10% slower
_BLOCK_BYTES = 8 << 20


def grouped_kernel_admits(xs, w) -> bool:
    """Whether the kernel's tiling takes the product of sorted rows ``xs``
    (M, K) by the groups' matrices ``w`` (G, K, N), arrays or their
    shapes (and with it the product back, (M, N) by (G, N, K): an expert
    layer's inner and outer products are judged together): whole row
    tiles, both widths whole lane tiles, one float type of two or four
    bytes on both sides."""
    (m, k), (_, _, n) = xs.shape, w.shape
    dtype = jnp.dtype(xs.dtype)
    return (
        m % ROW_TILE == 0 and k % 128 == 0 and n % 128 == 0
        and dtype == jnp.dtype(w.dtype)
        and jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize in (2, 4)
    )


def runs_grouped_kernel(xs, w) -> bool:
    """Whether the grouped product of ``xs`` (M, K) by ``w`` (G, K, N)
    runs the Pallas kernel in this process: a TPU to lower for, shapes the
    tiling admits, and by the static shapes no more than
    :data:`MAX_ROWS_A_GROUP` rows a group, a decode round's handful.
    Above that (a prefill's sorted picks) ``lax.ragged_dot`` stays."""
    return (
        backend.kernel_platform() == "tpu" and grouped_kernel_admits(xs, w)
        and xs.shape[0] <= MAX_ROWS_A_GROUP * w.shape[0]
    )


def group_visits(sizes, rows: int):
    """The grid's work list for ``sizes`` (G,) int32 rows a group, groups
    one after another from row 0 of ``rows`` rows in tiles of
    :data:`ROW_TILE`:
    ``(group, tile, offsets, count)``.  ``group`` and ``tile`` (W,) int32
    name visit ``w``'s group and row tile, in row order, a visit for every
    pair that holds a row; ``offsets`` (G + 1,) the rows where the groups
    start; ``count`` () how many visits there are, at least one (with no
    row at all, one visit of an empty group, which writes a tile of
    zeros).  ``W = rows / ROW_TILE + G - 1`` bounds it: each tile once and
    each group start inside a tile once more."""
    g, tm = sizes.shape[0], ROW_TILE
    i32 = jnp.int32
    ends = jnp.cumsum(sizes.astype(i32), dtype=i32)
    starts = ends - sizes
    first = starts // tm
    visits = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(visits, dtype=i32)
    w = jnp.arange(rows // tm + g - 1, dtype=i32)
    group = jnp.minimum(
        jnp.searchsorted(visit_ends, w, side="right").astype(i32), g - 1
    )
    tile = first[group] + w - (visit_ends - visits)[group]
    tile = jnp.clip(tile, 0, rows // tm - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), i32), ends])
    return group, tile, offsets, jnp.maximum(visit_ends[-1], 1)


def _slab(k: int, n: int, itemsize: int) -> int:
    """Columns of a weight block: the most lane tiles that divide ``n``
    with ``k`` rows of them inside :data:`_BLOCK_BYTES`."""
    fits = [
        t * 128 for t in range(1, n // 128 + 1)
        if (n // 128) % t == 0 and k * t * 128 * itemsize <= _BLOCK_BYTES
    ]
    return max(fits, default=128)


def _kernel(group_ref, tile_ref, off_ref, x_ref, *refs, gated: bool):
    """Grid (slab of N, visit).  A visit multiplies its row tile by its
    group's block and keeps the group's rows of the result."""
    o_ref = refs[-1]
    w = pl.program_id(1)
    g, t = group_ref[w], tile_ref[w]
    x = x_ref[...]
    y = jnp.dot(x, refs[-2][...], preferred_element_type=jnp.float32)
    if gated:
        gate = jnp.dot(x, refs[0][...], preferred_element_type=jnp.float32)
        y = jax.nn.silu(gate) * y
    row = t * ROW_TILE + lax.broadcasted_iota(jnp.int32, y.shape, 0)
    mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
    y = y.astype(o_ref.dtype)
    # a tile's visits are consecutive, so its block is still here on a
    # later one; the first finds whatever VMEM held
    opens = (w == 0) | (tile_ref[jnp.maximum(w - 1, 0)] != t)

    @pl.when(opens)
    def _():
        o_ref[...] = jnp.where(mine, y, 0.0)

    @pl.when(jnp.logical_not(opens))
    def _():
        o_ref[...] = jnp.where(mine, y, o_ref[...])


@functools.partial(jax.jit, static_argnames=("tn", "interpret"))
def _grouped_pallas(xs, w, w_gate, visits, *, tn, interpret):
    group, tile, offsets, count = visits
    (m, k), tm = xs.shape, ROW_TILE
    n = w.shape[-1]
    gated = w_gate is not None
    tn = tn or _slab(k, n, w.dtype.itemsize)
    weights = (w_gate, w) if gated else (w,)
    weight = pl.BlockSpec(
        (None, k, tn), lambda j, i, group, tile, off: (group[i], 0, j)
    )
    out_dtype = xs.dtype if gated else jnp.float32
    block = k * tn * w.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, gated=gated),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n // tn, count),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, group, tile, off: (tile[i], 0)),
                *[weight] * len(weights),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, i, group, tile, off: (tile[i], j)
            ),
        ),
        # a tile's block is revisited: both axes in order.  Each weight's
        # block twice in the pipeline, and room for the products
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * len(weights) * block + (16 << 20),
        ),
        name="moe_grouped_matmul",
        interpret=interpret,
    )(group, tile, offsets, xs, *weights)


def grouped_matmul(xs, w, sizes, w_gate=None, *, visits=None,
                   tn: int | None = None):
    """``xs[rows of group g] @ w[g]`` for every group: ``xs`` (M, K) rows
    sorted by group, ``w`` (G, K, N), ``sizes`` (G,) int32 rows a group
    (their sum may fall short of M), operands in one float type, f32
    accumulation.  Returns (M, N) float32; with ``w_gate`` (G, K, N) the
    gated-SiLU inner product ``silu(xs @ w_gate[g]) * (xs @ w[g])`` in
    ``xs``'s type.  Rows inside a tile that a group reaches and past the
    groups' rows are zeros; tiles that no group reaches are not written.
    ``visits``: :func:`group_visits` of ``sizes``, for a caller that
    multiplies the same groups more than once.  ``tn``: the columns of a
    weight block (default: the widest slab :data:`_BLOCK_BYTES` takes),
    for the tests.  On the CPU the kernel runs interpreted
    (``utils.backend.pallas_interpret``)."""
    m, k = xs.shape
    g, _, n = w.shape
    if not grouped_kernel_admits(xs, w) or n % (tn or 128):
        raise ValueError(
            f"the grouped kernel takes whole row tiles of {ROW_TILE}, whole "
            f"lane tiles of K and N and one float type: got {m} x {k} "
            f"{xs.dtype} by {g} x {k} x {n} {w.dtype}, slabs of {tn}"
        )
    if visits is None:
        visits = group_visits(sizes, m)
    return _grouped_pallas(
        xs, w, w_gate, visits, tn=tn, interpret=backend.pallas_interpret()
    )
