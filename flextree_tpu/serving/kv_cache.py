"""Paged/blocked KV cache: ragged sequences share one static-shaped pool.

The contiguous cache in ``models.generate`` allocates ``max_len`` slots
per sequence up front; a serving batch of ragged lengths wastes most of
that and, worse, couples every sequence's lifetime to the batch's.  The
paged layout breaks the coupling the way vLLM's PagedAttention does:

- the pool is per layer ``(num_blocks, block_size, *row)`` — one static
  shape for the whole server lifetime, so the decode step stays ONE
  compiled program regardless of which sequences are resident.  What a
  ``row`` (one cached position) is comes from the configuration, a layer
  at a time (``models.configs.pool_layout``: the parts a layer caches a
  POSITION, and the parts it holds a SLOT): K and V of ``(H, Dh)`` each
  in every layer of the dense and the Laguna block, ONE array of
  ``(kv_rank + d_rope,)`` for latent attention, and nothing at all under a
  linear-attention layer.  The pools are ``{part: [array a layer that
  caches it]}``, and every pool function below works on whatever parts
  and however many layers they name;
- what a layer holds a slot (a recurrent layer's state: one array of a
  fixed size a sequence, whatever its length) is the STATE, ``{part:
  [(slots, *shape) a layer that holds it]}`` (:func:`init_state`),
  beside the pools and never in blocks: the prefill hands a sequence's
  out whole and :func:`write_state` puts it in the slot's place, the
  decode program takes the state and returns it with the active slots'
  updated in place, a swap and a migration carry a slot's whole
  (:func:`read_state`).  ``{}`` for a block that keeps nothing a slot;
- each sequence owns a **block table** (a row of block ids): block
  ``p`` of the table holds cache positions ``p*block_size ..``; tables
  are plain int32 inputs to the jitted step, so the host can remap them
  between steps without recompiling;
- a host-side :class:`BlockAllocator` (LIFO free list) hands blocks out
  at admission and takes them back at retirement — freeing is O(blocks),
  immediate, and per sequence.

Block id 0 is the **null block**: never allocated, it pads every table
row past the sequence's reserved blocks.  Gathered null-block content is
always beyond the causal bound, where ``cached_attention``'s mask drives
the softmax weight to exactly 0.0 in f32 — so whatever the null block
holds contributes exactly nothing, and the paged decode stays **bitwise
identical** to the contiguous-cache decode (the property
``tests/test_serving.py::test_null_block_content_is_invisible`` pins).

The decode step has two attention paths behind a ``fused=`` switch:

- **gather** (``fused=False``) — gather the table's blocks into a
  per-row contiguous (S, P*block_size, H, Dh) view, run exactly the
  ``models.generate`` math (shared helpers, not copies — the bitwise
  contract depends on one definition), and scatter the newly produced
  K/V back into each row's current block.  This is the correctness
  ORACLE: it is the path proven bitwise against ``generate``.
- **fused** (``fused=True``) — ``ops.paged_attention`` walks the block
  table with an online-softmax accumulator, reading K/V straight from
  the pools and never materializing the (S, P*bs, H, Dh) view, and
  stops at the batch's causal frontier instead of the full table width.
  Identical masking, different floating-point summation order: gated
  against the gather oracle within ``ops.paged_attention.
  FUSED_DECODE_ATOL`` (``tests/test_paged_attention.py``), not bitwise.

Either way all phases live in one jitted function with the pool buffers
donated, so steady-state decode is two compiled programs total (prefill
+ paged decode), same as the contiguous path.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..models.configs import block_of, position_parts, slot_parts
from ..ops.paged_attention import put_blocks, take_blocks

__all__ = [
    "NULL_BLOCK",
    "CacheExhausted",
    "PagedCacheConfig",
    "BlockAllocator",
    "init_pools",
    "init_state",
    "write_state",
    "read_state",
    "write_prefill",
    "write_prefill_at",
    "write_swapped",
    "paged_decode_step",
    "make_paged_decode_fn",
    "decode_attention_layers",
    "gather_seq",
    "export_blocks",
    "write_imported",
]

#: Block id 0 is reserved: it pads table rows and is never allocated.
NULL_BLOCK = 0


class CacheExhausted(RuntimeError):
    """The allocator cannot satisfy a reservation — the admission layer's
    signal to keep the request queued.  ``code`` is the stable taxonomy
    tag, same pattern as ``FT_INIT_TIMEOUT`` / ``FT_STEP_TIMEOUT``."""

    code = "FT_CACHE_EXHAUSTED"

    def __init__(self, want: int, free: int):
        self.want, self.free = want, free
        super().__init__(
            f"{self.code}: need {want} cache blocks, {free} free"
        )


@dataclasses.dataclass(frozen=True)
class PagedCacheConfig:
    """Shape of the paged pool.  ``num_blocks`` counts the null block, so
    ``num_blocks - 1`` are allocatable; ``blocks_per_seq`` is the block
    table width P — the longest admissible sequence is ``max_len =
    block_size * blocks_per_seq`` tokens (prompt + generated)."""

    num_blocks: int
    block_size: int = 16
    blocks_per_seq: int = 8

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is reserved)")
        if self.block_size < 1 or self.blocks_per_seq < 1:
            raise ValueError("block_size and blocks_per_seq must be >= 1")

    @property
    def max_len(self) -> int:
        return self.block_size * self.blocks_per_seq

    def blocks_for(self, tokens: int) -> int:
        """Blocks needed to hold ``tokens`` cache positions."""
        return -(-tokens // self.block_size)


class BlockAllocator:
    """Host-side LIFO free list over block ids ``1..num_blocks-1``, with
    per-block reference counts for cross-request prefix sharing.

    LIFO keeps the working set of pool pages hot; double frees and
    foreign ids are loud errors (a silently double-freed block would be
    handed to two sequences and corrupt both).

    Refcount semantics: ``alloc`` hands blocks out at refcount 1;
    ``retain`` adds a holder (a second sequence sharing a cached prefix
    block, or the prefix index adopting a retired prompt block);
    ``release`` drops one holder and the free list regains the block only
    when the count reaches 0.  ``free`` keeps its historical meaning —
    "this block is exclusively mine and I am done" — and is LOUD when the
    block is shared (freeing a shared block out from under its other
    holders is exactly the corruption refcounts exist to prevent).
    ``fork_block`` is the copy-on-write primitive: given a SHARED block,
    it allocates a private twin for the caller to copy into; the caller
    then releases its reference on the shared original."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is reserved)")
        self.num_blocks = num_blocks
        self._free = list(range(num_blocks - 1, 0, -1))  # pop() yields 1 first
        self._allocated: set[int] = set()
        self._refcount: dict[int, int] = {}

    @property
    def num_free(self) -> int:
        return len(self._free)

    def refcount(self, block: int) -> int:
        """Current holder count (0 for free / never-allocated ids)."""
        return self._refcount.get(block, 0)

    def alloc(self, n: int) -> list[int]:
        """Take ``n`` blocks or raise :class:`CacheExhausted` (taking
        nothing — admission is all-or-nothing per request).  Each block
        comes out at refcount 1."""
        if n < 0:
            raise ValueError(f"cannot allocate {n} blocks")
        if n > len(self._free):
            raise CacheExhausted(n, len(self._free))
        out = [self._free.pop() for _ in range(n)]
        self._allocated.update(out)
        for b in out:
            self._refcount[b] = 1
        return out

    def retain(self, blocks) -> None:
        """Add one holder to each block (all must be allocated)."""
        blocks = list(blocks)
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(
                    f"cannot retain block {b}: not allocated"
                )
        for b in blocks:
            self._refcount[b] += 1

    def release(self, blocks) -> None:
        """Drop one holder from each block; a block returns to the free
        list only when its refcount reaches 0.  Duplicate ids and
        non-allocated blocks are loud — releasing the same block twice in
        one call would silently drop a holder someone else still is."""
        blocks = list(blocks)
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"duplicate block ids in release(): {blocks}")
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(
                    f"block {b} is not allocated (double release or "
                    f"foreign id)"
                )
        for b in blocks:
            self._refcount[b] -= 1
            if self._refcount[b] == 0:
                del self._refcount[b]
                self._allocated.remove(b)
                self._free.append(b)

    def free(self, blocks) -> None:
        """Return exclusively-held blocks to the free list.  Loud on
        duplicates, foreign ids, AND shared blocks — a holder that thinks
        it owns a shared block outright has a refcount bug upstream."""
        blocks = list(blocks)
        if len(set(blocks)) != len(blocks):
            raise ValueError(f"duplicate block ids in free(): {blocks}")
        for b in blocks:
            if b not in self._allocated:
                raise ValueError(
                    f"block {b} is not allocated (double free or foreign id)"
                )
            if self._refcount[b] != 1:
                raise ValueError(
                    f"block {b} is shared (refcount "
                    f"{self._refcount[b]}); use release(), not free()"
                )
        for b in blocks:
            del self._refcount[b]
            self._allocated.remove(b)
            self._free.append(b)

    def fork_block(self, src: int) -> int:
        """Copy-on-write fork: allocate a private twin for SHARED block
        ``src``.  The caller copies the pool contents (or re-derives them
        bitwise, as the suffix prefill does) into the returned block and
        then releases its own reference on ``src``.  Forking a private
        block is a loud error — a refcount-1 block needs no COW, and a
        caller asking for one has lost track of who shares what."""
        if src not in self._allocated:
            raise ValueError(f"cannot fork block {src}: not allocated")
        if self._refcount[src] < 2:
            raise ValueError(
                f"cannot fork block {src}: refcount "
                f"{self._refcount[src]} (not shared — write in place)"
            )
        return self.alloc(1)[0]


def init_pools(cfg, pcfg: PagedCacheConfig) -> dict:
    """``{part: [(num_blocks, block_size, *row) a layer that caches it]}``,
    zeros in the compute dtype: the parts, their rows and their layers are
    the configuration's (``models.configs.position_parts``) — K and V of
    ``(Hkv, Dh)`` in every layer of the dense block (``Hkv`` its
    ``n_heads``) and the Laguna block, one ``ckv`` of ``(kv_rank +
    d_rope,)`` under each latent-attention layer."""
    return {
        part: [
            jnp.zeros((pcfg.num_blocks, pcfg.block_size, *row), cfg.dtype)
            for _ in range(layers)
        ]
        for part, (row, layers) in position_parts(cfg).items()
    }


def init_state(cfg, slots: int) -> dict:
    """``{part: [(slots, *shape) a layer that holds it]}``, zeros in each
    part's own dtype (``models.configs.slot_parts``): what the layers hold
    a slot and not a position.  ``{}`` for a block that holds nothing so."""
    return {
        part: [jnp.zeros((slots, *shape), dtype) for _ in range(layers)]
        for part, ((shape, dtype), layers) in slot_parts(cfg).items()
    }


def write_state(state: dict, carried: dict, slot) -> dict:
    """Put one sequence's state in ``slot``'s place: ``carried`` is per
    part and layer ``(1, *shape)`` (a prefill's ``cache["state"]``, a
    swapped or a migrated sequence's), and replaces the slot's whole.  A
    slot's state is never cleared on its own: whoever admits a sequence
    writes what that sequence carries (a prefill's starts from zeros)."""
    return {
        part: [
            a.at[slot].set(c[0].astype(a.dtype))
            for a, c in zip(layers, carried[part])
        ]
        for part, layers in state.items()
    }


def read_state(state: dict, slot) -> dict:
    """One slot's state, per part and layer ``(1, *shape)``: what
    :func:`write_state` takes back."""
    return {
        part: [a[slot][None] for a in layers] for part, layers in state.items()
    }


def _map_pools(fn, pools: dict, source: dict) -> dict:
    """``fn(pool, source array)`` over every part and layer of ``pools``;
    ``source`` holds the same parts (and may hold more: a prefill's cache
    has its lengths beside them)."""
    return {
        part: [fn(p, a) for p, a in zip(layers, source[part])]
        for part, layers in pools.items()
    }


def write_prefill(pools: dict, cache: dict, block_ids) -> dict:
    """Scatter a single-sequence contiguous prefill cache into the pool.

    ``cache`` is ``prefill``'s output for a batch of ONE (per part and
    layer (1, max_len, *row) with zeros past the prompt); the first
    ``len(block_ids) * block_size`` positions land in ``block_ids`` in
    order.  Positions past the prompt scatter zeros — the same zeros the
    contiguous cache holds there, which the decode writes then fill in.
    """
    return write_prefill_at(pools, cache, block_ids, 0)


def write_prefill_at(pools: dict, cache: dict, block_ids,
                     start_block: int) -> dict:
    """Scatter a prefill cache's positions FROM ``start_block * bs``
    onward into ``block_ids`` — the suffix half of a prefix-cache hit.

    ``cache`` is ``prefill_suffix``'s output for a batch of ONE: its
    positions below ``start_block * bs`` belong to CACHED blocks this
    call must never rewrite (they may be shared with other sequences), so
    only the slice ``[start_block*bs, (start_block + len(block_ids))*bs)``
    is scattered.  ``start_block`` must be static (it selects a slice at
    trace time); the engine jits this with ``static_argnums``.
    """
    idx = jnp.asarray(block_ids, jnp.int32)
    n = int(idx.shape[0])
    if start_block < 0:
        raise ValueError(f"start_block must be >= 0, got {start_block}")

    def scatter(pool, c):
        bs = pool.shape[1]
        s0 = start_block * bs
        if c.shape[1] < s0 + n * bs:
            raise ValueError(
                f"prefill cache holds {c.shape[1]} positions, blocks "
                f"{start_block}..{start_block + n} need {s0 + n * bs}"
            )
        return put_blocks(
            pool, idx, c[0, s0 : s0 + n * bs].reshape(n, bs, *pool.shape[2:])
        )

    return _map_pools(scatter, pools, cache)


def write_swapped(pools: dict, kv: dict, block_ids) -> dict:
    """Scatter a swapped-out sequence's saved rows back into newly
    assigned blocks — the resume half of preemption.

    ``kv`` is per part and layer ``(n*bs, *row)`` with exactly
    ``len(block_ids) * block_size`` positions (the engine pads the saved
    ``length`` positions with zeros host-side).  The pad positions sit at
    or past the sequence's causal bound, so — the same argument as
    ``write_prefill``'s over-scatter — they are invisible until the
    decode writes overwrite them.  The restored bytes are the exact bytes
    ``gather_seq`` saved, which is what makes swap-in resume
    bit-identical.
    """
    idx = jnp.asarray(block_ids, jnp.int32)
    n = idx.shape[0]

    def scatter(pool, a):
        bs = pool.shape[1]
        if a.shape[0] != n * bs:
            raise ValueError(
                f"swapped rows hold {a.shape[0]} positions, "
                f"{n} blocks need {n * bs}"
            )
        return put_blocks(pool, idx, a.reshape(n, bs, *pool.shape[2:]))

    return _map_pools(scatter, pools, kv)


def check_decode_impl(impl: str) -> None:
    """``decode_impl`` once chose between the loop and a kernel that could
    not lower; the choice is ``ops.paged_attention``'s now.  The key is
    still accepted wherever it was, so it is still checked."""
    if impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown paged-attention impl {impl!r}")


def decode_attention_layers(cfg, pcfg: PagedCacheConfig,
                            fused: bool = True) -> tuple:
    """``(attention layers in the decode program, those of them that run
    the Pallas kernel)``, as :func:`paged_decode_step` will build them
    for this configuration in this process: fixed by the backend and the
    shapes, so known before the program is traced."""
    layers, kernel = block_of(cfg).kernel_layers(cfg, pcfg)
    return layers, kernel if fused else 0


def paged_decode_step(params, pools, tables, lengths, tokens, cfg,
                      fused: bool = False, impl: str = "jnp", state=None):
    """One decode step for S slots over the paged pool.

    ``tables`` (S, P) int32 block tables, ``lengths`` (S,) int32 cache
    positions already filled per slot, ``tokens`` (S,) int32 the token to
    decode at each slot's position.  Returns ``(logits, pools)`` — (S,
    vocab) f32 next-position logits and the pool with each slot's new row
    scattered at ``(tables[s, lengths[s]//bs], lengths[s] % bs)`` — and,
    from a block with routed experts, a third result: what its routers
    did.  A block that holds something a slot takes it as ``state``
    (:func:`init_state`) and returns it last, the active slots' updated and
    every other slot's as it was.

    Inactive slots are driven with table rows of all-NULL_BLOCK and
    length 0: their writes land in the null block and their logits are
    garbage the host discards; active rows never reference the null block
    below their causal bound, so pollution there is invisible (masked
    weights are exactly 0.0 — see the module docstring).

    The walk over the layers is the block's own (``models.configs.
    BLOCKS``: ``models.generate.paged_decode_dense``, ``models.laguna.
    paged_decode_step``, ``models.pangu_ultra_moe.paged_decode_step``).
    ``fused=False`` attends through the gather oracle of
    ``ops.paged_attention``, ``fused=True`` through its streamed entry:
    same masking, online-softmax summation order, within
    ``FUSED_DECODE_ATOL`` of the oracle.  Which of the streamed paths runs
    (the Pallas kernel on a TPU, the block-streaming loop elsewhere) is
    ``ops.paged_attention``'s to decide from the backend and the shapes;
    ``impl`` ("jnp" or "pallas") is accepted, checked and passed nowhere:
    the benchmark's traffic files still carry the key (ROADMAP D3).
    """
    check_decode_impl(impl)
    step = block_of(cfg).decode_step
    if state:
        return step(params, pools, tables, lengths, tokens, cfg, fused,
                    state=state)
    return step(params, pools, tables, lengths, tokens, cfg, fused)


def make_paged_decode_fn(cfg, donate: bool = True,
                         fused: bool = False, impl: str = "jnp"):
    """Jit ``paged_decode_step`` with the pool buffers donated (the old
    pool is dead the moment the new one exists — donation keeps steady-
    state decode allocation-free).  ``fused=`` selects the attention
    path (see :func:`paged_decode_step`); ``impl`` is checked here, at
    construction, and selects nothing.  For a block that holds something a
    slot the program takes the state as a sixth argument, donated too, and
    returns it last.  For a block that computes B > 1 positions a slot a
    pass (``Block.block_len``: a diffusion block) the program is its pass,
    ``(params, pools, tables, lengths, tokens (S, B), active (S,))``."""
    check_decode_impl(impl)
    block = block_of(cfg)
    if block.block_len(cfg) > 1:
        def paged_decode_block_pass(params, pools, tables, lengths, tokens,
                                    active):
            return block.decode_step(
                params, pools, tables, lengths, tokens, cfg, fused,
                active=active,
            )

        return jax.jit(
            paged_decode_block_pass, donate_argnums=(1,) if donate else ()
        )
    if not slot_parts(cfg):
        # a named ``def`` (a ``partial`` has no name, and its program is
        # ``jit__unknown`` in a profile): every block's decode program
        # holds ``paged_decode`` in its name, where the benchmark finds it
        def paged_decode_program(params, pools, tables, lengths, tokens):
            return paged_decode_step(
                params, pools, tables, lengths, tokens, cfg, fused
            )

        return jax.jit(
            paged_decode_program, donate_argnums=(1,) if donate else ()
        )

    def paged_decode_step_with_state(params, pools, tables, lengths, tokens,
                                     state):
        return paged_decode_step(
            params, pools, tables, lengths, tokens, cfg, fused, state=state
        )

    return jax.jit(
        paged_decode_step_with_state,
        donate_argnums=(1, 5) if donate else (),
    )


def export_blocks(pools: dict, block_ids) -> dict:
    """Pull a sequence's blocks out of the pool at BLOCK granularity —
    per part and layer ``(n, bs, *row)`` — for migration to another
    replica.

    This is deliberately NOT :func:`gather_seq`: no ``(n*bs, *row)``
    contiguous view is ever materialized.  The wire payload ships blocks
    exactly as the pool stores them, and the importing side scatters the
    same block-shaped arrays straight back with :func:`write_imported` —
    so the f32 path moves the pool bytes verbatim (the bitwise-identity
    argument) and neither side pays a reshape/copy beyond the device→host
    transfer itself.
    """
    idx = jnp.asarray(block_ids, jnp.int32)
    return {
        part: [take_blocks(p, idx) for p in layers]
        for part, layers in pools.items()
    }


def write_imported(pools: dict, kv: dict, block_ids) -> dict:
    """Scatter migrated block-shaped rows into newly assigned blocks — the
    receiving half of :func:`export_blocks`.

    ``kv`` is per part and layer ``(n, bs, *row)`` with exactly
    ``len(block_ids)`` blocks.  Positions in the final block past the
    migrated sequence's length sit at or beyond its causal bound, so
    — the same over-scatter argument as :func:`write_swapped` — whatever
    the tail holds is invisible until decode writes overwrite it.  On the
    f32 codec the scattered bytes are the exact bytes
    :func:`export_blocks` read, which is what keeps a migrated decode
    bitwise against the colocated engine.
    """
    idx = jnp.asarray(block_ids, jnp.int32)
    n = idx.shape[0]

    def scatter(pool, a):
        if a.shape[0] != n or a.shape[1:] != pool.shape[1:]:
            raise ValueError(
                f"imported rows shaped {tuple(a.shape)}, "
                f"{n} blocks of {tuple(pool.shape[1:])} expected"
            )
        return put_blocks(pool, idx, a)

    return _map_pools(scatter, pools, kv)


def gather_seq(pools: dict, block_ids, length: int | None = None) -> dict:
    """One sequence's contiguous view — per part and layer
    ``(n_blocks*bs, *row)``, truncated to ``length`` if given."""
    idx = jnp.asarray(block_ids, jnp.int32)
    return {
        part: [
            take_blocks(p, idx).reshape(-1, *p.shape[2:])[:length]
            for p in layers
        ]
        for part, layers in pools.items()
    }
