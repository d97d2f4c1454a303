"""Fused paged-attention decode vs the gather-materialize oracle.

The contract (``ops/paged_attention.py``): the block-streaming paths —
the ``fori_loop`` and the Pallas kernel, here under the interpreter (the
chip's compiler is asked in ``tests/test_tpu_aot.py``) — attend over
exactly the positions the
gather path attends over (pool positions ``< length`` plus the new
token at ``length``), differing only in floating-point summation order
(online softmax folds block by block; the oracle reduces the whole
gathered row at once).  So:

- fused output == gather oracle within the pinned ``FUSED_DECODE_ATOL``,
  across impls x chunk sizes x dtypes x ragged lengths (empty rows,
  mid-block, block-aligned, full table);
- the poisoned-null-block invariance — THE masking property the paged
  cache leans on — holds **bitwise** on the fused paths: whatever a
  masked position holds contributes exactly 0.0;
- ``paged_decode_step(fused=True)`` tracks its gather twin within the
  tolerance on logits while producing **bitwise-identical** pool
  scatters (the scatter is shared code, only attention differs).
"""

import importlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flextree_tpu.models.transformer import TransformerConfig, init_params
from flextree_tpu.ops import paged_attention as paged_attention_mod
from flextree_tpu.ops.paged_attention import (
    FUSED_DECODE_ATOL,
    kernel_admits,
    paged_attention,
    paged_attention_gather,
    runs_kernel,
)
from flextree_tpu.serving.kv_cache import (
    NULL_BLOCK,
    BlockAllocator,
    PagedCacheConfig,
    decode_attention_layers,
    init_pools,
    make_paged_decode_fn,
    paged_decode_step,
)
from flextree_tpu.utils import backend

# the MODULE that holds the dense decode walk (the package re-exports the
# function of the same name)
generate_module = importlib.import_module("flextree_tpu.models.generate")

S, H, D, N, BS, P = 5, 4, 16, 32, 8, 7
#: ragged mix: empty row, short, block-aligned, mid-block, near-full
LENGTHS = (0, 3, 8, 17, 41)


def _inputs(dtype=jnp.float32, seed=0, lengths=LENGTHS):
    rng = np.random.default_rng(seed)
    q, kn, vn = (
        jnp.asarray(rng.standard_normal((len(lengths), H, D)), dtype)
        for _ in range(3)
    )
    kp, vp = (
        jnp.asarray(rng.standard_normal((N, BS, H, D)), dtype)
        for _ in range(2)
    )
    tables = np.zeros((len(lengths), P), np.int32)
    free = list(range(1, N))
    for s, L in enumerate(lengths):
        n = int(L) // BS + 1  # blocks written + the one the write lands in
        tables[s, :n] = [free.pop() for _ in range(n)]
    return (q, kn, vn, kp, vp, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "impl,kwargs",
    [
        ("jnp", {"block_chunk": 1}),
        ("jnp", {"block_chunk": 2}),
        ("jnp", {"block_chunk": 4}),
        ("jnp", {"block_chunk": 64}),  # > P: clamped, single fold
        ("pallas", {}),
    ],
)
def test_fused_matches_gather_oracle(dtype, impl, kwargs):
    args = _inputs(dtype)
    ref = paged_attention_gather(*args).astype(jnp.float32)
    out = paged_attention(*args, impl=impl, **kwargs).astype(jnp.float32)
    tol = FUSED_DECODE_ATOL if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol,
                               rtol=0)


def test_full_table_and_boundary_lengths():
    """A maximally-full row (length == P*bs - 1, the largest value the
    serving layer can reach — a row AT max_len has no room to decode),
    and a length exactly at a block boundary — the off-by-one classes a
    frontier bound can hide."""
    lengths = (P * BS - 1, BS, 2 * BS)
    rng = np.random.default_rng(1)
    q, kn, vn = (
        jnp.asarray(rng.standard_normal((3, H, D)), jnp.float32)
        for _ in range(3)
    )
    kp, vp = (
        jnp.asarray(rng.standard_normal((N, BS, H, D)), jnp.float32)
        for _ in range(2)
    )
    tables = np.zeros((3, P), np.int32)
    free = list(range(1, N))
    tables[0, :] = [free.pop() for _ in range(P)]  # full row
    tables[1, :2] = [free.pop() for _ in range(2)]
    tables[2, :3] = [free.pop() for _ in range(3)]
    args = (q, kn, vn, kp, vp, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))
    ref = paged_attention_gather(*args)
    for impl in ("jnp", "pallas"):
        out = paged_attention(*args, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=FUSED_DECODE_ATOL, rtol=0)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_poisoned_null_block_invariance_bitwise(impl):
    """The load-bearing masking property: null-block content (including
    values big enough to overflow the score matmul) changes NOTHING —
    bitwise — because masked probabilities are exactly 0.0 and 0.0 * x
    never reaches the accumulator."""
    q, kn, vn, kp, vp, tables, lengths = _inputs()
    poisoned_k = kp.at[NULL_BLOCK].set(1e30)
    poisoned_v = vp.at[NULL_BLOCK].set(1e30)
    a = paged_attention(q, kn, vn, kp, vp, tables, lengths, impl=impl)
    b = paged_attention(q, kn, vn, poisoned_k, poisoned_v, tables, lengths,
                        impl=impl)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_unwritten_tail_of_current_block_is_invisible():
    """Positions >= length inside the partially-written current block are
    masked too — poison them and the fused output must not move."""
    q, kn, vn, kp, vp, tables, lengths = _inputs()
    row = 3  # length 17: block 2 holds 16..23; 16 written, 17.. unwritten
    blk = int(np.asarray(tables)[row, 2])
    # poison from offset 1 = position 17, the FIRST masked position —
    # the exact cell a `kpos <= length` off-by-one would expose
    kp2 = kp.at[blk, 1:].set(1e30)
    vp2 = vp.at[blk, 1:].set(1e30)
    a = paged_attention(q, kn, vn, kp, vp, tables, lengths)
    b = paged_attention(q, kn, vn, kp2, vp2, tables, lengths)
    np.testing.assert_array_equal(np.asarray(a)[row], np.asarray(b)[row])


def test_jnp_and_pallas_agree():
    args = _inputs(seed=2)
    a = paged_attention(*args, impl="jnp", block_chunk=1)
    b = paged_attention(*args, impl="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=FUSED_DECODE_ATOL, rtol=0)


def _grouped_case(lengths, g, hkv=2, d=16, bs=8, p=7, seed=3, chain=None):
    """Grouped queries (``g`` a K/V head) over a pool whose blocks are
    dealt out of order; ``chain``: rows whose table is full to the last
    column, whatever their length."""
    rng = np.random.default_rng(seed)
    s = len(lengths)
    f = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    n = 1 + s * p
    free = rng.permutation(np.arange(1, n)).tolist()
    tables = np.zeros((s, p), np.int32)
    for i, length in enumerate(lengths):
        if length == 0 and i not in (chain or ()):
            continue  # an empty slot: a null row
        need = p if i in (chain or ()) else length // bs + 1
        tables[i, :need] = [free.pop() for _ in range(need)]
    return (f(s, g * hkv, d), f(s, hkv, d), f(s, hkv, d), f(n, bs, hkv, d),
            f(n, bs, hkv, d), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


#: empty slots first, in the middle and last; lengths on (8, 16, 48) and
#: off (3, 17, 41) a block boundary; one slot in the table's last block
RAGGED = (0, 3, 8, 0, 17, 41, 16, 48, 55, 0)

#: the order the kernel reads a block's rows in, as (K/V heads, rows a
#: chunk).  "rows": (position, head), the pool as it is indexed.  The
#: others: (head, position), as the v5e holds a pool whose K/V heads fill
#: no sublane tile, a block of 8 positions in several chunks: 6 heads in
#: parts of 3 and 3; 10 heads in parts of 4, 4 and 2, where the last part
#: starts two heads early and serves only its own two
ORDERS = {"rows": (2, None), "heads-6": (6, 24), "heads-10": (10, 24),
          "heads-30": (30, 1024)}


@pytest.fixture
def read_as(monkeypatch):
    """``read_as(order)``: the K/V head count of the order, after making
    the kernel read in it.  The interpreter has no device layout, so what
    the entry observes (``pool_relayouts``) is replaced."""
    def force(order):
        hkv, chunk_rows = ORDERS[order]
        if chunk_rows is not None:
            monkeypatch.setattr(
                paged_attention_mod, "pool_relayouts", lambda pool: True)
            monkeypatch.setattr(paged_attention_mod, "_CHUNK_ROWS", chunk_rows)
        return hkv

    return force


@pytest.mark.parametrize("order", ["rows", "heads-6", "heads-10"])
@pytest.mark.parametrize("window", [None, 5, 24, 200],
                         ids=["full", "w5", "w24", "wider-than-any-row"])
@pytest.mark.parametrize("g", [1, 6, 9])
def test_kernel_grouped_queries_and_windows(g, window, order, read_as):
    """What the old kernel refused: 6 and 9 queries a K/V head, a window
    narrower and wider than the longest row, empty slots, a slot at the
    table's last block, lengths on and off a block boundary, in one
    batch; every slot walks its own blocks.  And what the kernel after it
    refused: a K/V head count that is a multiple neither of 8 nor of a
    chunk's heads, read head-major, a block in several compute steps."""
    hkv = read_as(order)
    assert order == "rows" or paged_attention_mod._chunk_heads(8, hkv) in (3, 4)
    args = _grouped_case(RAGGED, g, hkv=hkv)
    ref = paged_attention_gather(*args, window=window)
    out = paged_attention(*args, impl="pallas", window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=FUSED_DECODE_ATOL, rtol=0)
    loop = paged_attention(*args, impl="jnp", window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(loop),
                               atol=FUSED_DECODE_ATOL, rtol=0)


@pytest.mark.parametrize("order", ["rows", "heads-10"])
@pytest.mark.parametrize("window", [None, 12])
def test_kernel_reads_only_its_own_live_blocks_bitwise(window, order, read_as):
    """No slot pays for another: blocks a slot holds past its length, the
    blocks behind its window, the null block and every block of the pool
    that no table names can hold anything — the output does not move by
    a bit, because the kernel never brings them in; nor does it for what
    the unwritten tail of a slot's last block holds, which it does bring
    in and masks."""
    lengths = (0, 3, 8, 17, 41, 30)
    args = _grouped_case(lengths, 3, hkv=read_as(order), chain=(0, 2, 3))
    q, kn, vn, kp, vp, tables, lens = args
    tab = np.asarray(tables)
    live = set()
    for i, length in enumerate(lengths):
        first = 0 if window is None else max(length - (window - 1), 0) // 8
        live |= set(tab[i, first:-(-length // 8)].tolist())
    dead = np.array(sorted(set(range(kp.shape[0])) - live))
    assert NULL_BLOCK in dead and len(dead) > len(live)
    a = paged_attention(*args, impl="pallas", window=window)
    kp, vp = kp.at[dead].set(1e30), vp.at[dead].set(jnp.nan)
    for i, length in enumerate(lengths):
        if length % 8:  # positions length.. of the block the write lands in
            kp = kp.at[tab[i, length // 8], length % 8:].set(1e30)
            vp = vp.at[tab[i, length // 8], length % 8:].set(1e30)
    b = paged_attention(q, kn, vn, kp, vp, tables, lens,
                        impl="pallas", window=window)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(paged_attention_gather(*args, window=window)),
        atol=FUSED_DECODE_ATOL, rtol=0)


@pytest.mark.parametrize("order", ["rows", "heads-6"])
def test_kernel_chunks_several_blocks_a_step(monkeypatch, order, read_as):
    """The cells' shapes put 2 and 8 blocks in a compute step and keep 4
    chunks in VMEM; the toy shapes put the whole row in one.  Shrink the
    chunk so that a row is several steps with a ragged last one, and the
    ring of buffers wraps inside a row and across rows.  Head-major, 48
    rows a block: a block in three steps, in one, and two blocks a step."""
    args = _grouped_case(RAGGED, 6, hkv=read_as(order))
    ref = paged_attention_gather(*args, window=24)
    sizes = {"rows": ((32, 2), (48, 3), (16, 4)),
             "heads-6": ((16, 2), (48, 3), (96, 4))}[order]
    for rows, nbuf in sizes:
        monkeypatch.setattr(paged_attention_mod, "_CHUNK_ROWS", rows)
        monkeypatch.setattr(paged_attention_mod, "_CHUNKS_IN_VMEM", nbuf)
        out = paged_attention(*args, impl="pallas", window=24)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=FUSED_DECODE_ATOL, rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_walks_thirty_heads_of_128_in_parts_of_a_block(dtype, read_as):
    """The hybrid cell's heads at the module's own chunk: 30 K/V heads of
    128 under 30 query heads (padded to 32 rows for the kernel, dropped
    after), a block of 128 positions = 3,840 rows read head-major in three
    parts of 10 heads; an empty slot, a short one, one block exactly and
    one long among them; the null block and every unwritten tail hold
    1e30."""
    bs, hkv, lengths = 128, read_as("heads-30"), (0, 3, 128, 300)
    assert paged_attention_mod._chunk_heads(bs, hkv) == 10
    rng = np.random.default_rng(7)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    q, kn, vn = f(4, hkv, 128), f(4, hkv, 128), f(4, hkv, 128)
    kp, vp = np.array(f(8, bs, hkv, 128)), np.array(f(8, bs, hkv, 128))
    tables = np.zeros((4, 3), np.int32)
    tables[1, :1], tables[2, :2], tables[3, :3] = [5], [2, 7], [6, 1, 3]
    for pool in (kp, vp):
        pool[NULL_BLOCK] = 1e30
        for i, length in enumerate(lengths):
            pool[tables[i, length // bs], length % bs:] = 1e30
    args = (q, kn, vn, jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))
    ref = paged_attention_gather(*args).astype(jnp.float32)
    out = paged_attention(*args, impl="pallas").astype(jnp.float32)
    tol = FUSED_DECODE_ATOL if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol,
                               rtol=0)


def test_shape_validation_is_loud():
    q, kn, vn, kp, vp, tables, lengths = _inputs()
    with pytest.raises(ValueError, match="queries"):
        paged_attention(q[0], kn, vn, kp, vp, tables, lengths)
    with pytest.raises(ValueError, match="new-token"):
        paged_attention(q, kn[:, :2], vn, kp, vp, tables, lengths)
    with pytest.raises(ValueError, match="lengths"):
        paged_attention(q, kn, vn, kp, vp, tables, lengths[:-1])
    with pytest.raises(ValueError, match="impl"):
        paged_attention(q, kn, vn, kp, vp, tables, lengths, impl="cuda")


# ---------------------------------------------------- whole-decode-step level


@pytest.fixture(scope="module")
def model():
    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _decode_state(cfg, pcfg, lengths, seed=4):
    rng = np.random.default_rng(seed)
    pools = init_pools(cfg, pcfg)
    pools = {
        kind: [
            jnp.asarray(
                rng.standard_normal(p.shape).astype(np.float32), cfg.dtype
            )
            for p in pools[kind]
        ]
        for kind in ("k", "v")
    }
    alloc = BlockAllocator(pcfg.num_blocks)
    tables = np.zeros((len(lengths), pcfg.blocks_per_seq), np.int32)
    for s, L in enumerate(lengths):
        n = int(L) // pcfg.block_size + 1
        tables[s, :n] = alloc.alloc(n)
    tokens = rng.integers(0, cfg.vocab_size, (len(lengths),)).astype(np.int32)
    return pools, jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), tokens


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_decode_step_fused_vs_gather(model, impl, monkeypatch):
    """Logits within tolerance; layer 0's pool scatter is BITWISE (its
    K/V depend only on the embedding, before any attention differs) and
    deeper layers' scatters inherit the attention tolerance through the
    residual stream.  The step no longer hands ``impl`` down, so the
    kernel's case forces it where the step looks the entry up."""
    monkeypatch.setattr(generate_module, "paged_attention",
                        partial(paged_attention, impl=impl))
    cfg, params = model
    pcfg = PagedCacheConfig(num_blocks=24, block_size=8, blocks_per_seq=6)
    pools, tables, lengths, tokens = _decode_state(
        cfg, pcfg, (5, 12, 24, 33)
    )
    ref_logits, ref_pools = paged_decode_step(
        params, pools, tables, lengths, tokens, cfg, fused=False
    )
    logits, out_pools = paged_decode_step(
        params, pools, tables, lengths, tokens, cfg, fused=True
    )
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(ref_logits),
        atol=FUSED_DECODE_ATOL * 10, rtol=0,
    )  # logits pass through 2 more matmul layers than the attention out
    np.testing.assert_array_equal(
        np.asarray(out_pools["k"][0]), np.asarray(ref_pools["k"][0])
    )
    np.testing.assert_array_equal(
        np.asarray(out_pools["v"][0]), np.asarray(ref_pools["v"][0])
    )
    for l in range(1, cfg.n_layers):
        np.testing.assert_allclose(
            np.asarray(out_pools["k"][l]), np.asarray(ref_pools["k"][l]),
            atol=FUSED_DECODE_ATOL, rtol=0,
        )
        np.testing.assert_allclose(
            np.asarray(out_pools["v"][l]), np.asarray(ref_pools["v"][l]),
            atol=FUSED_DECODE_ATOL, rtol=0,
        )


def test_decode_step_fused_greedy_tokens_match_oracle(model):
    """The serving-level consequence: greedy argmax over fused logits
    equals the gather oracle's on this workload (the bench re-checks this
    on every rep of the real load run)."""
    cfg, params = model
    pcfg = PagedCacheConfig(num_blocks=24, block_size=8, blocks_per_seq=6)
    pools, tables, lengths, tokens = _decode_state(
        cfg, pcfg, (3, 9, 20, 40), seed=5
    )
    ref_logits, _ = paged_decode_step(
        params, pools, tables, lengths, tokens, cfg, fused=False
    )
    logits, _ = paged_decode_step(
        params, pools, tables, lengths, tokens, cfg, fused=True
    )
    np.testing.assert_array_equal(
        np.argmax(np.asarray(logits), axis=-1),
        np.argmax(np.asarray(ref_logits), axis=-1),
    )


# ------------------------------------------------- who decides which path runs


#: the dense cell's decode shapes at two layers: S32, 32 heads of 128,
#: blocks of 16, 48 a row, bf16 (traced from shapes, never run)
CELL = TransformerConfig(
    vocab_size=512, d_model=4096, n_heads=32, n_layers=2, d_ff=256,
    dtype=jnp.bfloat16,
)
CELL_POOL = PagedCacheConfig(num_blocks=97, block_size=16, blocks_per_seq=48)


def _decode_avals(cfg, pcfg, slots=32):
    params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    pools = jax.eval_shape(lambda: init_pools(cfg, pcfg))
    tables = jax.ShapeDtypeStruct((slots, pcfg.blocks_per_seq), jnp.int32)
    row = jax.ShapeDtypeStruct((slots,), jnp.int32)
    return params, pools, tables, row, row


def _kernels_in(cfg, pcfg):
    """Calls of the kernel in the traced decode program.  Layers of one
    shape share ONE traced ``pallas_call`` (the jitted wrapper: one
    Mosaic lowering a process, not one a layer)."""
    text = str(make_paged_decode_fn(cfg, donate=False, fused=True).trace(
        *_decode_avals(cfg, pcfg)
    ).jaxpr)
    calls = text.count("jit[name=_stream_kernel")
    assert text.count("pallas_call") == min(calls, 1)
    return calls


def test_on_the_cpu_every_layer_walks_the_table_in_the_loop(model):
    """The observable is the backend and the shape: the CPU backend keeps
    the ``fori_loop`` even at the cell's shapes, the counter says 0, and
    the compiled decode program holds no kernel."""
    assert backend.kernel_platform() == "cpu"
    assert decode_attention_layers(CELL, CELL_POOL) == (2, 0)
    assert _kernels_in(CELL, CELL_POOL) == 0
    cfg, _ = model
    toy = PagedCacheConfig(num_blocks=24, block_size=8, blocks_per_seq=6)
    assert decode_attention_layers(cfg, toy) == (cfg.n_layers, 0)
    # the gather path has attention layers and no fused walk at all
    assert decode_attention_layers(CELL, CELL_POOL, fused=False) == (2, 0)


def test_on_a_tpu_the_eligible_shapes_take_the_kernel_in_every_layer(
    model, monkeypatch
):
    """``kernel_platform`` patched to "tpu", trace only: the counter and
    the traced program agree, layer for layer; a head dimension that does
    not fill the lanes stays in the loop without a raise."""
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    assert decode_attention_layers(CELL, CELL_POOL) == (2, 2)
    assert _kernels_in(CELL, CELL_POOL) == 2
    cfg, _ = model  # heads of 8: nothing Mosaic's tiling takes
    toy = PagedCacheConfig(num_blocks=24, block_size=8, blocks_per_seq=6)
    assert decode_attention_layers(cfg, toy) == (cfg.n_layers, 0)
    assert _kernels_in(cfg, toy) == 0


@pytest.mark.parametrize("shape,dtype,admitted", [
    ((16, 32, 128), jnp.bfloat16, True),   # the dense cell
    ((16, 8, 128), jnp.bfloat16, True),    # the Laguna cell
    ((16, 32, 128), jnp.float32, True),
    ((16, 32, 64), jnp.bfloat16, False),   # half the lanes
    ((4, 32, 128), jnp.bfloat16, False),   # a block of 4 positions
    ((8, 8, 128), jnp.bfloat16, False),    # a block of 64 rows: half a tile of scores
    ((16, 128, 128), jnp.bfloat16, False), # a block past the chunk
    # K/V heads that fill no sublane tile: read (head, position), as the
    # v5e holds such a pool, any number of heads in parts of a block
    ((256, 30, 128), jnp.bfloat16, True),  # the hybrid cell
    ((128, 6, 128), jnp.float32, True),
    ((1024, 7, 128), jnp.bfloat16, True),  # a head's rows: a whole chunk
    ((16, 30, 128), jnp.bfloat16, False),  # a head's 16 rows: an eighth of the lanes
    ((192, 30, 128), jnp.bfloat16, False), # a tile and a half of scores
    ((2048, 30, 128), jnp.bfloat16, False),# a head's rows past the chunk
    ((256, 30, 64), jnp.bfloat16, False),
], ids=["dense", "laguna", "f32", "d64", "bs4", "rows64", "rows2048",
        "hybrid", "heads6-f32", "heads7-bs1024", "heads30-bs16",
        "heads30-bs192", "heads30-bs2048", "heads30-d64"])
def test_kernel_admits_what_its_tiling_takes(shape, dtype, admitted, monkeypatch):
    pool = jax.ShapeDtypeStruct((9, *shape), dtype)
    q = jax.ShapeDtypeStruct((2, 2 * shape[1] if shape[1] == 8 else shape[1],
                              shape[2]), dtype)
    assert kernel_admits(q, pool) is admitted
    assert runs_kernel(q, pool) is False  # the CPU backend: never
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    assert runs_kernel(q, pool) is admitted


def test_decode_impl_of_either_value_builds_the_same_program(model):
    """``decode_impl`` is accepted wherever it was (the benchmark's
    traffic files carry ``"jnp"``), checked, and selects nothing."""
    cfg, _ = model
    pcfg = PagedCacheConfig(num_blocks=24, block_size=8, blocks_per_seq=6)
    avals = _decode_avals(cfg, pcfg, slots=4)
    texts = {
        impl: make_paged_decode_fn(cfg, donate=True, fused=True, impl=impl)
        .lower(*avals).as_text()
        for impl in ("jnp", "pallas")
    }
    assert texts["jnp"] == texts["pallas"]
    assert "while" in texts["jnp"]  # the loop over table columns
    with pytest.raises(ValueError, match="impl"):
        make_paged_decode_fn(cfg, fused=True, impl="cuda")
    with pytest.raises(ValueError, match="impl"):
        make_paged_decode_fn(cfg, fused=False, impl="cuda")
