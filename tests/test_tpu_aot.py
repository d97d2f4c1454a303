"""Compile for a TPU v5e from abstract shapes, on a machine with no chip.

libtpu ships the real Mosaic and XLA:TPU compilers, and
``jax.experimental.topologies`` hands out abstract v5e devices, so
``jit(f).trace(avals).lower(lowering_platforms=("tpu",)).compile()`` says
what the chip's compiler will say — shapes that do not fit VMEM, block
specs Mosaic refuses, programs that do not partition — before any chip
time is spent.  It is not a chip run: it gives no time and cannot say the
result is right.  This is the cheap gate a kernel PR runs first.

Kernel-level tests are unmarked (a second or two each); the step-level
ones compile the flagship width (``flagship-d2048``: vocab 32768, d_model
2048, 16 heads of 128, 4 layers, d_ff 8192, bf16) and are ``slow``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from flextree_tpu.models.transformer import TransformerConfig, init_params
from flextree_tpu.ops.paged_attention import (
    kernel_admits, paged_attention, paged_attention_latent,
)
from flextree_tpu.ops.pallas_attention import flash_attention, kvgrid_tiles
from flextree_tpu.utils import backend

FLAGSHIP = TransformerConfig(
    vocab_size=32768, d_model=2048, n_heads=16, n_layers=4, d_ff=8192,
    dtype=jnp.bfloat16, attn_impl="flash",
)
# chip_smoke.py's attention shape: the one-chip train batch's q/k/v
B, T, H, D = 4, 2048, 16, 128


@pytest.fixture(scope="module")
def v5e():
    """Four abstract v5e devices (a 2x2 host), or skip with the reason."""
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        ).devices
    except Exception as e:  # noqa: BLE001 — no libtpu, or one that cannot
        pytest.skip(f"no TPU compiler to ask: {type(e).__name__}: {e}")


@pytest.fixture
def tpu_lowering(monkeypatch):
    """Lower kernels as on the chip (Mosaic, never the interpreter), with
    x64 off: the suite's x64 sends the Pallas TPU lowering into a
    RecursionError, and the chip path never runs under it."""
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    with jax.enable_x64(False):
        yield


def _mesh(devices, shape):
    n = int(np.prod(shape))
    return Mesh(np.array(devices[:n]).reshape(shape), ("dp", "sp", "tp"))


def _on(tree, sharding):
    """``tree``'s shapes as avals placed by ``sharding`` (one sharding, or
    a matching tree of them)."""
    if not isinstance(sharding, (dict, list, tuple)):
        sharding = jax.tree.map(lambda _: sharding, tree)
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding,
    )


def _compile(fn, *avals):
    jitted = fn if hasattr(fn, "trace") else jax.jit(fn)
    return jitted.trace(*avals).lower(lowering_platforms=("tpu",)).compile()


def _qkv(dev, dtype, t=T):
    one = NamedSharding(_mesh([dev], (1, 1, 1)), P())
    return [jax.ShapeDtypeStruct((B, t, H, D), dtype, sharding=one)] * 3


def _loss(q, k, v):
    return flash_attention(q, k, v).astype(jnp.float32).sum()


# ------------------------------------------------------------ kernel level


def test_flash_forward_is_a_mosaic_kernel(v5e, tpu_lowering):
    hlo = _compile(flash_attention, *_qkv(v5e[0], jnp.bfloat16)).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


def test_flash_backward_is_two_more_mosaic_kernels(v5e, tpu_lowering):
    grad = jax.grad(_loss, argnums=(0, 1, 2))
    hlo = _compile(grad, *_qkv(v5e[0], jnp.bfloat16)).as_text()
    # forward-with-lse, dq, dk/dv
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3


def test_flash_backward_that_cannot_fit_vmem_is_refused_at_trace(
    v5e, tpu_lowering
):
    """f32 T=4096: Mosaic's own figure is 18.00M against 16.00M.  The
    refusal must be ours (a ValueError while tracing, naming shape, dtype,
    bytes and the limit), not a JaxRuntimeError from inside a compile."""
    grad = jax.jit(jax.grad(_loss, argnums=(0, 1, 2)))
    with pytest.raises(ValueError, match="scoped-VMEM") as e:
        grad.trace(*_qkv(v5e[0], jnp.float32, t=4096))
    msg = str(e.value)
    assert "backward dk/dv" in msg and "float32" in msg
    assert "(4, 4096, 16, 128)" in msg
    assert str(18 * 2**20) in msg and str(16 * 2**20) in msg
    # the same shape in bf16 fits, and so does the f32 forward alone
    grad.trace(*_qkv(v5e[0], jnp.bfloat16, t=4096))
    jax.jit(flash_attention).trace(*_qkv(v5e[0], jnp.float32, t=4096))


def _paged_avals(dev, s, h, hkv, d, bs, p, n, dtype=jnp.bfloat16):
    one = NamedSharding(_mesh([dev], (1, 1, 1)), P())

    def a(*shape, dt=dtype):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    return (a(s, h, d), a(s, hkv, d), a(s, hkv, d), a(n, bs, hkv, d),
            a(n, bs, hkv, d), a(s, p, dt=jnp.int32), a(s, dt=jnp.int32))


#: the three serving cells' decode shapes (S, H, Hkv, D, bs, P, N), window
CELL_DECODE = {
    "dense": ((32, 32, 32, 128, 16, 48, 1537), None),
    "laguna-48-full": ((64, 48, 8, 128, 16, 96, 6145), None),
    "laguna-48-window": ((64, 48, 8, 128, 16, 96, 6145), 512),
    "laguna-72-full": ((64, 72, 8, 128, 16, 96, 6145), None),
    "laguna-72-window": ((64, 72, 8, 128, 16, 96, 6145), 512),
    "hybrid": ((8, 30, 30, 128, 256, 66, 529), None),
}


@pytest.mark.parametrize("cell", list(CELL_DECODE))
def test_paged_decode_is_one_mosaic_kernel_that_copies_no_pool(
    v5e, tpu_lowering, cell
):
    """Mosaic takes the kernel at the cells' exact shapes (grouped
    queries and a window among them), one ``tpu_custom_call`` a call; the
    pools go in as they are (the ``(N, bs * Hkv, D)`` view is a bitcast
    under the (8, 128)(2, 1) tiling, not a 201 MB copy); and nothing is
    left of the loop over table columns.  The hybrid cell's 30 K/V heads
    fill no sublane tile, so the v5e holds its pools with the block-size
    axis next to the lanes (``{3,1,2,0}``): the kernel takes them
    transposed, ``(N, Hkv * bs, D)``, which is that very order and a
    bitcast again, where the row-major view would copy 1.04 GB a pool."""
    import re

    shape, window = CELL_DECODE[cell]
    hlo = _compile(
        lambda *a: paged_attention(*a, window=window),
        *_paged_avals(v5e[0], *shape),
    ).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    n = shape[-1]
    assert not re.search(rf"= \w+\[{n},[\d,]*\]\S* copy\(", hlo)
    assert len(re.findall(rf"= bf16\[{n},\d+,128\]\S* bitcast\(", hlo)) == 2
    assert " while(" not in hlo


@pytest.mark.parametrize("shape,window", [
    ((4, 12, 6, 128, 128, 8, 33), None),      # grouped queries, a block a step
    ((4, 12, 6, 128, 128, 8, 33), 200),
    ((8, 30, 30, 128, 128, 66, 512), None),   # as many blocks as sublanes like
    ((8, 7, 7, 128, 1024, 17, 133), None),    # ONE head a step, seven a block
    ((8, 30, 30, 128, 1024, 17, 133), None),
], ids=["grouped-6", "grouped-6-window", "bs128", "heads7-bs1024", "bs1024"])
def test_what_the_kernel_admits_head_major_mosaic_takes_as_it_lies(
    v5e, tpu_lowering, shape, window
):
    """``kernel_admits`` promises for every K/V head count that fills no
    sublane tile what the hybrid cell's case above shows for 30 at a block
    of 256: Mosaic takes the kernel (aligned slices of the view, VMEM for
    four chunks of under two times 1,024 rows and the ``match`` table),
    and the transposed view is the order the v5e chose for the pool, a
    bitcast, also where the blocks axis would pad as little as the
    block-size axis."""
    import re

    avals = _paged_avals(v5e[0], *shape)
    assert kernel_admits(avals[0], avals[3])
    hlo = _compile(
        lambda *a: paged_attention(*a, window=window), *avals).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    n = shape[-1]
    assert not re.search(rf"= \w+\[{n},[\d,]*\]\S* copy\(", hlo)
    assert len(re.findall(rf"= bf16\[{n},\d+,128\]\S* bitcast\(", hlo)) == 2
    assert " while(" not in hlo


#: the latent cell's decode shapes: 32 slots, 128 heads over one 576-wide
#: row whose first 512 numbers are the values, 513 blocks of 544
LATENT = dict(s=32, h=128, r=576, bs=544, p=16, n=513, value_dim=512)


def _latent_avals(dev, s, h, r, bs, p, n, **_):
    one = NamedSharding(_mesh([dev], (1, 1, 1)), P())

    def a(*shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one)

    return (a(s, h, r), a(s, r), a(n, bs, r), a(s, p, dt=jnp.int32),
            a(s, dt=jnp.int32))


@pytest.mark.parametrize("bs,kernel", [(544, True), (128, True), (40, False)])
def test_latent_decode_is_one_mosaic_kernel_that_copies_no_pool(
    v5e, tpu_lowering, bs, kernel
):
    """Mosaic takes the latent kernel at the cell's shapes: one
    ``tpu_custom_call``, no loop.  At the cell's block size (544: no
    multiple of the 128 lanes) XLA:TPU keeps a (N, bs, 576) array
    row-major and the pool goes in as it is; at a block of 128 it keeps
    the 128-wide axis minor, and every use of the rows as rows is a copy
    of the whole pool (PERF.md section 6, PR 32).  A block that is no
    whole number of sublane tiles walks the loop."""
    import re

    shape = dict(LATENT, bs=bs, p=8704 // bs if 8704 % bs == 0 else 218,
                 n=32 * (8704 // bs) + 1)
    hlo = _compile(
        lambda *a: paged_attention_latent(*a, value_dim=512, scale=0.07),
        *_latent_avals(v5e[0], **shape),
    ).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == int(kernel)
    assert (" while(" in hlo) == (not kernel)
    copies = re.findall(rf"= bf16\[{shape['n']},{bs},576\]\S* copy\(", hlo)
    if kernel:
        assert len(copies) == (1 if bs == 128 else 0)


def test_the_flash_forward_takes_values_narrower_than_its_keys(v5e, tpu_lowering):
    """The latent prefill's expanded form at the cell's longest prompt:
    128 heads of 192-wide queries and keys over 128-wide values, through
    the ``kvgrid`` forward at the tiles the prefill derives from those
    widths (the ``loop`` forward keeps whole k and v in VMEM and is
    refused there at this length)."""
    one = NamedSharding(_mesh([v5e[0]], (1, 1, 1)), P())
    a = lambda d: jax.ShapeDtypeStruct((1, 8192, 128, d), jnp.bfloat16, sharding=one)  # noqa: E731
    tiles = kvgrid_tiles(192, 128, jnp.bfloat16)
    assert tiles == {"block_q": 1024, "block_k": 2048}
    hlo = _compile(
        lambda q, k, v: flash_attention(
            q, k, v, scale=0.07, variant="kvgrid", **tiles
        ),
        a(192), a(192), a(128),
    ).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    with pytest.raises(ValueError, match="kvgrid"):
        _compile(
            lambda q, k, v: flash_attention(q, k, v, variant="loop"),
            a(192), a(192), a(128),
        )


def test_a_shape_the_paged_kernel_refuses_walks_the_loop_without_a_raise(
    v5e, tpu_lowering
):
    """Heads of 64 do not fill the lanes: the entry falls to the
    ``fori_loop`` on what it observes, and the program compiles."""
    hlo = _compile(
        paged_attention, *_paged_avals(v5e[0], 32, 32, 32, 64, 16, 48, 1537)
    ).as_text()
    assert 'custom_call_target="tpu_custom_call"' not in hlo
    assert " while(" in hlo


# -------------------------------------------------------------- step level


@pytest.mark.slow
@pytest.mark.parametrize(
    "shape,batch,seq", [((1, 1, 1), 4, 2048), ((2, 2, 1), 8, 4096)]
)
def test_flagship_train_step_compiles(v5e, tpu_lowering, shape, batch, seq):
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_train_step,
        state_specs,
    )

    mesh = _mesh(v5e, shape)
    tc = TrainConfig()
    step = make_train_step(mesh, FLAGSHIP, tc)
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        state_specs(FLAGSHIP, train_cfg=tc, mesh=mesh),
        is_leaf=lambda x: isinstance(x, P),
    )
    state = _on(
        jax.eval_shape(
            lambda k: init_train_state(k, FLAGSHIP, tc), jax.random.PRNGKey(0)
        ),
        shardings,
    )
    tok = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=NamedSharding(mesh, P("dp", "sp"))
    )
    compiled = _compile(step, state, tok, tok)
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    mem = compiled.memory_analysis()
    per_chip = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    )
    assert per_chip < 16 * 2**30, f"{per_chip / 2**30:.1f} GiB a chip"


@pytest.mark.slow
def test_flagship_paged_decode_and_prefill_compile(v5e, tpu_lowering):
    from flextree_tpu.models.generate import prefill
    from flextree_tpu.serving.kv_cache import (
        PagedCacheConfig,
        init_pools,
        make_paged_decode_fn,
    )

    one = NamedSharding(_mesh(v5e, (1, 1, 1)), P())
    cfg = FLAGSHIP
    pcfg = PagedCacheConfig(num_blocks=2049, block_size=16, blocks_per_seq=128)
    params = _on(
        jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0)),
        one,
    )
    pools = _on(jax.eval_shape(lambda: init_pools(cfg, pcfg)), one)
    slots = 16
    tables = jax.ShapeDtypeStruct((slots, 128), jnp.int32, sharding=one)
    row = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    decode = _compile(
        make_paged_decode_fn(cfg, donate=True, fused=True, impl="jnp"),
        params, pools, tables, row, row,
    )
    # the paged kernel in every layer, whatever ``impl`` says
    assert decode.as_text().count(
        'custom_call_target="tpu_custom_call"'
    ) == cfg.n_layers
    # every donated pool buffer is aliased to an output, not copied
    pool_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(pools)
    )
    assert decode.memory_analysis().alias_size_in_bytes == pool_bytes
    prompt = jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one)
    _compile(
        lambda p, t: prefill(p, t, cfg, max_len=pcfg.max_len), params, prompt
    )


def test_dp4_step_syncs_large_leaves_in_their_own_shape(v5e, tpu_lowering):
    """The four-chip data-parallel step (mesh (4,1,1), the shape of the
    benchmark's ``train-dp4`` cell at small widths), compiled for the
    chip: every matrix is past ``planner.choose_in_place_bytes`` and is
    reduce-scattered and all-gathered in its own (8, 128)-tiled shape.
    Before PR 29 each went through a flat ``f32[rows*cols]`` view, which
    on the TPU is tiled differently: a ``copy`` through HBM in and another
    out (29 ms of a 305 ms step in the cell, PERF.md §6)."""
    import re

    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_train_step,
        state_specs,
    )

    cfg = TransformerConfig(
        vocab_size=1024, d_model=512, n_heads=4, n_layers=1, d_ff=2048,
        dtype=jnp.bfloat16, attn_impl="flash",
    )
    mesh = _mesh(v5e, (4, 1, 1))
    tc = TrainConfig()
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        state_specs(cfg, train_cfg=tc, mesh=mesh),
        is_leaf=lambda x: isinstance(x, P),
    )
    state = _on(
        jax.eval_shape(
            lambda k: init_train_state(k, cfg, tc), jax.random.PRNGKey(0)
        ),
        shardings,
    )
    tok = jax.ShapeDtypeStruct(
        (4, 256), jnp.int32, sharding=NamedSharding(mesh, P("dp", "sp"))
    )
    hlo = _compile(make_train_step(mesh, cfg, tc), state, tok, tok).as_text()
    matrices = {(1024, 512): 1, (512, 512): 4, (512, 2048): 1, (2048, 512): 1}
    for (rows, cols), n in matrices.items():
        assert f"f32[{rows * cols}]" not in hlo, (rows, cols)
        # (an operation may be printed again inside its async wrapper)
        assert len(re.findall(
            rf"= f32\[{rows},{cols}\]\S* all-gather\(", hlo
        )) >= n, (rows, cols)
        assert not re.search(rf"= f32\[{rows},{cols}\]\S* copy\(", hlo)
    # one bucket a matrix, one reduce-scatter stage each, in two dimensions
    alone = set(re.findall(r"ft_bucket\d+_dp_1leaves_\d+B/ft_rs_stage0_w4", hlo))
    assert len(alone) == sum(matrices.values())
    assert re.search(r"= f32\[\d+,\d+\]\S* reduce-scatter\(", hlo)
    # the three norm scales share one flat bucket
    assert "_dp_3leaves_6144B" in hlo
    _assert_state_updated_in_place(hlo, state)


# the benchmark's training configuration (benchmarks/configs/pythia-1.4b.json
# at its depth of 7, B4 x T2048 a chip, flash): its state is 5.09 GiB
PYTHIA_1_4B = TransformerConfig(
    vocab_size=50304, d_model=2048, n_heads=16, n_layers=7, d_ff=8192,
    dtype=jnp.bfloat16, attn_impl="flash",
)
# arguments + results + temporaries of the PARENT's program (f249152: no
# donation, so no result takes an argument's buffer), by this same analysis
# (my AOT compiles of the parent, PR 33): 5.089 + 5.089 + 2.938 and + 3.052
PARENT_STEP_GIB = {(1, 1, 1): 13.116, (4, 1, 1): 13.230}


def _assert_state_updated_in_place(hlo, state):
    """Every state leaf's result takes its argument's buffer, and no
    operation of a matrix's size stands in the entry computation but the
    fusions that write the update, the collectives and the moves between
    memories: no select and no copy of a state leaf outside them."""
    import re

    header = hlo.split("\n", 1)[0]
    aliased = re.findall(
        r"\{(\d+)\}: \((\d+), \{\}, (?:may|must)-alias\)", header)
    n_leaves = len(jax.tree.leaves(state))
    assert len(aliased) == n_leaves, (len(aliased), n_leaves)
    assert all(out == arg for out, arg in aliased)
    entry = hlo[hlo.index("\nENTRY "):]
    entry = entry[:entry.index("\n}\n")]
    shapes = {x.shape for x in jax.tree.leaves(state) if x.ndim == 2}
    for rows, cols in shapes:
        for op in ("select", "copy", "multiply", "add", "subtract"):
            assert not re.search(
                rf"= f32\[{rows},{cols}\]\S* {op}\(", entry
            ), (rows, cols, op)


@pytest.mark.parametrize("shape", list(PARENT_STEP_GIB))
def test_the_benchmark_step_updates_its_state_in_place(v5e, tpu_lowering, shape):
    """The one-chip and the dp4 step of the training cells, at the cells'
    own size, compiled for the chip: the donated state is updated in
    place (every leaf aliased, the guard's select inside the update's
    fusions), and the program needs at least 1 GiB less than the
    parent's, by the compiler's own count (it needs about 3 less)."""
    from flextree_tpu.parallel.train import (
        TrainConfig,
        init_train_state,
        make_train_step,
        state_specs,
    )

    mesh = _mesh(v5e, shape)
    tc = TrainConfig()
    shardings = jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        state_specs(PYTHIA_1_4B, train_cfg=tc, mesh=mesh),
        is_leaf=lambda x: isinstance(x, P),
    )
    state = _on(
        jax.eval_shape(
            lambda k: init_train_state(k, PYTHIA_1_4B, tc),
            jax.random.PRNGKey(0),
        ),
        shardings,
    )
    tok = jax.ShapeDtypeStruct(
        (4 * shape[0], 2048), jnp.int32,
        sharding=NamedSharding(mesh, P("dp", "sp")),
    )
    compiled = _compile(make_train_step(mesh, PYTHIA_1_4B, tc), state, tok, tok)
    _assert_state_updated_in_place(compiled.as_text(), state)
    mem = compiled.memory_analysis()
    state_bytes = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    # (the scalar step pads to a tile)
    assert state_bytes <= mem.alias_size_in_bytes < state_bytes + 4096
    per_chip = (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        + mem.temp_size_in_bytes - mem.alias_size_in_bytes
    ) / 2**30
    assert per_chip <= PARENT_STEP_GIB[shape] - 1.0, f"{per_chip:.3f} GiB"


# ---------------------------------------------------- the state-holding cell


def _kimi_cell():
    """(configuration object, pool shape) of
    ``kimi-linear-48b-a3b.gen-closed-c128``, from the benchmark's files."""
    import json
    import os

    from flextree_tpu.models.configs import config_from_dict
    from flextree_tpu.serving.kv_cache import PagedCacheConfig

    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
    with open(os.path.join(root, "configs", "kimi-linear-48b-a3b.json")) as f:
        cfg = config_from_dict(json.load(f))
    with open(os.path.join(root, "traffic", "gen-closed-c128.json")) as f:
        t = json.load(f)
    return cfg, t, PagedCacheConfig(
        t["num_blocks"], t["block_size"], t["blocks_per_seq"])


def test_the_latent_kernel_takes_32_heads_a_slot_over_128_slots(v5e, tpu_lowering):
    """The state cell's MLA layers: 128 slots x 32 heads over 1,025 blocks
    of 656: one Mosaic kernel, no loop, no copy of the pool (XLA:TPU keeps
    it row-major: no other axis pads less than the 576-wide one), and
    ``kernel_layers`` says so for all 3.  2,049 blocks of 320 would be
    laid out with the BLOCKS axis minor (2,049 -> 2,176 pads 6%, 576 ->
    640 11%) and copied whole, twice a layer a round."""
    import re

    from flextree_tpu.models.configs import block_of

    cfg, t, pcfg = _kimi_cell()
    shape = dict(s=t["slots"], h=cfg.n_heads, r=cfg.pool_row,
                 bs=pcfg.block_size, p=pcfg.blocks_per_seq, n=pcfg.num_blocks)
    hlo = _compile(
        lambda *a: paged_attention_latent(
            *a, value_dim=cfg.kv_rank, scale=cfg.softmax_scale),
        *_latent_avals(v5e[0], **shape),
    ).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert " while(" not in hlo
    assert not re.findall(
        rf"= bf16\[{shape['n']},{shape['bs']},576\]\S* copy\(", hlo)
    assert block_of(cfg).kernel_layers(cfg, pcfg) == (3, 3)


def test_the_state_cells_decode_program_updates_pools_and_state_in_place(
    v5e, tpu_lowering
):
    """The fused decode program at the cell's size: every latent pool and
    every slot's state aliased to its result (5.19 GB donated, none
    copied), the latent kernel once an MLA layer and the state's update
    kernel once a KDA layer (nothing else reads or writes a state), and
    the whole program inside the chip's memory beside nothing else."""
    import re

    from flextree_tpu.models import kimi_linear as kimi
    from flextree_tpu.serving.kv_cache import (
        init_pools, init_state, make_paged_decode_fn,
    )

    cfg, t, pcfg = _kimi_cell()
    slots = t["slots"]
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    params = _on(jax.eval_shape(
        lambda k: kimi.init_params(k, cfg), jax.random.PRNGKey(0)), one)
    pools = _on(jax.eval_shape(lambda: init_pools(cfg, pcfg)), one)
    state = _on(jax.eval_shape(lambda: init_state(cfg, slots)), one)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    compiled = _compile(
        make_paged_decode_fn(cfg, donate=True, fused=True), params, pools,
        i32(slots, pcfg.blocks_per_seq), i32(slots), i32(slots), state)
    hlo = compiled.as_text()
    assert hlo.count("paged_latent_attention") >= 3
    assert not re.findall(r"= bf16\[1025,656,576\]\S* copy\(", hlo)
    calls = re.findall(
        r"^\s*(?:ROOT )?%?(\w+?)[.\d]* = .*custom-call\(.*tpu_custom_call",
        hlo, re.M)
    # and the grouped kernel twice an expert layer (gate, up and the
    # activation one call, down the other): none of XLA's own grouped
    # products, whose 512-row tile holds an expert's four or five rows
    assert sorted(calls) == (
        ["kda_state_update"] * 10 + ["moe_grouped_matmul"] * 24
        + ["paged_latent_attention"] * 3
    )
    assert "ragged-dot-none" not in hlo
    assert _ops_on_a_state(hlo) == ["custom-call"] * 10
    mem = compiled.memory_analysis()
    carried = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves((pools, state)))
    assert carried <= mem.alias_size_in_bytes < 1.1 * carried
    state_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(state))
    assert state_bytes == slots * 21_708_800
    whole = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert whole < 14.5e9, f"{whole / 1e9:.2f} GB"


def _ops_on_a_state(hlo: str) -> list:
    """The operation of every instruction of ``hlo`` that takes or makes a
    whole ``f32[128,32,128,128]`` (parameters and tuple plumbing aside)."""
    import re

    ops = re.findall(
        r"^\s*(?:ROOT )?\S+ = (?=.*f32\[128,32,128,128\]).*?\s([\w-]+)\(",
        hlo, re.M)
    return [op for op in ops
            if op not in ("parameter", "get-tuple-element", "tuple", "bitcast")]


def test_the_state_update_is_one_mosaic_kernel_that_reads_the_state_once(
    v5e, tpu_lowering
):
    """The KDA decode update at the state cell's shape, 128 slots x 32
    heads of 128 x 128 float32: ONE Mosaic kernel, the donated state
    updated where it lies (268 MB aliased, no temporary), no copy of it
    round the call (the kernel states the array's own row-major layout)
    and no fusion that reads it a second time; the block says so for all
    10 KDA layers."""
    from flextree_tpu.models.configs import block_of
    from flextree_tpu.ops.linear_attention import delta_rule_step

    cfg, t, _ = _kimi_cell()
    s, h, d = t["slots"], cfg.kda_heads, cfg.kda_dim
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    a = lambda *shape, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dtype, sharding=one)
    row = a(s, h, d)
    compiled = _compile(
        jax.jit(delta_rule_step, donate_argnums=(5,)),
        row, row, row, row, a(s, h), a(s, h, d, d), a(s, dtype=jnp.bool_))
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "kda_state_update" in hlo
    assert _ops_on_a_state(hlo) == ["custom-call"]
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == s * h * d * d * 4
    assert mem.temp_size_in_bytes < 1 << 20
    assert block_of(cfg).state_kernel_layers(cfg) == (10, 10)


def test_on_the_cpu_no_state_layer_runs_the_update_kernel():
    """The same block where the tests run: the ``jnp`` body in all 10."""
    from flextree_tpu.models.configs import block_of

    cfg, _, _ = _kimi_cell()
    assert block_of(cfg).state_kernel_layers(cfg) == (10, 0)


def test_the_state_cells_prefill_program_keeps_xlas_grouped_product(
    v5e, tpu_lowering
):
    """A 512-token prompt is 4,096 sorted picks over 32 experts, 128 an
    expert by the shapes: XLA's 512-row tile is right there, so the
    prefill program holds ``lax.ragged_dot`` as XLA:TPU renames it, three
    a sparse layer, and no grouped kernel of the repo's."""
    import re

    from flextree_tpu.models import kimi_linear as kimi

    cfg, _, pcfg = _kimi_cell()
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    params = _on(jax.eval_shape(
        lambda k: kimi.init_params(k, cfg), jax.random.PRNGKey(0)), one)
    hlo = _compile(
        lambda p, tok: kimi.prefill(p, tok, cfg, max_len=pcfg.max_len),
        params, jax.ShapeDtypeStruct((1, 512), jnp.int32, sharding=one),
    ).as_text()
    assert "moe_grouped_matmul" not in hlo
    products = re.findall(r"^\s*%?ragged-dot-none\S* = ", hlo, re.M)
    assert len(products) == 3 * cfg.n_sparse == 36


#: (sorted picks, experts held, hidden, expert width) of a decode round in
#: the three catalog cells
CELL_EXPERTS = {
    "state": (1024, 32, 2304, 1024),
    "laguna": (640, 128, 3072, 1024),
    "latent": (256, 16, 7680, 2048),
}


@pytest.mark.parametrize("product", ["inner", "down"])
@pytest.mark.parametrize("cell", list(CELL_EXPERTS))
def test_the_grouped_kernel_compiles_at_a_cells_decode_shape(
    v5e, tpu_lowering, cell, product
):
    """Each cell's two calls: gate, up and the activation over the whole
    hidden width (7,680 in the latent cell: slabs of 512 columns, four
    blocks of 7.9 MB in VMEM), and the product back; ONE Mosaic kernel
    each, whose grid's length comes from the sizes."""
    from flextree_tpu.ops.grouped_matmul import (
        grouped_matmul, runs_grouped_kernel,
    )

    m, g, d, f = CELL_EXPERTS[cell]
    k, n = (d, f) if product == "inner" else (f, d)
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    a = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    xs, w = a(m, k), a(g, k, n)
    assert runs_grouped_kernel(xs, w)
    if product == "inner":
        fn = lambda xs, w, wg, sizes: grouped_matmul(xs, w, sizes, wg)  # noqa: E731
    else:
        fn = lambda xs, w, wg, sizes: grouped_matmul(xs, w, sizes)  # noqa: E731
    hlo = _compile(fn, xs, w, w, a(g, dt=jnp.int32)).as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    assert "moe_grouped_matmul" in hlo and "ragged-dot" not in hlo


def test_on_the_cpu_no_expert_layer_runs_the_grouped_kernel():
    """The same block where the tests run: ``lax.ragged_dot`` in all 12."""
    from flextree_tpu.models.configs import block_of

    cfg, t, _ = _kimi_cell()
    assert block_of(cfg).expert_kernel_layers(cfg, t["slots"]) == (12, 0)


def test_the_chunked_scan_compiles_at_the_longest_prompt(v5e, tpu_lowering):
    """One KDA layer's recurrence over 4,096 tokens, 32 heads of 128, in
    chunks of 64: the pairwise decays of a sub-chunk (a (.., 16, 16, 128)
    array a sub-chunk, 1.07 GB whole) stay inside their fusions, so the
    scan's temporaries are a fraction of that."""
    from flextree_tpu.ops.linear_attention import delta_rule_chunked

    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    a = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)  # noqa: E731
    wide = a(1, 4096, 32, 128)
    compiled = _compile(
        lambda *x: delta_rule_chunked(*x, chunk=64, sub=16),
        wide, wide, wide, wide, a(1, 4096, 32), a(1, 32, 128, 128))
    assert compiled.memory_analysis().temp_size_in_bytes < 0.9e9


# ------------------------------------------- the state beside K and V rows


def _olmo_cell():
    """(configuration object, traffic, pool shape) of
    ``olmo-hybrid-7b.longdoc-closed-c8``, from the benchmark's files."""
    import json
    import os

    from flextree_tpu.models.configs import config_from_dict
    from flextree_tpu.serving.kv_cache import PagedCacheConfig

    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
    with open(os.path.join(root, "configs", "olmo-hybrid-7b.json")) as f:
        cfg = config_from_dict(json.load(f))
    with open(os.path.join(root, "traffic", "longdoc-closed-c8.json")) as f:
        t = json.load(f)
    return cfg, t, PagedCacheConfig(
        t["num_blocks"], t["block_size"], t["blocks_per_seq"])


def test_the_scan_with_a_decay_a_head_compiles_at_the_longest_prompt(
    v5e, tpu_lowering
):
    """One linear layer's recurrence over 16,384 tokens, 30 heads with keys
    of 96 and values of 192, in chunks of 64, 4,096 tokens a pass as the
    block runs a long prompt: with one decay a head the pairwise decays are
    a (64, 64) matrix a chunk (0.03 GB a pass), where the channel form's
    (.., 16, 16, 96) array a sub-chunk would be 3.02 GB a prompt before its
    products; the scan's temporaries stay far under that (all 16,384
    tokens in one pass: 2.27 GB)."""
    from flextree_tpu.ops.linear_attention import delta_rule_chunked

    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    a = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one)  # noqa: E731
    t, h, dk, dv = 4096, 30, 96, 192
    keys = a(1, t, h, dk)
    compiled = _compile(
        lambda *x: delta_rule_chunked(*x, chunk=64),
        keys, keys, a(1, t, h, dv), a(1, t, h), a(1, t, h), a(1, h, dk, dv))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.7e9, f"{temp / 1e9:.2f} GB"


def test_the_hybrid_cells_decode_program_walks_its_pools_in_the_kernel_in_place(
    v5e, tpu_lowering
):
    """The fused decode program at the cell's size.  The two full layers
    walk their 30-head pools in the paged Mosaic kernel (two
    ``tpu_custom_call``s, nothing left of the loop over table columns),
    heads of (96, 192) are no lane tiles, so the six linear layers run
    the ``jnp`` update, and the block says both; every K and V pool and
    every slot's state is aliased to its result and NONE is copied whole:
    the v5e keeps a (529, 256, 30, 128) pool with the block-size axis next
    to the lanes (30 heads would pad to 32), a gather, a scatter or a
    row-major view of it would first copy it into row-major order (5.56
    GB of temporaries, 14.7 GB the program); ``put_rows`` writes it as it
    lies and the kernel reads it transposed, which is how it lies."""
    import re

    from flextree_tpu.models import olmo_hybrid as olmo
    from flextree_tpu.models.configs import block_of
    from flextree_tpu.serving.kv_cache import (
        init_pools, init_state, make_paged_decode_fn,
    )

    cfg, t, pcfg = _olmo_cell()
    slots = t["slots"]
    assert block_of(cfg).kernel_layers(cfg, pcfg) == (2, 2)
    assert block_of(cfg).state_kernel_layers(cfg) == (6, 0)
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    params = _on(jax.eval_shape(
        lambda k: olmo.init_params(k, cfg), jax.random.PRNGKey(0)), one)
    pools = _on(jax.eval_shape(lambda: init_pools(cfg, pcfg)), one)
    state = _on(jax.eval_shape(lambda: init_state(cfg, slots)), one)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)  # noqa: E731
    compiled = _compile(
        make_paged_decode_fn(cfg, donate=True, fused=True), params, pools,
        i32(slots, pcfg.blocks_per_seq), i32(slots), i32(slots), state)
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 2
    assert " while(" not in hlo
    mem = compiled.memory_analysis()
    carried = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves((pools, state)))
    assert carried == 4 * 529 * 256 * 30 * 128 * 2 + slots * 13_685_760
    assert carried <= mem.alias_size_in_bytes < 1.01 * carried
    assert not re.findall(r"= bf16\[529,[\d,]*\]\S* copy\(", hlo)
    assert mem.temp_size_in_bytes < 0.1e9
    whole = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert whole < 9.5e9, f"{whole / 1e9:.2f} GB"


def test_a_round_fed_the_picks_ids_on_the_device_is_the_same_executable(
    v5e, tpu_lowering
):
    """A round dispatched ahead takes the pick program's ids as they lie on
    the chip, where any other round takes a host array: the decode program
    compiled for the v5e for either ``tokens`` is one executable.  Printed
    without source lines, the two optimized modules are the same
    instruction for instruction, layout for layout; they differ only where
    the abstract argument states its placement (the device array's
    sharding, and that a host array's may be propagated), which at run
    time an uncommitted array on the engine's one device does not state
    (``tests/test_dispatch_ahead.py`` counts the compiles there)."""
    import hashlib
    import re

    from jax._src.lib import xla_client

    from flextree_tpu.serving.engine import greedy_ids
    from flextree_tpu.serving.kv_cache import (
        PagedCacheConfig, init_pools, make_paged_decode_fn,
    )

    one = NamedSharding(_mesh(v5e, (1, 1, 1)), P())
    cfg = TransformerConfig(vocab_size=1024, d_model=256, n_heads=2,
                            n_layers=2, d_ff=512, dtype=jnp.bfloat16)
    pcfg = PagedCacheConfig(num_blocks=65, block_size=16, blocks_per_seq=8)
    slots = 8
    params = _on(jax.eval_shape(
        lambda k: init_params(k, cfg), jax.random.PRNGKey(0)), one)
    pools = _on(jax.eval_shape(lambda: init_pools(cfg, pcfg)), one)
    tables = jax.ShapeDtypeStruct((slots, 8), jnp.int32, sharding=one)
    lengths = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one)
    decode = make_paged_decode_fn(cfg, donate=True, fused=True)
    logits = jax.ShapeDtypeStruct((slots, cfg.vocab_size), jnp.float32,
                                  sharding=one)
    [ids] = jax.tree.leaves(jax.eval_shape(jax.jit(greedy_ids), logits))
    host = jax.ShapeDtypeStruct((slots,), jnp.int32)  # a numpy array
    device = jax.ShapeDtypeStruct(ids.shape, ids.dtype, sharding=one)
    assert (ids.shape, ids.dtype) == (host.shape, host.dtype)

    options = xla_client._xla.HloPrintOptions.short_parsable()
    options.print_metadata = False  # the source lines of the two calls

    def compiled(tokens):
        exe = decode.trace(params, pools, tables, lengths, tokens).lower(
            lowering_platforms=("tpu",)).compile()
        [module] = exe.runtime_executable().hlo_modules()
        return module.to_string(options).splitlines()

    a, b = compiled(host), compiled(device)
    placement = re.compile(
        r"allow_spmd_sharding_propagation_to_parameters=\{[^}]*\}"
        r"|, sharding=\{replicated\}, "
        r'frontend_attributes=\{xla\.sdy\.sharding="[^"]*"\}')
    differ = [i for i, (x, y) in enumerate(zip(a, b)) if x != y]
    assert len(a) == len(b) > 1000 and len(differ) == 2
    assert "tokens" in b[differ[1]] and "parameter(24)" in b[differ[1]]
    print_a, print_b = (
        hashlib.sha256("\n".join(placement.sub("", x) for x in m).encode())
        .hexdigest() for m in (a, b))
    assert print_a == print_b


def test_the_hybrid_cells_longest_prefill_fits_beside_what_is_resident(
    v5e, tpu_lowering
):
    """The 16,384-token prefill program (six chunked scans over 256 chunks,
    two flash forwards over 30 heads) by the compiler's own memory
    analysis, plus what the engine keeps resident meanwhile (pools and
    state): under the chip's 16 GB.  This is where
    a prompt's temporaries show before a chip run does."""
    from flextree_tpu.models import olmo_hybrid as olmo
    from flextree_tpu.serving.kv_cache import init_pools, init_state

    cfg, t, pcfg = _olmo_cell()
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    params = _on(jax.eval_shape(
        lambda k: olmo.init_params(k, cfg), jax.random.PRNGKey(0)), one)
    compiled = _compile(
        lambda p, tok: olmo.prefill(p, tok, cfg, max_len=pcfg.max_len),
        params, jax.ShapeDtypeStruct((1, 16384), jnp.int32, sharding=one))
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2
    mem = compiled.memory_analysis()
    program = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    pools = jax.eval_shape(lambda: init_pools(cfg, pcfg))
    state = jax.eval_shape(lambda: init_state(cfg, t["slots"]))
    resident = sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves((pools, state)))
    print(f"prefill program {program / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
    assert program + resident < 14.5e9, (
        f"{program / 1e9:.2f} GB program + {resident / 1e9:.2f} GB resident")


# ------------------------------------------------ the diffusion block's pass


def _sdar_cell():
    """(configuration object, traffic, pool config) of
    ``sdar-30b-a3b-chat.gen-block4-closed-c32``, from the benchmark's
    files."""
    import json
    import os

    from flextree_tpu.models.configs import config_from_dict
    from flextree_tpu.serving.kv_cache import PagedCacheConfig

    root = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
    with open(os.path.join(root, "configs", "sdar-30b-a3b-chat.json")) as f:
        cfg = config_from_dict(json.load(f))
    with open(os.path.join(root, "traffic", "gen-block4-closed-c32.json")) as f:
        t = json.load(f)
    return cfg, t, PagedCacheConfig(
        t["num_blocks"], t["block_size"], t["blocks_per_seq"])


@pytest.mark.parametrize("hkv", [1, 2, 4])
def test_a_pool_of_heads_that_fill_a_tile_is_read_as_it_lies_row_major(
    v5e, tpu_lowering, hkv
):
    """1, 2 and 4 K/V heads fill a tile of their own: the v5e holds a
    (1025, 128, Hkv, 128) bf16 pool row-major (``T(4,128)`` tiles for 4
    heads, ``T(2,128)`` for 2; one head's axis is of size 1 and orders
    nothing), so ``pool_relayouts`` is false and the kernel reads it
    (position, head) through the ``(N, 128 * Hkv, 128)`` view, a bitcast:
    one Mosaic call, no copy of the pool, nearly no temporaries (taken
    head-major, as every head count that is no multiple of 8 was, 4 heads
    cost two whole-pool copies a call).  The B = 4 positions of a slot's
    open block fold into 32 x 4 = 128 query rows over the same walk."""
    import re

    from flextree_tpu.ops.paged_attention import pool_relayouts

    avals = _paged_avals(v5e[0], 32, 128, hkv, 128, 128, 32, 1025)
    assert not pool_relayouts(avals[3])
    assert kernel_admits(avals[0], avals[3])
    compiled = _compile(paged_attention, *avals)
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    if hkv > 1:
        assert re.search(
            rf"bf16\[1025,128,{hkv},128\]\{{3,2,1,0:T\({hkv},128\)", hlo)
    assert not re.search(r"= \w+\[1025,[\d,]*\]\S* copy\(", hlo)
    assert len(re.findall(
        rf"= bf16\[1025,{128 * hkv},128\]\S* bitcast\(", hlo)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2e6


def test_the_block_cells_pass_program_walks_in_the_kernel_in_place(
    v5e, tpu_lowering
):
    """The pass program at the cell's size: 32 slots x 4 positions over six
    layers.  Each layer's attention is ONE paged Mosaic call (the block's
    rows written first, the 4 positions folded into 128 query rows), each
    expert layer the grouped kernel (1,024 sorted picks over 128 experts of
    2048 x 768: two Mosaic calls), as the block says; every pool is aliased
    to its result and none is copied whole; the temporaries are the
    logits' and the picks'."""
    import re

    from flextree_tpu.models import sdar_moe
    from flextree_tpu.models.configs import block_of
    from flextree_tpu.serving.kv_cache import init_pools, make_paged_decode_fn

    cfg, t, pcfg = _sdar_cell()
    slots, b = t["slots"], cfg.block_len
    assert block_of(cfg).kernel_layers(cfg, pcfg) == (6, 6)
    assert block_of(cfg).expert_kernel_layers(cfg, slots) == (6, 6)
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    params = _on(jax.eval_shape(
        lambda k: sdar_moe.init_params(k, cfg), jax.random.PRNGKey(0)), one)
    pools = _on(jax.eval_shape(lambda: init_pools(cfg, pcfg)), one)

    def a(*s, dt=jnp.int32):
        return jax.ShapeDtypeStruct(s, dt, sharding=one)

    compiled = _compile(
        make_paged_decode_fn(cfg, donate=True, fused=True), params, pools,
        a(slots, pcfg.blocks_per_seq), a(slots), a(slots, b),
        a(slots, dt=jnp.bool_))
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 6 + 6 * 2
    # nothing is left of the loop over table columns: the only loops are
    # the grouped kernel's search for its visits
    loops = [ln for ln in hlo.splitlines() if " while(" in ln]
    assert loops and all("ft_moe_experts" in ln for ln in loops)
    mem = compiled.memory_analysis()
    carried = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert carried == 12 * 1025 * 128 * 4 * 128 * 2
    assert carried <= mem.alias_size_in_bytes < 1.01 * carried
    assert not re.findall(r"= bf16\[1025,[\d,]*\]\S* copy\(", hlo)
    assert mem.temp_size_in_bytes < 0.3e9, f"{mem.temp_size_in_bytes / 1e9:.2f} GB"
    whole = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert whole < 10.8e9, f"{whole / 1e9:.2f} GB"


def test_a_pass_fed_the_picks_open_blocks_on_the_device_is_the_same_executable(
    v5e, tpu_lowering
):
    """A diffusion pass dispatched ahead takes its open blocks from the pick
    program as they lie on the chip, where any other pass takes them from
    the host: at the cell's shapes the pick compiles for the v5e with its
    fold, and the pass program compiled for either ``tokens`` is one
    executable (the two optimized modules differ only where the abstract
    argument states its placement, as for the one-token round's ids)."""
    import hashlib
    import re

    from jax._src.lib import xla_client
    from jax.experimental.compilation_cache import compilation_cache

    from flextree_tpu.models import sdar_moe
    from flextree_tpu.serving.engine import unmask_greedy_ids
    from flextree_tpu.serving.kv_cache import init_pools, make_paged_decode_fn

    cfg, t, pcfg = _sdar_cell()
    slots, b = t["slots"], cfg.block_len
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    params = _on(jax.eval_shape(
        lambda k: sdar_moe.init_params(k, cfg), jax.random.PRNGKey(0)), one)
    pools = _on(jax.eval_shape(lambda: init_pools(cfg, pcfg)), one)

    def a(*s, dt=jnp.int32, placed=True):
        return jax.ShapeDtypeStruct(s, dt, sharding=one if placed else None)

    args = (a(slots, b, cfg.vocab_size, dt=jnp.float32),
            a(slots, b, dt=jnp.bool_), a(slots), a(slots, b),
            a(slots, dt=jnp.bool_))
    _, _, tokens, masked = jax.eval_shape(
        lambda *x: unmask_greedy_ids(*x, mask_id=cfg.mask_id), *args)
    assert (tokens.shape, tokens.dtype, masked.dtype) == (
        (slots, b), jnp.int32, jnp.bool_)
    pick = jax.jit(unmask_greedy_ids, static_argnames="mask_id")
    decode = make_paged_decode_fn(cfg, donate=True, fused=True)
    options = xla_client._xla.HloPrintOptions.short_parsable()
    options.print_metadata = False  # the source lines of the two calls

    def compiled(tokens):
        exe = decode.trace(
            params, pools, a(slots, pcfg.blocks_per_seq), a(slots), tokens,
            a(slots, dt=jnp.bool_)).lower(lowering_platforms=("tpu",)).compile()
        [module] = exe.runtime_executable().hlo_modules()
        return module.to_string(options).splitlines()

    # the pass program with placed tokens is the one the pass test above
    # compiles too: every compile here is off the persistent cache, which
    # cannot read a program back for a chip that is described, not attached
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        picked = pick.trace(*args, mask_id=cfg.mask_id).lower(
            lowering_platforms=("tpu",)).compile()
        host, device = compiled(a(slots, b, placed=False)), compiled(a(slots, b))
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert picked.memory_analysis().temp_size_in_bytes < 0.2e9
    placement = re.compile(
        r"allow_spmd_sharding_propagation_to_parameters=\{[^}]*\}"
        r"|, sharding=\{replicated\}, "
        r'frontend_attributes=\{xla\.sdy\.sharding="[^"]*"\}')
    differ = [i for i, (x, y) in enumerate(zip(host, device)) if x != y]
    assert len(host) == len(device) > 1000 and len(differ) == 2
    print_host, print_device = (
        hashlib.sha256("\n".join(placement.sub("", x) for x in m).encode())
        .hexdigest() for m in (host, device))
    assert print_host == print_device


def test_the_block_cells_longest_prefill_is_block_causal_flash_beside_what_is_resident(
    v5e, tpu_lowering
):
    """The 2,048-token prefill program: six block-causal flash forwards
    (the ``kvgrid`` kernel with ``block_len`` 4, one Mosaic call a layer),
    within the chip beside the weights and pools."""
    from flextree_tpu.models import sdar_moe
    from flextree_tpu.serving.kv_cache import init_pools

    cfg, t, pcfg = _sdar_cell()
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    params = _on(jax.eval_shape(
        lambda k: sdar_moe.init_params(k, cfg), jax.random.PRNGKey(0)), one)
    compiled = _compile(
        lambda p, tok: sdar_moe.prefill(p, tok, cfg, max_len=pcfg.max_len),
        params, jax.ShapeDtypeStruct((1, 2048), jnp.int32, sharding=one))
    kernels = [
        ln for ln in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in ln
    ]
    assert sum("ft_attn_block" in ln for ln in kernels) == 6
    mem = compiled.memory_analysis()
    program = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    pools = jax.eval_shape(lambda: init_pools(cfg, pcfg))
    resident = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))
    print(f"prefill program {program / 1e9:.2f} GB, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB")
    assert program + resident < 14.5e9, (
        f"{program / 1e9:.2f} GB program + {resident / 1e9:.2f} GB resident")


def test_the_block_cells_prefill_write_copies_no_pool(v5e, tpu_lowering):
    """A prompt's K/V written into its blocks of the twelve 4-head pools:
    a whole-block scatter would first copy each pool into ``T(8,128)``
    tiles and back (24 whole-pool copies), so ``put_blocks`` writes a
    block at a time into the pool as it lies; every pool aliased."""
    import re

    from flextree_tpu.serving.kv_cache import init_pools, write_prefill

    cfg, t, pcfg = _sdar_cell()
    one = NamedSharding(_mesh(v5e[:1], (1, 1, 1)), P())
    pools = _on(jax.eval_shape(lambda: init_pools(cfg, pcfg)), one)
    row = jax.ShapeDtypeStruct((1, pcfg.max_len, 4, 128), jnp.bfloat16, sharding=one)
    cache = {part: [row] * 6 for part in ("k", "v")}
    compiled = _compile(
        jax.jit(write_prefill, donate_argnums=(0,)), pools, cache,
        jax.ShapeDtypeStruct((16,), jnp.int32, sharding=one))
    hlo = compiled.as_text()
    assert not re.findall(r"= bf16\[1025,[\d,]*\]\S* copy\(", hlo)
    mem = compiled.memory_analysis()
    carried = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(pools))
    assert carried <= mem.alias_size_in_bytes and mem.temp_size_in_bytes < 0.05e9
