"""Layer 2: HLO linter — lower the jitted entrypoints and hold the
StableHLO to declared budgets.

``tests/test_hlo_lowering.py`` pins a handful of lowering facts with
one-off asserts; this layer generalizes them into a declarative contract:
every entrypoint (allreduce variants, the bucketed train step, the MoE and
pipeline steps) carries an :class:`HloBudget` stating what its compiled
program may contain —

- **collective counts**: scheduled collectives scale with buckets and
  stages, never with gradient leaves; chunked schedules multiply by the
  chunk count, never more;
- **op classes**: no ``all_to_all`` outside the entrypoints that earn it
  (Ulysses, MoE dispatch), no host transfers
  (``send``/``recv``/``infeed``/``outfeed``) anywhere;
- **dtype**: collectives on the bf16 path carry bf16 operands — a silent
  f32 upcast doubles wire bytes and is exactly the kind of regression a
  refactor introduces without failing any numeric test;
- **donation**: entrypoints jitted with donated buffers actually lower
  with ``tf.aliasing_output`` / ``jax.buffer_donor`` so XLA may alias (a dropped donation doubles
  peak memory, again numerically invisible).

Everything works on ``jax.jit(...).lower().as_text()`` — tracing plus
StableHLO emission, no XLA compile — so the whole layer runs in seconds
on the CPU host.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

from .base import Violation

__all__ = [
    "HloBudget",
    "collective_counts",
    "collective_operand_dtypes",
    "collective_wire_bytes",
    "lint_ir",
    "lower_entrypoints",
    "overlap_sync_budget",
    "sharded_sync_budget",
    "run_hlo_lint",
]

#: StableHLO ops that move data between host and device — never expected
#: in any FlexTree program (the whole point is staying on-fabric).
HOST_TRANSFER_OPS = (
    "stablehlo.send",
    "stablehlo.recv",
    "stablehlo.infeed",
    "stablehlo.outfeed",
)

COLLECTIVE_OPS = (
    "reduce_scatter",
    "all_gather",
    "all_reduce",
    "collective_permute",
    "all_to_all",
)


@dataclass(frozen=True)
class HloBudget:
    """Declared contract for one lowered entrypoint.  ``None`` = unchecked;
    counts are exact-or-max depending on ``exact`` (exact catches both
    regressions *and* silently-vanished collectives)."""

    reduce_scatter: int | None = None
    all_gather: int | None = None
    all_reduce: int | None = None
    collective_permute: int | None = None
    all_to_all: int | None = 0
    exact: bool = True
    #: allowed element types on collective operands (None = unchecked)
    collective_dtypes: tuple[str, ...] | None = None
    #: require at least one donated input to survive lowering
    require_donation: bool = False
    #: compressed entrypoints: at least one collective must carry this
    #: element type on the wire (e.g. "i8") — a refactor that decodes
    #: before the collective keeps the numerics quantized but silently
    #: multiplies the wire bytes back up (violation kind "codec-upcast")
    require_wire_dtype: str | None = None
    #: overlapped entrypoints: backward compute (dot_general) must appear
    #: AFTER the first scheduled sync collective in program order — the
    #: readiness-ordered step issues each bucket's collective mid-backward,
    #: so a program whose collectives all trail the last matmul has
    #: reintroduced the full-backward barrier (violation kind
    #: "overlap-serialization"; StableHLO emission preserves trace order,
    #: so the check is a pure text-order one).  Only meaningful on
    #: entrypoints whose forward has no collectives (dp-only meshes).
    require_compute_after_collective: bool = False
    #: sharded (ZeRO) entrypoints: at least one all_gather must FOLLOW the
    #: first optimizer sqrt (AdamW's sqrt(nu)) in program order — the
    #: sharded step gathers updated PARAMETERS, which exist only after the
    #: shard update; a step whose gathers all precede the optimizer math
    #: has regathered the GRADIENTS instead (the replicated schedule in
    #: disguise: numerically identical for f32, but the optimizer state is
    #: fully replicated again and the wire savings the sharding exists for
    #: are gone).  Violation kind "shard-regather"; only meaningful on
    #: entrypoints whose forward emits no all_gather (dp-only meshes) and
    #: whose only sqrt is AdamW's (rms_norm uses rsqrt, a different op).
    require_gather_after_update: bool = False
    note: str = ""


def collective_counts(ir: str) -> dict[str, int]:
    return {op: ir.count(f'"stablehlo.{op}"') for op in COLLECTIVE_OPS}


def collective_operand_dtypes(ir: str) -> dict[str, list[str]]:
    """Element type of each collective op's operand, parsed from the
    ``: (tensor<...xTY>, ...) -> ...`` suffix of its line.  The attribute
    dict mid-line contains nested ``<...>`` (channel handles), so only the
    trailing operand-type list is parsed — same lesson as
    ``tests/test_hlo_lowering.py``."""
    out: dict[str, list[str]] = {op: [] for op in COLLECTIVE_OPS}
    for line in ir.splitlines():
        for op in COLLECTIVE_OPS:
            if f'"stablehlo.{op}"' not in line:
                continue
            m = re.search(r":\s*\(tensor<([^>]*?)>", line)
            if m:
                elem = m.group(1).split("x")[-1]
                out[op].append(elem)
    return out


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "i64": 8, "i32": 4, "i16": 2, "i8": 1, "i1": 1,
    "ui64": 8, "ui32": 4, "ui16": 2, "ui8": 1,
}


_COLL_RE = re.compile(
    r'"stablehlo\.(reduce_scatter|all_reduce|all_gather|all_to_all|'
    r'collective_permute)"'
)
_SIG_RE = re.compile(r":\s*\(([^()]*)\)\s*->")
_GRP_RE = re.compile(r"replica_groups\s*=\s*dense<[^>]*>\s*:\s*tensor<\d+x(\d+)xi64>")
_TENSOR_RE = re.compile(r"tensor<([0-9x]*)([a-z][a-z0-9]*)>")


def collective_wire_bytes(ir: str) -> dict[str, float]:
    """Per-chip wire bytes of every collective in ``ir``, from the lowered
    StableHLO — the static accounting the sharded step's wire floors
    (``tests/test_sharded.py::TestWireBytes``) are checked against.

    Per op the operand bytes (every tensor in its ``: (...) ->``
    signature; region ops close with ``}) : (tensor<..>)``, and their
    reducer-body ops carry no parenthesized signature, so the first match
    after the op IS its own) are scaled by the op's wire factor over its
    replica-group width ``w``: ``(w-1)/w`` for reduce_scatter/all_to_all
    (each chip keeps 1/w), ``2(w-1)/w`` for all_reduce, ``w-1`` for
    all_gather (the operand is the 1/w tile; each chip receives ``w-1``
    more), ``1`` for collective_permute.  Only valid for programs whose
    collectives are not inside ``fori_loop`` bodies (loop trip counts are
    invisible to a text scan) — the flat tree lowers loop-free, which is
    why the sharded bench pins ``grad_topo`` flat.
    """
    out: dict[str, float] = {op: 0.0 for op in COLLECTIVE_OPS}
    for m in _COLL_RE.finditer(ir):
        op = m.group(1)
        window = ir[m.start() : m.start() + 8000]
        sig = _SIG_RE.search(window)
        if not sig:
            continue
        grp = _GRP_RE.search(window[: sig.end()])
        w = int(grp.group(1)) if grp else 1
        nbytes = 0
        for dims, ty in _TENSOR_RE.findall(sig.group(1)):
            n = 1
            for d in dims.split("x"):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES.get(ty, 4)
        if op in ("reduce_scatter", "all_to_all"):
            factor = (w - 1) / w if w > 1 else 0.0
        elif op == "all_reduce":
            factor = 2 * (w - 1) / w if w > 1 else 0.0
        elif op == "all_gather":
            factor = float(w - 1)
        else:
            factor = 1.0
        out[op] += nbytes * factor
    out["total"] = sum(out[op] for op in COLLECTIVE_OPS)
    return out


def lint_ir(name: str, ir: str, budget: HloBudget) -> list[Violation]:
    out: list[Violation] = []
    counts = collective_counts(ir)
    for op in COLLECTIVE_OPS:
        want = getattr(budget, op)
        if want is None:
            continue
        got = counts[op]
        bad = got != want if budget.exact else got > want
        if bad:
            rel = "!=" if budget.exact else ">"
            out.append(
                Violation(
                    "hlo",
                    "budget",
                    name,
                    f"{got} stablehlo.{op} ops {rel} budget {want}"
                    + (f" ({budget.note})" if budget.note else ""),
                )
            )
    for op in HOST_TRANSFER_OPS:
        if f'"{op}"' in ir:
            out.append(
                Violation(
                    "hlo",
                    "host-transfer",
                    name,
                    f"unexpected {op}: program round-trips through the host",
                )
            )
    if budget.collective_dtypes is not None:
        for op, dtypes in collective_operand_dtypes(ir).items():
            for dt in dtypes:
                if dt not in budget.collective_dtypes:
                    out.append(
                        Violation(
                            "hlo",
                            "dtype-drift",
                            name,
                            f"stablehlo.{op} operates on {dt}, allowed "
                            f"{budget.collective_dtypes}: a silent upcast "
                            f"multiplies wire bytes",
                        )
                    )
                    break
    if budget.require_wire_dtype is not None:
        seen = {dt for dts in collective_operand_dtypes(ir).values() for dt in dts}
        if budget.require_wire_dtype not in seen:
            out.append(
                Violation(
                    "hlo",
                    "codec-upcast",
                    name,
                    f"no collective carries {budget.require_wire_dtype} on "
                    f"the wire (saw {sorted(seen)}): the codec was decoded "
                    f"before the collective — numerics stay quantized while "
                    f"the wire bytes silently multiply back up",
                )
            )
    if budget.require_compute_after_collective:
        lines = ir.splitlines()
        first_coll = None
        last_dot = None
        for i, line in enumerate(lines):
            if first_coll is None and (
                '"stablehlo.reduce_scatter"' in line
                or '"stablehlo.all_to_all"' in line
            ):
                first_coll = i
            if "stablehlo.dot_general" in line:
                last_dot = i
        if first_coll is None or last_dot is None or last_dot < first_coll:
            out.append(
                Violation(
                    "hlo",
                    "overlap-serialization",
                    name,
                    "no backward compute (dot_general) follows the first "
                    "sync collective: every collective trails the full "
                    "backward — the readiness-ordered overlap has been "
                    "serialized behind a full-backward barrier",
                )
            )
    if budget.require_gather_after_update:
        # anchor AFTER the first sync collective (reduce_scatter /
        # all_to_all): the forward emits its own sqrt ops, but only the
        # optimizer's sqrt(nu) can appear after the gradient sync starts
        # — in the correct sharded step that sqrt precedes the parameter
        # all_gather; in the grad-regathering corruption every gather
        # lands before it
        lines = ir.splitlines()
        first_coll = None
        first_sqrt_after = None
        last_gather = None
        for i, line in enumerate(lines):
            if first_coll is None and (
                '"stablehlo.reduce_scatter"' in line
                or '"stablehlo.all_to_all"' in line
            ):
                first_coll = i
            if (
                first_coll is not None
                and first_sqrt_after is None
                and i > first_coll
                and "stablehlo.sqrt " in line
            ):
                first_sqrt_after = i
            if '"stablehlo.all_gather"' in line:
                last_gather = i
        if (
            first_coll is None
            or first_sqrt_after is None
            or last_gather is None
            or last_gather < first_sqrt_after
        ):
            out.append(
                Violation(
                    "hlo",
                    "shard-regather",
                    name,
                    "no all_gather follows the optimizer update (first "
                    "sqrt) in program order: the step gathers GRADIENTS "
                    "instead of updated parameter shards — the replicated "
                    "schedule in disguise, with the optimizer state fully "
                    "replicated again and the sharded wire savings gone",
                )
            )
    if budget.require_donation and not any(
        # a donation matched to an output at lowering, or left to XLA
        attr in ir for attr in ("tf.aliasing_output", "jax.buffer_donor")
    ):
        out.append(
            Violation(
                "hlo",
                "donation",
                name,
                "neither tf.aliasing_output nor jax.buffer_donor survived "
                "lowering: the donated input is being copied, doubling "
                "peak memory",
            )
        )
    return out


# ----------------------------------------------------------- entrypoints


def _require_devices(n: int = 8) -> None:
    import jax

    if len(jax.devices()) < n:
        raise RuntimeError(
            f"hlo lint needs {n} (virtual) devices, found "
            f"{len(jax.devices())} — run under the analysis CLI or the "
            f"test harness, which pin 8 virtual CPU devices"
        )


def _lower_allreduce(topo, op="sum", dtype=None, chunks=1, donate=False) -> str:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel import tree_allreduce
    from ..parallel.mesh import flat_mesh

    if dtype is None:
        dtype = jnp.float32 if op == "sum" else jnp.int32
    mesh = flat_mesh(8, "ft")

    def f(row):
        return tree_allreduce(row[0], "ft", topo, op=op, chunks=chunks)[None]

    fn = jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"))
    jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())
    # the input arrives row-sharded, as every caller's does: JAX matches a
    # donation to an output only when the shardings agree
    x = jax.ShapeDtypeStruct(
        (8, 64), dtype, sharding=NamedSharding(mesh, P("ft"))
    )
    return jitted.lower(x).as_text()


def _lower_compressed_allreduce(topo, codec, size: int = 2048, upcast: bool = False) -> str:
    """Lower ``compressed_allreduce`` with ``codec`` over an 8-device mesh.

    ``upcast=True`` builds the *corrupted* variant for the mutation
    self-test: quantize/dequantize locally, then run the plain f32
    collective — the classic silent wire upcast (numerically almost
    indistinguishable from the compressed path, 4x the wire bytes).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..ops.quantize import get_codec
    from ..parallel import tree_allreduce
    from ..parallel.compressed import compressed_allreduce
    from ..parallel.mesh import flat_mesh

    mesh = flat_mesh(8, "ft")

    def f(row):
        if upcast:
            c = get_codec(codec)
            return tree_allreduce(c.roundtrip(row[0], 0), "ft", topo)[None]
        return compressed_allreduce(row[0], "ft", topo=topo, codec=codec, step=0)[None]

    fn = jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"))
    return jax.jit(fn).lower(jnp.zeros((8, size), jnp.float32)).as_text()


def _lower_ring(dtype=None) -> str:
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel import ring_allreduce
    from ..parallel.mesh import flat_mesh

    mesh = flat_mesh(8, "ft")

    def f(row):
        return ring_allreduce(row[0], "ft")[None]

    fn = jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"))
    return jax.jit(fn).lower(jnp.zeros((8, 64), dtype or jnp.float32)).as_text()


def _small_model_cfg():
    import jax.numpy as jnp

    from ..models.transformer import TransformerConfig

    return TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64
    )


def _lower_train_step(bucket_bytes) -> str:
    import jax
    import jax.numpy as jnp

    from ..parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        make_train_step,
    )

    model_cfg = _small_model_cfg()
    mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, model_cfg), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    step = make_train_step(
        mesh, model_cfg, TrainConfig(bucket_bytes=bucket_bytes)
    )
    return step.lower(state_sds, tok, tok).as_text()


def _lower_native_train_step() -> str:
    import jax
    import jax.numpy as jnp

    from ..parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        make_train_step,
    )

    model_cfg = _small_model_cfg()
    mesh = make_mesh_nd(8, (2, 2, 2), ("dp", "sp", "tp"))
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, model_cfg), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    step = make_train_step(mesh, model_cfg, TrainConfig(grad_topo="psum"))
    return step.lower(state_sds, tok, tok).as_text()


def bucketed_sync_budget() -> tuple[int, int]:
    """(expected fused-sync reduce_scatter/all_gather count, synced leaf
    count) from the very bucket plan the sync executes — the generalized
    form of the one-off guard in ``tests/test_hlo_lowering.py``."""
    import jax
    import jax.numpy as jnp

    from ..parallel.bucketing import plan_buckets, replication_key
    from ..parallel.train import init_train_state, state_specs

    model_cfg = _small_model_cfg()
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, model_cfg), jax.random.PRNGKey(0)
    )
    pspecs = state_specs(model_cfg, "tp")["params"]
    flat_g, treedef = jax.tree.flatten(state_sds["params"])
    flat_s = treedef.flatten_up_to(pspecs)
    axis_sizes = {"dp": 2, "sp": 2, "tp": 2}
    buckets = plan_buckets(
        flat_g, flat_s, ("dp", "sp", "tp"),
        axis_sizes=axis_sizes, bucket_bytes=1 << 30,
    )
    expected = sum(len(b.axes) for b in buckets)
    n_synced = sum(1 for s in flat_s if replication_key(s, ("dp", "sp", "tp")))
    return expected, n_synced


def _lower_overlap_train_step(
    serialize: bool = False, codec: str = "f32"
) -> str:
    """Lower the readiness-ordered overlapped dense step (or, with
    ``serialize=True``, its full-backward-barrier twin) on a dp-only
    8-device mesh — tp=sp=1, so the forward emits NO collectives and
    every scheduled collective in the program belongs to the gradient
    sync (the precondition for ``require_compute_after_collective``)."""
    import jax
    import jax.numpy as jnp

    from ..parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        make_train_step,
    )

    model_cfg = _small_model_cfg()
    mesh = make_mesh_nd(8, (8, 1, 1), ("dp", "sp", "tp"))
    # explicit inner cap AND explicit flat topology so the budget is
    # environment-independent: one collective per fired boundary bucket,
    # immune to an ambient FT_TOPO (grad_topo=None would resolve through
    # the env var and diverge from overlap_sync_budget's flat(8) plan)
    train_cfg = TrainConfig(
        overlap=True, codec=codec, bucket_bytes=1 << 30, grad_topo="8"
    )
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, model_cfg, train_cfg),
        jax.random.PRNGKey(0),
    )
    tok = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    step = make_train_step(
        mesh, model_cfg, train_cfg, serialize_overlap=serialize
    )
    return step.lower(state_sds, tok, tok).as_text()


def overlap_sync_budget(codec: str = "f32") -> tuple[int, int]:
    """(number of fired overlap buckets, number of readiness segments)
    for the overlapped dense entrypoint above, from the very plan the
    step executes at trace time (``parallel.overlap.plan_overlap``) — so
    the collective-count budget tracks the planner, not a hand-kept
    constant.  On the dp-only mesh every bucket is one (dp, f32) group:
    one scheduled tree collective per bucket (rs+ag pair for the identity
    codec; grouped a2a/ag pairs for int8)."""
    import jax
    import jax.numpy as jnp

    from ..ops.quantize import get_codec
    from ..parallel.overlap import plan_overlap
    from ..parallel.train import TrainConfig, init_train_state, state_specs
    from ..schedule.stages import Topology

    model_cfg = _small_model_cfg()
    train_cfg = TrainConfig(overlap=True, codec=codec, bucket_bytes=1 << 30)
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, model_cfg, train_cfg),
        jax.random.PRNGKey(0),
    )
    pspecs = state_specs(model_cfg, "tp")["params"]
    c = get_codec(codec)
    # n_tokens/t_local are PER-DEVICE (inside shard_map the (8, 32) batch
    # shards to (1, 32) on the dp-8 mesh) — must match the traced values
    plan = plan_overlap(
        state_sds["params"], pspecs, ("dp", "sp", "tp"),
        {"dp": Topology.flat(8), "sp": None, "tp": None},
        {"dp": 8, "sp": 1, "tp": 1},
        n_tokens=32, t_local=32, d_model=model_cfg.d_model,
        codec=c if c.lossy else None,
    )
    return plan.n_buckets, len(plan.labels)


def _lower_split_collective(topo, phase: str, codec: str = "f32") -> str:
    """Lower a standalone reduce_scatter or all_gather over the 8-device
    mesh (divisible count, so the shard is a pure 1/N block)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel.allreduce import all_gather, reduce_scatter
    from ..parallel.mesh import flat_mesh

    mesh = flat_mesh(8, "ft")
    size = 2048

    def f(row):
        if phase == "rs":
            return reduce_scatter(row[0], "ft", topo, codec=codec)[None]
        return all_gather(row[0], "ft", topo, codec=codec)[None]

    n_in = size if phase == "rs" else size // 8
    fn = jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"))
    return jax.jit(fn).lower(jnp.zeros((8, n_in), jnp.float32)).as_text()


def _lower_sharded_train_step(codec: str = "f32", regather: bool = False) -> str:
    """Lower the ZeRO-1 sharded dense step on a dp-only 8-device mesh —
    tp=sp=1, so the forward emits NO collectives and every reduce-scatter
    / all_gather in the program belongs to the sharded sync (the
    precondition for ``require_gather_after_update``).

    ``regather=True`` builds the *corrupted* variant for the mutation
    self-test: the replicated step over the same explicit flat(8) plan —
    literally "a sharded step that secretly all-gathers gradients instead
    of parameters" (identical collective counts: one rs + one ag per
    bucket; bitwise-identical f32 numerics; the ONLY observable
    difference is that its gathers precede the optimizer sqrt)."""
    import jax
    import jax.numpy as jnp

    from ..parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        make_train_step,
    )

    model_cfg = _small_model_cfg()
    mesh = make_mesh_nd(8, (8, 1, 1), ("dp", "sp", "tp"))
    train_cfg = TrainConfig(
        shard_optimizer=not regather, codec=codec,
        bucket_bytes=1 << 30, grad_topo="8",
    )
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, model_cfg, train_cfg, mesh=mesh),
        jax.random.PRNGKey(0),
    )
    tok = jax.ShapeDtypeStruct((8, 32), jnp.int32)
    step = make_train_step(mesh, model_cfg, train_cfg)
    return step.lower(state_sds, tok, tok).as_text()


def sharded_sync_budget(codec: str = "f32") -> tuple[int, int]:
    """(number of ZeRO buckets, number of synced leaves) for the sharded
    dense entrypoint above, from the very bucket plan the step executes —
    one grad reduce-scatter AND one param all-gather per bucket on the
    dp-only flat(8) plan (for int8: 2 grouped all_to_alls per bucket for
    the grads — i8 payload + f32 scales — and 2 all_gathers for the
    params)."""
    import jax
    import jax.numpy as jnp

    from ..ops.quantize import get_codec
    from ..parallel.bucketing import plan_buckets, replication_key
    from ..parallel.train import init_train_state, state_specs, TrainConfig

    model_cfg = _small_model_cfg()
    state_sds = jax.eval_shape(
        lambda k: init_train_state(k, model_cfg), jax.random.PRNGKey(0)
    )
    pspecs = state_specs(model_cfg, "tp")["params"]
    flat_g, treedef = jax.tree.flatten(state_sds["params"])
    flat_s = treedef.flatten_up_to(pspecs)
    axis_sizes = {"dp": 8, "sp": 1, "tp": 1}
    c = get_codec(codec)
    buckets = plan_buckets(
        flat_g, flat_s, ("dp", "sp", "tp"),
        axis_sizes=axis_sizes, bucket_bytes=1 << 30,
        codec=c if c.lossy else None, sharded=True,
    )
    n_synced = sum(
        1
        for s in flat_s
        if any(axis_sizes[a] > 1 for a in replication_key(s, ("dp", "sp", "tp")))
    )
    return len(buckets), n_synced


def _lower_moe_step() -> str:
    import jax
    import jax.numpy as jnp

    from ..models.moe import MoEConfig
    from ..parallel.moe_train import (
        init_moe_train_state,
        make_mesh_moe,
        make_moe_train_step,
    )
    from ..parallel.train import TrainConfig

    cfg = MoEConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        n_experts=4, top_k=1, moe_every=2,
    )
    mesh = make_mesh_moe(8, (1, 2, 2, 2))
    state_sds = jax.eval_shape(
        lambda k: init_moe_train_state(k, cfg), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    step = make_moe_train_step(mesh, cfg, TrainConfig(bucket_bytes=1 << 30))
    return step.lower(state_sds, tok, tok).as_text()


def _lower_pipeline_step() -> str:
    import jax
    import jax.numpy as jnp

    from ..parallel.pipeline import (
        init_pipeline_train_state,
        make_mesh_4d,
        make_pipeline_train_step,
    )
    from ..parallel.train import TrainConfig

    cfg = _small_model_cfg()
    mesh = make_mesh_4d(8, (1, 2, 2, 2))
    state_sds = jax.eval_shape(
        lambda k: init_pipeline_train_state(k, cfg), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    step = make_pipeline_train_step(
        mesh, cfg, train_cfg=TrainConfig(bucket_bytes=1 << 30),
        n_microbatches=2,
    )
    return step.lower(state_sds, tok, tok).as_text()


def lower_entrypoints(full: bool = True) -> list[tuple[str, str, HloBudget]]:
    """(name, stablehlo text, budget) for every linted entrypoint.

    ``full=False`` lowers only the allreduce-family entrypoints (no model
    steps) — the fast subset.
    """
    _require_devices(8)
    rows: list[tuple[str, str, HloBudget]] = [
        (
            "tree_allreduce_sum_4x2_f32",
            _lower_allreduce((4, 2)),
            HloBudget(
                reduce_scatter=2, all_gather=2, all_reduce=0,
                collective_permute=0,
                collective_dtypes=("f32",),
                note="one grouped rs+ag pair per stage",
            ),
        ),
        (
            "tree_allreduce_sum_4x2_bf16",
            _lower_allreduce((4, 2), dtype="bfloat16"),
            HloBudget(
                reduce_scatter=2, all_gather=2, all_reduce=0,
                collective_permute=0,
                collective_dtypes=("bf16",),
                note="bf16 path must not upcast collectives to f32",
            ),
        ),
        (
            "tree_allreduce_bor_4x2_i32",
            _lower_allreduce((4, 2), op="bor"),
            HloBudget(
                reduce_scatter=0, all_gather=2, all_reduce=0,
                collective_permute=2,
                note="non-sum stages are the ppermute ring, one per stage",
            ),
        ),
        (
            "tree_allreduce_sum_4x2_chunks4",
            _lower_allreduce((4, 2), chunks=4),
            HloBudget(
                reduce_scatter=8, all_gather=8, all_reduce=0,
                collective_permute=0,
                note="chunks=C multiplies scheduled collectives by exactly C",
            ),
        ),
        (
            "ring_allreduce_f32",
            _lower_ring(),
            HloBudget(
                reduce_scatter=0, all_gather=0, all_reduce=0,
                collective_permute=2,
                note="two fori_loop neighbor permutes, O(1) in N",
            ),
        ),
        (
            "compressed_allreduce_bf16_4x2",
            _lower_compressed_allreduce((4, 2), "bf16"),
            HloBudget(
                reduce_scatter=2, all_gather=2, all_reduce=0,
                collective_permute=0,
                collective_dtypes=("bf16",),
                require_wire_dtype="bf16",
                note="bf16 codec: the scheduled collectives must carry "
                     "bf16 on the wire, never a silent f32 upcast",
            ),
        ),
        (
            "compressed_allreduce_int8_4x2",
            _lower_compressed_allreduce((4, 2), "int8"),
            HloBudget(
                reduce_scatter=0, all_gather=4, all_reduce=0,
                collective_permute=0, all_to_all=4,
                collective_dtypes=("i8", "f32"),
                require_wire_dtype="i8",
                note="int8 codec: per-stage grouped all_to_all of (i8 "
                     "payload, f32 scales) + encoded-forwarding gathers; "
                     "the bulk payload must be i8 on the wire",
            ),
        ),
        (
            "tree_allreduce_donated",
            _lower_allreduce((4, 2), donate=True),
            HloBudget(
                reduce_scatter=2, all_gather=2,
                require_donation=True,
                note="donated input must lower with jax.buffer_donor",
            ),
        ),
        (
            "reduce_scatter_f32_4x2",
            _lower_split_collective((4, 2), "rs"),
            HloBudget(
                reduce_scatter=2, all_gather=0, all_reduce=0,
                collective_permute=0,
                collective_dtypes=("f32",),
                note="phase 1 alone: one grouped reduce-scatter per stage, "
                     "NO allgather — the split seam (PR 7)",
            ),
        ),
        (
            "all_gather_f32_4x2",
            _lower_split_collective((4, 2), "ag"),
            HloBudget(
                reduce_scatter=0, all_gather=2, all_reduce=0,
                collective_permute=0,
                collective_dtypes=("f32",),
                note="phase 2 alone: one grouped allgather per stage, NO "
                     "reduce-scatter",
            ),
        ),
        (
            "reduce_scatter_int8_4x2",
            _lower_split_collective((4, 2), "rs", codec="int8"),
            HloBudget(
                reduce_scatter=0, all_gather=0, all_reduce=0,
                collective_permute=0, all_to_all=4,
                collective_dtypes=("i8", "f32"),
                require_wire_dtype="i8",
                note="compressed phase 1: per-stage grouped (i8 payload, "
                     "f32 scales) all_to_alls; int8 stays i8 on the wire",
            ),
        ),
    ]
    if not full:
        return rows

    native = collective_counts(_lower_native_train_step())
    expected_sync, n_synced_leaves = bucketed_sync_budget()
    bucketed_ir = _lower_train_step(bucket_bytes=1 << 30)
    rows.append(
        (
            "train_step_bucketed",
            bucketed_ir,
            HloBudget(
                reduce_scatter=native["reduce_scatter"] + expected_sync,
                all_gather=native["all_gather"] + expected_sync,
                # fused tails: at most one dense collective per bucket-axis
                # on top of the step's own psums
                all_reduce=native["all_reduce"] + expected_sync,
                exact=False,
                note=(
                    f"sync collectives scale with buckets "
                    f"({expected_sync} bucket-axes), never with the "
                    f"{n_synced_leaves} gradient leaves"
                ),
            ),
        )
    )
    rows.append(
        (
            "moe_train_step_bucketed",
            _lower_moe_step(),
            HloBudget(
                # MoE earns its all_to_alls (dispatch+combine per MoE layer,
                # forward and backward) but they must stay bounded and
                # static: 1 MoE layer x 2 exchanges x (fwd + bwd) = 4
                all_to_all=4,
                exact=False,
                note="MoE dispatch/combine only; no per-leaf sync blowup",
            ),
        )
    )
    rows.append(
        (
            "pipeline_train_step_bucketed",
            _lower_pipeline_step(),
            HloBudget(
                all_to_all=0,
                note="GPipe moves activations on collective_permute only",
            ),
        )
    )

    # readiness-ordered overlap (ISSUE 6): the overlapped step and its
    # full-backward-barrier twin carry the SAME collective-count budget —
    # overlap must relocate collectives, never add or drop them — and the
    # overlapped one must actually interleave them with backward compute
    n_buckets, n_segments = overlap_sync_budget()
    overlap_budget = dict(
        reduce_scatter=n_buckets, all_gather=n_buckets,
        collective_permute=0,
        note=(
            f"sync collectives scale with the {n_buckets} planned overlap "
            f"buckets over {n_segments} readiness segments; counts must "
            f"equal the serialized twin's"
        ),
    )
    rows.append(
        (
            "train_step_overlapped",
            _lower_overlap_train_step(serialize=False),
            HloBudget(require_compute_after_collective=True, **overlap_budget),
        )
    )
    rows.append(
        (
            "train_step_overlap_serialized",
            _lower_overlap_train_step(serialize=True),
            HloBudget(**overlap_budget),
        )
    )
    n_buckets_i8, _ = overlap_sync_budget("int8")
    rows.append(
        (
            "train_step_overlapped_int8",
            _lower_overlap_train_step(codec="int8"),
            HloBudget(
                reduce_scatter=0, all_to_all=2 * n_buckets_i8,
                collective_dtypes=None,
                require_wire_dtype="i8",
                require_compute_after_collective=True,
                note=(
                    "overlapped int8 sync keeps the wire dtype: grouped "
                    "(i8 payload, f32 scales) all_to_alls fired "
                    "mid-backward, never a decoded f32 collective"
                ),
            ),
        )
    )

    # ZeRO-1 sharded entrypoints (PR 7): one grad reduce-scatter + one
    # param all-gather per bucket, and the gather must FOLLOW the
    # optimizer update — a step that gathers grads instead is the
    # replicated schedule in disguise (the shard-regather mutant)
    nz, nz_leaves = sharded_sync_budget()
    rows.append(
        (
            "train_step_sharded",
            _lower_sharded_train_step(),
            HloBudget(
                reduce_scatter=nz, all_gather=nz, collective_permute=0,
                require_gather_after_update=True,
                note=(
                    f"sharded sync: {nz} buckets over {nz_leaves} synced "
                    f"leaves — one grad rs + one PARAM ag per bucket, "
                    f"gather after the shard update"
                ),
            ),
        )
    )
    nz_i8, _ = sharded_sync_budget("int8")
    rows.append(
        (
            "train_step_sharded_int8",
            _lower_sharded_train_step(codec="int8"),
            HloBudget(
                reduce_scatter=0, all_gather=2 * nz_i8,
                all_to_all=2 * nz_i8, collective_permute=0,
                require_wire_dtype="i8",
                require_gather_after_update=True,
                note=(
                    "sharded int8: grads ride grouped (i8, scales) "
                    "all_to_alls, params ride encoded-forwarding gathers "
                    "— int8 stays i8 on the reduce-scatter wire"
                ),
            ),
        )
    )
    return rows


def run_hlo_lint(full: bool = True) -> tuple[list[Violation], dict]:
    """Lint every entrypoint; returns (violations, per-entrypoint detail)."""
    violations: list[Violation] = []
    detail: dict = {}
    for name, ir, budget in lower_entrypoints(full=full):
        vs = lint_ir(name, ir, budget)
        violations += vs
        detail[name] = {
            "counts": collective_counts(ir),
            "violations": len(vs),
            "note": budget.note,
        }
    return violations, detail


# ------------------------------------------------- mutation entrypoints


def lower_leaf_unrolled_train_step() -> tuple[str, HloBudget]:
    """The 'leaf-unrolled collectives' corruption: the per-leaf train step
    (``bucket_bytes=0``) lowered against the *bucketed* budget.  The
    mutation self-test asserts the linter rejects it — this is the
    regression the bucketing tentpole exists to prevent."""
    native = collective_counts(_lower_native_train_step())
    expected_sync, n_synced = bucketed_sync_budget()
    ir = _lower_train_step(bucket_bytes=0)
    budget = HloBudget(
        reduce_scatter=native["reduce_scatter"] + expected_sync,
        all_gather=native["all_gather"] + expected_sync,
        all_reduce=native["all_reduce"] + expected_sync,
        exact=False,
        note=f"bucketed budget applied to a per-leaf ({n_synced}-leaf) sync",
    )
    return ir, budget


def lower_overlap_serialized_train_step() -> tuple[str, HloBudget]:
    """The 'overlap-serialization' corruption: the overlapped train step
    with the full-backward barrier reintroduced before the first
    collective (``make_train_step(serialize_overlap=True)``) lowered
    against the *overlapped* budget.  Numerically bitwise-identical to
    the overlapped step — only the linter's program-order check can see
    that every collective now trails the backward, un-hiding all the wire
    time the overlap tentpole exists to hide."""
    _require_devices(8)
    n_buckets, n_segments = overlap_sync_budget()
    ir = _lower_overlap_train_step(serialize=True)
    budget = HloBudget(
        reduce_scatter=n_buckets, all_gather=n_buckets,
        collective_permute=0,
        require_compute_after_collective=True,
        note=f"overlapped budget applied to the {n_segments}-segment "
             f"barrier twin",
    )
    return ir, budget


def lower_shard_regather_train_step() -> tuple[str, HloBudget]:
    """The 'shard-regather' corruption: a "sharded" step that secretly
    all-gathers GRADIENTS instead of updated parameters — which is
    exactly the replicated step over the same flat(8) bucket plan
    (identical collective counts: one rs + one ag per bucket;
    bitwise-identical f32 numerics; optimizer state silently fully
    replicated again).  Only the program-ORDER check can see it: every
    all_gather precedes the optimizer sqrt."""
    _require_devices(8)
    nz, nz_leaves = sharded_sync_budget()
    ir = _lower_sharded_train_step(regather=True)
    budget = HloBudget(
        reduce_scatter=nz, all_gather=nz, collective_permute=0,
        require_gather_after_update=True,
        note=f"sharded budget applied to the grad-regathering "
             f"({nz_leaves}-leaf replicated) step",
    )
    return ir, budget


def lower_codec_upcast_allreduce() -> tuple[str, HloBudget]:
    """The 'codec-upcast' corruption: an int8-codec entrypoint refactored
    to decode *before* the collective — quantized numerics (so every
    numeric test still passes), f32 on the wire (4x the bytes).  The
    linter must flag the missing i8 wire dtype."""
    _require_devices(8)
    ir = _lower_compressed_allreduce((4, 2), "int8", upcast=True)
    budget = HloBudget(
        reduce_scatter=0, all_gather=4, all_reduce=0,
        collective_permute=0, all_to_all=4,
        collective_dtypes=("i8", "f32"),
        require_wire_dtype="i8",
        note="int8-codec budget applied to a decode-before-wire program",
    )
    return ir, budget


def lower_dtype_drifted_allreduce() -> tuple[str, HloBudget]:
    """The 'dtype drift' corruption: a bf16 allreduce that silently
    upcasts to f32 around the collective — numerically near-identical,
    2x the wire bytes.  The linter must flag the f32 collectives."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel import tree_allreduce
    from ..parallel.mesh import flat_mesh

    _require_devices(8)
    mesh = flat_mesh(8, "ft")

    def f(row):
        drifted = tree_allreduce(
            row[0].astype(jnp.float32), "ft", (4, 2)
        )
        return drifted.astype(jnp.bfloat16)[None]

    fn = jax.shard_map(f, mesh=mesh, in_specs=P("ft"), out_specs=P("ft"))
    ir = jax.jit(fn).lower(jnp.zeros((8, 64), jnp.bfloat16)).as_text()
    budget = HloBudget(
        reduce_scatter=2, all_gather=2,
        collective_dtypes=("bf16",),
        note="bf16 entrypoint: collectives must stay bf16",
    )
    return ir, budget
