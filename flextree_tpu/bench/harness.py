"""Benchmark harness: A/B the FlexTree allreduce against the platform-native
collective, mirroring the reference's standalone harness
(``allreduce_over_mpi/benchmark.cpp``).

Correspondence:
- CLI flags ``--size --repeat --comm-type --to-file --tag``
    -> ``benchmark.cpp:67-116`` (same names; ``--comm-type`` values are
       ``flextree`` and ``xla`` — the latter standing in for the reference's
       ``mpi`` library baseline, ``benchmark.cpp:161-174``);
- per-rep timing with a completion gate -> ``benchmark.cpp:149-159``
  (``block_until_ready`` instead of ``MPI_Barrier``+``MPI_Wtime``);
- eyeball check of elements 9..19 plus a hard assert
    -> ``benchmark.cpp:180-189`` (ours also asserts; theirs only printed);
- config summary before the run -> ``benchmark.cpp:128-143``;
- result files ``{tag}.{N}.{size}.{topo}.{ar|comm}_test.{time}.json``
    -> ``benchmark.cpp:193-213``.

Reported metric: per-chip algorithmic (bus) bandwidth ``2(N-1)/N * S / t``
per BASELINE.md, plus min/avg wall time like ``benchmark.cpp:215``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.reduce import get_op
from ..parallel.mesh import allreduce_over_mesh, flat_mesh
from ..planner.cost_model import bus_bandwidth_GBps
from ..schedule.stages import Topology
from ..utils.logging import get_logger, result_file_name, write_result_file
from ..utils.timing import (
    BenchResult,
    time_chained,
    time_device_loop,
    time_jax_fn,
    time_jax_fn_inplace,
)

__all__ = [
    "BenchConfig",
    "BenchReport",
    "run_allreduce_bench",
    "AttentionBenchConfig",
    "AttentionBenchReport",
    "run_attention_bench",
    "autotune_attention",
    "chip_peak_tflops",
    "GradSyncBenchConfig",
    "run_grad_sync_bench",
    "TrainStepBenchConfig",
    "run_train_step_bench",
    "make_nosync_train_step",
]

log = get_logger("flextree.bench")


@dataclass(frozen=True)
class BenchConfig:
    size: int = 35  # elements per chip (reference default, benchmark.cpp:36)
    repeat: int = 10
    comm_type: str = "flextree"  # flextree | xla
    topo: str | None = None  # FT_TOPO-style spec; None -> env/flat
    devices: int | None = None  # None -> all available
    dtype: str = "float32"
    op: str = "sum"
    tag: str = "flextree"
    to_file: bool = False
    out_dir: str = "."
    # in-place timing (the reference benchmark's MPI_IN_PLACE compounding
    # loop, benchmark.cpp:149-159): each rep's output is the next rep's
    # input and the input buffer is donated.  The xla baseline is timed
    # both donated and non-donated and keeps its best (XLA's fused
    # all-reduce cannot always alias a donated buffer).
    in_place: bool = True


@dataclass(frozen=True)
class BenchReport:
    config: BenchConfig
    num_devices: int
    topo: str
    result: BenchResult
    bus_bw_GBps: float
    correct: bool
    result_path: str | None = None

    def payload(self) -> dict:
        return {
            "config": dataclasses.asdict(self.config),
            "num_devices": self.num_devices,
            "topo": self.topo,
            "times_s": list(self.result.times_s),
            "compile_s": self.result.compile_s,
            "min_s": self.result.min_s,
            "avg_s": self.result.avg_s,
            "bus_bw_GBps": self.bus_bw_GBps,
            "correct": self.correct,
        }


import functools


@functools.lru_cache(maxsize=64)
def _jitted_psum(mesh, axis, donate: bool = False):
    """Cached jitted lax.psum baseline — cached exactly like the flextree
    path's ``_jitted_allreduce`` so the A/B times collectives, not retraces."""

    def per_device(row):
        return lax.psum(row[0], axis)[None]

    return jax.jit(
        jax.shard_map(per_device, mesh=mesh, in_specs=P(axis), out_specs=P(axis)),
        donate_argnums=(0,) if donate else (),
    )


def _xla_psum_over_mesh(stacked, mesh, axis, op):
    """The platform-native baseline (the reference's ``--comm-type mpi``)."""
    if op != "sum":
        raise ValueError("the xla baseline benchmarks psum; use op=sum")
    return _jitted_psum(mesh, axis)(stacked)


def run_allreduce_bench(cfg: BenchConfig) -> BenchReport:
    from ..schedule.ir import resolve_collective

    n = cfg.devices or len(jax.devices())
    mesh = flat_mesh(n, "ft")
    # the widened resolver: IR-family specs ("swing", "gen:4,2@2")
    # benchmark like any legacy topo
    topo = resolve_collective(n, cfg.topo)
    dtype = jnp.dtype(cfg.dtype)
    rop = get_op(cfg.op)
    rop.check_dtype(dtype)

    # data[r, i] = (i % 256) + r, like benchmark.cpp:119-124 but with
    # per-rank-distinct rows so every op has a non-trivial reduction; values
    # are small so float32 sums stay exactly representable and integer
    # wraparound (int8 etc.) is identical on host and device
    base = np.arange(cfg.size, dtype=np.int64) % 256
    data = (base[None, :] + np.arange(n, dtype=np.int64)[:, None]).astype(dtype)
    # row i on device i BEFORE any call: JAX only honours a donation whose
    # input already has the output's sharding (an unsharded input is
    # dropped from the aliasing at lowering, with a warning, on every
    # backend), and a bench that reshards inside its first call times that
    stacked = jax.device_put(jnp.asarray(data), NamedSharding(mesh, P("ft")))
    if stacked.dtype != dtype:
        # e.g. float64 demoted to float32 when jax_enable_x64 is off; keep
        # the host copy consistent so the correctness check and byte counts
        # describe what actually ran
        log.warning("dtype %s demoted to %s on device", dtype, stacked.dtype)
        dtype = stacked.dtype
        data = data.astype(dtype)

    log.info(
        "bench config: devices=%d size=%d dtype=%s op=%s comm=%s topo=%s repeat=%d",
        n, cfg.size, cfg.dtype, cfg.op, cfg.comm_type, topo, cfg.repeat,
    )

    # ``fn`` is the non-donating variant used for the correctness check;
    # timing uses the in-place chained protocol when cfg.in_place (values
    # compound across reps exactly like the reference's MPI_IN_PLACE loop —
    # they may saturate to inf late in the chain, which is timing-neutral
    # for IEEE arithmetic; correctness is asserted on a pristine call below).
    if cfg.comm_type == "flextree":
        fn = lambda x: allreduce_over_mesh(x, mesh, topo=topo, op=cfg.op)
        if cfg.in_place:
            fn_timed = lambda x: allreduce_over_mesh(
                x, mesh, topo=topo, op=cfg.op, in_place=True
            )
            result = time_jax_fn_inplace(fn_timed, jnp.array(stacked), repeat=cfg.repeat)
        else:
            result = time_jax_fn(fn, stacked, repeat=cfg.repeat)
    elif cfg.comm_type == "xla":
        fn = lambda x: _xla_psum_over_mesh(x, mesh, "ft", cfg.op)
        if cfg.in_place:
            if cfg.op != "sum":
                raise ValueError("the xla baseline benchmarks psum; use op=sum")
            # give the baseline its best shot: donated and non-donated
            r_don = time_jax_fn_inplace(
                _jitted_psum(mesh, "ft", donate=True), jnp.array(stacked),
                repeat=cfg.repeat,
            )
            r_plain = time_jax_fn_inplace(
                _jitted_psum(mesh, "ft", donate=False), jnp.array(stacked),
                repeat=cfg.repeat,
            )
            result = r_don if r_don.min_s <= r_plain.min_s else r_plain
        else:
            result = time_jax_fn(fn, stacked, repeat=cfg.repeat)
    else:
        raise ValueError(f"unknown --comm-type {cfg.comm_type!r} (flextree|xla)")

    out = np.asarray(fn(stacked))
    # fold the op over the host rows in the on-device dtype: integer
    # wraparound then matches the device exactly; floats are compared with
    # tolerance since the collective may reassociate the sum
    expect = data[0]
    for r in range(1, n):
        expect = rop.np_fn(expect, data[r])
    got = out[0]
    if np.issubdtype(dtype, np.inexact) or dtype == jnp.bfloat16:
        correct = bool(
            np.allclose(
                got.astype(np.float64), expect.astype(np.float64),
                rtol=1e-3, atol=1e-3,
            )
        )
    else:
        correct = bool(np.array_equal(got, expect))
    lo, hi = 9, min(20, cfg.size)
    if hi > lo:  # the reference's eyeball print of data[9..19]
        log.info("elements %d..%d: %s (expect %s)", lo, hi - 1,
                 got[lo:hi].tolist(), expect[lo:hi].tolist())

    nbytes = cfg.size * stacked.dtype.itemsize
    bus = bus_bandwidth_GBps(n, nbytes, result.min_s * 1e6)
    log.info(
        "average time %.3f ms / min time %.3f ms / bus bw %.3f GB/s / correct=%s",
        result.avg_s * 1e3, result.min_s * 1e3, bus, correct,
    )

    path = None
    if cfg.to_file:
        name = result_file_name(
            cfg.tag, n, cfg.size, str(topo), comm_test=(cfg.comm_type == "xla")
        )
        report = BenchReport(cfg, n, str(topo), result, bus, correct, None)
        path = str(write_result_file(f"{cfg.out_dir}/{name}", report.payload()))
        log.info("wrote %s", path)

    return BenchReport(cfg, n, str(topo), result, bus, correct, path)


# ---------------------------------------------------------- gradient sync


@dataclass(frozen=True)
class GradSyncBenchConfig:
    """A/B the bucketed/fused gradient sync against per-leaf sync.

    ``n_leaves`` leaves of ``leaf_size`` float32 elements model a
    transformer's small-leaf tail (the many-small-leaves regime where
    per-leaf sync pays k x the per-dispatch overhead); ``n_leaves=1`` with
    a large ``leaf_size`` is the single-large-tensor regime where fusion
    must be a no-op cost-wise.
    """

    n_leaves: int = 48
    leaf_size: int = 16384  # float32 elements per leaf
    devices: int | None = None
    topo: str | None = None  # FT_TOPO-style; None -> env/flat
    repeat: int = 10
    chunks: int = 2  # the ours_chunked row's pipelining factor
    bucket_bytes: int | None = None  # None -> planner-derived
    # extra wire-codec rows (ops/quantize.py), e.g. ("bf16", "int8"):
    # each adds an ``ours_fused_<codec>`` row — excluded from the bitwise
    # identity check (lossy by design) and checked against the codec's
    # documented error bound instead
    codecs: tuple = ()


def run_grad_sync_bench(cfg: GradSyncBenchConfig) -> dict:
    """Rows: ``per_leaf`` (the historical sync), ``ours_fused`` (bucketed),
    ``ours_chunked`` (bucketed + chunk-pipelined) — min/avg ms each, the
    fused rows' speedup vs per-leaf, and a bitwise-identity check between
    the per-leaf and fused outputs (the sync's hard contract)."""
    from ..parallel.bucketing import plan_buckets
    from ..parallel.train import resolve_axis_topos, sync_grads

    n = cfg.devices or len(jax.devices())
    mesh = flat_mesh(n, "dp")
    topos = resolve_axis_topos(mesh, ("dp",), cfg.topo)
    rng = np.random.default_rng(0)
    tree = {
        f"leaf{i}": jnp.asarray(
            rng.standard_normal((n, cfg.leaf_size)).astype(np.float32)
        )
        for i in range(cfg.n_leaves)
    }
    dev_specs = {k: P() for k in tree}  # every leaf replicated -> synced
    io_specs = {k: P("dp") for k in tree}

    def make_fn(bucket_bytes, chunks, codec="f32"):
        def f(t):
            rows = {k: v[0] for k, v in t.items()}
            out = sync_grads(
                rows, dev_specs, ("dp",), topos,
                bucket_bytes=bucket_bytes, chunks=chunks, codec=codec,
            )
            return {k: v[None] for k, v in out.items()}

        return jax.jit(
            jax.shard_map(
                f, mesh=mesh, in_specs=(io_specs,), out_specs=io_specs,
                check_vma=False,
            )
        )

    variants = {
        "per_leaf": make_fn(0, 1),
        "ours_fused": make_fn(cfg.bucket_bytes, 1),
        "ours_chunked": make_fn(cfg.bucket_bytes, cfg.chunks),
    }
    for codec in cfg.codecs:
        variants[f"ours_fused_{codec}"] = make_fn(cfg.bucket_bytes, 1, codec)
    outs = {
        name: jax.block_until_ready(fn(tree))  # also warms the jit
        for name, fn in variants.items()
    }
    rows = _interleaved_times(
        {name: (fn, (tree,)) for name, fn in variants.items()}, cfg.repeat
    )
    for name in rows:
        if name != "per_leaf":
            rows[name]["vs_per_leaf"] = (
                rows["per_leaf"]["min_ms"] / rows[name]["min_ms"]
            )

    identical = all(
        np.asarray(outs["per_leaf"][k]).tobytes()
        == np.asarray(outs["ours_fused"][k]).tobytes()
        == np.asarray(outs["ours_chunked"][k]).tobytes()
        for k in tree
    )
    if not identical:
        raise RuntimeError("fused sync output diverged from per-leaf (bitwise)")
    if cfg.codecs:
        # lossy rows: no bitwise contract — hold them to the codec's
        # documented error bound against the exact per-leaf sync instead
        from ..ops.quantize import get_codec
        from ..schedule.stages import LonelyTopology

        t = Topology.resolve(n, cfg.topo)
        if isinstance(t, LonelyTopology):
            widths, lonely = t.tree.widths, t.lonely
        else:
            widths, lonely = t.widths, 0
        for codec in cfg.codecs:
            c = get_codec(codec)
            worst = 0.0
            for k in tree:
                exact = np.asarray(outs["per_leaf"][k], dtype=np.float64)
                got = np.asarray(
                    outs[f"ours_fused_{codec}"][k], dtype=np.float64
                )
                amax = float(np.abs(np.asarray(tree[k])).max())
                bound = c.error_bound(amax, n, widths, lonely) + 1e-5
                err = float(np.abs(got - exact).max())
                worst = max(worst, err / bound if bound else 0.0)
                if c.lossy and err > bound:
                    raise RuntimeError(
                        f"codec {codec} sync error {err:.5f} exceeds the "
                        f"documented bound {bound:.5f} on leaf {k}"
                    )
            rows[f"ours_fused_{codec}"]["err_over_bound"] = worst
    buckets = plan_buckets(
        [v[0] for v in tree.values()], [P()] * cfg.n_leaves, ("dp",),
        topos=topos, axis_sizes={"dp": n}, bucket_bytes=cfg.bucket_bytes,
    )
    total_mb = cfg.n_leaves * cfg.leaf_size * 4 / 2**20
    log.info(
        "grad sync %d leaves x %d f32 (%.1f MB, %d buckets): per_leaf %.2f ms,"
        " fused %.2f ms (%.2fx), chunked %.2f ms (%.2fx)",
        cfg.n_leaves, cfg.leaf_size, total_mb, len(buckets),
        rows["per_leaf"]["min_ms"],
        rows["ours_fused"]["min_ms"], rows["ours_fused"]["vs_per_leaf"],
        rows["ours_chunked"]["min_ms"], rows["ours_chunked"]["vs_per_leaf"],
    )
    return {
        "config": dataclasses.asdict(cfg),
        "num_devices": n,
        "topo": str(Topology.resolve(n, cfg.topo)),
        "total_mb": total_mb,
        "n_buckets": len(buckets),
        "identical": identical,
        "rows": rows,
    }


def _interleaved_times(calls: dict, repeat: int) -> dict:
    """Per-variant min/avg ms with the timed reps INTERLEAVED per round in
    a (deterministically) shuffled order instead of back-to-back blocks: on
    the timeshared 1-core bench host a sustained contention episode
    otherwise lands entirely on one variant and swings the A/B ratio ~20%
    run-to-run (the BENCH_ALLREDUCE r03/r04 lesson, same fix as bench.py's
    CPU A/B), and a FIXED round-robin order adds a position bias — each
    variant always inherits the cache state its fixed predecessor leaves
    behind.  ``calls`` maps name -> (jitted_fn, args); every fn must
    already be compiled/warm."""
    import random

    from ..utils.timing import Timer

    order = list(calls)
    shuffler = random.Random(0)
    times: dict[str, list[float]] = {name: [] for name in calls}
    for _ in range(repeat):
        shuffler.shuffle(order)
        for name in order:
            fn, fargs = calls[name]
            t = Timer()
            jax.block_until_ready(fn(*fargs))
            times[name].append(t.stop())
    return {
        name: {
            "min_ms": min(ts) * 1e3,
            "avg_ms": sum(ts) / len(ts) * 1e3,
            # raw per-round samples (round i of every variant ran in the
            # same shuffled round), so callers can form PAIRED per-round
            # statistics — on a heavily timeshared host the min of two
            # variants' independent draws swings far more than any
            # per-round ratio does
            "times_ms": [t * 1e3 for t in ts],
        }
        for name, ts in times.items()
    }


@dataclass(frozen=True)
class TrainStepBenchConfig:
    """End-to-end ``train_step_ms``: the full jitted train step (forward +
    backward + sync + AdamW) under per-leaf vs fused vs chunked gradient
    sync.  The default model is the many-small-leaves regime (50 gradient
    leaves, most under 20 KB) on a pure-dp mesh."""

    n_layers: int = 6
    d_model: int = 64
    d_ff: int = 128
    n_heads: int = 4
    vocab_size: int = 256
    batch: int = 8
    seq_len: int = 64
    devices: int | None = None
    topo: str | None = None  # grad_topo for the sync
    repeat: int = 5
    chunks: int = 2
    # add an ``ours_fused_supervised`` row: the fused step wrapped in the
    # runtime supervision host path (step watchdog on its persistent
    # worker thread + heartbeat Supervisor fed per-step durations) — the
    # fault-free overhead the ISSUE-4 acceptance bounds at <= 2%
    supervised: bool = True
    # add the readiness-ordered overlap rows (ISSUE 6): ``no_sync`` (the
    # same forward/backward/AdamW with the gradient sync elided — the
    # exposure baseline), ``ours_overlapped`` (TrainConfig(overlap=True))
    # and ``ours_overlap_serialized`` (its full-backward-barrier twin —
    # equal collective counts, bitwise-equal results).  Every sync row
    # then carries ``exposed_comm_ms`` (step-time delta over no_sync);
    # the overlapped row also carries ``hidden_comm_ms`` = the twin's
    # exposure minus its own — wire time that ran under backward compute.
    # Default False: the overlapped step is the slowest compile in the
    # suite (one vjp per layer) and pre-existing callers' artifacts
    # (BENCH_BUCKETING.json) keep their historical row schema.
    overlap: bool = False
    # add the ZeRO-1 sharded rows (PR 7): ``ours_sharded`` (f32 — updated
    # params asserted bitwise-identical to per-leaf) and
    # ``ours_sharded_int8`` (both wires quantized), each with the
    # per-rank optimizer-state ratio from the live layout
    # (zero.zero_shard_bytes).  Default False for the same
    # artifact-schema reason as ``overlap``.
    sharded: bool = False
    # add an ``ours_fused_recorded`` row (ISSUE 10): the fused step with
    # the flight recorder + metrics registry on its host path (step
    # start/end events with per-step flush to a JSONL spill, one
    # histogram observe) — ``recorder_overhead`` is the ratio the <= 2%
    # telemetry budget is checked against.  Default False for the same
    # artifact-schema reason as ``overlap``.
    recorder: bool = False


def make_nosync_train_step(mesh, model_cfg, train_cfg, axis_names=("dp", "sp", "tp")):
    """The sync-free twin of ``make_train_step``: identical forward,
    backward and AdamW, gradient sync elided — NOT a training step (the
    replicas would diverge) but the exposure baseline the overlap bench
    needs: ``step(with sync) - step(no sync)`` is the sync time that
    actually extended the step (``utils.profiling.exposed_split``)."""
    import jax as _jax

    from ..models.transformer import cross_entropy_loss, forward
    from ..parallel.train import (
        adamw_apply,
        maybe_clip_grads,
        metric_specs,
        state_specs,
        validate_tp,
    )

    dp, sp, tp = axis_names
    validate_tp(model_cfg, mesh.shape[tp])
    sspecs = state_specs(model_cfg, tp, train_cfg)
    data_spec = P(dp, sp)

    def device_step(state, tokens, targets):
        n_total_tokens = (
            tokens.size
            * lax.axis_size(dp)
            * lax.axis_size(sp)
            * lax.axis_size(tp)
        )

        def local_loss(params):
            logits = forward(params, tokens, model_cfg, tp_axis=tp, sp_axis=sp)
            loss_sum, _ = cross_entropy_loss(logits, targets)
            return loss_sum / n_total_tokens

        loss, grads = _jax.value_and_grad(local_loss)(state["params"])
        global_loss = lax.psum(lax.psum(lax.psum(loss, dp), sp), tp)
        metrics = {"loss": global_loss}
        # clip compute stays (compute parity with the real step — only
        # the SYNC is elided), and it also keeps the metrics pytree
        # matching metric_specs when clipping is configured
        grads = maybe_clip_grads(grads, sspecs["params"], train_cfg, metrics)
        new_state = adamw_apply(state, grads, train_cfg)
        return new_state, metrics

    mspec = metric_specs(train_cfg, {"loss": P()})
    return jax.jit(
        jax.shard_map(
            device_step, mesh=mesh, in_specs=(sspecs, data_spec, data_spec),
            out_specs=(sspecs, mspec), check_vma=False,
        )
    )


def run_train_step_bench(cfg: TrainStepBenchConfig) -> dict:
    """Rows of ``train_step_ms`` (min/avg) per sync strategy, plus a
    comm-vs-compute attribution: ``sync_ms`` times the gradient sync alone
    on the model's real gradient tree (the per-bucket ``comm_span`` scopes
    mark the same collectives in profiler traces), so
    ``step - sync = compute`` is readable per row.  With ``cfg.overlap``,
    the readiness-ordered rows and the exposed-vs-hidden comm split are
    added (see :class:`TrainStepBenchConfig`).  Also asserts the fused,
    chunked and overlapped steps' updated parameters are bitwise-identical
    to the per-leaf step's.
    """
    from ..models.transformer import TransformerConfig
    from ..parallel.train import (
        TrainConfig,
        init_train_state,
        make_mesh_nd,
        make_train_step,
        resolve_axis_topos,
        state_specs,
        sync_grads,
    )

    n = cfg.devices or len(jax.devices())
    mesh = make_mesh_nd(n, (n, 1, 1), ("dp", "sp", "tp"))
    model_cfg = TransformerConfig(
        vocab_size=cfg.vocab_size, d_model=cfg.d_model, n_heads=cfg.n_heads,
        n_layers=cfg.n_layers, d_ff=cfg.d_ff,
    )
    state = init_train_state(jax.random.PRNGKey(0), model_cfg)
    n_leaves = len(jax.tree.leaves(state["params"]))
    rng = np.random.default_rng(1)
    toks = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (cfg.batch, cfg.seq_len)), jnp.int32
    )
    tgts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (cfg.batch, cfg.seq_len)), jnp.int32
    )

    train_cfgs = {
        "per_leaf": TrainConfig(grad_topo=cfg.topo, bucket_bytes=0),
        "ours_fused": TrainConfig(grad_topo=cfg.topo),
        "ours_chunked": TrainConfig(grad_topo=cfg.topo, grad_chunks=cfg.chunks),
    }

    # comm attribution: the sync alone, on gradient-shaped data
    pspecs = state_specs(model_cfg, "tp")["params"]
    topos = resolve_axis_topos(mesh, ("dp", "sp", "tp"), cfg.topo)
    grads = jax.tree.map(
        lambda p: jnp.asarray(
            np.random.default_rng(2).standard_normal(p.shape).astype(np.float32)
        ),
        state["params"],
    )

    def make_sync(tc: TrainConfig):
        def f(g):
            return sync_grads(
                g, pspecs, ("dp", "sp", "tp"), topos,
                bucket_bytes=tc.bucket_bytes, chunks=tc.grad_chunks,
            )

        rep = jax.tree.map(lambda _: P(), pspecs)
        return jax.jit(
            jax.shard_map(
                f, mesh=mesh, in_specs=(rep,), out_specs=rep, check_vma=False
            )
        )

    steps, syncs, states_out = {}, {}, {}
    for name, tc in train_cfgs.items():
        steps[name] = make_train_step(mesh, model_cfg, tc)
        states_out[name], _ = jax.block_until_ready(steps[name](state, toks, tgts))
        syncs[name] = make_sync(tc)
        jax.block_until_ready(syncs[name](grads))

    if cfg.overlap:
        tc_ovl = TrainConfig(grad_topo=cfg.topo, overlap=True)
        steps["ours_overlapped"] = make_train_step(mesh, model_cfg, tc_ovl)
        steps["ours_overlap_serialized"] = make_train_step(
            mesh, model_cfg, tc_ovl, serialize_overlap=True
        )
        steps["no_sync"] = make_nosync_train_step(mesh, model_cfg, tc_ovl)
        for name in ("ours_overlapped", "ours_overlap_serialized", "no_sync"):
            out, _ = jax.block_until_ready(steps[name](state, toks, tgts))
            if name != "no_sync":
                states_out[name] = out

    sharded_states: dict = {}
    shard_bytes = None
    if cfg.sharded:
        import dataclasses as _dc

        from ..models.transformer import init_params, param_specs
        from ..parallel.train import zero_layout_for
        from ..parallel.zero import zero_shard_bytes

        tc_sh = TrainConfig(grad_topo=cfg.topo, shard_optimizer=True)
        for name, tc2 in (
            ("ours_sharded", tc_sh),
            ("ours_sharded_int8", _dc.replace(tc_sh, codec="int8")),
        ):
            st2 = init_train_state(
                jax.random.PRNGKey(0), model_cfg, tc2, mesh=mesh
            )
            steps[name] = make_train_step(mesh, model_cfg, tc2)
            sharded_states[name] = st2
            out, _ = jax.block_until_ready(steps[name](st2, toks, tgts))
            states_out[name] = out
        shapes = jax.eval_shape(
            lambda k: init_params(k, model_cfg), jax.random.PRNGKey(0)
        )
        layout = zero_layout_for(
            mesh, shapes, param_specs(model_cfg, "tp"), ("dp", "sp", "tp")
        )
        # per-variant accounting: the int8 state additionally carries the
        # sharded f32 master copy (lossy=True), so its ratio is higher
        shard_bytes = {
            "ours_sharded": zero_shard_bytes(layout),
            "ours_sharded_int8": zero_shard_bytes(layout, lossy=True),
        }

    supervised_ctx = None
    if cfg.supervised:
        # the fault-free supervision host path around the fused step: the
        # watchdog's queue round-trip to its persistent worker thread, a
        # step_scope timing + EWMA update, and the Supervisor's two-store
        # record_step (the beat itself rides the daemon thread, off-path)
        import tempfile
        import time as _time

        from ..runtime.supervisor import Supervisor, SupervisorConfig
        from ..runtime.watchdog import StepWatchdog
        from ..utils.profiling import Ewma

        hb_dir = tempfile.mkdtemp(prefix="ft_hb_bench_")
        sup = Supervisor(
            SupervisorConfig(rank=0, dir=hb_dir, interval_s=0.25)
        ).start()
        wd = StepWatchdog()
        ewma = Ewma()
        fused = steps["ours_fused"]

        def supervised_step(s, tk, tg):
            t0 = _time.perf_counter()
            out = wd.run(fused, s, tk, tg, timeout_s=60.0, step=0)
            dur = _time.perf_counter() - t0
            ewma.update(dur)
            sup.record_step(0, dur)
            return out

        steps["ours_fused_supervised"] = supervised_step
        supervised_ctx = (sup, wd, hb_dir)  # before warmup: cleanup on raise

    recorder_ctx = None
    if cfg.recorder:
        # the telemetry host path around the fused step: a step_start
        # event, the step, a step_end event whose FLUSH_KINDS membership
        # spills the JSONL buffer (write + flush to page cache, no
        # fsync), and one histogram observe — exactly what fit pays per
        # step with --obs-dir on
        import shutil as _shutil
        import tempfile as _tempfile
        import time as _rec_time

        from ..obs.metrics import MetricsRegistry
        from ..obs.recorder import FlightRecorder

        obs_dir = _tempfile.mkdtemp(prefix="ft_obs_bench_")
        rec = FlightRecorder(obs_dir, rank=0)
        reg = MetricsRegistry()
        hist = reg.histogram("train.step_ms")
        fused_for_rec = steps["ours_fused"]

        def recorded_step(s, tk, tg):
            t0 = _rec_time.perf_counter()
            rec.record("step_start", step=0)
            out = fused_for_rec(s, tk, tg)
            rec.record("step_end", step=0)
            hist.observe((_rec_time.perf_counter() - t0) * 1e3)
            return out

        steps["ours_fused_recorded"] = recorded_step
        recorder_ctx = (rec, obs_dir, _shutil)

    try:
        if supervised_ctx is not None:
            jax.block_until_ready(
                steps["ours_fused_supervised"](state, toks, tgts)
            )
        if recorder_ctx is not None:
            jax.block_until_ready(
                steps["ours_fused_recorded"](state, toks, tgts)
            )
        step_times = _interleaved_times(
            {
                n: (fn, (sharded_states.get(n, state), toks, tgts))
                for n, fn in steps.items()
            },
            cfg.repeat,
        )
        sync_times = _interleaved_times(
            {n: (fn, (grads,)) for n, fn in syncs.items()}, cfg.repeat
        )
    finally:
        if supervised_ctx is not None:  # don't leak threads/tmpdir on raise
            import shutil

            sup, wd, hb_dir = supervised_ctx
            wd.close()
            sup.stop()
            shutil.rmtree(hb_dir, ignore_errors=True)
        if recorder_ctx is not None:
            rec, obs_dir, _shutil = recorder_ctx
            rec.close()
            _shutil.rmtree(obs_dir, ignore_errors=True)
    rows = {}
    for name in train_cfgs:
        rows[name] = {
            "train_step_ms": step_times[name]["min_ms"],
            "train_step_avg_ms": step_times[name]["avg_ms"],
            "sync_ms": sync_times[name]["min_ms"],
            "compute_ms": max(
                step_times[name]["min_ms"] - sync_times[name]["min_ms"], 0.0
            ),
        }
    for name in ("ours_fused", "ours_chunked"):
        rows[name]["vs_per_leaf"] = (
            rows["per_leaf"]["train_step_ms"] / rows[name]["train_step_ms"]
        )
    if cfg.overlap:
        from ..utils.profiling import exposed_split

        nosync_ms = step_times["no_sync"]["min_ms"]
        rows["no_sync"] = {
            "train_step_ms": nosync_ms,
            "train_step_avg_ms": step_times["no_sync"]["avg_ms"],
        }
        # the serialized twin hides nothing, so its exposure IS the
        # overlapped program's comm total (equal collective counts, equal
        # payloads) — the comm_total the overlapped row's split is cut by
        twin_exposed = max(
            step_times["ours_overlap_serialized"]["min_ms"] - nosync_ms, 0.0
        )
        for name in ("ours_overlapped", "ours_overlap_serialized"):
            exp, hid = exposed_split(
                step_times[name]["min_ms"], nosync_ms, twin_exposed
            )
            rows[name] = {
                "train_step_ms": step_times[name]["min_ms"],
                "train_step_avg_ms": step_times[name]["avg_ms"],
                "exposed_comm_ms": exp,
                "hidden_comm_ms": hid,
                "vs_per_leaf": (
                    rows["per_leaf"]["train_step_ms"]
                    / step_times[name]["min_ms"]
                ),
            }
        for name in ("per_leaf", "ours_fused", "ours_chunked"):
            rows[name]["exposed_comm_ms"] = max(
                step_times[name]["min_ms"] - nosync_ms, 0.0
            )
        # clamped denominator: a zero exposure (fully hidden, or noise
        # crossing zero on this host) must not put Infinity into
        # artifacts that embed these rows (BENCH_OVERLAP.json)
        exp_o = rows["ours_overlapped"]["exposed_comm_ms"]
        rows["ours_overlapped"]["exposed_vs_serialized"] = (
            twin_exposed / max(exp_o, 0.1)
        )
    if cfg.supervised:
        t = step_times["ours_fused_supervised"]
        rows["ours_fused_supervised"] = {
            "train_step_ms": t["min_ms"],
            "train_step_avg_ms": t["avg_ms"],
            "sync_ms": sync_times["ours_fused"]["min_ms"],  # same collective
            "compute_ms": max(
                t["min_ms"] - sync_times["ours_fused"]["min_ms"], 0.0
            ),
            # the acceptance number: supervised/unsupervised fused step
            "supervision_overhead": t["min_ms"]
            / rows["ours_fused"]["train_step_ms"],
        }
    if cfg.recorder:
        t = step_times["ours_fused_recorded"]
        rows["ours_fused_recorded"] = {
            "train_step_ms": t["min_ms"],
            "train_step_avg_ms": t["avg_ms"],
            # the ISSUE-10 acceptance number: recorder-on/recorder-off
            # fused step, same protocol as supervision_overhead
            "recorder_overhead": t["min_ms"]
            / rows["ours_fused"]["train_step_ms"],
        }

    if cfg.sharded:
        for name in ("ours_sharded", "ours_sharded_int8"):
            rows[name] = {
                "train_step_ms": step_times[name]["min_ms"],
                "train_step_avg_ms": step_times[name]["avg_ms"],
                "vs_per_leaf": (
                    rows["per_leaf"]["train_step_ms"]
                    / step_times[name]["min_ms"]
                ),
                "opt_state_bytes_ratio": shard_bytes[name]["ratio"],
            }

    identical = True
    variants = ["ours_fused", "ours_chunked"]
    if cfg.overlap:
        variants += ["ours_overlapped", "ours_overlap_serialized"]
    if cfg.sharded:
        variants += ["ours_sharded"]  # int8 is lossy: bounded, not bitwise
    for name in variants:
        same = all(
            np.asarray(a).tobytes() == np.asarray(b).tobytes()
            for a, b in zip(
                jax.tree.leaves(states_out["per_leaf"]["params"]),
                jax.tree.leaves(states_out[name]["params"]),
            )
        )
        if not same:
            raise RuntimeError(
                f"{name} train step diverged from per-leaf (bitwise)"
            )
        identical = identical and same
    log.info(
        "train step (%d leaves): per_leaf %.2f ms, fused %.2f ms (%.2fx), "
        "chunked %.2f ms (%.2fx); sync %.2f -> %.2f ms",
        n_leaves,
        rows["per_leaf"]["train_step_ms"],
        rows["ours_fused"]["train_step_ms"], rows["ours_fused"]["vs_per_leaf"],
        rows["ours_chunked"]["train_step_ms"],
        rows["ours_chunked"]["vs_per_leaf"],
        rows["per_leaf"]["sync_ms"], rows["ours_fused"]["sync_ms"],
    )
    return {
        "config": dataclasses.asdict(cfg),
        "num_devices": n,
        "n_grad_leaves": n_leaves,
        "identical": identical,
        "rows": rows,
    }


# ---------------------------------------------------------------- attention


@dataclass(frozen=True)
class AttentionBenchConfig:
    batch: int = 4
    seq_len: int = 4096
    heads: int = 16
    head_dim: int = 128
    dtype: str = "bfloat16"
    impl: str = "flash"  # flash | reference | stock
    repeat: int = 20
    block_q: int = 256
    block_k: int = 512
    # forward k-walk structure (flash impl only): "loop" | "pipelined" |
    # "kvgrid" — see flextree_tpu.ops.pallas_attention.flash_attention
    variant: str = "loop"
    # "device_loop": in-jit chained fori_loop, slope of two iteration
    # counts — measures DEVICE time only, the fixed per-dispatch cost
    # cancels.  "chained": per-call python loop with a final fetch —
    # includes dispatch overhead; kept for comparison/CPU tests.
    timing: str = "device_loop"
    # "fwd": forward only.  "grad": grads of sum(attention) wrt (q, k, v) —
    # for flash/stock, exercises the forward-with-residuals plus both
    # blockwise backward kernels; reported FLOPs are per-impl hardware
    # FLOPs (flash & stock 4.5x fwd — qk recomputed in both the dq and dkv
    # kernels; reference 3x, P stored — see grad_flop_scale in
    # run_attention_bench).
    mode: str = "fwd"


from ..utils.device import tpu_generation  # dependency-free normalizer

#: bf16 peak TFLOP/s by generation, for MFU reporting.
_TPU_PEAK_TFLOPS = {
    "v5e": 197.0,
    "v6e": 918.0,
    "v5p": 459.0,
    "v4": 275.0,
    "v3": 123.0,
    "v2": 45.0,
}


def chip_peak_tflops() -> float | None:
    """bf16 peak of device 0; None on the CPU only (MFU then unreported).
    An accelerator whose ``device_kind`` is not in the table is an error:
    MFU must not silently vanish on the machine it is meant for."""
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return _TPU_PEAK_TFLOPS[tpu_generation(dev.device_kind)]


@dataclass(frozen=True)
class AttentionBenchReport:
    config: AttentionBenchConfig
    per_call_s: float
    tflops: float
    mfu: float | None = None  # tflops / chip bf16 peak, when on TPU
    result_path: str | None = None

    def payload(self) -> dict:
        return {
            "bench": "attention",
            "impl": self.config.impl,
            "mode": self.config.mode,
            "batch": self.config.batch,
            "seq_len": self.config.seq_len,
            "heads": self.config.heads,
            "head_dim": self.config.head_dim,
            "dtype": self.config.dtype,
            "block_q": self.config.block_q,
            "block_k": self.config.block_k,
            "variant": self.config.variant if self.config.impl == "flash" else None,
            "per_call_s": self.per_call_s,
            "tflops": self.tflops,
            "mfu": self.mfu,
        }


def stock_block_sizes(block_q: int, block_k: int):
    """Full ``BlockSizes`` for the stock Pallas flash kernel, forward AND
    backward, derived from one (block_q, block_k) pair.

    The backward blocks mirror the forward derivation (``block_*_major =
    max(block_k, block_q)``), so a single swept pair configures both
    passes — required for the grad A/B baseline (the
    stock bwd raises unless every backward block is set).  segment_ids
    stays None on both sides of the A/B — we don't benchmark segmenting.
    """
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    bkM = max(block_k, block_q)
    return BlockSizes(
        block_q=block_q,
        block_k_major=bkM,
        block_k=block_k,
        block_b=1,
        block_q_major_dkv=block_q,
        block_k_major_dkv=bkM,
        block_k_dkv=block_k,
        block_q_dkv=block_q,
        block_k_major_dq=bkM,
        block_k_dq=block_k,
        block_q_dq=block_q,
    )


def run_attention_bench(
    cfg: AttentionBenchConfig,
    *,
    tag: str = "flextree",
    to_file: bool = False,
    out_dir: str = ".",
) -> AttentionBenchReport:
    """Time one attention impl with a data-dependency chain
    (``flextree_tpu.utils.timing``: the device-loop slope by default, or
    the per-call chain) — a completion gate no async backend can fake."""
    from ..ops.pallas_attention import flash_attention
    from ..parallel.ring_attention import attention_reference

    layout_bhtd = False  # stock kernel's native layout is (B, H, T, D)
    if cfg.mode not in ("fwd", "grad"):
        raise ValueError(f"unknown mode {cfg.mode!r} (fwd|grad)")
    if cfg.impl == "flash":
        core = lambda q, k, v: flash_attention(  # noqa: E731
            q, k, v, causal=True, block_q=cfg.block_q, block_k=cfg.block_k,
            variant=cfg.variant,
        )
        fn = None  # grad/fwd wrap below
    elif cfg.impl == "reference":
        core = lambda q, k, v: attention_reference(q, k, v, causal=True)  # noqa: E731
        fn = None
    elif cfg.impl == "stock":
        # the stock Pallas TPU flash kernel, measured FAIRLY: inputs are
        # generated directly in its native (B, H, T, D) layout (timed
        # transposes would undersell the baseline) and its block sizes come from the config (bench.py
        # sweeps them; defaults below are the v5e-tuned winners)
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            BlockSizes,
            flash_attention as stock_flash,
        )

        layout_bhtd = True
        bs = stock_block_sizes(cfg.block_q, cfg.block_k)
        core = lambda q, k, v: stock_flash(  # noqa: E731
            q, k, v, causal=True, block_sizes=bs
        )
        fn = None
    else:
        raise ValueError(f"unknown attention impl {cfg.impl!r}")
    if fn is None:  # flash/reference/stock share the grad/fwd wrap
        if cfg.mode == "grad":
            g = jax.grad(lambda q, k, v: core(q, k, v).sum(), argnums=(0, 1, 2))

            def grad_all(q, k, v):
                dq, dk, dv = g(q, k, v)
                # fold all three grads into the chained carry: grad wrt q
                # alone lets XLA DCE the dk/dv backward work that the
                # 4.5x/3x hardware-FLOP scale below charges for
                return dq + dk + dv

            fn = jax.jit(grad_all)
        else:
            fn = jax.jit(core)

    b, t, h, d = cfg.batch, cfg.seq_len, cfg.heads, cfg.head_dim
    rng = np.random.default_rng(0)
    dtype = jnp.dtype(cfg.dtype)
    shape = (b, h, t, d) if layout_bhtd else (b, t, h, d)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape).astype(np.float32), dtype=dtype
    )
    q, k, v = mk(), mk(), mk()
    if cfg.timing == "device_loop":
        # cfg.repeat governs only the chained protocol; device_loop's
        # sample counts are its n_lo/n_hi/best_of — say so when the caller
        # set a non-default repeat expecting it to matter
        if cfg.repeat != type(cfg).repeat:
            log.warning(
                "timing='device_loop' ignores repeat=%d (fixed slope "
                "protocol); use timing='chained' if you want a repeat loop",
                cfg.repeat,
            )
        per_call = time_device_loop(fn, q, k, v)
    elif cfg.timing == "chained":
        per_call = time_chained(fn, q, k, v, n_calls=cfg.repeat)
    else:
        raise ValueError(
            f"unknown timing {cfg.timing!r} (device_loop|chained)"
        )
    # hardware-FLOP scale for grad mode, per impl: the flash path re-runs
    # the forward (custom_vjp) then 3 dq-kernel + 4 dkv-kernel matmuls over
    # the visible tiles -> (2+3+4)/2 = 4.5x fwd; XLA autodiff of the
    # full-matrix reference stores P and does 4 backward matmuls, no
    # recompute -> (2+4)/2 = 3x fwd.  The stock Pallas bwd has the same
    # structure as ours (qk recomputed in both the 3-matmul dq and
    # 4-matmul dkv kernels; fwd residuals o/l/m saved) -> 4.5x too.
    if cfg.mode == "grad":
        grad_flop_scale = 3.0 if cfg.impl == "reference" else 4.5
    else:
        grad_flop_scale = 1.0
    flops = 4 * b * h * t * t * d / 2 * grad_flop_scale  # causal
    tflops = flops / per_call / 1e12
    peak = chip_peak_tflops()
    report = AttentionBenchReport(
        cfg, per_call, tflops, round(tflops / peak, 4) if peak else None
    )
    log.info(
        "attention %s: %.3f ms/call, %.2f TFLOP/s%s",
        cfg.impl if cfg.mode == "fwd" else f"{cfg.impl}+grad",
        per_call * 1e3, report.tflops,
        f" ({report.mfu * 100:.1f}% MFU)" if report.mfu is not None else "",
    )
    if to_file:
        name = result_file_name(
            tag=tag,
            num_devices=1,
            size=b * t * h * d,
            topo=f"attn_{cfg.impl}",
        )
        path = str(write_result_file(f"{out_dir}/{name}", report.payload()))
        report = dataclasses.replace(report, result_path=path)
    return report


def autotune_attention(
    cfg: AttentionBenchConfig,
    blocks: tuple[tuple[int, int], ...] = (
        (256, 512), (512, 512), (512, 1024), (1024, 512)
    ),
    repeat: int | None = None,
    impl: str = "flash",
    variants: tuple[str, ...] | None = None,
) -> AttentionBenchReport:
    """Sweep explicit (block_q, block_k) pairs (x forward ``variants`` for
    the flash impl) and return the fastest report.  The default pairs are
    a shortlist, not a product: every combination is two compiles.  Works for
    ``impl="stock"`` too (block_k_major and the backward blocks are
    derived in ``run_attention_bench``)."""
    rep_kw = {} if repeat is None else {"repeat": repeat}
    if impl == "reference":
        # block sizes don't reach attention_reference; sweeping them would
        # re-run the identical benchmark len(blocks) times
        return run_attention_bench(
            dataclasses.replace(cfg, impl=impl, **rep_kw)
        )
    if variants is None or impl != "flash":
        variants = (cfg.variant,)
    # fail fast on a bad variant name — the per-combo except below is for
    # combos that don't FIT, and would otherwise silently drop the whole
    # schedule from the sweep
    unknown = set(variants) - {"loop", "pipelined", "kvgrid"}
    if unknown:
        raise ValueError(f"unknown flash variant(s): {sorted(unknown)}")
    best = None
    for variant in variants:
        for bq, bk in blocks:
            c = dataclasses.replace(cfg, impl=impl, block_q=bq, block_k=bk,
                                    variant=variant, **rep_kw)
            try:
                r = run_attention_bench(c)
            except Exception as e:  # noqa: BLE001 — a combo may not fit
                log.warning(
                    "autotune (%s, %d, %d) failed: %s", variant, bq, bk, e
                )
                continue
            if best is None or r.tflops > best.tflops:
                best = r
    if best is None:
        raise RuntimeError("no autotune configuration succeeded")
    return best
