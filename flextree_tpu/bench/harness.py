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
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.reduce import get_op
from ..parallel.mesh import allreduce_over_mesh, flat_mesh
from ..planner.calibrate import MeasuredPoint
from ..planner.cost_model import bus_bandwidth_GBps
from ..utils.logging import get_logger, result_file_name, write_result_file
from ..utils.timing import BenchResult, time_jax_fn, time_jax_fn_inplace

__all__ = ["BenchConfig", "BenchReport", "run_allreduce_bench", "measure_points"]

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


def measure_points(
    topos,
    sizes,
    *,
    repeat: int = 10,
    devices: int | None = None,
    stat: str = "median",
) -> list[MeasuredPoint]:
    """Time the FlexTree collective at each (topo, size-in-elements) point
    on the current backend, with :func:`run_allreduce_bench`'s in-place
    protocol, as the points ``planner.fit_cost_params`` fits.

    ``stat``: summary statistic over the ``repeat`` reps — ``"median"``
    (default; robust on a timeshared host where min-of-few is noise-bound)
    or ``"min"`` (the reference harness's headline,
    ``benchmark.cpp:215``).  The full sample is kept on each point.
    """
    if stat not in ("median", "min"):
        raise ValueError(f"stat must be 'median' or 'min', got {stat!r}")
    n = devices or len(jax.devices())
    points = []
    for size in sizes:
        for spec in topos:
            rep = run_allreduce_bench(
                BenchConfig(size=size, repeat=repeat, comm_type="flextree",
                            topo=spec, devices=n)
            )
            widths = (1,) if rep.topo == "1" else tuple(
                int(w) for w in rep.topo.split("*")
            )
            summary = (
                rep.result.median_s if stat == "median" else rep.result.min_s
            )
            points.append(
                MeasuredPoint(
                    widths, n, size * 4, summary * 1e6,
                    tuple(t * 1e6 for t in rep.result.times_s),
                )
            )
    return points
