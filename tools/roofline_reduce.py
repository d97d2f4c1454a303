#!/usr/bin/env python
"""HBM roofline for the local reduce kernel on the real TPU chip.

The local reduction is the allreduce's only compute (SURVEY §3.2 "HOT
LOOP"; the reference's OpenMP ``reduce_sum``, ``mpi_mod.hpp:246-660``), and
it is HBM-bandwidth-bound: folding W sources reads W·L and writes L
elements.  This tool measures ``flextree_tpu.ops.pallas_reduce`` achieved
HBM GB/s against the chip's peak.  No artifact of it is committed: the
roofline is not measured on today's code (ROADMAP S3 measures the kernel
beside XLA's fused sum and keeps one).

Timing is the slope protocol (``flextree_tpu.utils.timing.time_device_loop``):
an in-jit ``fori_loop`` chains each iteration's output back into the next
input with a dynamic-update-slice, and per-iteration time is the slope
between two loop lengths, which cancels the fixed per-dispatch cost
(dividing ONE chained run by its iteration count leaves a share of it in
every per-call number).  A
second, kernel-free chain with the identical DUS feedback is timed the same
way and subtracted, so the reported time is the reduce kernel alone; its
traffic is (W+1)·L·itemsize (read W sources, write 1).

Usage: python tools/roofline_reduce.py --out chiprun_out/reduce_roofline.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: HBM peak GB/s by generation (v5e 819, v4 1228, v5p 2765, v6e 1638);
#: device_kind normalization shared with the MFU table via
#: flextree_tpu.utils.device.tpu_generation
_TPU_PEAK_HBM = {
    "v5e": 819.0,
    "v6e": 1638.0,
    "v5p": 2765.0,
    "v4": 1228.0,
    "v3": 900.0,
}


def chip_peak_hbm_GBps():
    import jax

    from flextree_tpu.utils.device import tpu_generation

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    return _TPU_PEAK_HBM[tpu_generation(dev.device_kind)]


def make_input(w: int, length: int, dtype_name: str):
    """Build the (w, length) device input once; reusable across tile probes
    (for w=8 f32 it is a ~1 GB device buffer — rebuilding it per rows_tile
    probe would re-upload it every time)."""
    import jax.numpy as jnp
    import numpy as np

    dtype = jnp.dtype(dtype_name)
    rng = np.random.default_rng(0)
    return jnp.asarray(
        rng.standard_normal((w, length)).astype(np.float32) * 1e-3, dtype=dtype
    )


def measure_copy_ceiling(length: int, n_lo: int = 2, n_hi: int = 10,
                         samples: int = 3) -> float:
    """Achieved GB/s of a pure-copy Pallas kernel (read L + write L f32) —
    the practical streaming ceiling of this chip/backend, which can sit
    below the datasheet HBM number.  frac_of_peak should be read against
    this, not just the datasheet.

    The chain is the copy itself (its output matches its input, so each
    iteration's read depends on the previous write — nothing else runs, and
    nothing extra is charged; an earlier draft chained ``copy(c) * k``,
    whose unaccounted elementwise pass understated the ceiling ~2x).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    from flextree_tpu.utils.timing import time_device_loop

    rt = 1024
    rows = (length // 128 // rt) * rt  # whole tiles only; charge what moves
    eff_length = rows * 128
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((rows, 128)).astype(np.float32)
        * 1e-3
    )

    def copy_kernel(x_ref, o_ref):
        o_ref[:] = x_ref[:]

    copy = pl.pallas_call(
        copy_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.float32),
        grid=(rows // rt,),
        in_specs=[pl.BlockSpec((rt, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rt, 128), lambda i: (i, 0)),
    )

    t = time_device_loop(copy, x, n_lo=n_lo, n_hi=n_hi, samples=samples)
    return 2 * eff_length * 4 / t / 1e9


def measure_xla_fused_sum(w: int, length: int, n_lo: int = 2, n_hi: int = 10,
                          samples: int = 3) -> tuple[float, bool]:
    """Achieved GB/s of XLA's own fused ``jnp.sum(x, axis=0)`` over the same
    (w, L) f32 fold — the no-hand-kernel baseline the Pallas kernel must
    beat to justify existing.  Chain-isolated exactly like the Pallas rows:
    the kernel-free DUS chain (``measure_base``) is measured on the same
    input and subtracted, so the comparison is symmetric.

    Returns ``(GBps, isolated)``: ``isolated=False`` means the base
    subtraction was unusable and the number carries the uncorrected
    full-chain slope (understated), mirroring ``measure_point``."""
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from flextree_tpu.utils.timing import time_device_loop

    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((w, length)).astype(np.float32)
        * 1e-3
    )

    def body(c):
        out = jnp.sum(c, axis=0)
        return lax.dynamic_update_slice(c, out[None] * 1e-3, (0, 0))

    t_full = time_device_loop(body, x, n_lo=n_lo, n_hi=n_hi, samples=samples)
    t_base = measure_base(x, n_lo=n_lo, n_hi=n_hi, samples=samples)
    t = t_full - t_base
    isolated = t_base > 0.0 and t > 0
    if t <= 0:
        t = t_full
    return (w + 1) * length * 4 / t / 1e9, isolated


def measure_base(x, n_lo: int = 2, n_hi: int = 10, samples: int = 1) -> float:
    """Slope of the kernel-free DUS feedback chain for input ``x``.

    rows_tile-independent, so sweep callers measure it once per (w, dtype).
    Returns 0.0 when dispatch noise makes the tiny chain unmeasurable —
    callers then charge the kernel the full uncorrected slope rather than
    aborting the artifact run.
    """
    from jax import lax

    from flextree_tpu.utils.timing import time_device_loop

    def body_base(carry):
        return lax.dynamic_update_slice(carry, carry[:1] * 1e-3, (0, 0))

    try:
        return time_device_loop(body_base, x, n_lo=n_lo, n_hi=n_hi,
                                samples=samples)
    except RuntimeError:
        return 0.0


def measure_point(
    w: int,
    length: int,
    dtype_name: str,
    rows_tile: int = 512,
    sources_tile: int = 1,
    n_lo: int = 2,
    n_hi: int = 10,
    samples: int = 1,
    x=None,
    t_base: float | None = None,
):
    """Kernel-only per-call seconds, achieved HBM GB/s, and whether the
    kernel time was actually chain-isolated, for one point.

    Two chains, timed with the same slope protocol, subtracted:

    - full:  carry -> DUS(carry, reduce(carry) * 1e-3)
    - base:  carry -> DUS(carry, carry[0] * 1e-3)   (identical minus kernel)

    The base chain carries the DUS feedback write and the loop/fetch
    scaffolding; the difference is the pallas kernel's own time, charged
    with its (W+1)·L·itemsize traffic (the base's extra L-element read is
    the model's ~1/(w+1) error bar, in the conservative direction).
    Returns ``(kernel_s, GBps, isolated)``: ``isolated=False`` means the
    subtraction was unusable (noise) and ``kernel_s`` is the uncorrected
    full-chain slope — an understated bandwidth, flagged so the artifact
    doesn't mislabel it as kernel-only.
    """
    import jax.numpy as jnp
    from jax import lax

    from flextree_tpu.ops.pallas_reduce import reduce_stacked
    from flextree_tpu.utils.timing import time_device_loop

    dtype = jnp.dtype(dtype_name)
    if x is None:
        x = make_input(w, length, dtype_name)

    def body_full(carry):
        out = reduce_stacked(carry, op="sum", rows_tile=rows_tile,
                             sources_tile=sources_tile, interpret=False)
        return lax.dynamic_update_slice(carry, out[None] * 1e-3, (0, 0))

    t_full = time_device_loop(body_full, x, n_lo=n_lo, n_hi=n_hi,
                              samples=samples)
    if t_base is None:
        # body_base is rows_tile-independent; sweep callers measure it once
        # per (w, dtype) and pass it in to skip redundant compiles/timing
        t_base = measure_base(x, n_lo=n_lo, n_hi=n_hi, samples=samples)
    # t_base == 0.0 means the base chain was unmeasurable (dispatch noise):
    # the kernel gets charged the full slope, flagged as not isolated
    isolated = t_base > 0.0
    kernel_s = t_full - t_base
    if kernel_s <= 0:
        # chain noise swamped the kernel (tiny w·L): fall back to the
        # uncorrected slope rather than publishing a negative bandwidth
        kernel_s = t_full
        isolated = False
    moved = (w + 1) * length * dtype.itemsize
    return kernel_s, moved / kernel_s / 1e9, isolated


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--out",
        default=os.path.join(REPO, "chiprun_out", "reduce_roofline.json"),
    )
    ap.add_argument("--length", type=int, default=1 << 25)  # 128 MB f32
    ap.add_argument(
        "--sweep-tiles",
        action="store_true",
        help="also sweep rows_tile per point and report the best",
    )
    args = ap.parse_args()

    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("no TPU attached; refusing to write a CPU 'roofline'")
        return 1
    peak = chip_peak_hbm_GBps()
    copy_gbps = measure_copy_ceiling(args.length)
    xla_gbps, xla_isolated = measure_xla_fused_sum(8, args.length)
    print(f"copy ceiling: {copy_gbps:.0f} GB/s; XLA fused sum w=8: "
          f"{xla_gbps:.0f} GB/s"
          + ("" if xla_isolated else "  [NOT chain-isolated]"))
    tiles = (256, 512, 1024) if args.sweep_tiles else (512,)
    source_tiles = (1, 2, 4) if args.sweep_tiles else (1,)
    rows = []
    for w in (2, 4, 8):
        for dtype_name in ("float32", "bfloat16"):
            x = make_input(w, args.length, dtype_name)
            t_base = measure_base(x)
            best = None
            for rt in tiles:
                for st in source_tiles:
                    if w % st:
                        continue  # gcd clamp would duplicate an st row
                    dt, gbps, isolated = measure_point(
                        w, args.length, dtype_name, rows_tile=rt,
                        sources_tile=st, x=x, t_base=t_base,
                    )
                    if best is None or gbps > best[1]:
                        best = (dt, gbps, rt, st, isolated)
            dt, gbps, rt, st, isolated = best
            rows.append(
                {
                    "w": w,
                    "dtype": dtype_name,
                    "length": args.length,
                    "rows_tile": rt,
                    "sources_tile": st,
                    "per_call_ms": round(dt * 1e3, 3),
                    "achieved_GBps": round(gbps, 1),
                    "frac_of_peak": round(gbps / peak, 3) if peak else None,
                    "frac_of_copy_ceiling": (
                        round(gbps / copy_gbps, 3) if copy_gbps else None
                    ),
                    "kernel_isolated": isolated,
                }
            )
            print(f"w={w} {dtype_name} (rows_tile={rt}, sources_tile={st}): "
                  f"{gbps:.0f} GB/s"
                  + (f" ({gbps / peak * 100:.0f}% of peak)" if peak else "")
                  + (f" ({gbps / copy_gbps * 100:.0f}% of copy ceiling)"
                     if copy_gbps else "")
                  + ("" if isolated else "  [NOT chain-isolated]"))
    from flextree_tpu.utils.buildstamp import artifact_meta

    doc = {
        "description": "pallas_reduce (local reduction, the allreduce hot "
                       "loop) achieved HBM bandwidth vs chip roofline",
        "build": artifact_meta(),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "peak_hbm_GBps": peak,
        "measured_copy_ceiling_GBps": round(copy_gbps, 1),
        "xla_fused_sum_w8_GBps": round(xla_gbps, 1),
        "xla_fused_sum_isolated": xla_isolated,
        "ceiling_note": "a pure-copy Pallas kernel (read+write) achieves "
                        "measured_copy_ceiling_GBps on this chip/backend — "
                        "the practical streaming ceiling; frac_of_peak is "
                        "vs the datasheet number, but kernel quality should "
                        "be judged vs the copy ceiling and vs XLA's own "
                        "fused sum (xla_fused_sum_w8_GBps, chain-isolated "
                        "symmetrically with the kernel rows)",
        "traffic_model": "(W+1) * L * itemsize per kernel call; kernel time "
                         "isolated by slope timing minus a kernel-free "
                         "chain with identical DUS feedback (see module "
                         "docstring); rows with kernel_isolated=false "
                         "carry the uncorrected full-chain slope "
                         "(understated bandwidth)",
        "results": rows,
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
