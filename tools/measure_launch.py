#!/usr/bin/env python
"""Measure the TPU dispatch/launch constant for the cost model
(the analog of the reference's calibrated ``lo`` latency constant,
``cost_model/CostModel.h:1-37``).

``TpuCostParams.launch_us`` prices the fixed per-collective overhead each
tree stage pays beyond wire latency.  A single chip can't run a multi-chip
collective, so the measurable bound is the fixed per-*op* overhead of the
device runtime, bracketed from two sides:

- **device_op_us** (lower bound): slope of an in-jit chained
  ``lax.fori_loop`` over a trivial elementwise op on a tiny array
  (``time_device_loop``) — the device-side cost of issuing one more
  dependent op, with host dispatch cancelled by the slope.
- **host_dispatch_us** (upper bound): slope of a *host-side* chain of K
  separate jitted calls (data-dependent, one terminal fetch) at two K's —
  the full per-dispatch cost including the runtime queue.

A real per-collective launch sits between the two: it is issued inside one
jitted program (no host dispatch) but does more setup than an elementwise
op.  The recorded ``launch_us`` is the geometric midpoint of the bracket,
with both endpoints and the extrapolation stated in the provenance —
replacing the previous "default (single chip cannot measure multi-chip
dispatch)".

Usage: python tools/measure_launch.py           # prints the three numbers
       (calibrate_host.py embeds the same machinery into CALIBRATION.json)
"""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def measure_device_op_us(samples: int = 5) -> float:
    """Per-op device time of a trivial dependent elementwise op (µs)."""
    import jax
    import jax.numpy as jnp

    from flextree_tpu.utils.timing import time_device_loop

    x = jnp.ones((8, 128), jnp.float32)
    return time_device_loop(
        lambda a: a * 1.000001 + 1e-9, x, n_lo=8, n_hi=256, samples=samples
    ) * 1e6


def measure_host_dispatch_us(k_lo: int = 4, k_hi: int = 64,
                             best_of: int = 5) -> float:
    """Per-dispatch wall time of separate host-issued jitted calls (µs).

    The K calls are data-chained (x = f(x)) so the runtime can't elide or
    batch them away, with one terminal scalar fetch; the (k_hi - k_lo)
    slope cancels the fetch and the one-off sync."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a: a * 1.000001 + 1e-9)
    x0 = jnp.ones((8, 128), jnp.float32)
    float(jnp.sum(f(x0)))  # compile + warm

    def run(k: int) -> float:
        best = float("inf")
        for _ in range(best_of):
            x = x0
            t0 = time.perf_counter()
            for _ in range(k):
                x = f(x)
            float(jnp.sum(x))
            best = min(best, time.perf_counter() - t0)
        return best

    return (run(k_hi) - run(k_lo)) / (k_hi - k_lo) * 1e6


def measure_launch_bracket() -> dict:
    """Both bounds + the recorded midpoint, with provenance strings."""
    import math

    dev_us = measure_device_op_us()
    host_us = measure_host_dispatch_us()
    # guard against a noisy inversion: the bracket is only meaningful
    # when host >= device
    lo, hi = sorted((max(dev_us, 1e-3), max(host_us, 1e-3)))
    launch = math.sqrt(lo * hi)
    return {
        "device_op_us": round(dev_us, 3),
        "host_dispatch_us": round(host_us, 3),
        "launch_us": round(launch, 3),
        "provenance": (
            "measured bracket on the attached chip: device-side dependent-op "
            f"slope {dev_us:.3f}us (lower bound, time_device_loop n=8..256) "
            f"<= launch_us <= host dispatch slope {host_us:.3f}us (upper "
            "bound, data-chained jitted calls K=4..64); recorded value is the "
            "geometric midpoint "
            "— a per-collective launch is issued in-program (no host "
            "dispatch) but does more setup than an elementwise op"
        ),
    }


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("no TPU attached; numbers below are CPU-host, not committable")
    r = measure_launch_bracket()
    for k, v in r.items():
        print(f"{k}: {v}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
