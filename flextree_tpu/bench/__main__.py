"""Benchmark CLI: ``python -m flextree_tpu.bench --size 4096 --repeat 10
--comm-type flextree --topo 4,2``.

Flag set mirrors the reference harness (``benchmark.cpp:67-116``), with
``--devices`` / ``--cpu N`` replacing ``mpirun -np N`` (``--cpu N`` asks for
N virtual CPU devices by name; without it the run needs an accelerator) and
``--comm-type xla`` as the library-baseline A/B (``--comm-type mpi`` there).
``--version`` prints the package version like the reference's git-stamped
``--version`` (``benchmark.cpp:109-115``).
"""

from __future__ import annotations

import argparse


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="flextree_tpu.bench")
    ap.add_argument("--size", type=int, default=35, help="elements per chip")
    ap.add_argument("--repeat", type=int, default=10)
    ap.add_argument("--comm-type", choices=["flextree", "xla"], default="flextree")
    ap.add_argument("--topo", type=str, default=None, help="FT_TOPO-style widths")
    ap.add_argument("--devices", type=int, default=None)
    ap.add_argument(
        "--cpu",
        type=int,
        default=None,
        metavar="N",
        help="run on N virtual CPU devices (must be set before JAX starts real backends)",
    )
    ap.add_argument("--dtype", type=str, default="float32")
    ap.add_argument("--op", type=str, default="sum")
    ap.add_argument(
        "--no-in-place",
        action="store_true",
        help="time without buffer donation (default times the reference's "
        "MPI_IN_PLACE-style compounding loop, benchmark.cpp:149-159)",
    )
    ap.add_argument("--tag", type=str, default="flextree")
    ap.add_argument("--to-file", action="store_true")
    ap.add_argument("--out-dir", type=str, default=".")
    ap.add_argument("--version", action="store_true")
    args = ap.parse_args(argv)

    if args.version:
        from flextree_tpu.utils.buildstamp import version_string

        print(version_string())
        return 0

    import jax

    from ..utils.backend import announce_devices, enable_compile_cache

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu)
    enable_compile_cache()
    announce_devices("flextree_tpu.bench")

    from .harness import BenchConfig, run_allreduce_bench

    cfg = BenchConfig(
        size=args.size,
        repeat=args.repeat,
        comm_type=args.comm_type,
        topo=args.topo,
        devices=args.devices,
        dtype=args.dtype,
        op=args.op,
        tag=args.tag,
        to_file=args.to_file,
        out_dir=args.out_dir,
        in_place=not args.no_in_place,
    )
    report = run_allreduce_bench(cfg)
    return 0 if report.correct else 1


if __name__ == "__main__":
    raise SystemExit(main())
