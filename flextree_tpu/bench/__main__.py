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
    ap.add_argument(
        "--bench",
        choices=["allreduce", "attention"],
        default="allreduce",
        help="allreduce A/B (default) or fused-attention kernel benchmark",
    )
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
    # attention-bench geometry (--bench attention)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument(
        "--attn-impl", choices=["flash", "reference", "stock"], default="flash"
    )
    ap.add_argument(
        "--autotune", action="store_true",
        help="sweep the shortlisted (block_q, block_k) pairs from the v5e "
        "block sweep (flash/stock; reference runs once, blocks unused)",
    )
    ap.add_argument("--block-q", type=int, default=256)
    ap.add_argument("--block-k", type=int, default=512)
    ap.add_argument(
        "--attn-variant", choices=["loop", "pipelined", "kvgrid"],
        default="loop",
        help="flash forward k-walk structure (ablation knob for the "
        "MXU/VPU-overlap question; loop = the carry-serialized kernel)",
    )
    ap.add_argument(
        "--attn-mode", choices=["fwd", "grad"], default="fwd",
        help="grad: time grads of sum(attention) wrt (q, k, v) — the "
        "fwd-with-residuals pass plus both blockwise backward kernels "
        "(hw FLOPs incl. recompute); flash, stock, and reference",
    )
    ap.add_argument(
        "--attn-timing", choices=["device_loop", "chained"],
        default="device_loop",
        help="device_loop: in-jit fori_loop slope (device time only, immune "
        "to dispatch latency); chained: per-call python loop (includes it)",
    )
    ap.add_argument(
        "--attn-dtype",
        type=str,
        default="bfloat16",
        help="compute dtype for --bench attention (independent of --dtype)",
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

    if args.bench == "attention":
        from .harness import (
            AttentionBenchConfig,
            autotune_attention,
            run_attention_bench,
        )

        acfg_kw = dict(
            batch=args.batch,
            seq_len=args.seq_len,
            heads=args.heads,
            head_dim=args.head_dim,
            dtype=args.attn_dtype,
            impl=args.attn_impl,
            block_q=args.block_q,
            block_k=args.block_k,
            timing=args.attn_timing,
            mode=args.attn_mode,
            variant=args.attn_variant,
        )
        if args.attn_timing == "chained":
            acfg_kw["repeat"] = args.repeat  # device_loop ignores repeat
        acfg = AttentionBenchConfig(**acfg_kw)
        if args.autotune:
            report = autotune_attention(acfg, impl=args.attn_impl)
        else:
            report = run_attention_bench(
                acfg, tag=args.tag, to_file=args.to_file, out_dir=args.out_dir
            )
        mfu = f" ({report.mfu * 100:.1f}% MFU)" if report.mfu is not None else ""
        print(
            f"{report.config.impl}(bq={report.config.block_q}, "
            f"bk={report.config.block_k}): {report.per_call_s * 1e3:.3f} "
            f"ms/call, {report.tflops:.2f} TFLOP/s{mfu}"
            + (f" -> {report.result_path}" if report.result_path else "")
        )
        return 0

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
