"""Serving entrypoint: ``python -m flextree_tpu.serving``.

Drives one :class:`ServingEngine` over a synthetic open-batch workload
from the command line — the serving twin of ``python -m
flextree_tpu.trainer``, and the place both decode paths and both
admission modes stay drivable::

    # the defaults: fused decode, reservation admission
    python -m flextree_tpu.serving --requests 16

    # the gather oracle path (bitwise vs generate)
    python -m flextree_tpu.serving --no-fused-decode

    # vLLM-style on-demand allocation with swap-out preemption
    python -m flextree_tpu.serving --admission ondemand --preempt swap \\
        --blocks 33 --requests 24

    # a model from its configuration file (model_type + published keys):
    # how a model other than the default dense block is chosen
    python -m flextree_tpu.serving --config benchmarks/configs/laguna-s-2.1.json \\
        --slots 64 --block-size 16 --blocks-per-seq 96 --blocks 6145 \\
        --requests 64 --prompt-len 512 --max-new 64
    # (latent attention: one 576-wide cached row a token, blocks of 128)
    python -m flextree_tpu.serving \\
        --config benchmarks/configs/openpangu-ultra-moe-718b.json \\
        --slots 32 --block-size 128 --blocks-per-seq 68 --blocks 2177 \\
        --requests 32 --prompt-len 4096 --max-new 64

    # the flagship width on the chip JAX finds (no --cpu: landing on the
    # CPU unasked is an error)
    python -m flextree_tpu.serving --d-model 2048 --n-heads 16 --n-layers 4 \\
        --d-ff 8192 --vocab 32768 --dtype bfloat16 --slots 16 \\
        --block-size 16 --blocks-per-seq 128 --blocks 2049 --requests 8 \\
        --prompt-len 512 --max-new 32

Prints a JSON report: completions, throughput, TTFT percentiles, and the
cache-pressure accounting (free/active blocks, occupancy histogram,
preempt/resume counters) from the engine's metrics registry.
"""

from __future__ import annotations

import argparse
import json
import time


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="flextree_tpu.serving")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--blocks", type=int, default=65,
                    help="pool size INCLUDING the reserved null block")
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--blocks-per-seq", type=int, default=10)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="prompts are uniform over [4, prompt-len]")
    ap.add_argument(
        "--config", type=str, default=None,
        help="a model configuration file (JSON: a model_type and its "
        "published keys, as under benchmarks/configs/); the model, its "
        "sizes and its dtypes come from it, and --vocab, --d-model, "
        "--n-heads, --n-layers, --d-ff and --dtype are not read",
    )
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--n-heads", type=int, default=8)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--d-ff", type=int, default=512)
    ap.add_argument(
        "--dtype", choices=["float32", "bfloat16"], default="float32",
        help="compute and KV-pool dtype (TransformerConfig.dtype); "
        "parameters and logits stay float32",
    )
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--fused-decode", action=argparse.BooleanOptionalAction, default=True,
        help="fused paged-attention decode (ops/paged_attention.py): "
        "stream K/V blocks through an online softmax instead of "
        "materializing the gathered row — within a pinned tolerance of "
        "the gather oracle (the default). "
        "--no-fused-decode keeps the gather path, which is bitwise vs "
        "generate",
    )
    ap.add_argument(
        "--decode-impl", choices=["jnp", "pallas"], default="jnp",
        help="accepted and checked, selects nothing: the fused path picks "
        "the Pallas kernel (a TPU, shapes its tiling admits) or the "
        "block-streaming loop (anywhere else) from what it observes",
    )
    ap.add_argument(
        "--admission", choices=["reserve", "ondemand"], default="reserve",
        help="block admission policy (docs/SERVING.md): reserve = whole "
        "prompt+output budget up front (no preemption possible — the "
        "conservative default), ondemand = prompt blocks only, decode "
        "grows per block boundary and pool exhaustion preempts the "
        "newest sequence",
    )
    ap.add_argument(
        "--preempt", choices=["swap", "recompute"], default="swap",
        help="what an evicted sequence keeps: swap = K/V bytes to host "
        "memory (bit-identical resume), recompute = drop and replay "
        "prefill on resume (cheaper for short contexts)",
    )
    ap.add_argument("--report", type=str, default=None,
                    help="also write the JSON report to this path")
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (generation is single-device)")
    return ap.parse_args(argv)


def serve(args: argparse.Namespace):
    """Build the engine, answer the synthetic workload, and return
    ``(engine, requests, report)`` — everything ``main`` does short of
    printing."""
    import jax

    from ..utils.backend import announce_devices, enable_compile_cache

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    enable_compile_cache()
    announce_devices("flextree_tpu.serving")
    import jax.numpy as jnp
    import numpy as np

    from ..models.transformer import TransformerConfig, init_params
    from . import BatcherConfig, PagedCacheConfig, Request, ServingEngine

    pcfg = PagedCacheConfig(
        num_blocks=args.blocks, block_size=args.block_size,
        blocks_per_seq=args.blocks_per_seq,
    )
    bcfg = BatcherConfig(slots=args.slots, admission=args.admission,
                         preempt=args.preempt)
    how = {"fused": args.fused_decode, "decode_impl": args.decode_impl}
    if args.config is not None:
        with open(args.config, encoding="utf-8") as f:
            eng = ServingEngine.from_config(
                json.load(f), pcfg, bcfg, seed=args.seed, **how
            )
    else:
        cfg = TransformerConfig(
            vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=args.d_ff,
            dtype=getattr(jnp, args.dtype),
        )
        params = init_params(jax.random.PRNGKey(args.seed), cfg)
        eng = ServingEngine(params, cfg, pcfg, bcfg, **how)
    vocab = eng.cfg.vocab_size
    rng = np.random.default_rng(args.seed)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(
                0, vocab, (int(rng.integers(4, args.prompt_len + 1)),)
            ).astype(np.int32),
            max_new_tokens=args.max_new,
        )
        for i in range(args.requests)
    ]
    eng.warmup(
        sorted({r.prompt_len for r in reqs}),
        {pcfg.blocks_for(r.prompt_len + r.max_new_tokens) for r in reqs},
    )
    t0 = time.monotonic()
    submitted = sum(1 for r in reqs if eng.submit(r))
    eng.run_until_idle()
    makespan = time.monotonic() - t0
    tokens = sum(d.n_tokens for d in eng.completed.values())
    report = {
        "config": {
            "fused_decode": args.fused_decode,
            "decode_impl": args.decode_impl,
            "admission": args.admission,
            "preempt": args.preempt,
            "slots": args.slots,
            "blocks": args.blocks,
        },
        "submitted": submitted,
        "rejected": list(eng.batcher.rejected),
        "completed": len(eng.completed),
        "tokens": tokens,
        "makespan_s": round(makespan, 3),
        "throughput_tok_s": round(tokens / makespan, 2) if makespan else 0.0,
        **eng.report(),
    }
    return eng, reqs, report


def main(argv=None) -> int:
    args = parse_args(argv)
    _, _, report = serve(args)
    text = json.dumps(report, indent=1)
    print(text)
    if args.report:
        with open(args.report, "w") as f:
            f.write(text + "\n")
    return 0 if report["completed"] == report["submitted"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
