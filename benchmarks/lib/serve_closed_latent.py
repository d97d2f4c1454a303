"""Driver for traffic of kind ``serve_closed_latent``: the closed loop of
``serve_closed_model`` (its engine factory, window loop, ``ModelLoop``,
estimators and ``obs`` keys, imported) over a model whose cache is ONE
latent row a position (``model_type`` ``pangu_ultra_moe``).

What differs is the comparison that decides ``correct``.  Its three
numeric parts are ``serve_closed_model``'s (router scores, near-tie picks
counted, logits with the reference following the program's picks; the
engine's own ``_prefill`` of a ``check_prompt``-token prompt, ``_write``,
``check_steps`` teacher-forced ``_decode`` steps through the paged pool,
against ONE forward of ``benchmarks/reference/pangu_ultra_moe_decoder.
py``), under limits read for THIS configuration (below).  Its pool check
is this file's: the published ``num_key_value_heads`` is 128 and a latent
pool has no heads axis, so ``serve_closed_model``'s check cannot pass
here, and the check that matters is another one.  The pools are held to
ONE array a layer, in the configuration's compute dtype, of
``kv_lora_rank + qk_rope_head_dim`` numbers a position, and no second
part: a pool that held expanded K and V would pass every numeric limit
and take 71 times the memory the cell states; a float32 one twice.

The deck is the traffic file's, dealt as in every other cell:
``traffic.request_deck``'s shuffle by ``--seed``, through the imported
``ModelLoop``.
"""

from __future__ import annotations

from unittest import mock

import numpy as np

from . import serve_closed_model as base
from .harness import Cell, Run
from .serve_closed_model import (  # noqa: F401  (the driver's surface)
    ModelLoop, build_engine, compare_routing,
)

__all__ = ["run", "build_engine", "check_against_reference", "pool_ok"]

# The limits, each between two readings on the chip at the published
# widths and the cell's own check (8,192-token prefill, the pool write, 8
# decode steps; my chip runs, PR 32; PERF.md section 6).
# The bf16 program against the float32 reference, 43 runs over 37 seeds:
#   logits 0.0061 to 0.0087 (prefill) and 0.0083 to 0.0120 (decode steps)
#   of the largest reference logit; router scores 0.0107 to 0.0127 of a
#   position's largest; 2,266 to 2,567 of 262,400 picks differing, the
#   furthest 0.0043 to 0.0062 off the cut.
# The same program against the reference computed on parameters cut to 3
# mantissa bits (float8_e4m3's, at bfloat16's range: the nearest precision
# below the stated one), two seeds:
#   logits 0.153, 0.158 (prefill) and 0.168, 0.191 (decode); scores 0.210,
#   0.219; 39,225 and 41,490 picks differing, up to 0.124, 0.126 off the
#   cut: not correct by each limit.
# Each limit stands between: 3.3 times the worst clean logits reading and
# 3.8 times under the low-precision one; 3.9 times the worst clean score
# reading and 4.2 times under the low one (as the bound on a differing
# pick's distance from the cut: 8 times over, 2.5 times under).  They are
# tighter than ``serve_closed_model``'s (6e-2, 0.2): a sigmoid score's
# error is not divided by a sum over the experts as a softmax's is, and
# the largest of 256 sigmoids of near-unit logits is about 0.99 at every
# position, so the score share is nearly an absolute error and a small one.
SCORE_REL_TOL = 5e-2  # share of the position's largest reference score
LOGITS_REL_TOL = 4e-2  # share of the largest reference logit


def pool_ok(pools: dict, config: dict) -> bool:
    """The pools hold one latent row a position and nothing else: ONE
    part, a (blocks, block size, ``kv_lora_rank + qk_rope_head_dim``)
    array a layer, in the compute dtype."""
    import jax.numpy as jnp

    row = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    dtype = jnp.dtype(config.get("compute_dtype", "bfloat16"))
    return len(pools) == 1 and all(
        len(layers) == int(config["num_hidden_layers"]) and all(
            p.ndim == 3 and p.shape[2] == row and p.dtype == dtype
            for p in layers
        )
        for layers in pools.values()
    )


def _verdict(got, scores, choices, want: dict, engine, config: dict) -> dict:
    """The programs' results against the reference's, under the limits."""
    routing = compare_routing(scores, choices, want["scores"], want["choices"])
    scale = float(np.abs(want["logits"]).max())
    errs = np.abs(got - want["logits"]).max(axis=1) / scale
    held = pool_ok(engine.pools, config)
    ok = (
        got.shape == want["logits"].shape and bool(np.isfinite(got).all())
        and float(errs.max()) < LOGITS_REL_TOL
        and routing["score_rel_err"] < SCORE_REL_TOL
        and routing["differing_off_cut_max"] < SCORE_REL_TOL
        and held
    )
    return {"ok": bool(ok), "prefill_rel_err": float(errs[0]),
            "decode_rel_err_max": float(errs[1:].max()) if len(errs) > 1 else 0.0,
            "pool_ok": bool(held), **routing}


def check_against_reference(engine, config: dict, seed: int, prompt_len: int,
                            steps: int, n_blocks: int,
                            reference_params=None,
                            reference_config=None) -> dict:
    """The comparison of the module docstring.  ``reference_params`` and
    ``reference_config`` are the tests' way to make the two sides
    disagree."""
    seq = base._check_sequence(config, seed, prompt_len + steps)
    got, scores, choices = base._run_programs(
        engine, seq, prompt_len, steps, n_blocks
    )
    want = base._reference(
        engine.params if reference_params is None else reference_params,
        config if reference_config is None else reference_config,
        seq, choices, prompt_len,
    )
    return _verdict(got, scores, choices, want, engine, config)


def run(cell: Cell, seed: int, seconds: float, trace_dir, t_start: float,
        counter) -> Run:
    """``serve_closed_model.run`` with this file's comparison in place of
    its own, and nothing else of it changed: the window loop is shared by
    import.  (No file of the benchmark may be edited by the PR that brought
    this one, so the comparison is swapped where ``run`` looks it up;
    PERF.md section 7 queues the ``benchmark`` PR that lets ``run`` take
    the check from the traffic kind and folds the closed-loop drivers.)"""
    with mock.patch.object(
        base, "check_against_reference", check_against_reference
    ):
        return base.run(cell, seed, seconds, trace_dir, t_start, counter)
