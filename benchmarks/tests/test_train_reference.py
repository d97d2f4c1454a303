"""The plain reference against the program's forward pass and loss."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp

    from flextree_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(vocab_size=512, d_model=64, n_heads=2, n_layers=2,
                            d_ff=128, dtype=jnp.bfloat16, attn_impl="flash")
    params = init_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 512, (33,)).astype(np.int32)
    return cfg, params, tok[:-1], tok[1:]


def test_program_forward_and_loss_agree_with_the_reference(tiny):
    from benchmarks.lib.train import check_against_reference

    cfg, params, tok, tgt = tiny
    check = check_against_reference(params, cfg, tok, tgt)
    assert check["ok"], check
    assert check["logits_rel_err"] > 0  # bf16 against f32: close, not equal


def test_float32_program_matches_the_reference_to_rounding(tiny):
    import dataclasses

    import jax.numpy as jnp

    from benchmarks.lib.train import check_against_reference

    cfg, params, tok, tgt = tiny
    f32 = dataclasses.replace(cfg, dtype=jnp.float32, attn_impl="reference")
    check = check_against_reference(params, f32, tok, tgt)
    assert check["logits_rel_err"] < 1e-4 and check["loss_err"] < 1e-4, check


def test_the_check_fails_when_the_two_disagree(tiny):
    import jax.numpy as jnp

    from benchmarks.lib.train import check_against_reference

    cfg, params, tok, tgt = tiny
    spoiled = dict(params, layers=[
        dict(params["layers"][0], w2=jnp.zeros_like(params["layers"][0]["w2"])),
        *params["layers"][1:],
    ])
    check = check_against_reference(params, cfg, tok, tgt, reference_params=spoiled)
    assert not check["ok"], check


def test_reference_imports_nothing_from_the_program():
    import ast
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "reference", "dense_decoder.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    mods = [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    assert not [m for m in mods if m.startswith(("flextree_tpu", "benchmarks"))], mods
