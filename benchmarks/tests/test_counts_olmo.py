"""The ``olmo_hybrid`` counts against the program's own parameter tree and
against numbers worked by hand."""

import json
import math
import os

import jax
import pytest

from benchmarks.lib import counts_olmo as C

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(HERE, "configs", "olmo-hybrid-7b.json")) as f:
        return json.load(f)


def test_the_counts_are_the_parameter_trees_own_sizes(config):
    """Every matrix a decoded token multiplies with is a leaf of the tree
    ``init_params`` makes (by shape alone: nothing is allocated), and what
    the tree holds beside them is the embedding and the small leaves."""
    from flextree_tpu.models import olmo_hybrid as olmo
    from flextree_tpu.models.configs import config_from_dict

    cfg = config_from_dict(config)
    tree = jax.eval_shape(lambda k: olmo.init_params(k, cfg), jax.random.PRNGKey(0))
    size = lambda a: math.prod(a.shape)  # noqa: E731
    layers = tree["layers"]
    linear = [l for l, kind in zip(layers, cfg.linear) if kind]
    full = [l for l, kind in zip(layers, cfg.linear) if not kind]
    assert (len(linear), len(full)) == (6, 2)
    assert C.layers(config) == {"all": 8, "linear": 6, "full": 2}
    for layer in linear:
        assert C.linear_params(config) == sum(
            size(layer[k]) for k in ("wqkv", "w_g", "wo", "w_a", "w_b"))
    for layer in full:
        assert C.full_params(config) == sum(
            size(layer[k]) for k in ("wq", "wk", "wv", "wo"))
    for layer in layers:
        assert C.ffn_params(config) == sum(size(a) for a in layer["mlp"].values())
    matrices = C.weight_params(config)
    assert matrices == size(tree["head"]) + 6 * C.linear_params(config) \
        + 2 * C.full_params(config) + 8 * C.ffn_params(config)
    whole = sum(size(a) for a in jax.tree.leaves(tree))
    small = whole - matrices - size(tree["embed"])
    # norm scales, the convolutions' taps, A_log and dt_bias
    assert small == 3840 + 8 * 2 * 3840 + 6 * (192 + 4 * 11520 + 60) + 2 * 2 * 3840
    assert whole == pytest.approx(2.4357e9, rel=1e-4)  # 4.87 GB at bf16
    # what a slot and a position hold is the layout's own
    from flextree_tpu.serving import costs

    assert C.state_bytes_per_slot(config) == costs.state_bytes_per_slot(cfg) == 13_685_760
    assert C.cache_bytes_per_position(config) == costs.cache_bytes_per_position(cfg) == 30_720


def test_a_round_and_a_prompt_by_hand(config):
    c = config
    assert C.weight_bytes(c) == pytest.approx(4.10e9, rel=0.005)
    # 8 slots over 70,000 live positions: 4.10 + 2.15 + 0.22 GB, 7.9 ms
    by_bytes = C.decode_round_bytes(c, 70_000, 8)
    assert by_bytes == C.weight_bytes(c) + 70_000 * 30_720 + 8 * 2 * 13_685_760
    assert by_bytes / 819e9 == pytest.approx(7.9e-3, rel=0.02)
    assert by_bytes / 819e9 > 20 * C.decode_round_flops(c, 8, 70_000) / 197e12
    # a prompt token: 3.33 GFLOP of matrices, 27.6 MFLOP of scan
    per_token = 2 * (C.weight_params(c) - 3840 * 100352)
    assert per_token == pytest.approx(3.33e9, rel=0.005)
    assert C.scan_flops_per_token(c) == pytest.approx(27.6e6, rel=0.01)
    whole = C.prefill_flops(c, 16384)
    attention = 2 * 30 * 2 * 128 * 2 * 16384 * 16385 / 2
    assert whole == pytest.approx(
        per_token * 16384 + 2 * 3840 * 100352 + attention
        + C.scan_flops_per_token(c) * 16384)
    assert whole / 197e12 == pytest.approx(0.30, rel=0.05)  # seconds at the peak
