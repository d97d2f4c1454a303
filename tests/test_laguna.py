"""The Laguna block through the one ``ServingEngine`` against its plain
reference (``benchmarks/reference/laguna_decoder.py``), at a small shape:
hidden 64, heads [4, 6, 6, 6, 4] over 2 K/V heads of 16, window 8, 16
experts top-4 with one shared, a dense first layer, an untied head.
"""

import copy
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import harness, serve_closed_model as driver
from benchmarks.reference import laguna_decoder as ref
from flextree_tpu.models import laguna
from flextree_tpu.models.configs import config_from_dict
from flextree_tpu.models.moe import (
    MOE_COUNTS, dropless_experts, gated_ffn, route_topk_normalized,
)
from flextree_tpu.obs import flight_recorder
from flextree_tpu.ops.paged_attention import (
    FUSED_DECODE_ATOL, paged_attention, paged_attention_gather,
)
from flextree_tpu.serving import (
    BatcherConfig, PagedCacheConfig, Request, ServingEngine,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = harness._read_json(
    os.path.join(REPO, "benchmarks", "configs", "laguna-s-2.1.json"))


def tiny(dtype="float32", **over) -> dict:
    c = copy.deepcopy(PUBLISHED)
    c.update(
        vocab_size=128, hidden_size=64, intermediate_size=192,
        num_key_value_heads=2, head_dim=16, num_experts=16,
        num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, sliding_window=8,
        num_attention_heads_per_layer=[4, 6, 6, 6, 4],
        published={"num_experts": 16}, experts_held=[0, 16],
        compute_dtype=dtype, param_dtype=dtype,
    )
    c.update(over)
    return c


PCFG = PagedCacheConfig(num_blocks=40, block_size=4, blocks_per_seq=8)


def engine(config, seed=3, slots=3):
    return ServingEngine.from_config(
        config, PCFG, BatcherConfig(slots=slots), seed=seed)


# ------------------------------------------- engine against the reference


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefill_write_and_decode_through_the_paged_cache(dtype):
    """The engine's own programs (prefill of 20 tokens, the pool write, 6
    decode steps: twice the window behind them) against one reference
    forward; in f32 to 1e-4 with no pick differing, in bf16 under the
    cell's own limits."""
    config = tiny(dtype)
    got = driver.check_against_reference(engine(config), config, 5, 20, 6, 7)
    if dtype == "float32":
        assert got["prefill_rel_err"] < 1e-4 and got["decode_rel_err_max"] < 1e-4
        assert got["score_rel_err"] < 1e-4 and got["picks_differing"] == 0
    assert got["ok"], got
    assert got["picks"] == 4 * 26 * 4


def _mutated(name, config, params):
    """A reference that differs from the program by one mechanism."""
    config, params = copy.deepcopy(config), jax.tree.map(lambda a: a, params)
    if name == "no_gate":  # sigmoid(0): every head passes at one half
        for layer in params["layers"]:
            layer["wg"] = jnp.zeros_like(layer["wg"])
    elif name == "window_off_by_one":
        config["sliding_window"] += 1
    elif name == "rotary_fraction":
        config["rope_parameters"]["full_attention"]["partial_rotary_factor"] = 1
    elif name == "plain_rotary_for_yarn":
        config["rope_parameters"]["full_attention"]["rope_type"] = "default"
    elif name == "dropped_pick":  # what one expert's tokens lose if dropped
        experts = params["layers"][2]["experts"]
        experts["w_down"] = experts["w_down"].at[5].set(0.0)
    elif name == "no_routed_scale":
        config["moe_routed_scaling_factor"] = 1.0
    return config, params


@pytest.mark.parametrize("name", [
    "no_gate", "window_off_by_one", "rotary_fraction",
    "plain_rotary_for_yarn", "dropped_pick", "no_routed_scale",
])
def test_one_wrong_mechanism_fails_the_comparison(name):
    config = tiny()
    eng = engine(config)
    ref_config, ref_params = _mutated(name, config, eng.params)
    got = driver.check_against_reference(
        eng, config, 5, 20, 6, 7, reference_params=ref_params,
        reference_config=ref_config)
    assert not got["ok"], got


def test_a_float32_pool_fails_the_comparison():
    config = tiny("bfloat16")
    eng = engine(config)
    eng.pools = jax.tree.map(lambda a: a.astype(jnp.float32), eng.pools)
    assert eng.pools["k"][0].shape == (40, 4, 2, 16)  # n_kv_heads wide
    got = driver.check_against_reference(eng, config, 5, 20, 6, 7)
    # every numeric limit passes (a wider pool is closer to the reference)
    assert got["decode_rel_err_max"] < driver.LOGITS_REL_TOL
    assert not got["pool_ok"] and not got["ok"]


def test_requests_through_the_engine_follow_the_reference_greedily():
    """Whole requests through step(): every token the engine emits is the
    reference's argmax at that position, given the tokens before it."""
    config = tiny()
    eng = engine(config)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, (n,)).astype(np.int32) for n in (5, 11, 14)]
    for i, p in enumerate(prompts):
        assert eng.submit(Request(rid=i, prompt=p, max_new_tokens=9))
    eng.run_until_idle()
    fwd = jax.jit(lambda p, t: ref.forward(p, t, config)["logits"])
    for i, p in enumerate(prompts):
        out = eng.completed[i].tokens
        seq = np.concatenate([p, out])
        logits = np.asarray(fwd(eng.params, seq))
        want = logits[len(p) - 1 : -1].argmax(-1)
        assert np.array_equal(out, want)


# ------------------------------------------------------- the expert layer


def _sparse_layer(seed=0, n=24):
    cfg = config_from_dict(tiny())
    params = laguna.init_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, n, 64), jnp.float32)
    return cfg, params["layers"][2], x


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0-7 on one chip and 8-15 on the other: the two routed
    parts, with the shared expert counted once (attention, the norm and
    the router are computed alike on both), equal the uncut reference
    layer."""
    cfg, layer, x = _sparse_layer()
    h = ref._rms_norm(x[0], layer["ln2"], 1e-6)
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.softmax(h @ layer["router"], axis=-1)
        top, picks = jax.lax.top_k(scores, 4)
        w = top / top.sum(-1, keepdims=True) * 2.5
        whole = ref.routed_experts(layer["experts"], h, picks, w, (0, 16)) \
            + ref._gated(layer["shared"], h)
    parts = []
    for lo, hi in ((0, 8), (8, 16)):
        share = dict(layer, experts={
            k: v[lo:hi] for k, v in layer["experts"].items()})
        out, moe = laguna._ffn(
            share, x, dataclasses.replace(cfg, experts_held=(lo, hi)), 2)
        parts.append(np.asarray(out - x)[0])
        assert int(moe["sizes"].sum()) == int(((picks >= lo) & (picks < hi)).sum())
    shared = np.asarray(gated_ffn(layer["shared"], h))
    np.testing.assert_allclose(
        parts[0] + parts[1] - shared, np.asarray(whole), atol=2e-5)
    # and a share alone is the reference given the same share
    alone = ref.routed_experts(
        {k: v[8:] for k, v in layer["experts"].items()}, h, picks, w, (8, 16))
    np.testing.assert_allclose(parts[1] - shared, np.asarray(alone), atol=2e-5)


@pytest.mark.parametrize("rows", [None, "some"])
def test_a_router_skewed_to_one_expert_loses_no_pick(rows):
    """Every token's first pick is expert 3: no capacity, so expert 3
    computes all of them."""
    cfg, layer, x = _sparse_layer(1, n=40)
    h = jnp.abs(x[0]) + 0.5  # all positive: a column of ones wins every row
    router = layer["router"].at[:, 3].set(1.0)
    scores, picks, w = route_topk_normalized(h, router, 4, 2.5)
    assert bool((picks[:, 0] == 3).all())
    mask = None if rows is None else jnp.arange(40) % 3 != 0
    out, sizes = dropless_experts(h, picks, w, layer["experts"], (0, 16), mask)
    kept = 40 if mask is None else int(mask.sum())
    assert int(sizes[3]) == kept and int(sizes.sum()) == 4 * kept
    with jax.default_matmul_precision("highest"):
        want = ref.routed_experts(layer["experts"], h, picks, w, (0, 16))
    if mask is not None:
        want = jnp.where(mask[:, None], want, 0.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ------------------------------------------------ window and grouped decode


def _paged_case(lengths, seed=0, hq=6, hkv=2, d=16, bs=4, p=8):
    rng = np.random.default_rng(seed)
    s = len(lengths)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    tables = np.zeros((s, p), np.int32)
    nxt = 1
    for i, n in enumerate(lengths):
        need = n // bs + 1
        tables[i, :need] = np.arange(nxt, nxt + need)
        nxt += need
    return (f(s, hq, d), f(s, hkv, d), f(s, hkv, d), f(nxt, bs, hkv, d),
            f(nxt, bs, hkv, d), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


@pytest.mark.parametrize("lengths", [
    (3, 5), (7, 7), (8, 2), (9, 30), (21, 8, 0, 16, 27),
], ids=["below", "one-short", "at", "beyond", "mixed"])
@pytest.mark.parametrize("window", [8, None])
def test_windowed_grouped_decode_equals_the_gather_oracle(lengths, window):
    """The streamed walk over the per-row table slice against the gather
    oracle with the window mask, and against a softmax worked by hand."""
    q, kn, vn, kp, vp, tables, lens = _paged_case(lengths)
    want = paged_attention_gather(q, kn, vn, kp, vp, tables, lens, window=window)
    got = paged_attention(q, kn, vn, kp, vp, tables, lens, window=window)
    assert float(jnp.abs(got - want).max()) <= FUSED_DECODE_ATOL
    for i, n in enumerate(lengths):
        keys = np.concatenate([np.asarray(kp)[np.asarray(tables)[i]].reshape(
            -1, 2, 16)[:n], np.asarray(kn)[i][None]])
        vals = np.concatenate([np.asarray(vp)[np.asarray(tables)[i]].reshape(
            -1, 2, 16)[:n], np.asarray(vn)[i][None]])
        if window is not None:
            keys, vals = keys[-window:], vals[-window:]
        for h in range(6):
            sc = keys[:, h // 3] @ np.asarray(q)[i, h] / 4.0
            pr = np.exp(sc - sc.max())
            hand = (pr / pr.sum()) @ vals[:, h // 3]
            np.testing.assert_allclose(np.asarray(got)[i, h], hand, atol=1e-5)


def test_a_window_layer_reads_only_the_blocks_that_meet_its_window():
    """Blocks wholly behind the window are not read: overwriting them
    with huge values changes nothing, bit for bit."""
    q, kn, vn, kp, vp, tables, lens = _paged_case((30, 25))
    got = paged_attention(q, kn, vn, kp, vp, tables, lens, window=8)
    behind = np.concatenate([np.asarray(tables)[0, :5], np.asarray(tables)[1, :4]])
    kp2, vp2 = kp.at[behind].set(1e30), vp.at[behind].set(1e30)
    again = paged_attention(q, kn, vn, kp2, vp2, tables, lens, window=8)
    assert np.array_equal(np.asarray(got), np.asarray(again))


@pytest.mark.parametrize("window", [8, None])
def test_pallas_decode_takes_grouped_queries_and_a_window(window):
    """What the old kernel refused in one sentence, the new one computes
    (here under the interpreter): three queries a K/V head, a window."""
    q, kn, vn, kp, vp, tables, lens = _paged_case((21, 8, 0, 16, 27))
    want = paged_attention_gather(q, kn, vn, kp, vp, tables, lens, window=window)
    got = paged_attention(q, kn, vn, kp, vp, tables, lens, window=window,
                          impl="pallas")
    assert float(jnp.abs(got - want).max()) <= FUSED_DECODE_ATOL


# ----------------------------------------------------------------- rotary


def test_yarn_frequencies_follow_the_formulas():
    cfg = config_from_dict(PUBLISHED)
    spec = cfg.rope_full
    assert (spec.rotary_dim, spec.theta, spec.factor) == (64, 5e5, 128.0)
    freqs = spec.inv_freq()
    theirs, dim, scale = ref.inv_frequencies(
        PUBLISHED["rope_parameters"]["full_attention"], 128)
    assert dim == 64 and scale == pytest.approx(1.4852030263919618)
    np.testing.assert_allclose(freqs, np.asarray(theirs), rtol=1e-6)
    plain = 5e5 ** (-np.arange(32) / 32.0)
    # correction dimensions for 8192 positions: 32 rotations -> 9.03,
    # one rotation -> 17.5; unscaled up to 9, over 128 from 18 on
    np.testing.assert_allclose(freqs[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(freqs[18:], plain[18:] / 128, rtol=1e-6)
    mid = (13 - 9) / (18 - 9)
    assert freqs[13] == pytest.approx(
        plain[13] * (1 - mid) + plain[13] / 128 * mid, rel=1e-6)
    window = cfg.rope_window
    assert (window.rotary_dim, window.factor) == (128, None)
    np.testing.assert_allclose(
        window.inv_freq(), 1e4 ** (-np.arange(64) / 64.0), rtol=1e-6)


def test_full_layers_rotate_half_of_each_head_and_scale_it():
    spec = config_from_dict(PUBLISHED).rope_full
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 3, 128), jnp.float32)
    pos = jnp.asarray([0, 1, 7, 100, 5000])
    out = np.asarray(laguna.apply_rope_spec(x, pos, spec))
    x = np.asarray(x)
    assert np.array_equal(out[..., 64:], x[..., 64:])  # passed through
    ang = np.asarray(pos, np.float64)[:, None] * spec.inv_freq()[None, :]
    cos = np.cos(ang)[None, :, None, :] * spec.attention_factor
    sin = np.sin(ang)[None, :, None, :] * spec.attention_factor
    np.testing.assert_allclose(
        out[..., :32], x[..., :32] * cos - x[..., 32:64] * sin, atol=1e-4)
    np.testing.assert_allclose(
        out[..., 32:64], x[..., :32] * sin + x[..., 32:64] * cos, atol=1e-4)


# ------------------------------------------- the configuration, as published


def test_the_configuration_file_keeps_every_published_width():
    c = PUBLISHED
    cfg = config_from_dict(c)
    assert (cfg.d_model, cfg.head_dim, cfg.n_kv_heads, cfg.window) == (3072, 128, 8, 512)
    assert cfg.layer_heads == (48, 72, 72, 72, 48)
    assert cfg.layer_types[0] == cfg.layer_types[4] == laguna.FULL
    assert cfg.mlp_types == ("dense",) + ("sparse",) * 4
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (256, (0, 128), 10)
    assert (cfg.d_ff, cfg.d_expert, cfg.d_shared, cfg.routed_scale) == (12288, 1024, 1024, 2.5)
    assert cfg.vocab_size == 100352 and cfg.param_dtype == jnp.bfloat16
    assert sorted(c["reduced"]) == sorted([
        "num_hidden_layers", "num_experts", "layer_types", "mlp_layer_types",
        "gating_types", "num_attention_heads_per_layer"])
    assert len(c["assumed"]) == 5 and "2 chips" in c["deployment"]
    # the bytes the cut states: 5,880 M parameters
    shapes = jax.eval_shape(
        lambda k: laguna.init_params(k, cfg), jax.random.PRNGKey(0))
    count = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert 5.87e9 < count < 5.89e9
    assert all(a.dtype == jnp.bfloat16 for a in jax.tree.leaves(shapes))


def test_an_unknown_model_type_is_refused():
    with pytest.raises(ValueError, match="model_type"):
        config_from_dict({"model_type": "no_such_block"})


def test_the_prefix_cache_is_refused_for_this_block():
    with pytest.raises(NotImplementedError, match="prefix cache"):
        ServingEngine.from_config(
            tiny(), PCFG, BatcherConfig(slots=2, prefix_cache=True))


def test_the_cli_serves_a_configuration_file(tmp_path):
    from flextree_tpu.serving.__main__ import parse_args, serve

    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(tiny()))
    eng, reqs, report = serve(parse_args([
        "--cpu", "--config", str(path), "--requests", "5", "--blocks", "40",
        "--max-new", "6"]))
    assert isinstance(eng.cfg, laguna.LagunaConfig)
    assert report["completed"] == report["submitted"] == 5
    assert report["counters"]["serve.moe_picks"] > 0


# ------------------------------------------------ spans, ids and counters


def test_a_round_carries_what_its_routers_counted():
    eng = engine(tiny(experts_held=[0, 8], num_experts=8), slots=4)
    rng = np.random.default_rng(1)
    for i in range(3):
        assert eng.submit(Request(
            rid=i, prompt=rng.integers(0, 128, (6,)).astype(np.int32),
            max_new_tokens=5))
    with flight_recorder(None) as rec:
        eng.step()
        eng.step()
    books = [e for e in rec.events
             if e["kind"] == "span" and e["name"] == "ft.engine.bookkeeping"]
    assert len(books) == 2
    for book in books:
        assert set(MOE_COUNTS) <= set(book)
        # 3 active slots x 4 picks x 4 sparse layers; 8 held experts a layer
        assert book["picks"] == 3 * 4 * 4 and book["experts_held"] == 8 * 4
        assert 0 < book["local_picks"] <= book["picks"]
        assert 0 < book["experts_hit"] <= min(book["experts_held"], book["local_picks"])
        assert 1 <= book["max_expert_load"] <= 3
    counters = eng.report()["counters"]
    assert counters["serve.moe_picks"] == 2 * 48
    assert counters["serve.moe_local_picks"] == sum(b["local_picks"] for b in books)


def test_the_dense_engine_round_carries_no_router_counts():
    from flextree_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64)
    eng = ServingEngine(init_params(jax.random.PRNGKey(0), cfg), cfg, PCFG,
                        BatcherConfig(slots=2))
    assert eng.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                              max_new_tokens=3))
    with flight_recorder(None) as rec:
        eng.step()
    book = [e for e in rec.events if e.get("name") == "ft.engine.bookkeeping"][0]
    assert not set(MOE_COUNTS) & set(book)
    assert "serve.moe_picks" not in eng.report()["counters"]


NEW_SCOPES = ["ft_attn_window", "ft_attn_full", "ft_moe_router",
              "ft_moe_experts", "ft_moe_shared"]


@pytest.fixture(scope="module")
def decode_paths():
    """The ``op_name`` path of every operation of the lowered decode and
    prefill programs."""
    import re

    eng = engine(tiny())
    texts = [
        eng._decode.lower(
            eng.params, eng.pools, np.zeros((3, 8), np.int32),
            np.zeros((3,), np.int32), np.zeros((3,), np.int32),
        ).as_text(debug_info=True),
        eng._prefill.lower(
            eng.params, np.zeros((1, 12), np.int32)
        ).as_text(debug_info=True),
    ]
    return [re.findall(r'loc\("([^"]*)"', t) for t in texts]


@pytest.mark.parametrize("scope", NEW_SCOPES + ["ft_mlp", "ft_head", "ft_norm", "ft_embed"])
def test_the_served_programs_hold_the_scope(decode_paths, scope):
    import re

    for paths in decode_paths:
        assert any(re.search(rf"\b{scope}\b", p) for p in paths), scope


def test_the_new_scopes_never_nest_and_are_whole_names(decode_paths):
    import re

    from benchmarks.readers import spans as S

    for paths in decode_paths:
        for p in paths:
            found = S._SCOPE.findall(p)
            assert len(set(found)) <= 1, p
            # the reader's pattern takes the whole name: a window layer's
            # operations never count under the dense ft_attn
            assert "ft_attn" not in found and "ft_moe" not in found


# ------------------------------------------------------------ the benchmark


def _cells():
    return [w["name"] for w in harness.load_benchmark()["workloads"]]


def test_load_cell_finds_the_new_cell():
    name = "laguna-s-2.1.chat-closed-c64"
    assert name in _cells()
    cell = harness.load_cell(name)
    assert cell.chips == 1 and cell.traffic["kind"] == "serve_closed_model"
    assert cell.config["model_type"] == "laguna"
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"serve_tokens_per_s", "serve_ttft_p50_ms",
                        "serve_gap_p95_ms", "setup_s"}
    per_layer = {m["name"] for m in cell.per_layer}
    assert {"moe.experts_share", "moe.router_share", "attn.window_share",
            "attn.full_share", "moe.local_pick_share", "moe.experts_hit_share",
            "kernels.moe_decode_roofline", "engine.idle_fetch_ms",
            "device.idle_share.serve"} <= per_layer
    assert "kernels.decode_roofline" not in per_layer
    t = cell.traffic
    # every slot's worst case fits: admission never waits on memory
    assert t["num_blocks"] == t["slots"] * t["blocks_per_seq"] + 1
    assert t["block_size"] * t["blocks_per_seq"] == max(t["prompt_lens"]) + max(t["max_new"])


NEW_CELLS = ["laguna-s-2.1.chat-closed-c64", "pythia-1.4b.train-dp4-t2048"]


@pytest.mark.parametrize("name", NEW_CELLS)
def test_run_py_rehearses_the_new_cell(name):
    if name not in _cells():
        pytest.skip(f"{name} is not in BENCHMARK.json (PERF.md section 7)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks", "run.py"),
         "--workload", name, "--rehearsal", "--trace", "1", "--seed",
         "2147483999"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and line["failed"] == 0
    assert all(m["value"] is None for m in line["metrics"].values())
    if name.startswith("laguna"):
        assert {"moe.local_pick_share", "moe.experts_hit_share"} <= set(line["metrics"])


def test_the_decode_roofline_counts_what_a_round_must_read():
    from benchmarks.lib import counts_laguna as C

    c = PUBLISHED
    assert C.expert_bytes(c) == 3 * 3072 * 1024 * 2
    assert C.kv_bytes_per_position(c) == 2 * 8 * 128 * 2
    assert C.layer_kinds(c) == (2, 3)
    # outside the routed experts: 432 M in the layers, 308 M in the head
    assert C.other_weight_bytes(c) == pytest.approx(2 * 740.6e6, rel=2e-3)
    everything = C.decode_round_bytes(c, 512, 64 * 900, 64 * 512)
    assert everything == C.other_weight_bytes(c) + 512 * C.expert_bytes(c) \
        + 4096 * (2 * 64 * 900 + 3 * 64 * 512)
    fewer = C.decode_round_bytes(c, 470, 64 * 900, 64 * 512)
    assert everything - fewer == 42 * C.expert_bytes(c)


# ------------------------------------------- the new readers, worked by hand


def _moe_trace_ctx(with_counts=True):
    """Two decode rounds in a 1000 ns window.  Chip 0: the grouped
    product 200 ns (renamed by XLA: no scope on its path), the dispatch
    under ft_moe_experts 50, a fusion that READS the product 100, window
    attention 150; the decode program runs 300 and 200 ns."""
    import types

    from benchmarks.lib import xplane as X
    from benchmarks.lib.harness import ReaderContext, Run
    from benchmarks.lib.peaks import Peaks

    E = X.Event
    counts = {"experts_hit": 470, "experts_held": 512} if with_counts else {}
    host = [
        E("bench_window", 0, 1000),
        E("ft.engine.bookkeeping", 300, 50, dict(counts)),
        E("ft.engine.bookkeeping", 800, 50, dict(counts)),
    ]
    ops = [
        E("%ragged-dot-none.3 = f32[640,1024]{1,0} custom-call(%a, %b)", 0, 200,
          {"tf_op": "ragged-dot-none"}),
        E("%fusion.4 = bf16[640,1024]{1,0} fusion(%ragged-dot-none.3)", 200, 100,
          {"tf_op": "jit(f)/ft_moe_shared/mul"}),
        E("%fusion.5 = bf16[640,3072]{1,0} fusion(%x)", 500, 50,
          {"tf_op": "jit(f)/ft_moe_experts/gather"}),
        E("%fusion.6 = f32[64,8,9,128]{3,2,1,0} fusion(%y)", 550, 150,
          {"tf_op": "jit(f)/ft_attn_window/while/body/dot_general"}),
    ]
    modules = [E("jit__unknown(123)", 0, 300), E("jit__unknown(123)", 500, 200)]
    planes = [
        X.Plane("/host:CPU", [X.Line("python3", host)]),
        X.Plane("/device:TPU:0", [X.Line("XLA Ops", ops),
                                  X.Line("XLA Modules", modules)]),
    ]
    obs = {"rounds": [(0.0, 0.0, 64, 64, 64 * 900), (0.0, 0.0, 64, 64, 64 * 900)],
           "live_capped": [64 * 512, 64 * 512]}
    run = Run(True, 0, 0, {}, obs, 0.0, None)
    cell = types.SimpleNamespace(name="toy", config=PUBLISHED)
    peaks = Peaks(197e12, 819e9, 16e9, "test")
    return ReaderContext(cell, run, {}, X.Trace(planes), (0.0, 1000.0), peaks=peaks)


def _metric(name):
    return harness._read_json(
        os.path.join(REPO, "benchmarks", "metrics", f"{name}.json"))


def test_experts_share_counts_the_renamed_grouped_products():
    from benchmarks.readers import moe as M, spans as S

    ctx = _moe_trace_ctx()
    meta = _metric("moe.experts_share")
    assert meta["reader"] == "moe:experts_share"
    # 200 (the product, by name) + 50 (under the scope) of 500 busy; the
    # fusion that reads the product counts under its own scope
    assert M.experts_share(ctx, **meta["args"]) == pytest.approx(50.0)
    assert S.scope_share(ctx, ["ft_moe_experts"]) == pytest.approx(10.0)
    assert S.scope_share(ctx, **_metric("attn.window_share")["args"]) == pytest.approx(30.0)
    assert S.scope_share(ctx, **_metric("attn.full_share")["args"]) == 0.0


def test_moe_decode_roofline_is_least_time_over_traced_time():
    from benchmarks.lib import counts_laguna as C
    from benchmarks.readers import moe as M

    meta = _metric("kernels.moe_decode_roofline")
    got = M.moe_decode_roofline(_moe_trace_ctx(), **meta["args"])
    least = C.decode_round_bytes(PUBLISHED, 470, 64 * 900, 64 * 512) / 819e9
    assert got == pytest.approx(100.0 * least / (500e-9 / 2))
    # a parent commit's rounds carry no count: nothing to read, no error
    assert M.moe_decode_roofline(_moe_trace_ctx(False), **meta["args"]) is None
