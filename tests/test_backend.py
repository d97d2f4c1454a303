"""``utils/backend.py`` — the one place that decides where the program
runs — and the CLI behaviour built on it (``--dtype``, the device line,
the CPU-by-request rule)."""

import jax
import pytest

from flextree_tpu import trainer
from flextree_tpu.bench import __main__ as bench_cli
from flextree_tpu.serving import __main__ as serving_cli
from flextree_tpu.utils import backend


# ------------------------------------------------------------ compile cache


def test_compile_cache_placed_from_outside_is_left_alone(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    assert backend.enable_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before  # nothing set


def test_compile_cache_defaults_to_one_fixed_dir_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    before = jax.config.jax_compilation_cache_dir
    try:
        first = backend.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert backend.enable_compile_cache() == first  # same path twice
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # inside the checkout, git-ignored, and built from nothing that moves
    assert first == backend.REPO_CACHE_DIR
    assert first.endswith("/.jax_cache")
    with open(first[: -len(".jax_cache")] + ".gitignore") as f:
        assert ".jax_cache/" in f.read().split()


# ---------------------------------------------------------------- interpret


def test_pallas_interpret_by_platform(monkeypatch):
    assert backend.pallas_interpret() is True  # the suite runs on the CPU
    assert backend.pallas_interpret(False) is False  # explicit wins
    monkeypatch.setattr(backend, "kernel_platform", lambda: "tpu")
    assert backend.pallas_interpret() is False
    assert backend.pallas_interpret(True) is True
    monkeypatch.setattr(backend, "kernel_platform", lambda: "rocm")
    with pytest.raises(RuntimeError, match="'rocm'"):
        backend.pallas_interpret()


# --------------------------------------------------------------------- CLIs

TINY = ["--vocab", "64", "--d-model", "32", "--n-heads", "2",
        "--n-layers", "1", "--d-ff", "64"]


def test_trainer_cli_accepts_bfloat16(capsys):
    assert trainer.main(
        TINY + ["--dtype", "bfloat16", "--steps", "2", "--batch", "8",
                "--seq-len", "16", "--corpus-tokens", "4000",
                "--log-every", "1"]
    ) == 0
    out = capsys.readouterr().out
    assert out.startswith("device: platform=cpu kind=cpu count=8\n")
    assert "planner constants: built-in defaults" in out
    assert "dense: 2 steps on mesh" in out


def test_serving_cli_accepts_bfloat16(capsys):
    args = serving_cli.parse_args(
        TINY + ["--dtype", "bfloat16", "--requests", "2", "--max-new", "3",
                "--prompt-len", "6", "--blocks", "9", "--blocks-per-seq", "2"]
    )
    eng, reqs, report = serving_cli.serve(args)
    assert capsys.readouterr().out.startswith("device: platform=cpu ")
    assert report["completed"] == report["submitted"] == len(reqs) == 2
    assert eng.pools["k"][0].dtype == jax.numpy.bfloat16


@pytest.mark.parametrize(
    "main,argv",
    [(trainer.main, ["--steps", "1"]),
     (serving_cli.main, ["--requests", "1"]),
     (bench_cli.main, ["--size", "8", "--repeat", "1"])],
)
def test_cli_that_lands_on_the_cpu_unasked_exits_nonzero(
    monkeypatch, capsys, main, argv
):
    """The suite's backend IS the CPU; say nobody asked for it."""
    monkeypatch.setattr(backend, "_requested_platforms", lambda: ())
    with pytest.raises(SystemExit) as e:
        main(argv)
    assert e.value.code not in (0, None)
    assert "fell back to the CPU" in str(e.value.code)
    # the device line came first, and nothing ran after the refusal
    assert capsys.readouterr().out == "device: platform=cpu kind=cpu count=8\n"
