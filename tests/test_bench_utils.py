"""Tests for the allreduce benchmark harness, timing/logging utils and
the Pallas reduction kernel."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flextree_tpu.bench import BenchConfig, run_allreduce_bench
from flextree_tpu.ops import reduce_stacked, reduce_stacked_reference, SUPPORTED_OPS
from flextree_tpu.utils import (
    BenchResult,
    Timer,
    result_file_name,
    time_jax_fn,
    write_result_file,
)

RNG = np.random.default_rng(0)


class TestTimer:
    def test_elapsed_monotone(self):
        t = Timer()
        a = t.elapsed_s
        b = t.elapsed_s
        assert b >= a >= 0
        t.stop()  # freeze so unit conversions read the same instant
        assert t.elapsed_ms == pytest.approx(t.elapsed_s * 1e3)
        assert t.elapsed_us == pytest.approx(t.elapsed_s * 1e6)
        assert t.elapsed_ns == pytest.approx(t.elapsed_s * 1e9)

    def test_stop_freezes(self):
        t = Timer()
        s = t.stop()
        assert t.elapsed_s == s

    def test_restart(self):
        t = Timer()
        t.stop()
        t.restart()
        assert t.elapsed_s < 1.0


class TestTimeJaxFn:
    def test_basic(self):
        f = jax.jit(lambda x: x * 2 + 1)
        r = time_jax_fn(f, jnp.ones(16), repeat=3, warmup=1)
        assert len(r.times_s) == 3
        assert r.min_s <= r.avg_s
        assert r.compile_s > 0
        assert r.median_s >= r.min_s


class TestBenchResult:
    def test_stats(self):
        r = BenchResult((3.0, 1.0, 2.0), 0.1)
        assert r.min_s == 1.0 and r.avg_s == 2.0 and r.median_s == 2.0


class TestResultFiles:
    def test_name_scheme(self):
        name = result_file_name("tag", 8, 100, "4,2")
        parts = name.split(".")
        assert parts[0] == "tag" and parts[1] == "8" and parts[2] == "100"
        assert parts[3] == "4-2" and parts[4] == "ar_test"
        assert result_file_name("t", 8, 1, "4*2").split(".")[3] == "4-2"
        assert result_file_name("t", 8, 1, "", comm_test=True).split(".")[3:5] == [
            "flat",
            "comm_test",
        ]

    def test_write(self, tmp_path):
        p = write_result_file(tmp_path / "x.json", {"a": 1})
        assert json.loads(p.read_text()) == {"a": 1}


@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 virtual devices")
class TestHarness:
    def test_flextree_run(self, tmp_path):
        cfg = BenchConfig(
            size=1000, repeat=2, topo="4,2", to_file=True, out_dir=str(tmp_path)
        )
        rep = run_allreduce_bench(cfg)
        assert rep.correct
        assert rep.bus_bw_GBps > 0
        assert rep.result_path
        with open(rep.result_path) as fh:
            assert json.load(fh)["correct"]

    def test_xla_baseline_run(self):
        rep = run_allreduce_bench(BenchConfig(size=1000, repeat=2, comm_type="xla"))
        assert rep.correct

    def test_ring_run(self):
        rep = run_allreduce_bench(BenchConfig(size=1000, repeat=2, topo="1"))
        assert rep.correct

    def test_bad_comm_type(self):
        with pytest.raises(ValueError):
            run_allreduce_bench(BenchConfig(comm_type="mpi"))

    def test_baseline_jit_is_cached(self):
        """The A/B is only fair if the psum baseline doesn't retrace per
        call (regression: fresh jit wrapper per invocation)."""
        from flextree_tpu.bench.harness import _jitted_psum
        from flextree_tpu.parallel import flat_mesh

        mesh = flat_mesh(8, "ft")
        assert _jitted_psum(mesh, "ft") is _jitted_psum(mesh, "ft")


class TestPallasReduce:
    @pytest.mark.parametrize("opname", ["sum", "band", "max", "min", "bor"])
    def test_matches_reference(self, opname):
        w, L = 5, 3000
        if opname in ("band", "bor"):
            x = RNG.integers(0, 2**20, (w, L)).astype(np.int32)
        else:
            x = RNG.standard_normal((w, L)).astype(np.float32)
        got = np.asarray(reduce_stacked(jnp.asarray(x), op=opname))
        want = np.asarray(reduce_stacked_reference(jnp.asarray(x), op=opname))
        if x.dtype == np.float32:
            np.testing.assert_allclose(got, want, rtol=1e-5)
        else:
            np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("w,st", [(8, 2), (8, 4), (6, 2), (5, 4), (3, 2)])
    def test_sources_tile_matches_reference(self, w, st):
        """The sources_tile DMA-granularity knob changes the grid walk, not
        the result — including w not divisible by st (gcd clamp)."""
        x = RNG.standard_normal((w, 2000)).astype(np.float32)
        got = np.asarray(
            reduce_stacked(jnp.asarray(x), op="sum", sources_tile=st)
        )
        want = np.asarray(reduce_stacked_reference(jnp.asarray(x)))
        # grouped folding reassociates the f32 sum; bound the difference,
        # don't demand bit equality
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-6)

    def test_single_source_passthrough(self):
        x = RNG.standard_normal((1, 100)).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(reduce_stacked(jnp.asarray(x))), x[0])

    def test_large_and_unaligned(self):
        # not a multiple of 128: exercises identity padding
        x = RNG.standard_normal((3, 128 * 513 + 7)).astype(np.float32)
        got = np.asarray(reduce_stacked(jnp.asarray(x)))
        np.testing.assert_allclose(got, x.sum(0), rtol=1e-5)

    def test_rejects_bad_shapes(self):
        with pytest.raises(ValueError):
            reduce_stacked(jnp.ones((2, 3, 4)))

    def test_rejects_bad_dtype_op(self):
        with pytest.raises(TypeError):
            reduce_stacked(jnp.ones((2, 8), jnp.float32), op="band")


def test_time_device_loop_measures_slope():
    """The slope protocol returns a positive per-call time that scales with
    the work, and rejects an output-shape-changing fn at trace time."""
    import jax
    import jax.numpy as jnp

    from flextree_tpu.utils.timing import time_device_loop

    x = jnp.ones((64, 64), jnp.float32)
    light = lambda a: a * 1.000001  # noqa: E731
    heavy = jax.jit(lambda a: (a @ a.T) * 1e-3 + a)
    t_light = time_device_loop(light, x, n_lo=2, n_hi=64, best_of=3)
    t_heavy = time_device_loop(heavy, x, n_lo=2, n_hi=64, best_of=3)
    assert t_light > 0 and t_heavy > 0

    import pytest

    bad = lambda a: jnp.concatenate([a, a])  # noqa: E731 — shape grows
    with pytest.raises(Exception):
        time_device_loop(bad, x)


def test_time_interleaved_times_every_variant_once_a_round():
    """The shuffled-interleaved timer (the planner's autotune and feedback
    probes time their candidates with it): each round calls every variant
    exactly once, in an order that changes between rounds, and each row
    carries min/avg and the raw per-round samples."""
    from flextree_tpu.utils.timing import time_interleaved

    log = []

    def variant(name):
        def fn(x):
            log.append(name)
            return x + 1

        return fn, (jnp.ones(4),)

    names = ["a", "b", "c", "d"]
    repeat = 6
    rows = time_interleaved({n: variant(n) for n in names}, repeat)
    rounds = [log[i : i + len(names)] for i in range(0, len(log), len(names))]
    assert len(rounds) == repeat
    assert all(sorted(r) == names for r in rounds)
    assert len({tuple(r) for r in rounds}) > 1  # shuffled, not round-robin
    assert list(rows) == names
    for row in rows.values():
        assert set(row) == {"min_ms", "avg_ms", "times_ms"}
        assert len(row["times_ms"]) == repeat
        assert row["min_ms"] == min(row["times_ms"]) > 0
        assert row["avg_ms"] == pytest.approx(sum(row["times_ms"]) / repeat)
