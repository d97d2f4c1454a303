"""Adding a traffic mix and a per-layer metric is adding files and entries:
a throw-away copy of the benchmark gets one of each and runs them, and no
file that was there is edited."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def digest(root):
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", ".work")]
        for f in files:
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(os.path.join(REPO, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    # the program itself is not copied: the checkout finds it beside it
    os.symlink(os.path.join(REPO, "flextree_tpu"), root / "flextree_tpu")
    return root


def run(root, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "run.py"), *argv],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )


def test_new_traffic_and_metric_are_files_and_entries_only(copy):
    before = digest(copy / "benchmarks")
    bench_path = copy / "BENCHMARK.json"
    bench = json.loads(bench_path.read_text())

    # a new traffic mix: a data file for the generator that is there
    with open(copy / "benchmarks" / "traffic" / "chat-closed-c32.json") as f:
        mix = json.load(f)
    mix.update(clients=2, slots=2, about="throw-away")
    (copy / "benchmarks" / "traffic" / "throwaway-c2.json").write_text(json.dumps(mix))
    # a new per-layer metric: a metric file and a reader of its own
    (copy / "benchmarks" / "readers" / "throwaway.py").write_text(
        "def rounds(ctx):\n    return float(len(ctx.obs['rounds']))\n")
    (copy / "benchmarks" / "metrics" / "throwaway.rounds.json").write_text(json.dumps(
        {"reader": "throwaway:rounds"}))
    tiny = json.loads((copy / "benchmarks" / "rehearsal" / "traffic"
                       / "chat-closed-c32.json").read_text())
    cell = "pythia-6.9b.throwaway-c2"
    bench["workloads"].append({"name": cell, "config": "pythia-6.9b",
                               "traffic": "throwaway-c2", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"].startswith("serve_"):
            m["workloads"].append(cell)
    # another percentile of the same samples is an entry, found by its name
    bench["end_to_end"].append({
        "name": "serve_gap_p50_ms", "unit": "ms", "better": "lower",
        "bound": 0.05, "source": "host_clock", "workloads": [cell]})
    bench["per_layer"].append({
        "name": "throwaway.rounds", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "serve_tokens_per_s", "workloads": [cell]})
    bench_path.write_text(json.dumps(bench))
    # the rehearsal sizes of the new mix are a file of their own too
    (copy / "benchmarks" / "rehearsal" / "traffic" / "throwaway-c2.json").write_text(
        json.dumps(dict(tiny, clients=2, slots=2)))

    for trace, want in ((0, "serve_tokens_per_s"), (1, "throwaway.rounds")):
        proc = run(copy, "--workload", cell, "--seed", "3", "--seconds", "1",
                   "--trace", str(trace), "--rehearsal")
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["rehearsal"] is True and line["correct"] is True
        assert want in line["metrics"], line["metrics"]
        assert ("serve_gap_p50_ms" in line["metrics"]) == (trace == 0)
        assert all(v["value"] is None for v in line["metrics"].values())

    after = digest(copy / "benchmarks")
    assert {k: v for k, v in after.items() if k in before} == before  # nothing edited
    assert set(after) - set(before) == {
        "traffic/throwaway-c2.json", "readers/throwaway.py",
        "metrics/throwaway.rounds.json", "rehearsal/traffic/throwaway-c2.json"}


def test_without_a_tpu_and_without_the_flag_there_is_no_result(copy):
    proc = run(copy, "--workload", "pythia-1.4b.train-t2048", "--seed", "1",
               "--seconds", "1", "--trace", "0")
    assert proc.returncode == 2
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".work"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    proc = run(tmp_path, "--workload", "pythia-1.4b.train-t2048", "--seed", "1",
               "--seconds", "1", "--trace", "0", "--rehearsal")
    assert proc.returncode != 0
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("trace", [0, 1])
def test_every_cell_rehearses_end_to_end(copy, cell, trace):
    proc = run(copy, "--workload", cell, "--seed", str(2**31 + 17),
               "--seconds", "1", "--trace", str(trace), "--rehearsal")
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] and all(v["value"] is None for v in line["metrics"].values())
    bench = json.loads((copy / "BENCHMARK.json").read_text())
    kind = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in bench[kind]
             if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= names
    if not trace:
        assert set(line["metrics"]) == names
