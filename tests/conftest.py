"""Test harness config: force an 8-device virtual CPU mesh for the suite.

The reference had no tests and targeted a real 16-host cluster (SURVEY §4);
we simulate multi-chip on CPU so the whole suite runs anywhere.

``jax_platforms=cpu`` (the same setting as ``JAX_PLATFORMS=cpu``) means
only the CPU backend is ever initialized, so the suite never takes a chip
another process may hold.  It and the device count must be set before
anything calls ``jax.devices()`` — conftest import time is early enough.
"""

import os
import re
import tempfile

# hermeticity: a developer shell may export the planner-calibration env vars
# (README suggests FLEXTREE_CALIBRATION=CALIBRATION.json); the golden
# planner tests pin the invented defaults, so ambient calibration must not
# leak into the suite
os.environ.pop("FLEXTREE_CALIBRATION", None)
os.environ.pop("FLEXTREE_CALIBRATION_BACKEND", None)
# likewise the autotune plan cache: tests must never read or write the
# developer's user-level default cache — pin it to a per-run temp file
os.environ["FLEXTREE_PLAN_CACHE"] = os.path.join(
    tempfile.gettempdir(), f"flextree_plan_cache_test_{os.getpid()}.json"
)
# and the JAX compile cache: the CLIs under test call
# utils.backend.enable_compile_cache, which leaves a cache placed from
# outside alone — place it in a per-run directory (before jax is imported,
# so JAX reads it) and no test reads or writes the checkout's .jax_cache
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    import atexit
    import shutil

    _jax_cache = tempfile.mkdtemp(prefix="flextree_jax_cache_test_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _jax_cache
    atexit.register(shutil.rmtree, _jax_cache, ignore_errors=True)

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)


_HLO_DEBUG = re.compile(
    r'metadata=\{[^}]*\}|op_name="[^"]*"'
    # the source-location tables jax 0.9 prints ahead of the computations
    r"|^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*",
    re.M,
)


def strip_hlo_debug(hlo_text: str) -> str:
    """Compiled-HLO text minus everything that only says WHERE in the
    Python source an op came from — what the program-equality tests
    compare (two call paths to one program differ in nothing else)."""
    return _HLO_DEBUG.sub("", hlo_text)


def own_copy(state):
    """A train state with buffers of its own.  A built step DONATES the
    state it is given (``parallel.train.jit_step``): a test that hands
    ONE state to several steps gives each step but the last a copy."""
    from flextree_tpu.parallel.train import copy_state

    return copy_state(state)


def pytest_collection_modifyitems(config, items):
    """Deselect ``perf``-marked tests unless the -m expression names perf.

    These assert rank order on live wall-clock timings of the 8-vdev mesh —
    correct code flakes under host load, so they are opt-in
    (`-m perf`), not part of any default or `-m "not slow"` run.  A hook
    rather than addopts so it composes with every -m expression.
    """
    markexpr = config.getoption("markexpr", "") or ""
    if "perf" in markexpr:
        return
    selected, deselected = [], []
    for item in items:
        (deselected if "perf" in item.keywords else selected).append(item)
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = selected


def topology_strategy(max_width: int = 16, max_n: int = 512):
    """Shared hypothesis strategy: random ordered-factorization topologies
    (used by test_schedule_properties.py and test_native_schedule.py)."""
    import numpy as np
    from hypothesis import strategies as st

    from flextree_tpu.schedule.stages import Topology

    @st.composite
    def topologies(draw):
        n_stages = draw(st.integers(1, 4))
        widths = tuple(draw(st.integers(2, max_width)) for _ in range(n_stages))
        while len(widths) > 1 and int(np.prod(widths)) > max_n:
            widths = widths[:-1]  # drop stages until the cap is honored
        return Topology(int(np.prod(widths)), widths)

    return topologies()
