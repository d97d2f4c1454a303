"""Where the program runs, asked in one place.

Every entry point and every kernel front end needs the same four answers:
which device JAX found, whether landing on the CPU was asked for, whether
a Pallas kernel lowers for the chip or runs interpreted, and where
compiled programs are kept between runs.  Keeping them here means no call
site decides any of them by itself — a kernel that quietly interprets, or
a CLI that quietly trains on the host, is the failure this module exists
to make loud.
"""

from __future__ import annotations

import os

import jax

__all__ = [
    "REPO_CACHE_DIR",
    "announce_devices",
    "enable_compile_cache",
    "pallas_interpret",
]

#: The in-checkout compile cache (git-ignored).  Fixed on purpose: the
#: directory is part of the cache key, so a path built from a pid, a clock
#: or ``tempfile`` never hits.
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def _requested_platforms() -> tuple:
    """What JAX was asked for, in priority order — ``JAX_PLATFORMS`` and
    the ``jax_platforms`` option are one setting (a CLI's ``--cpu`` writes
    the latter).  The first entry is the default backend asked for."""
    return tuple(p for p in (jax.config.jax_platforms or "").split(",") if p)


def announce_devices(prog: str) -> None:
    """Print ``platform``, ``device_kind`` and count as JAX reports them;
    exit if the CPU was landed on unasked.

    JAX falls back to the CPU when it finds no accelerator, and every
    number printed afterwards then describes the host.  Running on the CPU
    is fine when ``--cpu`` / ``JAX_PLATFORMS=cpu`` said so (the test suite
    does); reaching it by accident is an error.
    """
    devs = jax.devices()
    print(
        f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}",
        flush=True,
    )
    if devs[0].platform == "cpu" and _requested_platforms()[:1] != ("cpu",):
        raise SystemExit(
            f"{prog}: JAX found no accelerator and fell back to the CPU. "
            f"Pass --cpu (or set JAX_PLATFORMS=cpu) to run there on purpose."
        )


def kernel_platform() -> str:
    """The platform Pallas kernels lower for: the default backend.  The
    one place to override when compiling for a chip that is not attached
    (``tests/test_tpu_aot.py`` patches it to ``"tpu"``)."""
    return jax.default_backend()


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Resolve a ``pallas_call``'s ``interpret`` flag.

    An explicit ``True``/``False`` wins.  ``None`` means: the Mosaic
    kernel on a TPU, the interpreter on the CPU (how the test suite runs
    the kernels), and an error anywhere else — these kernels are written
    for Mosaic, and interpreting them on another accelerator would be a
    slow wrong answer to "does the kernel run here".
    """
    if interpret is not None:
        return bool(interpret)
    platform = kernel_platform()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here lower for TPU or run interpreted on the CPU; "
        f"the default backend is {platform!r}. Pass interpret= explicitly "
        f"to choose."
    )


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already read it and
    nothing is changed — the cache is placed from outside.  Otherwise it
    goes to :data:`REPO_CACHE_DIR`.  Call before the first compile.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
