"""Device-kind normalization — dependency-free (no jax import).

Single source of truth for every consumer that keys off the TPU chip
generation: MFU peaks (``bench/harness.py``), HBM roofline peaks
(``tools/roofline_reduce.py``), and calibration section names
(``tools/calibrate_host.py``).  Living here, the host-side tools can
normalize a device string without paying the jax-based bench harness's
import chain.
"""

from __future__ import annotations

__all__ = ["TPU_GENERATIONS", "tpu_generation"]

#: device_kind substring -> canonical generation name, most specific
#: first.  A bare "v5" is NOT taken for a v5p: a kind this table does not
#: name is an error, never a guess — a wrong peak makes every utilisation
#: figure wrong without a word.
TPU_GENERATIONS = (
    ("v5 lite", "v5e"),
    ("v5litepod", "v5e"),
    ("v5e", "v5e"),
    ("v6 lite", "v6e"),
    ("v6e", "v6e"),
    ("v5p", "v5p"),
    ("v4", "v4"),
    ("v3", "v3"),
    ("v2", "v2"),
)


def tpu_generation(device_kind: str) -> str:
    """Canonical generation name ("v5e", "v5p", ...) for a device_kind
    string; ``ValueError`` for a kind the table does not name."""
    kind = device_kind.lower()
    for sub, gen in TPU_GENERATIONS:
        if sub in kind:
            return gen
    raise ValueError(
        f"unknown TPU device_kind {device_kind!r}: add it to "
        f"flextree_tpu.utils.device.TPU_GENERATIONS (and its peaks to the "
        f"tables keyed by generation) before measuring on it"
    )
