"""Host-level convenience wrappers: run FlexTree collectives over a Mesh.

The reference's standalone entry point takes per-rank buffers already living
on N processes (``benchmark.cpp:119-153``); the JAX analog is a stacked
``(N, ...)`` array laid out one row per device, reduced under ``shard_map``.
Also provides torus-aware topology selection: on a real TPU slice the stage
widths should factor along physical mesh axes (SURVEY §7 "hard parts").
"""

from __future__ import annotations

import functools
import math

import jax
from jax.sharding import Mesh, PartitionSpec as P

from ..schedule.ir import resolve_collective
from ..schedule.stages import Topology
from .allreduce import allreduce

__all__ = ["allreduce_over_mesh", "topology_from_mesh", "flat_mesh"]


def flat_mesh(n_devices: int | None = None, axis_name: str = "ft") -> Mesh:
    """A 1-D mesh over the first ``n_devices`` local devices."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if not 1 <= n <= len(devs):
        raise ValueError(f"asked for {n} devices, have {len(devs)}")
    return jax.make_mesh((n,), (axis_name,), devices=devs[:n])


def topology_from_mesh(mesh: Mesh, axis_name=None) -> Topology:
    """Derive stage widths from the mesh's physical shape.

    A multi-axis mesh maps naturally onto hierarchical stages: one stage per
    mesh axis, width = axis size — e.g. a (4, 2) mesh gives widths ``(4, 2)``,
    so each stage's groups ride one torus axis.  For a 1-D mesh this
    degenerates to flat.  This is the TPU retarget of the planner's role:
    factoring N *along torus axes* rather than abstractly.
    """
    if axis_name is not None:
        names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        widths = tuple(mesh.shape[a] for a in names)
        n = math.prod(widths)
    else:
        widths = tuple(mesh.shape[a] for a in mesh.axis_names)
        n = mesh.size
    if n == 1:
        return Topology.flat(1)
    widths = tuple(w for w in widths if w > 1) or (n,)
    return Topology(n, widths)


def allreduce_over_mesh(
    stacked, mesh: Mesh, topo=None, op="sum", axis_name=None, in_place: bool = False
):
    """Allreduce a stacked ``(N, ...)`` array: row ``i`` lives on device ``i``
    of ``mesh``'s ``axis_name`` axis; every output row is the full reduction.

    This is the host-side harness the benchmark and tests use — the analog of
    the reference benchmark calling ``MPI_Allreduce_FT`` on each rank's local
    buffer (``benchmark.cpp:153``).

    ``in_place=True`` donates ``stacked`` to the computation — the analog of
    the reference's ``MPI_IN_PLACE`` path (``mpi_mod.hpp:1193-1215``; the
    reference benchmark always runs in-place, ``benchmark.cpp:153``).  The
    caller's array is consumed; XLA reuses its buffer for the output, which
    removes the output allocation + copy from the hot path — provided
    ``stacked`` already lives row-per-device on ``mesh``: JAX matches a
    donation to an output only when their shardings agree, and drops it
    (with a "donated buffers were not usable" warning) for an unsharded
    input.  Compiled for a v5e 2x2 the row-sharded input is aliased whole
    (``alias_size`` == the input's bytes) for flat, tree and ring.
    """
    axis = axis_name or mesh.axis_names[0]
    n = mesh.shape[axis]
    if stacked.shape[0] != n:
        raise ValueError(
            f"stacked.shape[0]={stacked.shape[0]} must equal mesh axis {axis!r} size {n}"
        )
    # resolve through the widened front door so the IR families
    # ("swing", "gen:4,2@2", IRFamilySpec) work at the host level too
    topo = resolve_collective(n, topo)
    return _jitted_allreduce(
        mesh, axis, topo, op if isinstance(op, str) else op.name, in_place
    )(stacked)


@functools.lru_cache(maxsize=256)
def _jitted_allreduce(mesh: Mesh, axis: str, topo, op: str, donate: bool = False):
    """Cache the compiled collective per (mesh, axis, topo, op) so repeated
    host-level calls (benchmark loops) hit the jit cache instead of
    rebuilding a fresh closure every call."""

    def per_device(row):
        return allreduce(row[0], axis, topo, op)[None]

    return jax.jit(
        jax.shard_map(per_device, mesh=mesh, in_specs=P(axis), out_specs=P(axis)),
        donate_argnums=(0,) if donate else (),
    )
