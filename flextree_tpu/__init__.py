"""flextree-tpu: a TPU-native topology-parameterized collective framework.

Brand-new implementation of the capabilities of
Youhe-Jiang/AllReduce-Over-MPI ("FlexTree"): hierarchical allreduce with
configurable per-level tree widths, ring / flat / recursive-halving-doubling
special cases, an analytical cost model that picks the tree shape, and an A/B
benchmark harness — re-architected for TPU: schedules lower to
``lax.psum_scatter`` / ``lax.all_gather`` / ``lax.ppermute`` with
``axis_index_groups`` under ``shard_map``, so stages ride ICI/DCN and the
planner factors the device count along physical torus axes.
"""

from .schedule import (
    BlockLayout,
    Operation,
    LonelyTopology,
    Topology,
    TopologyError,
    get_stages,
    owned_blocks,
    parse_topo,
    recv_plan,
    ring_plan,
    send_plan,
)
from .ops import ReduceOp, SUPPORTED_OPS, get_op

__version__ = "0.1.0"

__all__ = [
    "BlockLayout",
    "Operation",
    "Topology",
    "LonelyTopology",
    "TopologyError",
    "get_stages",
    "owned_blocks",
    "parse_topo",
    "recv_plan",
    "ring_plan",
    "send_plan",
    "ReduceOp",
    "SUPPORTED_OPS",
    "get_op",
    "__version__",
]


def __getattr__(name):
    # Lazy: keep `import flextree_tpu` JAX-free for the pure schedule layer.
    if name in _PARALLEL_EXPORTS:
        from . import parallel

        return getattr(parallel, name)
    if name in _MODEL_EXPORTS:
        from . import models

        return getattr(models, name)
    if name in _INTERPOSE_EXPORTS:
        from . import interpose

        return getattr(interpose, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# Names re-exported lazily from flextree_tpu.parallel (the JAX backend).
_PARALLEL_EXPORTS = (
    "allreduce",
    "tree_allreduce",
    "ring_allreduce",
    "reduce_scatter",
    "allgather",
    "allreduce_over_mesh",
    "flat_mesh",
    "topology_from_mesh",
    "ring_attention",
    "attention_reference",
    "TrainConfig",
    "factor_devices",
    "init_train_state",
    "make_mesh_3d",
    "make_train_step",
    "state_specs",
)

# Names re-exported lazily from flextree_tpu.models (the model substrate).
_MODEL_EXPORTS = (
    "TransformerConfig",
    "cross_entropy_loss",
    "forward",
    "init_params",
    "param_specs",
)

# The lax.psum interposer (the reference's MPI_Allreduce shadowing analog).
_INTERPOSE_EXPORTS = ("interposed", "install", "uninstall", "is_installed")
