"""Utilities: timing, logging, profiling, checkpointing, result files."""

from .buildstamp import artifact_meta, build_info, version_string
from .checkpoint import (
    CheckpointCorrupt,
    latest_checkpoint,
    list_checkpoints,
    restore_checkpoint,
    restore_train_state,
    save_checkpoint,
    save_train_state,
    verify_checkpoint,
)
from .logging import get_logger, result_file_name, write_result_file
from .profiling import debug_dump_schedule, debug_enabled
from .timing import BenchResult, Timer, time_jax_fn

__all__ = [
    "artifact_meta",
    "build_info",
    "version_string",
    "save_checkpoint",
    "restore_checkpoint",
    "save_train_state",
    "restore_train_state",
    "latest_checkpoint",
    "list_checkpoints",
    "verify_checkpoint",
    "CheckpointCorrupt",
    "get_logger",
    "result_file_name",
    "write_result_file",
    "BenchResult",
    "Timer",
    "time_jax_fn",
    "debug_dump_schedule",
    "debug_enabled",
]
