"""Training substrate: DP step builder with COVAP phase-specialised
executables, host loop, metrics."""
from .trainer import (
    TrainConfig,
    Trainer,
    build_overlapped_step,
    build_train_step,
    copy_train_state,
    make_train_state,
    restore_pod_block,
    strip_pod_block,
)

__all__ = [
    "TrainConfig",
    "Trainer",
    "build_overlapped_step",
    "build_train_step",
    "copy_train_state",
    "make_train_state",
    "restore_pod_block",
    "strip_pod_block",
]
