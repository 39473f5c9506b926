"""Stage-1 one-shot tuning: masked optimizer, train step, checkpointing —
plus consistency distillation of the few-step student (ISSUE 16)."""

from videop2p_tpu.train.checkpoint import (
    latest_checkpoint,
    restore_checkpoint,
    save_checkpoint,
)
from videop2p_tpu.train.distill import (
    DistillConfig,
    DistillState,
    apply_time_head,
    distill_step,
    distill_steps,
    init_time_head,
    load_student,
    make_distill_optimizer,
    save_student,
)
from videop2p_tpu.train.masking import (
    DEFAULT_TRAINABLE,
    count_params,
    merge_params,
    partition_params,
    trainable_mask,
)
from videop2p_tpu.train.tuner import (
    StepLoss,
    TrainState,
    TuneConfig,
    diffusion_loss,
    loss_step,
    loss_steps,
    make_lr_schedule,
    make_optimizer,
    next_token_loss,
    train_step,
    train_steps,
)

__all__ = [
    "latest_checkpoint",
    "restore_checkpoint",
    "save_checkpoint",
    "DistillConfig",
    "DistillState",
    "apply_time_head",
    "distill_step",
    "distill_steps",
    "init_time_head",
    "load_student",
    "make_distill_optimizer",
    "save_student",
    "DEFAULT_TRAINABLE",
    "count_params",
    "merge_params",
    "partition_params",
    "trainable_mask",
    "StepLoss",
    "TrainState",
    "TuneConfig",
    "diffusion_loss",
    "loss_step",
    "loss_steps",
    "make_lr_schedule",
    "make_optimizer",
    "next_token_loss",
    "train_step",
    "train_steps",
]
