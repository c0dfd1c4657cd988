"""The single training engine (L5) parameterizing every workload."""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:train")
from solvingpapers_tpu.train.optim import warmup_cosine, make_optimizer, OptimizerConfig
from solvingpapers_tpu.train.state import TrainState
from solvingpapers_tpu.train.engine import Trainer, TrainConfig, lm_loss_fn
from solvingpapers_tpu.train.objectives import (
    classification_loss_fn,
    reconstruction_loss_fn,
    vae_loss_fn,
    make_kd_loss_fn,
)

_imported()
