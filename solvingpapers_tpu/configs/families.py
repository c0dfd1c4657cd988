"""The family table: one record for every value of `RunConfig.model_family`,
and the only place outside its own `models/<family>.py` that knows a family
by name. `configs/factory.py` and `cli.py` read the record. A new family
costs its model file, one record here and its presets in
`configs/registry.py`.

Model, objective and `init_fn` are import paths, `"<module>:<name>"`,
resolved when asked for: building one model must not import every family
(`startup_import_s` is measured). `tests/test_layering.py` resolves them all.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

from solvingpapers_tpu.metrics.mfu import looped_flops_per_token

_MODELS = "solvingpapers_tpu.models"
_OBJECTIVES = "solvingpapers_tpu.train.objectives"
_LM = "solvingpapers_tpu.train.engine:lm_loss_fn"
_CLASSIFY = f"{_OBJECTIVES}:classification_loss_fn"
# cross-entropy alone, head and loss in chunks of rows
_CHUNKED = f"{_OBJECTIVES}:chunked_head_loss_fn"
_DSV3 = dict(objective=f"{_OBJECTIVES}:dsv3_loss_fn",
             init_fn=f"{_OBJECTIVES}:dsv3_init_fn")
_RECURRENT = (
    "its recurrent layers (Gated DeltaNet, Kimi Delta Attention, Mamba-2) "
    "keep recurrent state, and no cache manager here holds that yet "
    "(ROADMAP R-M7)")
_SELECTED = (
    "its attention reads only the keys a lightning indexer picks for each "
    "query, and here no cache holds the indexer's keys beside the paged "
    "keys and values and no decode step selects (ROADMAP R-M13)")
_LOOPED = (
    "a looped model keeps keys and values a (pass, layer) and may leave the "
    "loop at a gate threshold, and no cache manager or decode step here does "
    "that yet (ROADMAP R-M15)")


@dataclasses.dataclass(frozen=True)
class Family:
    model: str  # the Flax module, built from `RunConfig.model`
    objective: str  # the LossFn `Trainer` is given
    init_fn: str | None = None  # `Trainer`'s; None: parameters alone
    # why `cli serve` refuses the family (what it prints behind the family's
    # name before it returns 2); None: it serves
    unservable: str | None = None
    # (model config, sequence length) -> operations a token for the logged
    # `mfu`, where the trainer's own count would be wrong
    flops_per_token: Callable[[Any, int], float] | None = None


FAMILIES: dict[str, Family] = {
    "gpt": Family(f"{_MODELS}.gpt:GPT", _LM),
    "gpt_pipe": Family(f"{_MODELS}.gpt_pipe:GPTPipe", _LM),
    "llama3": Family(f"{_MODELS}.llama3:Llama", _LM),
    "llama3_pipe": Family(f"{_MODELS}.llama3_pipe:LlamaPipe", _LM),
    "gemma": Family(f"{_MODELS}.gemma:Gemma", _LM),
    "deepseekv3": Family(f"{_MODELS}.deepseekv3:DeepSeekV3", **_DSV3),
    "dsv3_pipe": Family(f"{_MODELS}.deepseekv3_pipe:DSV3Pipe", **_DSV3),
    "qwen3next": Family(f"{_MODELS}.qwen3next:Qwen3Next",
                        f"{_OBJECTIVES}:qwen3next_loss_fn",
                        unservable=_RECURRENT),
    "kimi_linear": Family(f"{_MODELS}.kimi_linear:KimiLinear", _CHUNKED,
                          unservable=_RECURRENT),
    "nemotron_h": Family(f"{_MODELS}.nemotron_h:NemotronH", _CHUNKED,
                         unservable=_RECURRENT),
    "granite_hybrid": Family(f"{_MODELS}.granite_hybrid:GraniteHybrid",
                             _CHUNKED, unservable=_RECURRENT),
    "keye_vl": Family(f"{_MODELS}.keye_vl:KeyeVL",
                      f"{_OBJECTIVES}:keye_vl_loss_fn", unservable=_SELECTED),
    # a looped model's weights count once a USE (T passes of the layers, T
    # heads), not once
    "ouro": Family(f"{_MODELS}.ouro:Ouro", f"{_OBJECTIVES}:ouro_loss_fn",
                   unservable=_LOOPED,
                   flops_per_token=looped_flops_per_token),
    "vit": Family(f"{_MODELS}.vit:ViT", _CLASSIFY),
    "alexnet": Family(f"{_MODELS}.alexnet:AlexNet", _CLASSIFY),
    "kd": Family(f"{_MODELS}.kd:MLPClassifier", _CLASSIFY),
    "ae": Family(f"{_MODELS}.autoencoder:AutoEncoder",
                 f"{_OBJECTIVES}:reconstruction_loss_fn"),
    "vae": Family(f"{_MODELS}.autoencoder:VAE", f"{_OBJECTIVES}:vae_loss_fn"),
}


def resolve(path: str):
    """The object an import path `"<module>:<name>"` names."""
    module, name = path.split(":")
    return getattr(importlib.import_module(module), name)
