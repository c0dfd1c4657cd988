"""Model FLOP/s utilization of one pipeline stage's window of a
Granite-hybrid decoder: tokens/s times the operations a token needs ON THIS
STAGE (`kernels/granite_hybrid_model.py`: its mixers, a SwiGLU in every
layer, the sliced tied head, no recomputation), over the chip's bf16 peak
(`peaks.json`) times the chips used. Read in the traced run, as `mfu_pct`
is."""
from benchmarks.kernels.granite_hybrid_model import train_flops_per_token


def read(obs):
    sz = obs.get("sizes")
    if "tokens_per_step" not in obs or not (
            hasattr(sz, "ssm_heads") and hasattr(sz, "ffn")):
        return None
    rate = obs["steps"] * obs["tokens_per_step"] / obs["window_s"]
    flops = train_flops_per_token(sz, obs["seq_len"])
    chips = obs["trace"].n_devices if obs.get("trace") else 1
    return 100.0 * rate * flops / (obs["peaks"]["bf16_flops_per_s"] * chips)
