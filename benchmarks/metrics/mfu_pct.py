"""Model FLOP/s utilization of this run's window: tokens/s times the
operations a token needs (`kernels/deepseekv3_model.py`), over the chip's
bf16 peak (`peaks.json`) times the chips used. Read in the traced run, whose
window is shorter and carries the profiler, so it sits a little under the
untraced `train_tokens_per_s` times the same factor."""
from benchmarks.kernels.deepseekv3_model import train_flops_per_token


def read(obs):
    if "tokens_per_step" not in obs or "sizes" not in obs:
        return None
    rate = obs["steps"] * obs["tokens_per_step"] / obs["window_s"]
    flops = train_flops_per_token(obs["sizes"], obs["seq_len"])
    chips = obs["trace"].n_devices if obs.get("trace") else 1
    return 100.0 * rate * flops / (obs["peaks"]["bf16_flops_per_s"] * chips)
