"""Model FLOP/s utilization of one expert-parallel rank's window of a
decoder whose attention reads an indexer's keys: tokens/s times the
operations a token needs ON THIS RANK (`kernels/keye_vl_model.py`: the held
experts' share of the routed pairs, the selected pairs and not the causal
ones, the indexer, the sliced head), over the chip's bf16 peak
(`peaks.json`) times the chips used. Read in the traced run, as `mfu_pct`
is."""
from benchmarks.kernels.keye_vl_model import train_flops_per_token


def read(obs):
    sz = obs.get("sizes")
    if "tokens_per_step" not in obs or not hasattr(sz, "idx_heads"):
        return None
    rate = obs["steps"] * obs["tokens_per_step"] / obs["window_s"]
    flops = train_flops_per_token(sz, obs["seq_len"])
    chips = obs["trace"].n_devices if obs.get("trace") else 1
    return 100.0 * rate * flops / (obs["peaks"]["bf16_flops_per_s"] * chips)
