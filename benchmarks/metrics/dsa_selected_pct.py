"""Share of the causal (query, key) pairs that attention reads: the
trainer's logged `train_dsa_selected_fraction` (selected pairs over causal
pairs, a mean over layers), mean over the rows logged in the window, times
100 (program counter). 23.4 at 16,384 tokens and a top-2,048; 100 means the
selection is not being applied. None where the program logs no such
counter."""


def read(obs):
    vals = [r["train_dsa_selected_fraction"] for r in obs.get("rows", [])
            if "train_dsa_selected_fraction" in r]
    return 100.0 * sum(vals) / len(vals) if vals else None
