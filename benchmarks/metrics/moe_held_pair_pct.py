"""Share of all routed (token, expert) pairs that fall on the experts this
rank holds: the trainer's logged `train_moe_held_pair_fraction`, mean over
the rows logged in the window (program counter). 100 * held / router when
routing is balanced (6.25 with 32 of 512); None where the program logs no
such counter."""


def read(obs):
    vals = [r["train_moe_held_pair_fraction"] for r in obs.get("rows", [])
            if "train_moe_held_pair_fraction" in r]
    return 100.0 * sum(vals) / len(vals) if vals else None
