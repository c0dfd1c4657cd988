"""Share of routed (token, expert) assignments thrown away at the experts'
capacity: the trainer's logged `train_moe_drop_fraction`, mean over the
rows logged in the window (program counter)."""


def read(obs):
    vals = [r["train_moe_drop_fraction"] for r in obs.get("rows", [])
            if "train_moe_drop_fraction" in r]
    return 100.0 * sum(vals) / len(vals) if vals else None
