"""Share of the routed experts' row tiles that hold a token and are
multiplied (`kernels/moe_grouped.py` skips the others): the trainer's logged
`train_moe_live_tile_fraction`, mean over the rows logged in the window
(program counter). 100 where the experts' einsums run over every slot;
nothing where no row has the counter."""


def read(obs):
    vals = [r["train_moe_live_tile_fraction"] for r in obs.get("rows", [])
            if "train_moe_live_tile_fraction" in r]
    return 100.0 * sum(vals) / len(vals) if vals else None
