"""Mean entropy of the exit distribution a token, in nats: the trainer's
logged `train_exit_entropy` of the window's last row (program counter). At
most ln(passes) (1.386 with four); a gate that collapses onto one exit
reads 0. None where the program logs no such counter."""


def read(obs):
    vals = [r["train_exit_entropy"] for r in obs.get("rows", [])
            if "train_exit_entropy" in r]
    return vals[-1] if vals else None
