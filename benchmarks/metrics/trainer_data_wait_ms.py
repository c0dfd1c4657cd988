"""Mean host time a step that `Trainer.fit` spent in `next()` of its
batch iterator, as the trainer counts it itself: the mean of the window's
logged `data_wait_ms` (program counter)."""


def read(obs):
    vals = [r["data_wait_ms"] for r in obs.get("rows", [])
            if "data_wait_ms" in r]
    return sum(vals) / len(vals) if vals else None
