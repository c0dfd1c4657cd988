"""Mean time `Trainer.fit` waited in `next()` of its batch iterator, per
step of the window (the benchmark's timing wrapper; host clock)."""


def read(obs):
    waits = obs.get("data_waits")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
