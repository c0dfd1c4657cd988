"""Slots decoding after each `eng.step()` of the window, mean, over the
engine's `n_slots`."""


def read(obs):
    occ = obs.get("occupancy")
    return 100.0 * sum(occ) / len(occ) if occ else None
