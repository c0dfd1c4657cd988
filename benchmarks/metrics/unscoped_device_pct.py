"""Share of the train step's top-level device time whose instruction
carries no layer scope: what the program's scopes do not cover. A scope
that a refactor drops shows here."""
from benchmarks.trace import layers


def read(obs):
    table = layers.layer_ms(obs)
    if not table:
        return None
    return 100.0 * table.get(layers.UNSCOPED, 0.0) / sum(table.values())
