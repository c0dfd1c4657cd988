"""Device time a step of attention over the indexer's keys: the indexer's
projections, rotation and scores (`L_dsa_index`), the top-k and the masks
(`L_dsa_select`), scores, softmax and values over the selection with the
heads' mean (`L_dsa_attend`) and the indexer's KL (`L_dsa_loss`), forward,
backward and recomputed (device trace through `trace/layers.py`; a loop
over query blocks is one event under the scope it was called in). None
against a program that has no such scopes."""
from benchmarks.kernels.dsa_rule import SCOPES
from benchmarks.trace import layers


def read(obs):
    return layers.sum_ms(obs, SCOPES)
