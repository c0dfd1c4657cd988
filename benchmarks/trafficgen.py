"""The one general traffic generator. A traffic file gives distributions and
a rate; this module turns them, with `--seed`, into the requests of one run.

Every seed gets the same multiset of lengths and of gaps between arrivals
(drawn once from the file's `mix_seed`), in another order, and its own token
ids: so runs with different seeds do the same amount of work.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True, eq=False)
class Req:
    due_s: float  # when the request is due to be sent (open loop), else 0
    prompt: np.ndarray  # int32 token ids
    max_new: int


def draw_lengths(rng, spec: dict, n: int) -> np.ndarray:
    """`n` whole numbers from `spec`: {"dist": "lognormal", "median",
    "sigma", "min", "max"} or {"dist": "fixed", "value"}."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]), np.int64)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], size=n)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def draw_gaps(rng, spec: dict, n: int) -> np.ndarray:
    """`n` gaps between arrivals with mean 1/rate_rps: "poisson"
    (exponential gaps) or "gamma" with coefficient of variation `cv`."""
    mean = 1.0 / spec["rate_rps"]
    kind = spec.get("arrivals", "poisson")
    if kind == "poisson":
        return rng.exponential(mean, size=n)
    if kind == "gamma":
        shape = 1.0 / spec["cv"] ** 2
        return rng.gamma(shape, mean / shape, size=n)
    raise ValueError(f"unknown arrival process {kind!r}")


def make_requests(traffic: dict, seed: int, vocab: int, n: int,
                  open_loop: bool) -> list[Req]:
    """`n` requests for one run."""
    mix = np.random.default_rng(traffic["mix_seed"])
    p_len = draw_lengths(mix, traffic["prompt_len"], n)
    o_len = draw_lengths(mix, traffic["output_len"], n)
    gaps = draw_gaps(mix, traffic, n) if open_loop else np.zeros(n)
    rng = np.random.default_rng(seed)
    order, gap_order = rng.permutation(n), rng.permutation(n)
    due = np.cumsum(gaps[gap_order]) if open_loop else gaps
    return [Req(float(due[i]),
                rng.integers(0, vocab, size=int(p_len[j])).astype(np.int32),
                int(o_len[j]))
            for i, j in enumerate(order)]


def percentile(values, q: float) -> float:
    """The q-th percentile with linear interpolation (the arithmetic of
    `metrics/writer.percentiles`); NaN for no values; inf where enough
    values are inf."""
    arr = np.sort(np.asarray(list(values), np.float64))
    if arr.size == 0:
        return float("nan")
    pos = (arr.size - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or arr[lo] == arr[hi]:
        return float(arr[lo])
    if math.isinf(arr[hi]):
        return float("inf")
    return float(arr[lo] + (arr[hi] - arr[lo]) * (pos - lo))
