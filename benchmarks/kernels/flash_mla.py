"""The flash attention kernels of `kernels/flash_attention.py` as the
profiler trace shows them, and what each call needs by the algorithm.

In the trace a Pallas kernel is an `XLA Ops` event whose text is a
`custom-call` with `custom_call_target="tpu_custom_call"`; the kernels carry
no name of their own, so a call is told apart by what it returns
(`kind_of`): the forward kernel an output and a row of log-sum-exps, the
backward-dq kernel one array, the backward-dkv kernel two arrays of one
shape.

Operations are counted for causal attention of S queries over S keys with n
query heads, scores of width w_qk and values of width w_v (in this model
both are the kernel's head width, latent + rope_dim, since k = v = the cached
row; the model discards the rope part of the values afterwards). A product
of an (S, S) tile structure counts half of S * S, what causality leaves.
"""

from __future__ import annotations

import re

MOSAIC = 'custom_call_target="tpu_custom_call"'


def is_mosaic(op_text: str) -> bool:
    """An `XLA Ops` event of a Pallas/Mosaic kernel."""
    return MOSAIC in op_text


def result_shapes(op_text: str) -> list[tuple[str, tuple[int, ...]]]:
    """[(dtype, dims)] of what the instruction returns, from its text
    (`%name = (bf16[8,16384,128]{...}, f32[8,1,16384]{...}) custom-call(`)."""
    head = op_text.split(" custom-call(")[0]
    head = head.split("=", 1)[1] if "=" in head else head
    return [(m.group(1), tuple(int(d) for d in m.group(2).split(",") if d))
            for m in re.finditer(r"\b([a-z]+\d+)\[([\d,]*)\]", head)]


def kind_of(op_text: str) -> str | None:
    """'fwd', 'bwd_dq' or 'bwd_dkv' for a flash kernel's event; None for
    anything else."""
    if not is_mosaic(op_text):
        return None
    shapes = result_shapes(op_text)
    if len(shapes) == 1:
        return "bwd_dq"
    if len(shapes) == 2 and shapes[0][1] == shapes[1][1]:
        return "bwd_dkv"
    if len(shapes) == 2:
        return "fwd"
    return None


def flops(kind: str, seq: int, heads: int, width: int) -> float:
    """Operations one call needs: 2 per multiply-add, causal half.
    fwd: QK^T and PV. bwd_dq: QK^T again, dO V^T, dS K. bwd_dkv: QK^T
    again, P^T dO, dO V^T, dS^T Q."""
    products = {"fwd": 2, "bwd_dq": 3, "bwd_dkv": 4}[kind]
    return products * 2.0 * heads * (seq * seq / 2.0) * width


def hbm_bytes(kind: str, seq: int, heads: int, width: int,
              itemsize: int = 2) -> float:
    """Bytes one call has to move at the least: each operand read once,
    each result written once. q, o, do, dq are (heads, S, width); k and v
    are one shared head (S, width) each; lse and delta are (heads, S)
    float32; the dkv kernel writes dk and dv per query head."""
    q = heads * seq * width * itemsize
    kv = 2 * seq * width * itemsize
    row = heads * seq * 4
    if kind == "fwd":
        return q + kv + q + row
    if kind == "bwd_dq":
        return q + kv + q + 2 * row + q
    if kind == "bwd_dkv":
        return q + kv + q + 2 * row + 2 * q
    raise ValueError(kind)


def least_seconds(kind: str, seq: int, heads: int, width: int,
                  peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for one call, and which peak
    bounds it."""
    t_c = flops(kind, seq, heads, width) / peaks["bf16_flops_per_s"]
    t_m = hbm_bytes(kind, seq, heads, width) / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def roofline_share(obs: dict, kinds: tuple[str, ...]):
    """100 * (least time for the calls of `kinds` in the traced window) /
    (the time the trace gives them); None where the trace has none. One
    event covers the whole batch."""
    tr, sz = obs.get("trace"), obs.get("sizes")
    if tr is None or sz is None:
        return None
    least = spent = 0.0
    for text, durs in tr.ops.items():
        kind = kind_of(text)
        if kind in kinds:
            t, _ = least_seconds(kind, obs["seq_len"], sz.heads,
                                 sz.latent + sz.rope_dim, obs["peaks"])
            least += t * len(durs) * obs["batch_size"]
            spent += sum(durs)
    return 100.0 * least / spent if spent > 0 else None
