"""Operations one token costs ONE PIPELINE STAGE of an Ouro-style looped
decoder in training, from the reference's sizes: what `mfu_pct.ouro_looped`
divides by the chip's peak.

6 * N per token (2 operations a multiply-add forward, 4 backward), N the
weights a token multiplies with ON THIS STAGE, counted once a USE:
  * a layer application: the query, key, value and output projections and
    the SwiGLU's three matrices; `layers` of them in each of `ut_steps`
    passes, the same weights every pass;
  * the untied head over the whole vocabulary and the exit gate's row,
    once a pass; not the embedding lookup.
Plus what has no weights: causal attention's two products over the
sequence (6 * heads * 2 * head_dim * S / 2 a token and layer application:
the causal half, as `kernels/flash_gqa.py` counts a call). Recomputed
operations (a layer application's forward runs again under remat, the
head's chunk again in its backward) are the program's cost and are not
counted.
"""

from __future__ import annotations


def stage_params(sz) -> dict[str, float]:
    """Weights a token multiplies with in ONE use, by part."""
    d = sz.dim
    attn = d * (sz.heads + 2 * sz.kv_heads) * sz.head_dim \
        + sz.heads * sz.head_dim * d
    return {"attn": attn, "ffn": 3 * d * sz.ffn, "head": sz.vocab * d,
            "gate": d}


def train_flops_per_token(sz, seq_len: int) -> float:
    p = stage_params(sz)
    applications = sz.ut_steps * sz.layers
    weights = (applications * (p["attn"] + p["ffn"])
               + sz.ut_steps * (p["head"] + p["gate"]))
    scores = applications * sz.heads * 2 * sz.head_dim * seq_len / 2.0
    return 6.0 * (weights + scores)
