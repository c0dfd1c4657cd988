"""Operations one token costs ONE PIPELINE STAGE of a Granite-hybrid decoder
(every layer a mixer AND a dense SwiGLU, a tied head) in training, from the
reference's sizes: what `mfu_pct.granite_pp4` divides by the chip's peak.

6 * N per token (2 operations a multiply-add forward, 4 backward), N the
weights a token multiplies with ON THIS STAGE:
  * a Mamba-2 mixer: the input projection to [z | x | B | C | dt], the
    width-`conv` convolution over [x | B | C], and the output projection;
  * an attention mixer: the query, key, value and output projections;
  * the SwiGLU's three matrices, in EVERY layer;
  * the tied head over the vocabulary's slice, the embedding's rows used
    as a matrix once; not the embedding lookup.
Plus what has no weights: causal attention's two products over the sequence
(6 * heads * 2 * head_dim * S / 2 a token: the causal half, as
`kernels/flash_gqa.py` counts a call), and the recurrence's state, two P x
N multiply-adds a head and token (decay-and-write, read out). Recomputed
operations (a layer's forward runs again under remat, the head's chunk
again in its backward) and the chunked form's extra products are the
program's cost and are not counted.
"""

from __future__ import annotations


def stage_params(sz) -> dict[str, float]:
    """Weights a token multiplies with on this stage, by part."""
    d = sz.dim
    d_in = sz.ssm_heads * sz.ssm_head_dim
    conv_dim = d_in + 2 * sz.ssm_groups * sz.ssm_state
    mamba = (d * (d_in + conv_dim + sz.ssm_heads) + sz.conv * conv_dim
             + d_in * d)
    attn = d * (sz.heads + 2 * sz.kv_heads) * sz.head_dim \
        + sz.heads * sz.head_dim * d
    return {"mamba": mamba, "attn": attn, "ffn": 3 * d * sz.ffn,
            "head": sz.vocab * d}


def train_flops_per_token(sz, seq_len: int) -> float:
    p = stage_params(sz)
    n_m, n_a = sz.pattern.count("M"), sz.pattern.count("*")
    weights = (n_m * p["mamba"] + n_a * p["attn"] + sz.layers * p["ffn"]
               + p["head"])
    scores = n_a * sz.heads * 2 * sz.head_dim * seq_len / 2.0
    state = n_m * 2 * sz.ssm_heads * sz.ssm_head_dim * sz.ssm_state
    return 6.0 * (weights + scores + state)
