"""MFU accounting (SURVEY.md hard part #5).

flops-per-token uses the PaLM-appendix convention: 6N for the
fwd+bwd matmul flops of N *active* parameters plus the 12·L·D·S
attention-score term. For MoE models pass the active (routed top-k +
shared + non-expert) parameter count, not the total.
"""

from __future__ import annotations

import math
import warnings

import jax

# bf16 peak TFLOP/s per chip by TPU generation (public spec sheets).
_PEAK_TFLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5": 459e12,  # v5p
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}

_warned_kinds: set[str] = set()


def chip_peak_flops(device=None) -> float:
    """bf16 peak FLOP/s for `device`, or NaN when the chip is unknown.

    NaN is a deliberate sentinel: CPU hosts and unrecognized backends
    have no table entry, and the old conservative-default behavior
    (assume v5e) silently mis-scaled every downstream MFU number —
    garbage that looked plausible. NaN instead propagates visibly
    through `mfu()` and lets callers gate (`math.isfinite`) the gauge
    out entirely, which every consumer in this repo now does."""
    device = device or jax.devices()[0]
    kind = str(getattr(device, "device_kind", "") or "").lower()
    for key, val in _PEAK_TFLOPS.items():
        if key in kind:
            return val
    # unknown device: warn once per kind so the absent-MFU mystery is
    # self-explaining, then return the sentinel
    if kind not in _warned_kinds:
        _warned_kinds.add(kind)
        warnings.warn(
            f"chip_peak_flops: unrecognized device_kind {kind!r}; "
            "returning NaN — MFU gauges will be omitted rather than "
            "mis-scaled (extend metrics.mfu._PEAK_TFLOPS for new chips)",
            stacklevel=2,
        )
    return float("nan")


def transformer_flops_per_token(
    n_active_params: int, n_layers: int, dim: int, seq_len: int, training: bool = True
) -> float:
    """6N + 12·L·D·S per trained token (2N + 4·L·D·S for inference)."""
    mult = 6 if training else 2
    attn = (12 if training else 4) * n_layers * dim * seq_len
    return mult * n_active_params + attn


def looped_flops_per_token(cfg, seq_len: int, training: bool = True) -> float:
    """Operations a token of a looped decoder (`models/ouro.py`'s config):
    its `num_hidden_layers` layers are applied in each of `total_ut_steps`
    passes and the head (with the exit gate's row) after each, so the
    weights count once a USE, not once: 6 * uses (2 for inference), plus
    causal attention's two products over half the sequence a layer
    application. Recomputation under remat is not counted."""
    d, n, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
    layer = (d * (n + 2 * cfg.num_key_value_heads) * hd + n * hd * d
             + 3 * d * cfg.intermediate_size)
    uses = cfg.total_ut_steps * cfg.num_hidden_layers
    weights = uses * layer + cfg.total_ut_steps * (cfg.vocab_size * d + d)
    scores = uses * n * 2 * hd * seq_len / 2.0
    return (6.0 if training else 2.0) * (weights + scores)


def mfu(tokens_per_sec: float, flops_per_token: float, n_chips: int = 1, device=None) -> float:
    """Model FLOP utilization, or NaN when it cannot be computed
    honestly (unknown chip peak, non-finite inputs, zero peak) — NaN
    never raises and never masquerades as a real utilization."""
    peak = chip_peak_flops(device) * n_chips
    achieved = tokens_per_sec * flops_per_token
    if not (math.isfinite(peak) and peak > 0 and math.isfinite(achieved)):
        return float("nan")
    return achieved / peak


def active_param_count(params, top_experts: int | None = None, n_experts: int | None = None) -> int:
    """Parameters touched per token. For MoE pytrees (stacked expert weights
    under .../moe/w1|w2|w3) only top_experts/n_experts of the routed expert
    params count as active — the correct N for the 6N flops model
    (SURVEY.md hard part #5: 'MoE's active-params-only flops')."""
    import jax.tree_util as jtu

    total = 0
    routed = 0
    for path, leaf in jtu.tree_flatten_with_path(params)[0]:
        p = "/".join(str(getattr(k, "key", k)) for k in path)
        total += leaf.size
        if "/moe/w1" in p or "/moe/w2" in p or "/moe/w3" in p:
            routed += leaf.size
    if top_experts and n_experts and routed:
        total -= routed - routed * top_experts // n_experts
    return total
