"""Between the plain reference's flat weights and
`models/granite_hybrid.py`'s parameter tree: the same arrays under the
program's names. The program's side of this file is names and shapes
only."""

from __future__ import annotations

import re

from benchmarks.adapters.deepseekv3 import (  # noqa: F401
    _norms, _path_keys, adam_of,
)
from benchmarks.reference.granite_hybrid_ref import Sizes

# program path (joined by "/", layer index taken out) -> reference name
_LAYER_LEAVES = {
    "input_layernorm": "norm_in",
    "post_attention_layernorm": "norm_post",
    "mixer/in_proj": "in_proj",
    "mixer/conv_w": "conv",
    "mixer/conv_b": "conv_b",
    "mixer/dt_bias": "dt_bias",
    "mixer/A_log": "A_log",
    "mixer/D": "D",
    "mixer/norm_weight": "ssm_norm",
    "mixer/out_proj": "ssm_out",
    "attn/q_proj": "q_proj",
    "attn/k_proj": "k_proj",
    "attn/v_proj": "v_proj",
    "attn/o_proj": "o_proj",
    "gate_proj": "gate",
    "up_proj": "up",
    "down_proj": "down",
}
# the head is the embedding: one leaf
_TOP_LEAVES = {"tok_emb/embedding": "tok_emb", "norm_f": "norm_f"}
_KINDS = {"mamba": "M", "attention": "*"}


def sizes_of(model_cfg) -> Sizes:
    """The reference's sizes, read from a GraniteHybridConfig."""
    return Sizes(
        vocab=model_cfg.vocab_size, block=model_cfg.block_size,
        dim=model_cfg.hidden_size, layers=model_cfg.num_hidden_layers,
        pattern="".join(_KINDS[k] for k in model_cfg.layer_types[
            :model_cfg.num_hidden_layers]),
        heads=model_cfg.num_attention_heads,
        kv_heads=model_cfg.num_key_value_heads,
        head_dim=model_cfg.hidden_size // model_cfg.num_attention_heads,
        attn_scale=model_cfg.attention_multiplier,
        ssm_heads=model_cfg.mamba_n_heads,
        ssm_head_dim=model_cfg.mamba_d_head,
        ssm_groups=model_cfg.mamba_n_groups,
        ssm_state=model_cfg.mamba_d_state, conv=model_cfg.mamba_d_conv,
        ffn=model_cfg.intermediate_size,
        emb_scale=model_cfg.embedding_multiplier,
        res_scale=model_cfg.residual_multiplier,
        logits_scale=model_cfg.logits_scaling,
        norm_eps=model_cfg.rms_norm_eps,
        dt_min=model_cfg.time_step_min, dt_max=model_cfg.time_step_max,
        dt_floor=model_cfg.time_step_floor,
    )


def reference_name(path: tuple[str, ...]) -> str:
    joined = "/".join(path)
    if joined in _TOP_LEAVES:
        return _TOP_LEAVES[joined]
    m = re.match(r"layer_(\d+)/(.+)$", joined)
    if m and m.group(2) in _LAYER_LEAVES:
        return f"l{m.group(1)}.{_LAYER_LEAVES[m.group(2)]}"
    raise KeyError(f"no reference weight for the program's leaf {joined!r}")


def to_program_tree(weights: dict, like):
    """`weights` (reference names) arranged as the tree `like` (the
    program's parameters, arrays or shapes). Every leaf of `like` must find
    a weight of its shape, and every weight a leaf."""
    import jax

    used = set()

    def pick(path, leaf):
        name = reference_name(_path_keys(path))
        w = weights[name]
        if tuple(w.shape) != tuple(leaf.shape):
            raise ValueError(f"{name}: reference {w.shape}, program "
                             f"{leaf.shape}")
        used.add(name)
        return w.astype(leaf.dtype)

    tree = jax.tree_util.tree_map_with_path(pick, like)
    if used != set(weights):
        raise ValueError(f"weights the program has no leaf for: "
                         f"{sorted(set(weights) - used)}")
    return tree


def leaf_norms(tree) -> dict:
    """{reference name: 2-norm} of a tree shaped like the program's
    parameters (the parameters, Adam's first moment, a difference)."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(_norms(tree))[0]
    return {reference_name(_path_keys(p)): float(v) for p, v in flat}
