"""Between the plain reference's flat weights and `models/kimi_linear.py`'s
parameter tree: the same arrays under the program's names. The program's
side of this file is names and shapes only."""

from __future__ import annotations

import re

from benchmarks.adapters.deepseekv3 import (  # noqa: F401
    _norms, _path_keys, adam_of,
)
from benchmarks.reference.kimi_linear_ref import Sizes

# program path (joined by "/", layer index taken out) -> reference name
_LAYER_LEAVES = {
    "mixer/input_norm": "in_norm",
    "ffn/post_norm": "post_norm",
    "mixer/attn/q_proj": "q_proj",
    "mixer/attn/kv_a_proj": "kva",
    "mixer/attn/kv_a_norm": "kva_norm",
    "mixer/attn/kv_b_proj": "kvb",
    "mixer/attn/o_proj": "o_proj",
    "mixer/kda/in_proj_qkv": "qkv",
    "mixer/kda/in_proj_fob": "fob",
    "mixer/kda/f_up": "f_up",
    "mixer/kda/g_up": "g_up",
    "mixer/kda/conv_w": "conv",
    "mixer/kda/A_log": "A_log",
    "mixer/kda/dt_bias": "dt_bias",
    "mixer/kda/norm_weight": "kda_norm",
    "mixer/kda/o_proj": "kda_out",
    "ffn/mlp_gate": "mlp_gate",
    "ffn/mlp_up": "mlp_up",
    "ffn/mlp_down": "mlp_down",
    "ffn/moe/gate/kernel": "gate",
    "ffn/moe/select_bias": "bias",
    "ffn/moe/w1": "w1",
    "ffn/moe/w2": "w2",
    "ffn/moe/w3": "w3",
    "ffn/moe/shared_expert/gate/kernel": "s_gate",
    "ffn/moe/shared_expert/up/kernel": "s_up",
    "ffn/moe/shared_expert/down/kernel": "s_down",
}
_TOP_LEAVES = {"tok_emb/embedding": "tok_emb", "norm_f": "norm_f",
               "lm_head/kernel": "head"}


def sizes_of(model_cfg) -> Sizes:
    """The reference's sizes, read from a KimiLinearConfig."""
    return Sizes(
        vocab=model_cfg.vocab_size, block=model_cfg.block_size,
        dim=model_cfg.hidden_size, layers=model_cfg.num_hidden_layers,
        attn_layers=tuple(model_cfg.full_attn_layers),
        dense_layers=model_cfg.first_k_dense_replace,
        heads=model_cfg.num_attention_heads, latent=model_cfg.kv_lora_rank,
        nope_dim=model_cfg.qk_nope_head_dim,
        rope_dim=model_cfg.qk_rope_head_dim, v_dim=model_cfg.v_head_dim,
        kda_heads=model_cfg.linear_num_heads,
        kda_dim=model_cfg.linear_head_dim,
        conv=model_cfg.short_conv_kernel_size,
        dense_hidden=model_cfg.intermediate_size,
        router=model_cfg.router_experts, held=model_cfg.num_experts,
        first=model_cfg.first_expert, top_k=model_cfg.num_experts_per_token,
        expert_hidden=model_cfg.moe_intermediate_size,
        shared_hidden=(model_cfg.moe_intermediate_size
                       * model_cfg.num_shared_experts),
        renorm=model_cfg.moe_renormalize,
        route_scale=model_cfg.routed_scaling_factor,
        capacity_factor=model_cfg.capacity_factor,
        norm_eps=model_cfg.rms_norm_eps,
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
