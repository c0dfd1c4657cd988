"""Between the plain reference's flat weights and `models/keye_vl.py`'s
parameter tree: the same arrays under the program's names. The program's
side of this file is names and shapes only."""

from __future__ import annotations

import re

from benchmarks.adapters.deepseekv3 import (  # noqa: F401
    _norms, _path_keys, adam_of,
)
from benchmarks.reference.keye_vl_ref import Sizes

# program path (joined by "/", layer index taken out) -> reference name
_LAYER_LEAVES = {
    "input_norm": "in_norm",
    "post_norm": "post_norm",
    "attn/q_proj": "q_proj",
    "attn/k_proj": "k_proj",
    "attn/v_proj": "v_proj",
    "attn/q_norm": "q_norm",
    "attn/k_norm": "k_norm",
    "attn/o_proj": "o_proj",
    "attn/indexer_q_proj": "idx_q",
    "attn/indexer_k_proj": "idx_k",
    "attn/indexer_weights_proj": "idx_w",
    "moe/gate/kernel": "gate",
    "moe/w1": "w1",
    "moe/w2": "w2",
    "moe/w3": "w3",
}
_TOP_LEAVES = {"tok_emb/embedding": "tok_emb", "norm_f": "norm_f",
               "lm_head/kernel": "head"}


def sizes_of(model_cfg) -> Sizes:
    """The reference's sizes, read from a KeyeVLConfig."""
    return Sizes(
        vocab=model_cfg.vocab_size, block=model_cfg.block_size,
        dim=model_cfg.hidden_size, layers=model_cfg.num_hidden_layers,
        heads=model_cfg.num_attention_heads,
        kv_heads=model_cfg.num_key_value_heads, head_dim=model_cfg.head_dim,
        rope_theta=model_cfg.rope_theta,
        idx_heads=model_cfg.indexer_num_heads,
        idx_dim=model_cfg.indexer_head_dim, topk=model_cfg.topk,
        router=model_cfg.router_experts, held=model_cfg.num_experts,
        first=model_cfg.first_expert, top_k=model_cfg.num_experts_per_tok,
        expert_hidden=model_cfg.moe_intermediate_size,
        renorm=model_cfg.norm_topk_prob,
        capacity_factor=model_cfg.capacity_factor,
        balance_weight=model_cfg.router_aux_loss_coef,
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
