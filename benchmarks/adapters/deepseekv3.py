"""Between the plain reference's flat weights and `models/deepseekv3.py`'s
parameter tree: the same arrays under the program's names. The program's
side of this file is names and shapes only."""

from __future__ import annotations

import functools
import re

from benchmarks.reference.deepseekv3_ref import Adam, Sizes

# program path (joined by "/", layer index taken out) -> reference name
_LAYER_LEAVES = {
    "norm1/weight": "norm1",
    "mla/w_dkv/kernel": "w_dkv",
    "mla/w_q": "w_q",
    "mla/w_k": "w_k",
    "mla/w_v": "w_v",
    "mla/out/kernel": "w_o",
    "mla/w_qr": "w_qr",
    "mla/w_kr/kernel": "w_kr",
    "norm2/weight": "norm2",
    "moe/gate/kernel": "gate",
    "moe/w1": "w1",
    "moe/w2": "w2",
    "moe/w3": "w3",
    "moe/shared_expert/gate/kernel": "s_gate",
    "moe/shared_expert/up/kernel": "s_up",
    "moe/shared_expert/down/kernel": "s_down",
}
_TOP_LEAVES = {"tok_emb/embedding": "tok_emb", "norm_f/weight": "norm_f"}


def sizes_of(model_cfg) -> Sizes:
    """The reference's sizes, read from a DeepSeekV3Config. What the
    reference does not model has to be off."""
    unsupported = {
        "mtp_heads": 0, "noisy_topk": False, "use_aux_free": True,
        "use_shared_expert": True, "context_parallel": False,
    }
    for key, want in unsupported.items():
        if getattr(model_cfg, key) != want:
            raise ValueError(
                f"the plain reference needs {key}={want!r}, the "
                f"configuration has {getattr(model_cfg, key)!r}")
    return Sizes(
        vocab=model_cfg.vocab_size, block=model_cfg.block_size,
        dim=model_cfg.dim, layers=model_cfg.n_layers, heads=model_cfg.n_heads,
        latent=model_cfg.latent_dim, experts=model_cfg.n_experts,
        top_k=model_cfg.top_experts, rope_dim=model_cfg.rope_dim,
        rope_theta=model_cfg.rope_theta, pe_scale=model_cfg.pe_scale,
        capacity_factor=(None if model_cfg.moe_impl == "dense"
                         else model_cfg.capacity_factor),
        balance_weight=model_cfg.balance_loss_weight,
        bias_rate=model_cfg.aux_free_bias_update_rate,
        norm_eps=model_cfg.norm_eps,
    )


def adam_of(opt_cfg) -> Adam:
    if opt_cfg.name != "adamw" or opt_cfg.accum_steps != 1:
        raise ValueError("the plain reference follows plain AdamW only")
    return Adam(
        max_lr=opt_cfg.max_lr, warmup_steps=opt_cfg.warmup_steps,
        total_steps=opt_cfg.total_steps, min_lr_ratio=opt_cfg.min_lr_ratio,
        b1=opt_cfg.b1, b2=opt_cfg.b2, eps=opt_cfg.eps,
        weight_decay=opt_cfg.weight_decay, grad_clip=opt_cfg.grad_clip)


def reference_name(path: tuple[str, ...]) -> str:
    joined = "/".join(path)
    if joined in _TOP_LEAVES:
        return _TOP_LEAVES[joined]
    m = re.match(r"layer_(\d+)/(.+)$", joined)
    if m and m.group(2) in _LAYER_LEAVES:
        return f"l{m.group(1)}.{_LAYER_LEAVES[m.group(2)]}"
    raise KeyError(f"no reference weight for the program's leaf {joined!r}")


def _path_keys(path) -> tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


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


@functools.cache
def _norms_fn():
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: jax.tree.map(
        lambda v: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))), t))


def _norms(tree):
    return _norms_fn()(tree)


def leaf_norms(tree) -> dict:
    """{reference name: 2-norm} of a tree shaped like the program's
    parameters (the parameters, Adam's first moment, a difference)."""
    import jax

    norms = _norms(tree)
    flat = jax.tree_util.tree_flatten_with_path(norms)[0]
    return {reference_name(_path_keys(p)): float(v) for p, v in flat}
