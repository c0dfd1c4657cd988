"""Task objectives for the shared Trainer.

Each reference training loop's objective as a LossFn
(model, params, batch, rng, model_state, train) -> (loss, aux, model_state):

  * lm_loss_fn (train/engine.py)    — gpt/llama3/gemma/deepseekv3 LM CE
  * classification_loss_fn          — ViT.ipynb cell 13, kd.py teacher
  * reconstruction_loss_fn          — autoencoder.ipynb cells 6-7 (MSE)
  * vae_loss_fn                     — variational autoencoder.ipynb cell 6
  * make_kd_loss_fn                 — kd.py:48-68 distillation objective
                                      (teacher frozen under stop_gradient)
  * dsv3_loss_fn, qwen3next_loss_fn, chunked_head_loss_fn, keye_vl_loss_fn,
    ouro_loss_fn —
    the decoder families' (which trains under which: configs/families.py)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from solvingpapers_tpu import ops


def classification_loss_fn(model, params, batch, rng, model_state, train):
    """CE over class logits + accuracy (ViT.ipynb cells 13-15; kd.py:145-156)."""
    logits = model.apply(
        {"params": params},
        batch["x"],
        deterministic=not train,
        rngs={"dropout": rng} if train else None,
    )
    loss = ops.cross_entropy(logits, batch["y"])
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(jnp.float32))
    return loss, {"accuracy": acc}, model_state


def reconstruction_loss_fn(model, params, batch, rng, model_state, train):
    """Mean-square reconstruction of the input (autoencoder.ipynb cell 7)."""
    recon = model.apply({"params": params}, batch["x"], deterministic=not train)
    x32 = batch["x"].astype(jnp.float32)
    loss = jnp.mean(jnp.square(recon.astype(jnp.float32) - x32))
    return loss, {}, model_state


def vae_loss_fn(model, params, batch, rng, model_state, train):
    """Summed BCE + KL ELBO (variational autoencoder.ipynb cells 6, 8)."""
    recon, mu, logvar = model.apply(
        {"params": params},
        batch["x"],
        deterministic=not train,
        rngs={"sample": rng} if train else None,
    )
    total, bce, kl = ops.vae_loss(recon, batch["x"], mu, logvar)
    # reference reports the batch-summed loss; optimize the per-sample mean
    # so LR settings are batch-size independent
    n = batch["x"].shape[0]
    return total / n, {"bce": bce / n, "kl": kl / n}, model_state


def dsv3_init_fn(model, rngs, batch):
    """Init returning (params, model_state): DeepSeekV3 carries the MoE
    routing bias in the 'moe_state' collection (deepseekv3 cell 23 buffer).
    Initializes through the MTP branch when enabled so its params exist."""
    variables = model.init(rngs, batch["x"], return_mtp=model.cfg.mtp_heads > 0)
    return variables["params"], {"moe_state": variables["moe_state"]}


@jax.named_scope("L_moe_stats")
def _aggregate_moe_metrics(collection) -> dict:
    """Mean each sown per-layer MoE stat (models/deepseekv3.py MoELayer)
    into one train-metric scalar: moe_load_entropy, moe_load_max_fraction,
    moe_drop_fraction, moe_bias_norm."""
    layer_stats = jax.tree.leaves(
        collection,
        is_leaf=lambda x: isinstance(x, dict) and "load_entropy" in x,
    )
    layer_stats = [s for s in layer_stats if isinstance(s, dict)]
    if not layer_stats:
        return {}
    keys = [k for k in layer_stats[0] if k != "ci"]  # ci is an (E,) vector
    return {
        f"moe_{k}": jnp.mean(jnp.stack([s[k] for s in layer_stats]))
        for k in keys
    }


def dsv3_loss_fn(model, params, batch, rng, model_state, train):
    """DeepSeekV3 objective: next-token CE (+ weighted MTP loss when
    mtp_heads > 0), threading the mutable MoE routing bias through the step
    (the functional form of cell 23's no-grad buffer update + cell 54's loss).
    """
    cfg = model.cfg
    use_mtp = cfg.mtp_heads > 0
    variables = {"params": params, **(model_state or {})}
    kwargs = dict(deterministic=not train, return_mtp=use_mtp)
    moe_metrics = {}
    balance_terms: list = []
    if train:
        (out, _), mutated = model.apply(
            variables,
            batch["x"],
            rngs={"dropout": rng},
            mutable=["moe_state", "moe_metrics"],
            **kwargs,
        )
        new_ms = {"moe_state": mutated["moe_state"]}
        raw_metrics = mutated.get("moe_metrics", {})
        moe_metrics = _aggregate_moe_metrics(raw_metrics)
        if getattr(cfg, "balance_loss_weight", 0.0) > 0.0:
            # sown per layer by MoELayer (differentiable, unlike the stats)
            balance_terms = [
                leaf
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                    raw_metrics
                )[0]
                if any(
                    getattr(k, "key", None) == "balance_loss" for k in path
                )
            ]
    else:
        out, _ = model.apply(variables, batch["x"], **kwargs)
        new_ms = model_state
    if use_mtp:
        logits, mtp_logits = out
    else:
        logits, mtp_logits = out, None

    main = ops.cross_entropy(logits, batch["y"])
    with jax.named_scope("L_loss_head"):
        aux = {"perplexity": jnp.exp(main), **moe_metrics}
    loss = main
    if balance_terms:
        with jax.named_scope("L_moe_stats"):
            bal = jnp.mean(jnp.stack(balance_terms))
        aux["balance_loss"] = bal
        loss = loss + cfg.balance_loss_weight * bal
    if mtp_logits is not None:
        # mtp_loss wants the stream shifted so head j's target is token
        # i+(j+1)+1; y already holds tokens 1..T, pad the unknown tail.
        # Under CP the tail of a shard is the HEAD of the right neighbor:
        # a k-token halo (ppermute) replaces the pad except on the last
        # shard, and the loss psums sum/count over 'context' so the global
        # mean matches the dense computation exactly.
        k = cfg.mtp_heads
        if getattr(cfg, "context_parallel", False):
            from solvingpapers_tpu.sharding import cp_halo_right

            # append (not shift): mtp_loss wants the local T columns PLUS
            # the k halo columns as the target stream
            stream = jnp.concatenate(
                [batch["y"], cp_halo_right(batch["y"], k, fill=-1)], axis=1
            )
            mtp = ops.mtp_loss(mtp_logits, stream, k, ignore_index=-1,
                               axis_names=("context",))
        else:
            pad = jnp.full((batch["y"].shape[0], k), -1, batch["y"].dtype)
            mtp = ops.mtp_loss(
                mtp_logits, jnp.concatenate([batch["y"], pad], axis=1), k,
                ignore_index=-1,
            )
        aux["mtp_loss"] = mtp
        # add to the accumulated loss (main + any balance term), not to
        # `main` — overwriting silently dropped the balance loss whenever
        # MTP was on
        loss = loss + cfg.mtp_loss_weight * mtp
    return loss, aux, new_ms


def _balance_loss(raw, router_experts: int):
    """The held-experts families' load-balancing loss from what
    `HeldExpertsMoE` sows a layer ("balance": `chosen`, `prob`): E * sum_e
    F_e P_e with F the share of tokens that chose an expert and P its mean
    router probability, both over the tokens of all layers together."""
    with jax.named_scope("L_moe_stats"):
        is_balance = lambda x: isinstance(x, dict) and "chosen" in x  # noqa: E731
        layers = [b for b in jax.tree.leaves(raw, is_leaf=is_balance)
                  if is_balance(b)]
        chosen = jnp.mean(jnp.stack([b["chosen"] for b in layers]), axis=0)
        prob = jnp.mean(jnp.stack([b["prob"] for b in layers]), axis=0)
        return router_experts * jnp.sum(chosen * prob)


def qwen3next_loss_fn(model, params, batch, rng, model_state, train):
    """Qwen3-Next objective: next-token cross-entropy plus
    `router_aux_loss_coef` times the family's load-balancing loss
    (`_balance_loss`). The MoE's counters (`moe_drop_fraction`,
    `moe_held_pair_fraction`, the load statistics) ride along as the
    DeepSeekV3 family's do."""
    cfg = model.cfg
    variables = {"params": params}
    if not train:
        logits, _ = model.apply(variables, batch["x"])
        main = ops.cross_entropy(logits, batch["y"])
        return main, {"perplexity": jnp.exp(main)}, model_state
    (logits, _), mutated = model.apply(
        variables, batch["x"], mutable=["moe_metrics"]
    )
    raw = mutated.get("moe_metrics", {})
    main = ops.cross_entropy(logits, batch["y"])
    with jax.named_scope("L_loss_head"):
        aux = {"perplexity": jnp.exp(main), **_aggregate_moe_metrics(raw)}
    balance = _balance_loss(raw, cfg.router_experts)
    aux["balance_loss"] = balance
    return main + cfg.router_aux_loss_coef * balance, aux, model_state


def _chunked_head(model, params, batch, train, collections):
    """The chunked head-with-loss of `chunked_head_loss_fn`: (mean
    cross-entropy, its aux, what the model sowed into `collections`)."""
    variables, mutated = {"params": params}, {}
    if train:
        (hidden, _), mutated = model.apply(
            variables, batch["x"], head=False, mutable=list(collections))
    else:
        hidden, _ = model.apply(variables, batch["x"], head=False)
    # the kernel after the rows, as a tied head's transpose was traced
    main = ops.head_cross_entropy(
        hidden, model.head_kernel(params), batch["y"])
    with jax.named_scope("L_loss_head"):
        aux = {"perplexity": jnp.exp(main),
               **_aggregate_moe_metrics(mutated.get("moe_metrics", {}))}
    return main, aux, mutated


def chunked_head_loss_fn(model, params, batch, rng, model_state, train):
    """Next-token cross-entropy and nothing else (`kimi_linear`,
    `nemotron_h`, `granite_hybrid`: no source's config states a balance
    loss, and the selection bias takes no gradient), head and loss together
    a chunk of rows at a time (`ops.head_cross_entropy`): at 16,384 tokens
    the whole logits and their cotangent, 640 MB each, were the step's
    memory peak. The model hands back its normed rows (`head=False`) and
    names the head's kernel (`head_kernel`: `lm_head`'s, or a tied head's
    embedding transposed, whose gradient is then the float32 sum of the
    lookup's and the chunks'). Expert layers' counters (`moe_drop_fraction`,
    `moe_held_pair_fraction`, the load statistics) ride along."""
    main, aux, _ = _chunked_head(model, params, batch, train,
                                 ("moe_metrics",))
    return main, aux, model_state


def keye_vl_loss_fn(model, params, batch, rng, model_state, train):
    """The selected-attention family's objective: the chunked
    head-with-loss of `chunked_head_loss_fn`, plus `router_aux_loss_coef`
    times the held-experts balance loss (`_balance_loss`, as
    `qwen3next_loss_fn`), plus the lightning indexer's own loss, the mean
    over the layers of the `index_kl` each layer sows (`models/keye_vl.py`;
    the weight 1 / layers is this repo's). Two parameter groups see
    different losses: the indexer's three matrices a layer take their
    gradient from the KL alone (its inputs and its target are detached and
    the selection passes none), every other weight from the other two terms
    alone. Logged beside the MoE's counters: `dsa_index_kl` (nats, a layer)
    `dsa_selected_fraction` (selected pairs over causal pairs; 1.0 when
    nothing is left out) and `dsa_live_tile_fraction` (of the attention's
    backward kernels' causal tiles, those that hold a selected pair: what a
    kernel that skipped the others would still run)."""
    main, aux, mutated = _chunked_head(model, params, batch, train,
                                       ("moe_metrics", "dsa_metrics"))
    if not train:
        return main, aux, model_state
    balance = _balance_loss(mutated["moe_metrics"], model.cfg.router_experts)
    with jax.named_scope("L_dsa_loss"):
        is_stats = lambda x: isinstance(x, dict) and "index_kl" in x  # noqa: E731
        layers = [s for s in jax.tree.leaves(mutated["dsa_metrics"],
                                             is_leaf=is_stats) if is_stats(s)]
        index_kl = jnp.mean(jnp.stack([s["index_kl"] for s in layers]))
        aux.update(
            balance_loss=balance, dsa_index_kl=index_kl,
            **{f"dsa_{name}": jnp.mean(jnp.stack([s[name] for s in layers]))
               for name in ("selected_fraction", "live_tile_fraction")})
    return (main + model.cfg.router_aux_loss_coef * balance + index_kl,
            aux, model_state)


@jax.named_scope("L_exit_gate")
def exit_distribution(gate_logits: jax.Array) -> jax.Array:
    """log p of leaving a looped model after pass t, from the T gate logits
    (T, ...): p_t = lambda_t prod_{j<t} (1 - lambda_j) for t < T and p_T =
    prod_{j<T} (1 - lambda_j), lambda = sigmoid(logit); in logs, so a gate
    far from 0 keeps its gradient. The last pass's gate decides nothing."""
    zero = jnp.zeros_like(gate_logits[:1])
    stayed = jnp.concatenate(
        [zero, jnp.cumsum(jax.nn.log_sigmoid(-gate_logits[:-1]), 0)], 0)
    leave = jnp.concatenate(
        [jax.nn.log_sigmoid(gate_logits[:-1]), zero], 0)
    return stayed + leave


def ouro_loss_fn(model, params, batch, rng, model_state, train):
    """The looped family's objective (Ouro's stage I): with l_t the
    next-token cross-entropy a token of pass t's exit and p_t the exit
    distribution its gates give (`exit_distribution`), mean over tokens of
    sum_t p_t l_t - beta H(p): the expected loss under the learned exit
    distribution, and an entropy term (a KL to the uniform prior over
    exits) that keeps the gates from collapsing onto one pass; beta is the
    config's `exit_entropy_weight`. The T heads-with-loss run as ONE
    chunked pass over the T x B x S rows (`ops.head_nll_rows`): the logits
    are never whole. Logged beside the loss: the mean l_t of every pass
    (`ce_ut<t>`), the mean entropy (`exit_entropy`, nats) and the mean
    exit pass (`exit_mean_step`, 1..T)."""
    (hidden, gate_logits), _ = model.apply(
        {"params": params}, batch["x"], head=False)
    n_ut = hidden.shape[0]
    nll = ops.head_nll_rows(
        hidden, params["lm_head"]["kernel"],
        jnp.broadcast_to(batch["y"], hidden.shape[:-1]))
    log_p = exit_distribution(gate_logits)
    with jax.named_scope("L_exit_gate"):
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, 0)
        loss = jnp.mean(jnp.sum(p * nll, 0)
                        - model.cfg.exit_entropy_weight * entropy)
        steps = jnp.arange(1, n_ut + 1, dtype=jnp.float32)
        aux = {"exit_entropy": jnp.mean(entropy),
               "exit_mean_step": jnp.mean(jnp.tensordot(steps, p, 1))}
    with jax.named_scope("L_loss_head"):
        per_pass = jnp.mean(nll.reshape(n_ut, -1), -1)
        aux.update({f"ce_ut{t + 1}": per_pass[t] for t in range(n_ut)})
    return loss, aux, model_state


def make_kd_loss_fn(teacher_model, teacher_params, temperature=7.0, alpha=0.3):
    """Distillation objective with a frozen teacher (kd.py:48-68, 110-142).

    The teacher forward runs inside the jitted step under stop_gradient —
    the functional equivalent of the reference's `with torch.no_grad()`.
    """

    def kd_loss_fn(model, params, batch, rng, model_state, train):
        teacher_logits = jax.lax.stop_gradient(
            teacher_model.apply(
                {"params": teacher_params}, batch["x"], deterministic=True
            )
        )
        student_logits = model.apply(
            {"params": params},
            batch["x"],
            deterministic=not train,
            rngs={"dropout": rng} if train else None,
        )
        loss = ops.distillation_loss(
            student_logits, teacher_logits, batch["y"], temperature, alpha
        )
        acc = jnp.mean(
            (jnp.argmax(student_logits, -1) == batch["y"]).astype(jnp.float32)
        )
        return loss, {"accuracy": acc}, model_state

    return kd_loss_fn
