"""DeepSeekV3 tests (SURVEY.md §4 plan): MoE routing mass, aux-free bias
sign updates, dispatch-vs-dense equality, shared-expert passthrough, MLA
cached-decode equivalence, MTP shapes/loss, loss-goes-down smoke, and
expert-parallel sharded equality on the virtual 8-device mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu import ops
from solvingpapers_tpu.data import load_char_corpus
from solvingpapers_tpu.data.batches import lm_batch_iterator
from solvingpapers_tpu.infer import generate
from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.train import OptimizerConfig, TrainConfig, Trainer
from solvingpapers_tpu.train.objectives import dsv3_init_fn, dsv3_loss_fn

TINY = DeepSeekV3Config(
    vocab_size=64, block_size=32, dim=32, n_layers=2, n_heads=4, latent_dim=8,
    n_experts=4, top_experts=2, dropout=0.0, attn_dropout=0.0,
)


def init_model(cfg=TINY, seed=0, seq=16, batch=2):
    model = DeepSeekV3(cfg)
    toks = jnp.zeros((batch, seq), jnp.int32)
    variables = model.init(
        {"params": jax.random.key(seed)}, toks, return_mtp=cfg.mtp_heads > 0
    )
    return model, variables


# ------------------------------------------------------------------- routing


def test_topk_gate_probs_mass_and_support():
    logits = jax.random.normal(jax.random.key(0), (64, 8))
    probs = ops.moe.topk_gate_probs(logits, 2)
    np.testing.assert_allclose(np.asarray(probs.sum(-1)), 1.0, rtol=1e-6)
    assert int((probs > 0).sum(-1).max()) == 2
    assert int((probs > 0).sum(-1).min()) == 2


def test_aux_free_bias_update_signs():
    # expert 0 overloaded, expert 3 starved -> bias moves down for 0, up for 3
    probs = jnp.array([[1.0, 0.0, 0.0, 0.0]] * 30 + [[0.0, 0.5, 0.5, 0.0]] * 10)
    bias = jnp.zeros(4)
    new = ops.moe.aux_free_bias_update(probs, bias, rate=0.001)
    assert float(new[0]) < 0 and float(new[3]) > 0


def test_dispatch_equals_dense_when_capacity_ample():
    d, h, e, t = 16, 24, 4, 64
    key = jax.random.key(1)
    x = jax.random.normal(key, (t, d))
    w1 = jax.random.normal(jax.random.key(2), (e, d, h)) * 0.1
    w2 = jax.random.normal(jax.random.key(3), (e, d, h)) * 0.1
    w3 = jax.random.normal(jax.random.key(4), (e, h, d)) * 0.1
    probs = ops.moe.topk_gate_probs(jax.random.normal(jax.random.key(5), (t, e)), 2)

    def f(xe):
        a = jnp.einsum("ecd,edh->ech", xe, w1)
        g = jnp.einsum("ecd,edh->ech", xe, w2)
        return jnp.einsum("ech,ehd->ecd", ops.swish(a) * g, w3)

    def f_all(xt):
        a = jnp.einsum("td,edh->eth", xt, w1)
        g = jnp.einsum("td,edh->eth", xt, w2)
        return jnp.einsum("eth,ehd->etd", ops.swish(a) * g, w3)

    out_dispatch = ops.moe.moe_dispatch_combine(x, probs, f, capacity=t)
    out_dense = ops.moe.moe_dense_combine(x, probs, f_all)
    np.testing.assert_allclose(
        np.asarray(out_dispatch), np.asarray(out_dense), rtol=1e-5, atol=1e-5
    )


def one_hot_dispatch_combine(x, probs, expert_fn, capacity):
    """The plain reference of `moe_dispatch_combine`: rows moved by products
    with the (T, E, C) one-hot of the kept (token, expert) pairs, float32."""
    _, pos, keep = ops.moe._dispatch_slots(probs, capacity)
    dispatch = jax.nn.one_hot(
        jnp.where(keep, pos, capacity), capacity, dtype=jnp.float32
    )
    xe = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    ye = expert_fn(xe.astype(x.dtype)).astype(jnp.float32)
    out = jnp.einsum("tec,ecd->td", dispatch * probs[..., None], ye)
    return out.astype(x.dtype)


# name -> (tokens, capacity factor, a row of tied logits, dtype, tolerance)
DISPATCH_CASES = {
    "capacity_ample": (256, 4.0, False, jnp.float32, 1e-5),
    "capacity_binding": (256, 0.5, False, jnp.float32, 1e-5),
    "tied_row": (256, 2.0, True, jnp.float32, 1e-5),
    "decode_size": (8, 1.0, False, jnp.float32, 1e-5),
    "more_slots_than_tokens": (4, 1.0, False, jnp.float32, 1e-5),
    "bf16": (256, 2.0, False, jnp.bfloat16, 3e-2),
}


@pytest.mark.parametrize("case", sorted(DISPATCH_CASES))
def test_dispatch_by_index_matches_one_hot_reference(case):
    t, factor, tied, dtype, tol = DISPATCH_CASES[case]
    e, k, d, h = 8, 2, 16, 24
    keys = jax.random.split(jax.random.key(11), 4)
    x = jax.random.normal(keys[0], (t, d), dtype)
    logits = jax.random.normal(keys[1], (t, e))
    if tied:  # `probs > 0` selects all eight experts of this token
        logits = logits.at[3].set(0.25)
    w1 = (jax.random.normal(keys[2], (e, d, h)) * 0.3).astype(dtype)
    w3 = (jax.random.normal(keys[3], (e, h, d)) * 0.3).astype(dtype)
    cap = ops.moe.expert_capacity(t, e, k, factor)
    probs = ops.moe.topk_gate_probs(logits, k)
    kept = np.asarray(ops.moe._dispatch_slots(probs, cap)[2])
    if case == "capacity_binding":
        assert ((probs > 0).sum(0) > cap).sum() >= 3  # drops in several experts
    if tied:
        assert int((probs[3] > 0).sum()) == e
    if case in ("decode_size", "more_slots_than_tokens"):
        assert cap == 8

    def loss(impl):
        def f(x, logits, w1, w3):
            def expert_fn(xe):
                hid = jnp.tanh(jnp.einsum("ecd,edh->ech", xe, w1))
                return jnp.einsum("ech,ehd->ecd", hid, w3)

            out = impl(x, ops.moe.topk_gate_probs(logits, k), expert_fn, cap)
            mix = jnp.cos(jnp.arange(t * d, dtype=jnp.float32)).reshape(t, d)
            return jnp.sum(out.astype(jnp.float32) * mix), out

        return jax.value_and_grad(f, argnums=(0, 1, 2, 3), has_aux=True)

    (_, out), grads = loss(ops.moe.moe_dispatch_combine)(x, logits, w1, w3)
    (_, want), want_grads = loss(one_hot_dispatch_combine)(x, logits, w1, w3)
    assert out.dtype == dtype
    for got, ref in zip((out, *grads), (want, *want_grads)):
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        assert np.abs(ref).max() > 1e-3  # nothing compared is all zeros
        np.testing.assert_allclose(got, ref, rtol=tol, atol=tol * np.abs(ref).max())
    # a token no expert kept comes back as zeros, as through the one-hot
    lost = ~kept.any(axis=1)
    assert lost.any() == (case == "capacity_binding")
    assert not np.asarray(out, np.float32)[lost].any()


def test_moe_dense_and_dispatch_model_agree():
    import dataclasses

    cfg_disp = dataclasses.replace(TINY, moe_impl="dispatch", capacity_factor=8.0)
    cfg_dense = dataclasses.replace(TINY, moe_impl="dense")
    model_d, variables = init_model(cfg_disp)
    model_e = DeepSeekV3(cfg_dense)
    toks = jax.random.randint(jax.random.key(7), (2, 16), 0, TINY.vocab_size)
    out_d, _ = model_d.apply(variables, toks)
    out_e, _ = model_e.apply(variables, toks)  # same params, different routing impl
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_e), rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- model


def test_forward_shape_and_weight_tying():
    model, variables = init_model()
    toks = jnp.zeros((2, 16), jnp.int32)
    logits, caches = model.apply(variables, toks)
    assert logits.shape == (2, 16, TINY.vocab_size)
    assert caches is None
    assert "lm_head" not in variables["params"]  # tied to tok_emb
    assert "routing_bias" in variables["moe_state"]["layer_0"]["moe"]


@pytest.mark.parametrize("rope_dim", [0, 8], ids=["norope", "rope"])
def test_cached_decode_equals_full_forward(rope_dim):
    import dataclasses as dc

    model, variables = init_model(cfg=dc.replace(TINY, rope_dim=rope_dim))
    rng = jax.random.key(1)
    prompt = jax.random.randint(rng, (2, 5), 0, TINY.vocab_size)
    params = variables["params"]
    moe_state = {"moe_state": variables["moe_state"]}

    out = generate(model, params, prompt, rng, max_new_tokens=8,
                   extra_variables=moe_state)
    # one compiled forward a prefix length, not one program an operation
    forward = jax.jit(
        lambda toks: model.apply({"params": params, **moe_state}, toks))
    toks = prompt
    for _ in range(8):
        logits, _ = forward(toks)
        toks = jnp.concatenate([toks, jnp.argmax(logits[:, -1], -1)[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))


def test_flash_mla_matches_dense_mla():
    """use_flash MLA (absorbed-query attention == MQA over the latent
    stream, served by the Pallas kernel) must match the dense einsum path —
    same params, values, and grads."""
    import dataclasses

    model_d, variables = init_model()
    cfg_f = dataclasses.replace(TINY, use_flash=True)
    model_f = DeepSeekV3(cfg_f)
    toks = jax.random.randint(jax.random.key(3), (2, 16), 0, TINY.vocab_size)

    out_d, _ = model_d.apply(variables, toks)
    out_f, _ = model_f.apply(variables, toks)  # same param structure
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_d), rtol=2e-4, atol=2e-4
    )

    def loss(m):
        def f(p):
            logits, _ = m.apply({**variables, "params": p}, toks)
            return ops.cross_entropy(logits, toks)
        return jax.grad(f)(variables["params"])

    gd, gf = loss(model_d), loss(model_f)
    flat_d = jax.tree_util.tree_flatten_with_path(gd)[0]
    flat_f = jax.tree_util.tree_flatten_with_path(gf)[0]
    assert [str(p) for p, _ in flat_d] == [str(p) for p, _ in flat_f]
    for (pa, a), (_, bv) in zip(flat_d, flat_f):
        np.testing.assert_allclose(
            np.asarray(bv), np.asarray(a), rtol=5e-3, atol=5e-4,
            err_msg=str(pa),
        )


def test_mtp_shapes_and_loss():
    import dataclasses

    cfg = dataclasses.replace(TINY, mtp_heads=2)
    model, variables = init_model(cfg)
    toks = jax.random.randint(jax.random.key(2), (2, 16), 0, cfg.vocab_size)
    (logits, mtp_logits), _ = model.apply(variables, toks, return_mtp=True)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert mtp_logits.shape == (2, 16, 2, cfg.vocab_size)

    batch = {"x": toks, "y": jnp.roll(toks, -1, axis=1)}
    loss, aux, ms = dsv3_loss_fn(
        model, variables["params"], batch, jax.random.key(3),
        {"moe_state": variables["moe_state"]}, True,
    )
    assert jnp.isfinite(loss)
    assert "mtp_loss" in aux and jnp.isfinite(aux["mtp_loss"])


# ------------------------------------------------------------------ training


def _train(mesh_cfg=None, devices=None, steps=30, cfg=TINY, seed=0):
    mesh = create_mesh(
        mesh_cfg or MeshConfig(data=1, fsdp=1, model=1),
        devices if devices is not None else jax.devices()[:1],
    )
    _, train_toks, _ = load_char_corpus(synthetic_chars=20_000)
    tcfg = TrainConfig(
        steps=steps, batch_size=8, log_every=10_000, eval_every=0,
        optimizer=OptimizerConfig(max_lr=3e-3, warmup_steps=5, total_steps=steps),
    )
    trainer = Trainer(DeepSeekV3(cfg), tcfg, loss_fn=dsv3_loss_fn,
                      init_fn=dsv3_init_fn, mesh=mesh)
    from solvingpapers_tpu.sharding import batch_sharding

    it = lm_batch_iterator(train_toks, 8, cfg.block_size, seed=seed,
                           sharding=batch_sharding(mesh))
    b0 = next(it)
    state = trainer.init_state(b0)
    trainer._build_steps()
    losses = []
    state, m = trainer._train_step(state, b0)
    losses.append(float(m["train_loss"]))
    for _ in range(steps):
        state, m = trainer._train_step(state, next(it))
        losses.append(float(m["train_loss"]))
    return losses, state


def test_loss_decreases_and_bias_updates():
    losses, state = _train(steps=30)
    assert losses[-1] < losses[0] - 0.3, losses[:3] + losses[-3:]
    bias = jax.device_get(
        state.model_state["moe_state"]["layer_0"]["moe"]["routing_bias"]
    )
    assert np.any(bias != 0.0), "aux-free routing bias never updated"


@pytest.mark.parametrize(
    "mesh_cfg",
    [
        MeshConfig(data=2, fsdp=1, model=1, expert=4),
        MeshConfig(data=2, fsdp=2, model=2, expert=1),
    ],
    ids=["ep4_dp2", "dp2_fsdp2_tp2"],
)
def test_sharded_train_matches_single_device(mesh_cfg, devices):
    single, _ = _train(steps=2, seed=11)
    sharded, _ = _train(mesh_cfg, devices, steps=2, seed=11)
    np.testing.assert_allclose(sharded[:3], single[:3], rtol=5e-4, atol=5e-5)


# -------------------------------------------------------- MoE observability


def test_moe_overload_reports_drops_and_bias_reacts():
    """Feeding identical tokens collapses routing onto one top-k expert set:
    the sown metrics must report drops > 0 at finite capacity and the
    aux-free bias must push the hot experts down within the same step
    (VERDICT r1 item 4 / SURVEY.md hard part #1)."""
    from solvingpapers_tpu.models.deepseekv3 import MoELayer

    cfg = DeepSeekV3Config(
        vocab_size=64, block_size=64, dim=16, n_layers=1, n_heads=2,
        latent_dim=8, n_experts=8, top_experts=2, dropout=0.0,
        attn_dropout=0.0, capacity_factor=1.0,
    )
    layer = MoELayer(cfg)
    x = jnp.broadcast_to(
        jax.random.normal(jax.random.key(0), (1, 1, 16)), (1, 64, 16)
    )
    variables = layer.init({"params": jax.random.key(1)}, x)
    (_, mutated) = layer.apply(
        {"params": variables["params"], "moe_state": variables["moe_state"]},
        x, deterministic=False,
        mutable=["moe_state", "moe_metrics"],
        rngs={"dropout": jax.random.key(2)},
    )
    stats = jax.tree.leaves(
        mutated["moe_metrics"],
        is_leaf=lambda v: isinstance(v, dict) and "load_entropy" in v,
    )[0]
    # 64 identical tokens x top-2 -> 2 experts get 64 each; cap = 16
    assert float(stats["drop_fraction"]) > 0.5
    assert float(stats["load_max_fraction"]) > 0.4
    assert float(stats["load_entropy"]) < 0.5
    # bias_norm is sown AFTER the in-step update: it must have moved
    assert float(stats["bias_norm"]) > 0.0
    bias = np.asarray(mutated["moe_state"]["routing_bias"])
    assert (bias < 0).sum() == 2 and (bias > 0).sum() == 6, bias


def test_moe_metrics_flow_through_train_step():
    """The Trainer's train metrics must carry the aggregated moe_* fields."""
    cfg = TINY
    model = DeepSeekV3(cfg)
    tcfg = TrainConfig(
        steps=1, batch_size=4, log_every=1, eval_every=0,
        optimizer=OptimizerConfig(max_lr=1e-3, total_steps=4),
    )
    trainer = Trainer(model, tcfg, loss_fn=dsv3_loss_fn, init_fn=dsv3_init_fn,
                      mesh=create_mesh(MeshConfig(data=1), jax.devices()[:1]))
    text_toks = np.arange(2048) % cfg.vocab_size
    it = lm_batch_iterator(text_toks, 4, cfg.block_size)
    batch = next(it)
    st = trainer.init_state(batch)
    trainer._build_steps()
    _, m = trainer._train_step(st, batch)
    m = jax.device_get(m)
    for k in ("train_moe_load_entropy", "train_moe_load_max_fraction",
              "train_moe_drop_fraction", "train_moe_bias_norm"):
        assert k in m, sorted(m)
        assert np.isfinite(m[k])
    assert 0.0 <= m["train_moe_drop_fraction"] <= 1.0
    assert 0.0 <= m["train_moe_load_entropy"] <= 1.0 + 1e-6


# ------------------------------------------------------- context parallelism


@pytest.mark.parametrize(
    "use_flash,rope_dim",
    [(False, 0), (True, 0), (False, 8), (True, 8)],
    ids=["jnp", "flash", "jnp_rope", "flash_rope"],
)
def test_dsv3_cp_train_step_matches_dense(devices, use_flash, rope_dim):
    """The flagship under CP: MLA rings over the LATENT stream (k = v =
    latents, one shared kv head) inside the stock CP Trainer; the MoE
    routing-bias update is psum'd so state stays shard-invariant. One step
    must equal the dense single-device step — params AND moe_state.
    (Parity is exact in the drop-free regime; once capacity binds, CP
    decides drops per shard — standard distributed-MoE semantics.)"""
    import dataclasses as dc

    cfg = dc.replace(
        TINY, block_size=32, dropout=0.0, attn_dropout=0.0,
        rope_dim=rope_dim,  # decoupled-RoPE k rides the latent ring (cat)
    )
    batch_x = jax.random.randint(jax.random.key(0), (4, 32), 0, cfg.vocab_size)
    batch = {"x": batch_x, "y": jnp.roll(batch_x, -1, axis=1)}
    tcfg = TrainConfig(
        steps=1, batch_size=4, log_every=1, eval_every=0,
        optimizer=OptimizerConfig(name="sgd", max_lr=1e-1, warmup_steps=0,
                                  total_steps=4, grad_clip=1.0),
    )

    dense = Trainer(DeepSeekV3(cfg), tcfg, loss_fn=dsv3_loss_fn,
                    init_fn=dsv3_init_fn,
                    mesh=create_mesh(MeshConfig(data=1), jax.devices()[:1]))
    d_state = dense.init_state(batch)
    dense._build_steps()
    d_state, d_metrics = dense._train_step(d_state, batch)

    cp_cfg = dc.replace(cfg, context_parallel=True, use_flash=use_flash)
    cp_tcfg = dc.replace(tcfg, context_parallel=True,
                         mesh=MeshConfig(data=2, context=4))
    cp = Trainer(DeepSeekV3(cp_cfg), cp_tcfg, loss_fn=dsv3_loss_fn,
                 init_fn=dsv3_init_fn,
                 mesh=create_mesh(MeshConfig(data=2, context=4), devices))
    c_state = cp.init_state(batch)
    cp._build_steps()
    c_state, c_metrics = cp._train_step(c_state, batch)

    np.testing.assert_allclose(
        float(jax.device_get(c_metrics["train_loss"])),
        float(jax.device_get(d_metrics["train_loss"])), rtol=2e-5,
    )
    # the aux-free routing bias must update identically (shard-invariant)
    for a, b in zip(jax.tree.leaves(jax.device_get(c_state.model_state)),
                    jax.tree.leaves(jax.device_get(d_state.model_state))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(jax.device_get(c_state.params)),
                    jax.tree.leaves(jax.device_get(d_state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)
    # moe observability flows under CP too
    assert "train_moe_load_entropy" in c_metrics


def test_balance_loss_composes_with_mtp():
    """The total must carry BOTH auxiliary terms: loss = main +
    w_bal*balance + w_mtp*mtp (a loss = main + w_mtp*mtp overwrite
    silently dropped the balance term whenever MTP was on)."""
    import dataclasses as dc

    cfg = dc.replace(TINY, mtp_heads=1, balance_loss_weight=0.01,
                     dropout=0.0, attn_dropout=0.0)
    model = DeepSeekV3(cfg)
    toks = jax.random.randint(jax.random.key(0), (2, 16), 0, cfg.vocab_size)
    batch = {"x": toks, "y": jnp.roll(toks, -1, axis=1)}
    params, ms = dsv3_init_fn(model, {"params": jax.random.key(1)}, batch)
    loss, aux, _ = dsv3_loss_fn(model, params, batch, jax.random.key(2),
                                ms, True)
    main = jnp.log(aux["perplexity"])
    expect = (main + 0.01 * aux["balance_loss"]
              + cfg.mtp_loss_weight * aux["mtp_loss"])
    np.testing.assert_allclose(float(loss), float(expect), rtol=1e-6)


def test_dsv3_cp_mtp_train_step_matches_dense(devices):
    """MTP under context parallelism (VERDICT r3 missing #3): the i+k
    target shift crosses shard boundaries, resolved by a k-token ppermute
    halo from the right neighbor (sharding.cp_halo_right) for both the
    shifted-embedding stream and the loss targets, with the MTP loss
    psum'ing sum/count over 'context' so the global mean is exact. One CP
    step with mtp_heads=2 must equal the dense single-device step —
    dsv3_mtp and dsv3_long_cp are no longer mutually exclusive."""
    import dataclasses as dc

    cfg = dc.replace(TINY, block_size=32, dropout=0.0, attn_dropout=0.0,
                     mtp_heads=2)
    batch_x = jax.random.randint(jax.random.key(4), (4, 32), 0, cfg.vocab_size)
    batch = {"x": batch_x, "y": jnp.roll(batch_x, -1, axis=1)}
    tcfg = TrainConfig(
        steps=1, batch_size=4, log_every=1, eval_every=0,
        optimizer=OptimizerConfig(name="sgd", max_lr=1e-1, warmup_steps=0,
                                  total_steps=4, grad_clip=1.0),
    )

    dense = Trainer(DeepSeekV3(cfg), tcfg, loss_fn=dsv3_loss_fn,
                    init_fn=dsv3_init_fn,
                    mesh=create_mesh(MeshConfig(data=1), jax.devices()[:1]))
    d_state = dense.init_state(batch)
    dense._build_steps()
    d_state, d_metrics = dense._train_step(d_state, batch)

    cp_cfg = dc.replace(cfg, context_parallel=True)
    cp_tcfg = dc.replace(tcfg, context_parallel=True,
                         mesh=MeshConfig(data=2, context=4))
    cp = Trainer(DeepSeekV3(cp_cfg), cp_tcfg, loss_fn=dsv3_loss_fn,
                 init_fn=dsv3_init_fn,
                 mesh=create_mesh(MeshConfig(data=2, context=4), devices))
    c_state = cp.init_state(batch)
    cp._build_steps()
    c_state, c_metrics = cp._train_step(c_state, batch)

    np.testing.assert_allclose(
        float(jax.device_get(c_metrics["train_mtp_loss"])),
        float(jax.device_get(d_metrics["train_mtp_loss"])), rtol=2e-5,
    )
    np.testing.assert_allclose(
        float(jax.device_get(c_metrics["train_loss"])),
        float(jax.device_get(d_metrics["train_loss"])), rtol=2e-5,
    )
    for a, b in zip(jax.tree.leaves(jax.device_get(c_state.params)),
                    jax.tree.leaves(jax.device_get(d_state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_moe_expert_sliced_combine_matches_unsharded(devices):
    """The shard_map EP compute pattern: expert weights sliced over the
    'expert' axis, each member dispatching its local columns, partial
    combines psum'd — must equal the unsharded dispatch."""
    from jax.sharding import PartitionSpec as P

    d, h, e, t = 16, 24, 4, 64
    mesh = create_mesh(MeshConfig(data=1, expert=4), devices[:4])
    x = jax.random.normal(jax.random.key(0), (t, d))
    w1 = jax.random.normal(jax.random.key(1), (e, d, h)) * 0.1
    w2 = jax.random.normal(jax.random.key(2), (e, d, h)) * 0.1
    w3 = jax.random.normal(jax.random.key(3), (e, h, d)) * 0.1
    probs = ops.moe.topk_gate_probs(
        jax.random.normal(jax.random.key(4), (t, e)), 2)

    def fn(w1, w2, w3):
        def f(xe):
            a = jnp.einsum("ecd,edh->ech", xe, w1)
            g = jnp.einsum("ecd,edh->ech", xe, w2)
            return jnp.einsum("ech,ehd->ecd", ops.swish(a) * g, w3)
        return f

    ref = ops.moe.moe_dispatch_combine(x, probs, fn(w1, w2, w3), capacity=t)

    def local(x, probs, w1, w2, w3):
        # w* arrive as this member's (1, ...) expert slice, so the op's
        # `start` index is unused here (weights are already local)
        return ops.moe.moe_expert_sliced_combine(
            x, probs, lambda xe, start: fn(w1, w2, w3)(xe), capacity=t)

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(), P("expert"), P("expert"), P("expert")),
        out_specs=P(),
    )(x, probs, w1, w2, w3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_cp_decode_matches_dense_generate(devices):
    """The inference half of the CP story: generate_cp over a context=4
    mesh (context-sharded CPLatentCache, ring prefill, distributed-softmax
    decode steps) must emit token-for-token the dense single-device
    generate's greedy output."""
    import dataclasses as dc

    from solvingpapers_tpu.infer import generate_cp

    cfg = dc.replace(TINY, block_size=64, rope_dim=8, pe_scale=0.02)
    model, variables = init_model(cfg, seq=16, batch=2)
    params = variables["params"]
    extra = {"moe_state": variables["moe_state"]}
    prompt = jax.random.randint(jax.random.key(7), (2, 32), 0, cfg.vocab_size)

    ref = generate(model, params, prompt, jax.random.key(1),
                   max_new_tokens=12, extra_variables=extra)

    cp_cfg = dc.replace(cfg, context_parallel=True)
    mesh = create_mesh(MeshConfig(data=1, context=4), devices[:4])
    out = generate_cp(DeepSeekV3(cp_cfg), params, prompt, jax.random.key(1),
                      mesh, max_new_tokens=12, extra_variables=extra)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_cp_decode_32k_prompt(devices):
    """Long-context generation beyond one chip's worth of cache: a
    32k-token prompt sharded over the 8-device mesh prefills via the
    latent ring and decodes under CP — the dsv3_long_cp inference path at
    reduced width (full width runs on real chips; this pins that the
    sharded-cache machinery executes at ≥32k length)."""
    from solvingpapers_tpu.infer import generate_cp

    s0, new = 32768, 4
    cfg = DeepSeekV3Config(
        vocab_size=256, block_size=s0 + 16, dim=64, n_layers=1, n_heads=2,
        latent_dim=16, rope_dim=8, pe_scale=0.02, n_experts=4, top_experts=2,
        capacity_factor=1.0, dropout=0.0, attn_dropout=0.0,
        context_parallel=True,
    )
    # init params via a short dense twin (params are seq-length independent)
    import dataclasses as dc

    dense = DeepSeekV3(dc.replace(cfg, context_parallel=False))
    variables = dense.init(
        {"params": jax.random.key(0)}, jnp.zeros((1, 16), jnp.int32)
    )
    prompt = jax.random.randint(jax.random.key(3), (1, s0), 0, cfg.vocab_size)
    mesh = create_mesh(MeshConfig(data=1, context=8), devices)
    out = generate_cp(
        DeepSeekV3(cfg), variables["params"], prompt, jax.random.key(1),
        mesh, max_new_tokens=new,
        extra_variables={"moe_state": variables["moe_state"]},
    )
    assert out.shape == (1, s0 + new)
    gen = np.asarray(out[:, s0:])
    assert ((gen >= 0) & (gen < cfg.vocab_size)).all()


@pytest.mark.parametrize("ep", [2, 4])
def test_moe_all_to_all_combine_matches_unsharded(devices, ep):
    """Token-dispatch EP: tokens AND expert weights sharded over 'expert',
    tokens physically moved by two tiled all_to_alls — must equal the
    unsharded dispatch in the drop-free regime (ep2 and ep4)."""
    from jax.sharding import PartitionSpec as P

    d, h, e, t = 16, 24, 8, 64
    mesh = create_mesh(MeshConfig(data=1, expert=ep), devices[:ep])
    x = jax.random.normal(jax.random.key(0), (t, d))
    w1 = jax.random.normal(jax.random.key(1), (e, d, h)) * 0.1
    w2 = jax.random.normal(jax.random.key(2), (e, d, h)) * 0.1
    w3 = jax.random.normal(jax.random.key(3), (e, h, d)) * 0.1
    probs = ops.moe.topk_gate_probs(
        jax.random.normal(jax.random.key(4), (t, e)), 2)

    def fn(w1, w2, w3):
        def f(xe):
            a = jnp.einsum("ecd,edh->ech", xe, w1)
            g = jnp.einsum("ecd,edh->ech", xe, w2)
            return jnp.einsum("ech,ehd->ecd", ops.swish(a) * g, w3)
        return f

    ref = ops.moe.moe_dispatch_combine(x, probs, fn(w1, w2, w3), capacity=t)

    def local(x, probs, w1, w2, w3):
        # w* arrive as this member's local expert slice -> start unused
        return ops.moe.moe_all_to_all_combine(
            x, probs, lambda xe, start: fn(w1, w2, w3)(xe),
            capacity=x.shape[0])

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P("expert"), P("expert"), P("expert"), P("expert"),
                  P("expert")),
        out_specs=P("expert"),
    )(x, probs, w1, w2, w3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_dsv3_cp_ep_all_to_all_train_step_matches_dense(devices):
    """ep_impl='all_to_all' under the CP shard_map (data=2 x context=2 x
    expert=2): one train step — loss, moe_state, params — must equal the
    dense single-device step, same bar as the sliced path's test."""
    import dataclasses as dc

    cfg = dc.replace(TINY, block_size=32, dropout=0.0, attn_dropout=0.0)
    batch_x = jax.random.randint(jax.random.key(5), (4, 32), 0, cfg.vocab_size)
    batch = {"x": batch_x, "y": jnp.roll(batch_x, -1, axis=1)}
    tcfg = TrainConfig(
        steps=1, batch_size=4, log_every=1, eval_every=0,
        optimizer=OptimizerConfig(name="sgd", max_lr=1e-1, warmup_steps=0,
                                  total_steps=4, grad_clip=1.0),
    )

    dense = Trainer(DeepSeekV3(cfg), tcfg, loss_fn=dsv3_loss_fn,
                    init_fn=dsv3_init_fn,
                    mesh=create_mesh(MeshConfig(data=1), jax.devices()[:1]))
    d_state = dense.init_state(batch)
    dense._build_steps()
    d_state, d_metrics = dense._train_step(d_state, batch)

    mesh_cfg = MeshConfig(data=2, context=2, expert=2)
    cp_cfg = dc.replace(cfg, context_parallel=True, ep_impl="all_to_all")
    cp_tcfg = dc.replace(tcfg, context_parallel=True, mesh=mesh_cfg)
    cp = Trainer(DeepSeekV3(cp_cfg), cp_tcfg, loss_fn=dsv3_loss_fn,
                 init_fn=dsv3_init_fn,
                 mesh=create_mesh(mesh_cfg, devices))
    c_state = cp.init_state(batch)
    cp._build_steps()
    c_state, c_metrics = cp._train_step(c_state, batch)

    np.testing.assert_allclose(
        float(jax.device_get(c_metrics["train_loss"])),
        float(jax.device_get(d_metrics["train_loss"])), rtol=2e-5,
    )
    np.testing.assert_allclose(
        float(jax.device_get(c_metrics["train_moe_drop_fraction"])),
        float(jax.device_get(d_metrics["train_moe_drop_fraction"])),
        atol=1e-6,
    )
    for a, b in zip(jax.tree.leaves(jax.device_get(c_state.model_state)),
                    jax.tree.leaves(jax.device_get(d_state.model_state))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(jax.device_get(c_state.params)),
                    jax.tree.leaves(jax.device_get(d_state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_dsv3_cp_ep_train_step_matches_dense(devices):
    """CP composed with an 'expert' mesh axis (data=2 x context=2 x
    expert=2): expert weights are STORED sharded over 'expert' (ZeRO
    layout at rest, gathered in-step), sequence rings over 'context'. One
    step must equal the dense single-device step — params and moe_state."""
    import dataclasses as dc

    cfg = dc.replace(TINY, block_size=32, dropout=0.0, attn_dropout=0.0)
    batch_x = jax.random.randint(jax.random.key(5), (4, 32), 0, cfg.vocab_size)
    batch = {"x": batch_x, "y": jnp.roll(batch_x, -1, axis=1)}
    tcfg = TrainConfig(
        steps=1, batch_size=4, log_every=1, eval_every=0,
        optimizer=OptimizerConfig(name="sgd", max_lr=1e-1, warmup_steps=0,
                                  total_steps=4, grad_clip=1.0),
    )

    dense = Trainer(DeepSeekV3(cfg), tcfg, loss_fn=dsv3_loss_fn,
                    init_fn=dsv3_init_fn,
                    mesh=create_mesh(MeshConfig(data=1), jax.devices()[:1]))
    d_state = dense.init_state(batch)
    dense._build_steps()
    d_state, d_metrics = dense._train_step(d_state, batch)

    mesh_cfg = MeshConfig(data=2, context=2, expert=2)
    cp_cfg = dc.replace(cfg, context_parallel=True)
    cp_tcfg = dc.replace(tcfg, context_parallel=True, mesh=mesh_cfg)
    cp = Trainer(DeepSeekV3(cp_cfg), cp_tcfg, loss_fn=dsv3_loss_fn,
                 init_fn=dsv3_init_fn,
                 mesh=create_mesh(mesh_cfg, devices))
    c_state = cp.init_state(batch)
    # expert weights must be STORED sharded over the expert axis
    w1 = c_state.params["layer_0"]["moe"]["w1"]
    assert "expert" in str(w1.sharding.spec), w1.sharding.spec
    cp._build_steps()
    c_state, c_metrics = cp._train_step(c_state, batch)

    np.testing.assert_allclose(
        float(jax.device_get(c_metrics["train_loss"])),
        float(jax.device_get(d_metrics["train_loss"])), rtol=2e-5,
    )
    for a, b in zip(jax.tree.leaves(jax.device_get(c_state.model_state)),
                    jax.tree.leaves(jax.device_get(d_state.model_state))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)
    for a, b in zip(jax.tree.leaves(jax.device_get(c_state.params)),
                    jax.tree.leaves(jax.device_get(d_state.params))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_cp_ep_uses_sliced_expert_compute(devices, monkeypatch):
    """Under the CP shard_map the MoE layer must go through
    moe_expert_sliced_combine (sharded expert FLOPs), not the replicated
    full-stack dispatch — the equality test above would pass either way."""
    import dataclasses as dc

    from solvingpapers_tpu import ops as sp_ops

    calls = {"sliced": 0}
    real = sp_ops.moe.moe_expert_sliced_combine

    def spy(*args, **kwargs):
        calls["sliced"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(sp_ops.moe, "moe_expert_sliced_combine", spy)

    cfg = dc.replace(TINY, block_size=32, dropout=0.0, attn_dropout=0.0,
                     context_parallel=True)
    batch_x = jax.random.randint(jax.random.key(5), (4, 32), 0, cfg.vocab_size)
    batch = {"x": batch_x, "y": jnp.roll(batch_x, -1, axis=1)}
    mesh_cfg = MeshConfig(data=2, context=2, expert=2)
    tcfg = TrainConfig(
        steps=1, batch_size=4, log_every=1, eval_every=0,
        context_parallel=True, mesh=mesh_cfg,
        optimizer=OptimizerConfig(name="sgd", max_lr=1e-1, warmup_steps=0,
                                  total_steps=4),
    )
    tr = Trainer(DeepSeekV3(cfg), tcfg, loss_fn=dsv3_loss_fn,
                 init_fn=dsv3_init_fn, mesh=create_mesh(mesh_cfg, devices))
    state = tr.init_state(batch)
    tr._build_steps()
    state, metrics = tr._train_step(state, batch)
    assert calls["sliced"] > 0, "CP step did not take the sliced-EP path"
    assert float(jax.device_get(metrics["train_loss"])) > 0


def test_balance_loss_recovers_induced_overload():
    """VERDICT r2: an induced routing overload must recover. Gate kernel
    initialized to send ~every token to expert 0 (load_max ~1); training
    with the sequence-wise balance loss (aux-free bias off, to isolate the
    mechanism) must spread the load back out."""
    import dataclasses as dc

    cfg = dc.replace(TINY, use_aux_free=False, balance_loss_weight=0.2)
    model = DeepSeekV3(cfg)
    toks = jax.random.randint(jax.random.key(0), (8, 16), 0, cfg.vocab_size)
    batch = {"x": toks, "y": jnp.roll(toks, -1, axis=1)}
    variables = model.init({"params": jax.random.key(1)}, toks)
    params = variables["params"]
    # induce collapse: every layer's gate strongly prefers expert 0
    for lname in [k for k in params if k.startswith("layer_")]:
        kern = params[lname]["moe"]["gate"]["kernel"]
        biased = jnp.zeros_like(kern).at[:, 0].set(2.0)
        params[lname]["moe"]["gate"]["kernel"] = biased
    ms = {"moe_state": variables["moe_state"]}

    import optax

    tx = optax.adam(2e-2)
    opt_state = tx.init(params)

    @jax.jit  # 120 steps op by op took minutes of the suite's time
    def step(params, opt_state, key):
        def loss_fn(p):
            loss, aux, _ = dsv3_loss_fn(model, p, batch, key, ms, True)
            return loss, aux
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, aux

    _, aux0, _ = dsv3_loss_fn(model, params, batch, jax.random.key(2), ms, True)
    assert float(aux0["moe_load_max_fraction"]) > 0.9  # overload induced
    for i in range(120):
        params, opt_state, aux = step(params, opt_state, jax.random.key(i))
    # meaningful recovery (full rebalance is asymptotic through the
    # top-k renormalization): max load sheds >= 0.2, entropy rises, the
    # balance objective itself decreases
    assert float(aux["moe_load_max_fraction"]) < float(
        aux0["moe_load_max_fraction"]) - 0.2, aux
    assert float(aux["moe_load_entropy"]) > float(
        aux0["moe_load_entropy"]) + 0.2, aux
    assert float(aux["balance_loss"]) < float(aux0["balance_loss"]) - 0.2
