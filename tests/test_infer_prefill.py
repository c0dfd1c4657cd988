"""Long-context generation tests (SURVEY.md §3.4; deepseekv3 cell 40's
sampling loop is part of the reference flagship).

The prefill path passes a static attend_len so cached attention runs
end-aligned causal over only the written cache slots — these tests pin
(a) chunked prefill == single-shot prefill == full-prefix recompute, and
(b) weights trained under context parallelism export to a plain decode.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu.infer import generate
from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3, DeepSeekV3Config
from solvingpapers_tpu.models.gpt import GPT, GPTConfig
from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig

GPT_TINY = GPTConfig(vocab_size=64, block_size=64, dim=32, n_layers=2,
                     n_heads=2, dropout=0.0)
DSV3_TINY = DeepSeekV3Config(
    vocab_size=64, block_size=64, dim=32, n_layers=2, n_heads=4, latent_dim=8,
    rope_dim=8, n_experts=4, top_experts=2, dropout=0.0, attn_dropout=0.0,
)


def _full_forward_decode(model, variables, prompt, n):
    # one compiled forward a prefix length, not one program an operation
    forward = jax.jit(
        lambda v, toks: model.apply(v, toks, deterministic=True)[0])
    toks = prompt
    for _ in range(n):
        logits = forward(variables, toks)
        toks = jnp.concatenate(
            [toks, jnp.argmax(logits[:, -1], -1)[:, None]], axis=1
        )
    return toks


@pytest.mark.parametrize("chunk", [None, 5, 8], ids=["one-shot", "chunk5", "chunk8"])
def test_gpt_chunked_prefill_matches_full_forward(chunk):
    model = GPT(GPT_TINY)
    rng = jax.random.key(0)
    prompt = jax.random.randint(rng, (2, 17), 0, GPT_TINY.vocab_size)
    params = model.init({"params": rng}, prompt)["params"]
    out = generate(model, params, prompt, rng, max_new_tokens=6,
                   prefill_chunk=chunk)
    ref = _full_forward_decode(model, {"params": params}, prompt, 6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("use_flash", [False, True], ids=["dense", "flash"])
@pytest.mark.parametrize("chunk", [None, 8], ids=["one-shot", "chunk8"])
def test_dsv3_chunked_prefill_matches_full_forward(chunk, use_flash):
    cfg = dc.replace(DSV3_TINY, use_flash=use_flash)
    model = DeepSeekV3(cfg)
    rng = jax.random.key(1)
    prompt = jax.random.randint(rng, (2, 17), 0, cfg.vocab_size)
    variables = model.init({"params": rng}, prompt)
    out = generate(model, variables["params"], prompt, rng, max_new_tokens=6,
                   extra_variables={"moe_state": variables["moe_state"]},
                   prefill_chunk=chunk)
    ref = _full_forward_decode(model, variables, prompt, 6)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_cp_trained_weights_export_to_plain_decode(devices):
    """Weights trained under context parallelism (replicated at rest) decode
    on a non-CP model config: cached decode == full-prefix recompute with
    the SAME trained params — the export path for dsv3_long_cp."""
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.sharding import MeshConfig, batch_sharding, create_mesh
    from solvingpapers_tpu.train import OptimizerConfig, Trainer, TrainConfig
    from solvingpapers_tpu.train.objectives import dsv3_init_fn, dsv3_loss_fn

    cp_cfg = dc.replace(DSV3_TINY, block_size=32, context_parallel=True)
    mesh_cfg = MeshConfig(data=2, context=4)
    mesh = create_mesh(mesh_cfg, devices)
    tcfg = TrainConfig(
        steps=2, batch_size=4, log_every=100, eval_every=0,
        context_parallel=True, mesh=mesh_cfg,
        optimizer=OptimizerConfig(max_lr=1e-3, warmup_steps=0, total_steps=4),
    )
    tr = Trainer(DeepSeekV3(cp_cfg), tcfg, loss_fn=dsv3_loss_fn,
                 init_fn=dsv3_init_fn, mesh=mesh)
    toks = np.arange(4096) % cp_cfg.vocab_size
    it = lm_batch_iterator(toks, 4, cp_cfg.block_size,
                           sharding=batch_sharding(mesh, context=True))
    state = tr.fit(it)

    # export: CP params are replicated at rest -> plain host pytrees
    params = jax.device_get(state.params)
    moe_state = jax.device_get(state.model_state["moe_state"])

    decode_cfg = dc.replace(cp_cfg, context_parallel=False)
    model = DeepSeekV3(decode_cfg)
    prompt = jnp.asarray(np.arange(10)[None, :] % decode_cfg.vocab_size,
                         jnp.int32)
    out = generate(model, params, prompt, jax.random.key(2), max_new_tokens=5,
                   extra_variables={"moe_state": moe_state})
    ref = _full_forward_decode(
        model, {"params": params, "moe_state": moe_state}, prompt, 5
    )
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_generate_autochunks_long_flash_prefill():
    """A use_flash model prefilling a >4096 prompt with no prefill_chunk
    must auto-chunk instead of raising from the kernel's block picker mid-
    trace (advisor r3: only the CLI auto-chunked; direct generate() callers
    hit an avoidable ValueError on e.g. a 4500-token prompt)."""
    cfg = dc.replace(
        GPT_TINY, block_size=4608, n_layers=1, use_flash=True
    )
    model = GPT(cfg)
    prompt = jax.random.randint(jax.random.key(0), (1, 4500), 0,
                                cfg.vocab_size)
    params = model.init({"params": jax.random.key(1)}, prompt[:, :8])["params"]
    out = generate(model, params, prompt, jax.random.key(2),
                   max_new_tokens=2)
    assert out.shape == (1, 4502)
    np.testing.assert_array_equal(np.asarray(out[:, :4500]),
                                  np.asarray(prompt))


def test_batched_generate_per_sequence_eos_pads_with_eos():
    """Per-sequence eos_id early-stop in a batched generate: sequences
    hitting EOS at different steps must pad the rest of their row with
    EOS (done-flag semantics), not keep sampling — and rows that never
    emit EOS must be byte-identical to the eos_id=None stream."""
    model = GPT(GPT_TINY)
    rng = jax.random.key(7)
    params = model.init(
        {"params": rng}, jnp.zeros((1, 8), jnp.int32))["params"]
    n = 12
    eos = prompts = ref_gen = None
    for seed in range(8):
        prompts = jax.random.randint(jax.random.key(seed), (3, 9), 0,
                                     GPT_TINY.vocab_size)
        ref = generate(model, params, prompts, jax.random.key(0),
                       max_new_tokens=n)
        ref_gen = np.asarray(ref[:, 9:])
        # an eos candidate the greedy streams emit at >= 2 DIFFERENT
        # steps, early enough that the padded tail is non-empty
        for cand in range(GPT_TINY.vocab_size):
            firsts = [np.flatnonzero(row == cand) for row in ref_gen]
            hits = [f[0] for f in firsts if f.size]
            if len(set(hits)) >= 2 and all(h < n - 1 for h in hits):
                eos = cand
                break
        if eos is not None:
            break
    assert eos is not None, "no staggered-EOS candidate in 8 seeds"

    out = generate(model, params, prompts, jax.random.key(0),
                   max_new_tokens=n, eos_id=eos)
    np.testing.assert_array_equal(np.asarray(out[:, :9]),
                                  np.asarray(prompts))
    out_gen = np.asarray(out[:, 9:])
    stops = []
    for row_ref, row_out in zip(ref_gen, out_gen):
        first = np.flatnonzero(row_ref == eos)
        if first.size:
            i = int(first[0])
            stops.append(i)
            np.testing.assert_array_equal(row_out[: i + 1], row_ref[: i + 1])
            assert (row_out[i + 1:] == eos).all(), (
                f"row kept sampling past its EOS at step {i}: {row_out}"
            )
        else:
            np.testing.assert_array_equal(row_out, row_ref)
    assert len(set(stops)) >= 2, "rows did not finish at different steps"


def test_llama_prefill_matches_full_forward():
    cfg = LlamaConfig(vocab_size=64, max_seq_len=64, dim=32, n_layers=2,
                      n_heads=4, n_kv_heads=2, dropout=0.0)
    model = Llama(cfg)
    rng = jax.random.key(3)
    prompt = jax.random.randint(rng, (2, 13), 0, cfg.vocab_size)
    params = model.init({"params": rng}, prompt)["params"]
    out = generate(model, params, prompt, rng, max_new_tokens=5,
                   prefill_chunk=4)
    ref = _full_forward_decode(model, {"params": params}, prompt, 5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
