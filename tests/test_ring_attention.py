"""Context-parallel attention tests on the virtual 8-device mesh:
ring attention and Ulysses must equal single-device dense attention
(SURVEY.md §4 multichip test plan; capability added beyond the reference).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu import ops
from solvingpapers_tpu.sharding import MeshConfig, create_mesh
from solvingpapers_tpu.sharding.ring_attention import (
    ring_attention,
    ulysses_attention,
)


def make_qkv(key, b, s, n, h, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    return (
        jax.random.normal(kq, (b, s, n, h), dtype),
        jax.random.normal(kk, (b, s, n, h), dtype),
        jax.random.normal(kv, (b, s, n, h), dtype),
    )


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
@pytest.mark.parametrize("ctx", [4, 8])
def test_ring_attention_matches_dense(devices, causal, ctx):
    mesh = create_mesh(
        MeshConfig(data=8 // ctx, fsdp=1, model=1, expert=1, context=ctx), devices
    )
    q, k, v = make_qkv(jax.random.key(0), 2, 64, 2, 16)
    out = ring_attention(q, k, v, mesh, causal=causal)
    ref = ops.dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_attention_grads_flow(devices):
    mesh = create_mesh(MeshConfig(data=2, context=4), devices)
    q, k, v = make_qkv(jax.random.key(1), 2, 32, 2, 8)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(ops.dot_product_attention(q, k, v, causal=True) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
def test_ulysses_matches_dense(devices, causal):
    ctx = 4
    mesh = create_mesh(MeshConfig(data=2, context=ctx), devices)
    # heads must be divisible by the context axis
    q, k, v = make_qkv(jax.random.key(2), 2, 32, 4, 16)
    attn_fn = functools.partial(ops.dot_product_attention, causal=causal)
    out = ulysses_attention(q, k, v, mesh, attn_fn)
    ref = ops.dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ring_long_sequence_streams(devices):
    """8-way context split of a longer sequence (the memory win: each device
    only ever holds S/8 of K/V plus one in-flight chunk)."""
    mesh = create_mesh(MeshConfig(data=1, context=8), devices)
    q, k, v = make_qkv(jax.random.key(3), 1, 512, 2, 16)
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_llama_context_parallel_training_matches_dense(devices):
    """End-to-end CP: a Llama forward+backward with its attention running
    the ppermute ring inside shard_map (sequence sharded over 'context')
    must match the dense single-device model exactly."""
    import dataclasses

    from jax.sharding import PartitionSpec as P

    from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig

    base = LlamaConfig(vocab_size=64, max_seq_len=64, dim=32, n_layers=2,
                       n_heads=4, n_kv_heads=2, dropout=0.0)
    cp_cfg = dataclasses.replace(base, context_parallel=True)
    dense, cp = Llama(base), Llama(cp_cfg)

    mesh = create_mesh(MeshConfig(data=2, context=4), devices)
    toks = jax.random.randint(jax.random.key(0), (2, 64), 0, base.vocab_size)
    targets = jnp.roll(toks, -1, axis=1)
    positions = jnp.broadcast_to(jnp.arange(64), (2, 64))
    params = dense.init({"params": jax.random.key(1)}, toks)["params"]

    tok_spec = P(("data",), "context")

    def local_loss(params, x, pos, y):
        logits, _ = cp.apply({"params": params}, x, positions=pos)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        total = jax.lax.psum(jnp.sum(nll), ("data", "context"))
        count = jax.lax.psum(nll.size, ("data", "context"))
        return total / count

    cp_loss = jax.shard_map(
        local_loss, mesh=mesh,
        in_specs=(P(), tok_spec, tok_spec, tok_spec), out_specs=P(),
    )

    def dense_loss(params):
        logits, _ = dense.apply({"params": params}, toks, positions=positions)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(nll)

    # jitted: op by op, the ring's shard_map took minutes of the suite's time
    l_cp, g_cp = jax.jit(jax.value_and_grad(
        lambda p: cp_loss(p, toks, positions, targets)
    ))(params)
    l_d, g_d = jax.value_and_grad(dense_loss)(params)
    np.testing.assert_allclose(float(l_cp), float(l_d), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_cp), jax.tree.leaves(g_d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_ring_gqa_repeats_inside_ring(devices):
    """K/V enter the ring with n_kv heads (less ppermute traffic) and are
    repeated per step; result equals dense GQA attention."""
    mesh = create_mesh(MeshConfig(data=2, context=4), devices)
    kq, kk, kv = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(kq, (2, 64, 4, 16))
    k = jax.random.normal(kk, (2, 64, 2, 16))
    v = jax.random.normal(kv, (2, 64, 2, 16))
    out = ring_attention(q, k, v, mesh, causal=True)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_cp_llama_forward_matches_dense(devices, impl):
    """CP model forward (positions defaulted -> must derive GLOBAL positions
    from the axis index) == dense, for both context impls, with GQA heads
    (8 q / 4 kv over a 4-way axis exercises the head-split + repeat_kv
    composition)."""
    import dataclasses

    from jax.sharding import PartitionSpec as P

    from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig

    base = LlamaConfig(vocab_size=64, max_seq_len=32, dim=32, n_layers=1,
                       n_heads=8, n_kv_heads=4, dropout=0.0)
    cp = Llama(dataclasses.replace(base, context_parallel=True,
                                   context_impl=impl))
    dense = Llama(base)
    mesh = create_mesh(MeshConfig(data=2, context=4), devices)
    toks = jax.random.randint(jax.random.key(2), (2, 32), 0, 64)
    params = dense.init({"params": jax.random.key(3)}, toks)["params"]
    out = jax.shard_map(
        lambda p, x: cp.apply({"params": p}, x)[0],
        mesh=mesh, in_specs=(P(), P(("data",), "context")),
        out_specs=P(("data",), "context", None),
    )(params, toks)
    ref, _ = dense.apply({"params": params}, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_cp_model_rejects_plain_decode_cache(devices):
    """CP decode is supported as of round 5 — but only through the
    context-sharded CPKVCache; a PLAIN per-shard KVCache would silently
    attend only local slots and must be rejected with a pointer to the
    right API."""
    import dataclasses

    from jax.sharding import PartitionSpec as P

    from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig

    cfg = LlamaConfig(vocab_size=64, max_seq_len=32, dim=16, n_layers=1,
                      n_heads=2, n_kv_heads=2, dropout=0.0,
                      context_parallel=True)
    model = Llama(cfg)
    toks = jnp.zeros((1, 8), jnp.int32)
    mesh = create_mesh(MeshConfig(data=1, context=4), devices[:4])

    def run(p, x):
        caches = model.init_caches(1, 32)  # plain KVCache: wrong under CP
        out, _ = model.apply({"params": p}, x, caches=caches)
        return out

    base = Llama(dataclasses.replace(cfg, context_parallel=False))
    params = base.init({"params": jax.random.key(0)}, toks)["params"]
    with pytest.raises(TypeError, match="CPKVCache"):
        jax.shard_map(run, mesh=mesh,
                      in_specs=(P(), P(("data",), "context")),
                      out_specs=P(("data",), "context", None))(params, toks)


# ---------------------------------------------------------------- ring-flash


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
@pytest.mark.parametrize("ctx", [4, 8])
def test_ring_flash_matches_dense(devices, causal, ctx):
    """Ring attention with the Pallas kernel per chunk (interpret mode on
    the CPU mesh) == single-device dense attention."""
    from solvingpapers_tpu.sharding.ring_attention import ring_flash_attention

    mesh = create_mesh(MeshConfig(data=8 // ctx, context=ctx), devices)
    q, k, v = make_qkv(jax.random.key(7), 2, 128, 2, 16)
    out = ring_flash_attention(q, k, v, mesh, causal=causal)
    ref = ops.dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_gqa_matches_dense(devices):
    from solvingpapers_tpu.sharding.ring_attention import ring_flash_attention

    mesh = create_mesh(MeshConfig(data=2, context=4), devices)
    kq, kk, kv = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(kq, (2, 128, 4, 16))
    k = jax.random.normal(kk, (2, 128, 2, 16))
    v = jax.random.normal(kv, (2, 128, 2, 16))
    out = ring_flash_attention(q, k, v, mesh, causal=True)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_grads_match_dense(devices):
    """The custom-VJP ring backward (per-chunk _bwd_chunk sweeps with the
    global lse, dk/dv traveling the ring) == dense gradients, GQA shapes."""
    from solvingpapers_tpu.sharding.ring_attention import ring_flash_attention

    mesh = create_mesh(MeshConfig(data=2, context=4), devices)
    kq, kk, kv = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(kq, (2, 64, 4, 16))
    k = jax.random.normal(kk, (2, 64, 2, 16))
    v = jax.random.normal(kv, (2, 64, 2, 16))

    def loss_ring(q, k, v):
        return jnp.sum(ring_flash_attention(q, k, v, mesh, causal=True) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(ops.dot_product_attention(q, k, v, causal=True) ** 2)

    gr = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_cp_llama_ring_flash_forward_matches_dense(devices):
    """use_flash + context_parallel ring through the model layer == dense."""
    import dataclasses

    from jax.sharding import PartitionSpec as P

    from solvingpapers_tpu.models.llama3 import Llama, LlamaConfig

    base = LlamaConfig(vocab_size=64, max_seq_len=128, dim=32, n_layers=1,
                       n_heads=4, n_kv_heads=2, dropout=0.0)
    cp = Llama(dataclasses.replace(base, context_parallel=True,
                                   context_impl="ring", use_flash=True))
    dense = Llama(base)
    mesh = create_mesh(MeshConfig(data=2, context=4), devices)
    toks = jax.random.randint(jax.random.key(10), (2, 128), 0, 64)
    params = dense.init({"params": jax.random.key(11)}, toks)["params"]
    out = jax.jit(jax.shard_map(
        lambda p, x: cp.apply({"params": p}, x)[0],
        mesh=mesh, in_specs=(P(), P(("data",), "context")),
        out_specs=P(("data",), "context", None),
        check_vma=False,  # pallas-in-scan vs the jax-0.9 vma checker
    ))(params, toks)
    ref, _ = dense.apply({"params": params}, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
