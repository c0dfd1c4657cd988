"""Flash attention kernel vs. the dense jnp reference (interpret mode).

SURVEY.md §4 test plan: every kernel ships with a pure-jnp reference and
interpret-mode equality tests — forward and gradients, causal and
bidirectional, MHA and GQA/MQA head layouts.
"""

import functools
import hashlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import (
    assert_same_bits, checkpoint_names, equations, kernel_calls,
    two_remat_layers)

from solvingpapers_tpu import ops
from solvingpapers_tpu.metrics import hlo_cost
from solvingpapers_tpu.kernels import flash_attention
from solvingpapers_tpu.kernels.flash_attention import (
    FLASH_RESIDUALS, flash_blocks, selected_probs)

# the module (the package re-exports the function under the same name)
flash_module = sys.modules["solvingpapers_tpu.kernels.flash_attention"]

# sub-minute correctness core: `pytest -m fast` is the ~4-minute gate
pytestmark = pytest.mark.fast


def make_qkv(key, b, sq, skv, n, n_kv, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, sq, n, d), dtype)
    k = jax.random.normal(kk, (b, skv, n_kv, d), dtype)
    v = jax.random.normal(kv, (b, skv, n_kv, d), dtype)
    return q, k, v


def selection(key, b, sq, skv, share=0.3):
    """A selection mask (B, Sq, Skv), one for all heads: `share` of the
    pairs at random, and every query's own (end-aligned) key, so that no
    row is empty under the causal mask either."""
    own = jnp.arange(skv)[None, :] == jnp.arange(sq)[:, None] + (skv - sq)
    return (jax.random.uniform(key, (b, sq, skv)) < share) | own


CASES = [
    # (b, sq, skv, n, n_kv, d, causal, with a selection mask)
    pytest.param(2, 128, 128, 4, 4, 64, True, False, id="mha_causal"),
    pytest.param(2, 128, 128, 4, 4, 64, False, False, id="mha_bidir"),
    pytest.param(2, 128, 128, 4, 2, 32, True, False, id="gqa_causal"),
    pytest.param(1, 128, 128, 4, 1, 32, True, False, id="mqa_causal"),
    pytest.param(1, 256, 256, 2, 2, 64, True, False, id="multiblock_causal"),
    pytest.param(1, 64, 256, 2, 2, 32, False, False, id="cross_qkv_lens"),
    # end-aligned causal mask: query i sees kv <= i + (skv - sq)
    pytest.param(1, 64, 256, 2, 2, 32, True, False,
                 id="cross_qkv_lens_causal"),
    pytest.param(2, 128, 192, 4, 2, 32, True, False, id="cross_gqa_causal"),
    # a selection mask beside the causal one (`ops/dsa.py`'s call): 4 heads
    # on 1, a batch row's mask shared by its heads; then a span's geometry,
    # its queries the last 64 of 256 keys
    pytest.param(2, 128, 128, 4, 1, 32, True, True, id="selected_4on1"),
    pytest.param(2, 64, 256, 4, 2, 32, True, True, id="selected_span"),
    pytest.param(1, 128, 128, 2, 2, 32, False, True, id="selected_bidir"),
]


@pytest.mark.parametrize("b,sq,skv,n,n_kv,d,causal,selected", CASES)
def test_forward_matches_dense(b, sq, skv, n, n_kv, d, causal, selected):
    q, k, v = make_qkv(jax.random.key(0), b, sq, skv, n, n_kv, d)
    mask = selection(jax.random.key(9), b, sq, skv) if selected else None
    out = jax.jit(functools.partial(
        flash_attention, causal=causal, interpret=True, block_q=64,
        block_k=64, mask=mask))(q, k, v)
    ref = ops.dot_product_attention(
        q, k, v, None if mask is None else mask[:, None], causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "b,sq,skv,n,n_kv,d,causal,selected",
    [CASES[0], CASES[2], CASES[4], CASES[1], CASES[6], *CASES[8:]],
)
def test_grads_match_dense(b, sq, skv, n, n_kv, d, causal, selected):
    q, k, v = make_qkv(jax.random.key(1), b, sq, skv, n, n_kv, d)
    mask = selection(jax.random.key(9), b, sq, skv) if selected else None

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, interpret=True,
                            block_q=64, block_k=64, mask=mask)
        return jnp.sum(o * jnp.cos(o))

    def loss_dense(q, k, v):
        o = ops.dot_product_attention(
            q, k, v, None if mask is None else mask[:, None], causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=2e-4, atol=2e-4)


def _stripped_jaxpr(fn, *args) -> str:
    """A traced program's text with what differs between two checkouts of
    one program taken out: function addresses and source locations."""
    text = str(jax.make_jaxpr(fn)(*args))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    return re.sub(r"[\w/.-]+\.py:\d+", "", text)


def test_no_mask_traces_to_the_program_it_was_before_there_was_one():
    """`mask=None` is a Python branch: forward and backward of an unmasked
    call (GQA, a value width of its own, end-aligned causal; and the
    bidirectional forward in the resolver's own tiles) trace to the jaxpr
    PR 47's tree traced, operand for operand and equation for equation (its
    text's SHA-256, taken on that tree with this function): no mask
    operand, no `where` for one, the same `in_specs`. The six benchmark
    cells that run these kernels unmasked rest on it. And a mask does
    change the trace (the digest is of something)."""
    q = jnp.zeros((2, 128, 4, 32))
    k = jnp.zeros((2, 256, 2, 32))
    v = jnp.zeros((2, 256, 2, 16))

    def step(mask=None):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True, block_q=64, mask=mask)),
            argnums=(0, 1, 2))

    digest = lambda text: hashlib.sha256(text.encode()).hexdigest()  # noqa: E731
    assert digest(_stripped_jaxpr(step(), q, k, v)) == (
        "10e4038275e214a658ab213a5992df84009e04099c798bbbb82a3ebf9ad69582")
    assert digest(_stripped_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=False, interpret=True), q, k, v)) == (
        "7285f4d67f389c3ad4f2d88ac8a48775d7cf7952fc7ce6462d79b3cdb7de46bb")
    masked = _stripped_jaxpr(step(jnp.ones((2, 128, 256), bool)), q, k, v)
    assert masked != _stripped_jaxpr(step(), q, k, v)
    assert "i8[2,128,256]" in masked
    # the masked kernels go by names of their own, outside the vocabulary
    # that reads a device trace's flash kernels as a layer of theirs: their
    # time is the scope's that calls them (`ops/dsa.py`: `L_dsa_attend`)
    names = set(kernel_grids(step(jnp.ones((2, 128, 256), bool)), q, k, v))
    assert names == {"flash_masked_fwd", "flash_masked_bwd_dq",
                     "flash_masked_bwd_dkv"}
    assert set(kernel_grids(step(), q, k, v)) == set(hlo_cost.KERNEL_SCOPES)


@pytest.mark.parametrize("sq,skv", [(128, 128), (64, 256)],
                         ids=["square", "span"])
def test_a_mask_of_all_ones_is_the_unmasked_call_bit_for_bit(sq, skv):
    q, k, v = make_qkv(jax.random.key(2), 2, sq, skv, 4, 2, 32)
    mix = jax.random.normal(jax.random.key(3), (2, sq, 4, 32))

    def program(mask):
        return jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(
            flash_attention(q, k, v, causal=True, interpret=True, block_q=64,
                            block_k=64, mask=mask) * mix), argnums=(0, 1, 2)))

    assert_same_bits(program(jnp.ones((2, sq, skv), jnp.int8))(q, k, v),
                     program(None)(q, k, v))


def test_a_row_whose_mask_is_empty_comes_out_zero_and_passes_no_gradient():
    """Rows 0..31 select no key at all, and rows 32..63 none in the first
    key tile: the empty rows are 0, not a mean of the values, their
    gradients 0, and every other row is the dense reference's."""
    q, k, v = make_qkv(jax.random.key(4), 1, 128, 128, 2, 1, 32)
    rows = jnp.arange(128)[:, None]
    mask = selection(jax.random.key(5), 1, 128, 128)
    mask = mask & (rows >= 32) & ((rows >= 64) | (jnp.arange(128) >= 32))
    mask = mask.at[:, 32:64, 40].set(True)
    flash = functools.partial(flash_attention, causal=True, interpret=True,
                              block_q=32, block_k=32, mask=mask)
    out = jax.jit(flash)(q, k, v)
    ref = ops.dot_product_attention(q, k, v, mask[:, None], causal=True)
    assert float(jnp.max(jnp.abs(out[:, :32]))) == 0.0
    np.testing.assert_allclose(out[:, 32:], ref[:, 32:], rtol=2e-5, atol=2e-5)
    seen = rows[None, :, :, None] >= 32
    got = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))),
                           (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.where(seen, jnp.sin(
        ops.dot_product_attention(*a, mask[:, None], causal=True)), 0.0)),
        (0, 1, 2)))(q, k, v)
    assert float(jnp.max(jnp.abs(got[0][:, :32]))) == 0.0
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("b,sq,skv,n,n_kv,d,causal,selected", CASES[8:])
def test_heads_mean_probability_matches_the_dense_softmax(
        b, sq, skv, n, n_kv, d, causal, selected):
    """`selected_probs` from the forward kernel's log-sum-exp: the mean over
    the heads of the dense reference's softmax at the pairs the masks let
    through, 0 elsewhere, every row summing to 1; in tiles of its own."""
    q, k, v = make_qkv(jax.random.key(6), b, sq, skv, n, n_kv, d)
    mask = selection(jax.random.key(9), b, sq, skv)
    @jax.jit
    def program(q, k, v):
        _, lse = flash_attention(q, k, v, causal=causal, interpret=True,
                                 block_q=64, block_k=64, mask=mask,
                                 return_lse=True)
        return lse, selected_probs(q, k, lse, mask, causal=causal,
                                   interpret=True, block_q=32, block_k=128)

    lse, got = program(q, k, v)
    assert lse.shape == (b, n, sq) and lse.dtype == jnp.float32
    seen = mask[:, None]
    if causal:
        seen = seen & ops.causal_mask(sq, skv)
    scores = jnp.einsum("bqnh,bknh->bnqk", q, ops.repeat_kv(k, n // n_kv))
    want = jnp.mean(jax.nn.softmax(
        jnp.where(seen, scores * d ** -0.5, -1e30), axis=-1), axis=1)
    np.testing.assert_allclose(got, jnp.where(seen[:, 0], want, 0.0),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(jnp.sum(got, -1), 1.0, rtol=1e-5)


def test_32_on_8_at_width_64_with_a_scale_of_its_own():
    """The Granite-hybrid attention layer's shape: 32 query heads on 8
    key-value heads (query head i reads key-value head i // 4) of width 64,
    half the 128 lanes, and a softmax scaled by 1/64 where the default is
    64^-0.5 = 1/8: forward and the three gradients against the dense
    reference at that scale, and not equal to it at the default."""
    q, k, v = make_qkv(jax.random.key(7), 1, 128, 128, 32, 8, 64)
    mix = jax.random.normal(jax.random.key(8), (1, 128, 32, 64))

    def loss(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, **kw) * mix)

    flash = functools.partial(flash_attention, interpret=True, block_q=64,
                              block_k=64)
    out = flash(q, k, v, causal=True, scale=1 / 64)
    want = ops.dot_product_attention(q, k, v, causal=True, scale=1 / 64)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    assert float(jnp.max(jnp.abs(flash(q, k, v, causal=True) - want))) > 0.1
    # a key-value head serves its four query heads and no other
    moved = flash(q, k, v.at[:, :, 3].add(1.0), causal=True, scale=1 / 64)
    changed = np.asarray(jnp.max(jnp.abs(moved - out), axis=(0, 1, 3)) > 1e-6)
    assert changed.tolist() == [12 <= i < 16 for i in range(32)]
    got = jax.grad(loss(flash, scale=1 / 64), argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(loss(ops.dot_product_attention, scale=1 / 64),
                   argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, ref):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


def test_causal_seq_q_longer_than_seq_k():
    """seq_q > seq_k causal: the end-aligned mask leaves the earliest q rows
    with no visible kv. The kernel emits 0 for those rows (guarded softmax
    denominator) — not NaN — so a caller summing over all rows keeps finite
    values and gradients; visible rows must match dense exactly. Also a
    regression for the DMA-elision clamp, whose unfloored form indexed
    before the kv array here."""
    q, k, v = make_qkv(jax.random.key(6), 1, 192, 64, 2, 2, 32)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=64, block_k=64)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    ref_np, out_np = np.asarray(ref), np.asarray(out)
    # offset = 64 - 192 = -128: q rows < 128 see nothing -> 0 output (the
    # dense path's big-neg fill degenerates to a uniform average there; both
    # are arbitrary for an all-masked row, but 0 is finite and grad-safe).
    assert (out_np[:, :128] == 0.0).all()
    assert np.isfinite(out_np).all()
    np.testing.assert_allclose(out_np[:, 128:], ref_np[:, 128:],
                               rtol=2e-5, atol=2e-5)
    # A sum over ALL rows (empty ones included) must give finite grads, and
    # grads w.r.t. the visible region must match dense.
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, interpret=True, block_q=64, block_k=64) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.sum(jnp.where(
        jnp.arange(192)[None, :, None, None] >= 128,
        ops.dot_product_attention(*a, causal=True), 0.0) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("bq,bk", [(32, 64), (64, 32), (128, 32)])
def test_asymmetric_blocks_match_dense(bq, bk):
    """Non-square (block_q, block_k) exercise the clamped causal index maps
    (dead-step DMA elision) with q/kv block boundaries out of phase."""
    q, k, v = make_qkv(jax.random.key(5), 1, 128, 192, 4, 2, 32)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    gf = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, interpret=True, block_q=bq, block_k=bk) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: jnp.sum(
        ops.dot_product_attention(*a, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-5, atol=5e-5)


def test_odd_seq_falls_back_to_smaller_blocks():
    # 96 = 64 + 32; _pick_block must find a divisor block (32)
    q, k, v = make_qkv(jax.random.key(2), 1, 96, 96, 2, 2, 32)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_bf16_forward_close():
    q, k, v = make_qkv(jax.random.key(3), 1, 128, 128, 2, 2, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2
    )


def test_rejects_bad_head_ratio():
    q, k, v = make_qkv(jax.random.key(4), 1, 64, 64, 3, 2, 32)
    with pytest.raises(ValueError, match="not a multiple"):
        flash_attention(q, k, v, interpret=True)


def test_sharded_flash_matches_dense(devices):
    """shard_map-wrapped kernel under dp/fsdp/tp == single-device dense
    (interpret mode inside shard_map on the virtual CPU mesh)."""
    from solvingpapers_tpu.kernels import sharded_flash_attention
    from solvingpapers_tpu.sharding import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(data=2, fsdp=2, model=2), devices)
    q, k, v = make_qkv(jax.random.key(9), 4, 128, 128, 4, 2, 32)
    out = sharded_flash_attention(q, k, v, mesh, causal=True, interpret=True)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_sharded_flash_grads_match(devices):
    from solvingpapers_tpu.kernels import sharded_flash_attention
    from solvingpapers_tpu.sharding import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(data=2, model=4), devices)
    q, k, v = make_qkv(jax.random.key(10), 2, 64, 64, 4, 4, 16)

    def loss_sharded(q, k, v):
        o = sharded_flash_attention(q, k, v, mesh, causal=True, interpret=True)
        return jnp.sum(o**2)

    def loss_dense(q, k, v):
        return jnp.sum(ops.dot_product_attention(q, k, v, causal=True) ** 2)

    gs = jax.grad(loss_sharded, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gs, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_sharded_flash_rejects_bad_head_split(devices):
    from solvingpapers_tpu.kernels import sharded_flash_attention
    from solvingpapers_tpu.sharding import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(data=2, model=4), devices)
    q, k, v = make_qkv(jax.random.key(11), 2, 64, 64, 4, 2, 16)  # kv 2 < tp 4
    with pytest.raises(ValueError, match="divide the model axis"):
        sharded_flash_attention(q, k, v, mesh, interpret=True)


def test_sharded_flash_mqa_kv1_replicated(devices):
    """MLA's absorbed-query shape: one shared kv head stays replicated over
    the model axis while q heads shard (local q->kv map resolves to 0)."""
    from solvingpapers_tpu.kernels import sharded_flash_attention
    from solvingpapers_tpu.sharding import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(data=2, model=4), devices)
    q, k, v = make_qkv(jax.random.key(12), 2, 64, 64, 8, 1, 16)
    out = sharded_flash_attention(q, k, v, mesh, causal=True, interpret=True)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_trainer_routes_flash_through_sharded_kernel_under_tp(devices, monkeypatch):
    """A use_flash model on a model>1 mesh must go through the shard_map
    wrapper (pallas_call is GSPMD-opaque: the direct call would all-gather
    q/k/v) and still match single-device flash training bit-for-bit-ish."""
    import solvingpapers_tpu.kernels as kernels
    from solvingpapers_tpu.data import load_char_corpus
    from solvingpapers_tpu.data.batches import lm_batch_iterator
    from solvingpapers_tpu.models.gpt import GPT, GPTConfig
    from solvingpapers_tpu.sharding import MeshConfig, batch_sharding, create_mesh
    from solvingpapers_tpu.train import OptimizerConfig, Trainer, TrainConfig

    model_cfg = GPTConfig(vocab_size=64, block_size=32, dim=32, n_layers=2,
                          n_heads=4, dropout=0.0, use_flash=True)
    _, train_toks, _ = load_char_corpus(synthetic_chars=20_000)
    opt = OptimizerConfig(max_lr=1e-3, warmup_steps=0, total_steps=10)

    calls = {"sharded": 0}
    real = kernels.sharded_flash_attention

    def spy(*args, **kwargs):
        calls["sharded"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(kernels, "sharded_flash_attention", spy)

    def run(mesh_config, devs):
        mesh = create_mesh(mesh_config, devs)
        cfg = TrainConfig(steps=2, batch_size=8, log_every=100, eval_every=0,
                          optimizer=opt)
        trainer = Trainer(GPT(model_cfg), cfg, mesh=mesh)
        it = lm_batch_iterator(train_toks, 8, model_cfg.block_size, seed=7,
                               sharding=batch_sharding(mesh))
        b0 = next(it)
        state = trainer.init_state(b0)
        trainer._build_steps()
        losses = []
        state, m = trainer._train_step(state, b0)
        losses.append(float(m["train_loss"]))
        state, m = trainer._train_step(state, next(it))
        losses.append(float(m["train_loss"]))
        return losses

    single = run(MeshConfig(data=1), devices[:1])
    assert calls["sharded"] == 0  # 1-device mesh: direct kernel, no wrapper
    sharded = run(MeshConfig(data=2, fsdp=1, model=2), devices[:4])
    assert calls["sharded"] > 0, "TP mesh did not route through sharded flash"
    np.testing.assert_allclose(sharded, single, rtol=2e-4, atol=2e-5)


# (q heads, kv heads, key width, value width): keys wider than values, as
# latent attention decompressed has them (192 / 128 at the published size);
# values wider than keys; grouped queries with unequal widths
WIDTHS = [pytest.param(4, 4, 48, 32, id="keys_wider"),
          pytest.param(2, 2, 16, 40, id="values_wider"),
          pytest.param(4, 2, 24, 16, id="gqa_keys_wider")]


def _qkv_two_widths(n, n_kv, dk, dv, s=128):
    q, k, _ = make_qkv(jax.random.key(3), 2, s, s, n, n_kv, dk)
    v = jax.random.normal(jax.random.key(4), (2, s, n_kv, dv))
    return q, k, v


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "bidir"])
@pytest.mark.parametrize("n,n_kv,dk,dv", WIDTHS)
def test_value_width_of_its_own_forward_matches_dense(n, n_kv, dk, dv, causal):
    q, k, v = _qkv_two_widths(n, n_kv, dk, dv)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=32)
    assert out.shape == (2, 128, n, dv)
    want = ops.dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("n,n_kv,dk,dv", WIDTHS)
def test_value_width_of_its_own_grads_match_dense(n, n_kv, dk, dv):
    q, k, v = _qkv_two_widths(n, n_kv, dk, dv)
    mix = jax.random.normal(jax.random.key(5), (2, 128, n, dv))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * mix)

    flash = loss(functools.partial(flash_attention, causal=True, block_q=32,
                                   block_k=64))
    dense = loss(functools.partial(ops.dot_product_attention, causal=True))
    got = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)


# --- the forward kernel's results survive a caller's remat (FLASH_RESIDUALS)

# (q heads, kv heads, key width, value width)
KEPT = [pytest.param(4, 4, 16, 16, id="mha"),
        pytest.param(32, 2, 16, 16, id="gqa_32on2"),
        pytest.param(4, 4, 24, 16, id="keys192_values128")]


def _layer_like(n, n_kv, dk, dv, s=64, width=24):
    """A layer as the families wrap one in remat: projections, the flash
    call, an output projection; and its inputs."""
    keys = jax.random.split(jax.random.key(11), 5)
    x = jax.random.normal(keys[0], (1, s, width))
    w = {"q": jax.random.normal(keys[1], (width, n * dk)) * 0.2,
         "k": jax.random.normal(keys[2], (width, n_kv * dk)) * 0.2,
         "v": jax.random.normal(keys[3], (width, n_kv * dv)) * 0.2,
         "o": jax.random.normal(keys[4], (n * dv, width)) * 0.2}

    def layer(w, x):
        q = (x @ w["q"]).reshape(1, s, n, dk)
        k = (x @ w["k"]).reshape(1, s, n_kv, dk)
        v = (x @ w["v"]).reshape(1, s, n_kv, dv)
        ctx = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
        return x + jnp.tanh(ctx.reshape(1, s, n * dv) @ w["o"])

    return layer, w, x


@pytest.mark.parametrize("n,n_kv,dk,dv", KEPT)
def test_forward_kernel_runs_once_under_a_remat_that_keeps_its_results(
        n, n_kv, dk, dv):
    """Under `save_only_these_names(*FLASH_RESIDUALS)` the gradient of two
    rematerialised layers holds one forward kernel a layer; under a remat
    with no policy two (the names are identities there); the backward
    kernels one a layer either way; output and gradients bit for bit."""
    layer, w, x = _layer_like(n, n_kv, dk, dv)
    kept = two_remat_layers(layer, keep=FLASH_RESIDUALS)
    plain = two_remat_layers(layer)
    backward = {"flash_mla_bwd_dq": 2, "flash_mla_bwd_dkv": 2}
    assert kernel_calls(kept, w, x) == {"flash_mla_fwd": 2, **backward}
    assert kernel_calls(plain, w, x) == {"flash_mla_fwd": 4, **backward}
    assert_same_bits(jax.jit(kept)(w, x), jax.jit(plain)(w, x))


@pytest.mark.parametrize("n,n_kv,dk,dv", KEPT)
def test_names_stand_in_the_forward_rule_only(n, n_kv, dk, dv):
    """`_flash`, the primal, names nothing; differentiated, the two
    residuals carry FLASH_RESIDUALS, in the kernel's own layout."""
    layer, w, x = _layer_like(n, n_kv, dk, dv)
    assert checkpoint_names(layer, w, x) == []
    assert checkpoint_names(jax.checkpoint(layer, prevent_cse=True), w, x) == []
    s = x.shape[1]
    assert checkpoint_names(
            jax.grad(lambda w, x: jnp.sum(layer(w, x))), w, x) == [
        ("flash_lse", (n, 1, s)), ("flash_o", (n, s, dv))]


# --- the forward kernel's tile pair is its own (`flash_blocks`)

# (sq, skv, q heads, kv heads, key width, value width, forward pair,
#  backward pair)
PAIRS = [
    pytest.param(256, 256, 2, 2, 32, 32, (64, 128), (64, 64),
                 id="wider_key_tile"),
    pytest.param(256, 256, 2, 2, 32, 32, (128, 64), (64, 64),
                 id="wider_query_tile"),
    pytest.param(256, 256, 2, 2, 32, 32, (32, 256), (128, 128),
                 id="one_key_block"),
    pytest.param(128, 256, 2, 2, 32, 32, (64, 128), (32, 64),
                 id="end_aligned_prefill"),
    pytest.param(192, 64, 2, 2, 32, 32, (32, 64), (64, 32),
                 id="seq_q_longer"),
    pytest.param(256, 256, 4, 2, 24, 16, (32, 128), (64, 32),
                 id="gqa_value_width_of_its_own"),
]


def kernel_grids(fn, *args):
    """{`name=`: grid} of the `pallas_call`s the traced `fn` holds."""
    return {eqn.params["name"]: tuple(eqn.params["grid_mapping"].grid)
            for eqn in equations(jax.make_jaxpr(fn)(*args).jaxpr)
            if eqn.primitive.name == "pallas_call"}


@pytest.mark.parametrize("sq,skv,n,n_kv,dk,dv,forward,backward", PAIRS)
def test_forward_tiles_of_its_own_match_dense_and_the_shared_tiles(
        monkeypatch, sq, skv, n, n_kv, dk, dv, forward, backward):
    """The forward kernel in one tile pair, the backward kernels in another:
    `o` and the three gradients against the dense reference, and against
    the call whose forward runs in the backward's tiles (`o` and `lse` are
    whole arrays: the backward reads them in its own blocks)."""
    q, k, _ = make_qkv(jax.random.key(13), 2, sq, skv, n, n_kv, dk)
    v = jax.random.normal(jax.random.key(14), (2, skv, n_kv, dv))
    mix = jax.random.normal(jax.random.key(15), (2, sq, n, dv))
    # rows that see no key (seq_q > seq_k, end-aligned) are 0 in the kernel
    # and arbitrary in the dense reference
    seen = (jnp.arange(sq) >= sq - skv)[None, :, None, None]

    def program(fn):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(jnp.where(
            seen, fn(q, k, v, causal=True), 0.0) * mix), argnums=(0, 1, 2))

    def flash_with(pairs):
        monkeypatch.setattr(flash_module, "flash_blocks",
                            lambda *a, **kw: pairs)
        # a new function a call: JAX keeps a trace by the function
        fn = program(functools.partial(flash_attention, interpret=True))
        return kernel_grids(fn, q, k, v), jax.jit(fn)(q, k, v)

    _, shared = flash_with((backward, backward))
    grids, got = flash_with((forward, backward))
    want = jax.jit(program(ops.dot_product_attention))(q, k, v)
    heads = 2 * n
    assert grids == {
        "flash_mla_fwd": (heads, sq // forward[0], skv // forward[1]),
        "flash_mla_bwd_dq": (heads, sq // backward[0], skv // backward[1]),
        "flash_mla_bwd_dkv": (heads, skv // backward[1], sq // backward[0])}
    for other in (want, shared):
        np.testing.assert_allclose(got[0], other[0], rtol=2e-5)
        for name, a, b in zip("qkv", got[1], other[1]):
            np.testing.assert_allclose(a, b, rtol=5e-5, atol=5e-5,
                                       err_msg=name)
    # undifferentiated, the primal runs in the forward pair too
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = ops.dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(jnp.where(seen, out, 0.0),
                               jnp.where(seen, ref, 0.0),
                               rtol=2e-5, atol=2e-5)


# (seq, key width, value width, forward pair, backward pair): the six cells
# whose step holds the kernels, as `tools/sweep_flash_fwd.py` has them
CELLS = [
    pytest.param(4096, 128, 128, (1024, 1024), (512, 512),
                 id="ouro_2p6b_pp6"),
    pytest.param(16_384, 192, 128, (1024, 1024), (512, 512),
                 id="kimi_linear_ep32"),
    pytest.param(16_384, 128, 128, (1024, 1024), (1024, 1024),
                 id="dsv3_long"),
    pytest.param(16_384, 256, 256, (1024, 1024), (512, 512),
                 id="qwen3next_ep16"),
    pytest.param(16_384, 128, 128, (1024, 1024), (1024, 1024),
                 id="nemotron3_nano_ep16"),
    pytest.param(8192, 64, 64, (1024, 1024), (1024, 1024),
                 id="granite4_h_micro_pp4"),
]


@pytest.mark.parametrize("seq,dk,dv,forward,backward", CELLS)
def test_blocks_at_the_cells_shapes(seq, dk, dv, forward, backward):
    """What the forward sweep chose for the forward kernel, and for the
    backward kernels what `auto_block` always gave; with dropout on, or a
    block named by the caller, ONE tiling for all three kernels (the
    dropout mask of a tile is seeded by the tile's number in its tiling)."""
    assert flash_blocks(seq, seq, dk, dv) == (forward, backward)
    assert flash_blocks(seq, seq, dk, dv, 0.1) == (backward, backward)
    assert flash_blocks(seq, seq, dk, dv, block_q=256) == (
        (256, backward[1]),) * 2
    assert flash_blocks(seq, seq, dk, dv, block_k=2048) == (
        (backward[0], 2048),) * 2
    assert flash_blocks(seq, seq, dk, dv, 0.1, 256, 128) == ((256, 128),) * 2


def test_blocks_of_a_call_that_has_a_selection_mask():
    """`flash_blocks` sees the mask operand (an array or its shape, never a
    flag) and gives the call the pairs the masked sweeps chose at
    keye_vl2_ep8's shape; a span's geometry and a short sequence shrink
    them to divisors; a named block and dropout set ONE tiling as without
    a mask; heads wider than the sweep saw keep the default block."""
    mask = jax.ShapeDtypeStruct((1, 16_384, 16_384), jnp.int8)
    assert flash_blocks(16_384, 16_384, 128, 128, mask=mask) == (
        (1024, 1024), (1024, 1024))
    assert flash_blocks(2048, 16_384, 128, 128, mask=mask) == (
        (1024, 1024), (1024, 1024))
    assert flash_blocks(64, 64, 8, 8, mask=mask) == ((64, 64), (64, 64))
    assert flash_blocks(16_384, 16_384, 128, 128, 0.1, mask=mask) == (
        (1024, 1024),) * 2
    assert flash_blocks(16_384, 16_384, 128, 128, block_q=256,
                        mask=mask) == ((256, 1024),) * 2
    assert flash_blocks(16_384, 16_384, 256, 256, mask=mask) == (
        (512, 512),) * 2
    # and the unmasked call's backward pair is not the masked one's below
    # LONG_SEQ
    assert flash_blocks(4096, 4096, 128, 128)[1] == (512, 512)
    assert flash_blocks(4096, 4096, 128, 128, mask=mask)[1] == (1024, 1024)


def test_blocks_shrink_to_a_divisor_of_either_sequence():
    """The forward pair shrinks like the backward's: a short or ragged
    sequence runs in the largest power-of-two part of the asked block that
    divides it, the query side as one block where that is no whole number
    of lanes; end-aligned prefill gets each side from its own length; a
    width the sweep never saw keeps the backward's pair."""
    assert flash_blocks(256, 256, 256, 256) == ((256, 256), (256, 256))
    assert flash_blocks(96, 96, 32, 32) == ((96, 96), (96, 96))
    assert flash_blocks(2016, 2016, 128, 128) == ((2016, 32), (2016, 32))
    assert flash_blocks(512, 4096, 128, 128) == ((512, 1024), (512, 512))
    assert flash_blocks(4096, 4096, 512, 128) == ((512, 512), (512, 512))
