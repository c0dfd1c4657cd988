"""The gated delta rule: the kernels of the timed path (interpreted here)
against the rule token by token (`gated_delta_rule_recurrent`), values and
all five gradients, in float32 and with bfloat16 inputs, at lengths that
are and are not multiples of a chunk and of a grid step's tile, one and two
value heads a key head; the causal convolution and the gated norm with
their own backward rules."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import assert_same_bits, checkpoint_names, kernel_calls

from solvingpapers_tpu.kernels import gated_delta as kernel
from solvingpapers_tpu.ops import gated_delta as gd
from solvingpapers_tpu.ops.conv import causal_depthwise_conv

pytestmark = pytest.mark.fast

B, HK, DK, DV = 2, 2, 16, 8
CHUNK = 16  # a grid step holds kernel.CHUNKS_A_STEP = 4 of them: 64 tokens


def inputs(seq, dtype, hv=4, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, seq, HK, DK)).astype(dtype)
    k = jax.random.normal(ks[1], (B, seq, HK, DK)).astype(dtype)
    v = jax.random.normal(ks[2], (B, seq, hv, DV)).astype(dtype)
    # decays from heads that forget in a token to heads that hardly do
    a = jax.random.uniform(ks[3], (hv,), minval=1e-3, maxval=16.0)
    g = -a * jax.nn.softplus(jax.random.normal(ks[4], (B, seq, hv)) + 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, seq, hv)))
    return q, k, v, g, beta


@jax.jit
def chunked(*args):
    return gd.gated_delta_rule(*args, chunk=CHUNK)


recurrent = jax.jit(gd.gated_delta_rule_recurrent)


def grads(fn, args, mix):
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * mix),
        argnums=(0, 1, 2, 3, 4)))(*args)


# (tokens, value heads): one chunk; a ragged chunk; one whole tile; whole
# tiles and a ragged one (the carries in VMEM cross two grid steps, forward
# and backward); the same with one value head a key head
CASES = [(16, 4), (23, 4), (64, 4), (150, 4), (16, 2), (75, 2)]


@pytest.mark.parametrize("seq, hv", CASES)
def test_chunked_rule_matches_the_recurrence_float32(seq, hv):
    args = inputs(seq, jnp.float32, hv)
    want = recurrent(*args)
    got = chunked(*args)
    assert got.shape == want.shape == (B, seq, hv, DV)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("seq, hv", CASES)
def test_chunked_rule_gradients_match_the_recurrence_float32(seq, hv):
    args = inputs(seq, jnp.float32, hv)
    mix = jax.random.normal(jax.random.key(9), (B, seq, hv, DV))
    got, want = grads(chunked, args, mix), grads(recurrent, args, mix)
    for name, a, b in zip("qkvgb", got, want):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, err_msg=name)


@pytest.mark.parametrize("seq, hv", [(23, 4), (64, 4), (150, 2)])
def test_chunked_rule_with_bfloat16_inputs(seq, hv):
    """bfloat16 q, k, v (the chip's dtype): the result is bfloat16 and lies
    within bfloat16's rounding of the float32 recurrence on the same
    (rounded) inputs; so do the gradients, q's, k's and v's bfloat16 too."""
    args = inputs(seq, jnp.bfloat16, hv)
    as32 = tuple(a.astype(jnp.float32) for a in args)
    want = recurrent(*as32)
    got = chunked(*args)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) < 0.03 * scale
    mix = jax.random.normal(jax.random.key(9), (B, seq, hv, DV))
    g_got, g_want = grads(chunked, args, mix), grads(recurrent, as32, mix)
    assert [a.dtype for a in g_got] == [a.dtype for a in args]
    for name, a, b in zip("qkvgb", g_got, g_want):
        gap = float(jnp.linalg.norm(a.astype(jnp.float32) - b))
        assert gap < 0.03 * float(jnp.linalg.norm(b)), (name, gap)


def test_repeated_keys_cost_the_inverse_no_precision():
    """Every key of a chunk the same and beta near one: powers of the
    system's matrix grow past float32 before they vanish, forward
    substitution in blocks does not care."""
    seq = 64
    q, k, v, g, beta = inputs(seq, jnp.float32)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    beta = jnp.full_like(beta, 0.99)
    g = jnp.full_like(g, -1e-3)
    want = recurrent(q, k, v, g, beta)
    got = jax.jit(lambda *a: gd.gated_delta_rule(*a, chunk=64))(q, k, v, g, beta)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("grp", [1, 2])
def test_packed_unit_lower_inverse_and_its_backward(grp):
    """`grp` unit lower-triangular 16 x 16 matrices side by side along the
    lanes: each is inverted by itself, and the backward rule (from the
    inverse alone) is the derivative of the inverse."""
    c = 16
    m = jnp.tril(jax.random.normal(jax.random.key(0), (3, grp, c, c)), -1)
    pack = lambda x: jnp.moveaxis(x, 1, 2).reshape(3, c, grp * c)  # noqa: E731
    inv = kernel._unit_lower_inverse(pack(m), c, grp)
    want = jnp.linalg.inv(m + jnp.eye(c))
    np.testing.assert_allclose(
        inv, pack(want), atol=1e-5 * float(jnp.max(jnp.abs(want))))
    mix = jax.random.normal(jax.random.key(1), m.shape)
    got = jax.grad(lambda a: jnp.sum(
        kernel._unit_lower_inverse(pack(a), c, grp) * pack(mix)))(m)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(a + jnp.eye(c)) * mix))(m)
    # a gradient of the places above the diagonal too; A has none there
    np.testing.assert_allclose(
        jnp.tril(got, -1), jnp.tril(want, -1),
        atol=1e-4 * float(jnp.max(jnp.abs(want))))


def test_rule_refuses_heads_and_chunks_it_cannot_pack():
    q, k, v, g, beta = inputs(16, jnp.float32, hv=3)
    with pytest.raises(ValueError, match="3 value heads over 2"):
        gd.gated_delta_rule(q, k, v, g, beta)
    q, k, v, g, beta = inputs(16, jnp.float32)
    with pytest.raises(ValueError, match="power of two"):
        gd.gated_delta_rule(q, k, v, g, beta, chunk=24)


@pytest.mark.parametrize("silu", [False, True])
def test_causal_depthwise_conv_and_its_backward(silu):
    x = jax.random.normal(jax.random.key(0), (2, 11, 6))
    w = jax.random.normal(jax.random.key(1), (4, 6))

    def plain(x, w):
        xp = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        y = sum(xp[:, j:j + 11] * w[j] for j in range(4))
        return y * jax.nn.sigmoid(y) if silu else y

    conv = lambda x, w: causal_depthwise_conv(x, w, silu)  # noqa: E731
    np.testing.assert_allclose(conv(x, w), plain(x, w), atol=1e-6)
    if not silu:
        # causal: the first output sees the first input alone, through w[-1]
        np.testing.assert_allclose(conv(x, w)[:, 0], x[:, 0] * w[3], atol=1e-6)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda x, w: jnp.sum(jnp.sin(fn(x, w))), argnums=(0, 1))(x, w)
    for a, b in zip(grads(conv), grads(plain)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_gated_rms_norm_and_its_backward():
    o = jax.random.normal(jax.random.key(0), (2, 5, 3, 8))
    z = jax.random.normal(jax.random.key(1), (2, 5, 3, 8))
    w = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), (8,))

    def plain(o, z, w):
        n = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * w
        return n * z * jax.nn.sigmoid(z)

    np.testing.assert_allclose(gd.gated_rms_norm(o, z, w, 1e-6),
                               plain(o, z, w), atol=1e-6)
    grads = lambda fn: jax.grad(  # noqa: E731
        lambda o, z, w: jnp.sum(jnp.sin(fn(o, z, w))), argnums=(0, 1, 2)
    )(o, z, w)
    got = grads(lambda o, z, w: gd.gated_rms_norm(o, z, w, 1e-6))
    for a, b in zip(got, grads(plain)):
        np.testing.assert_allclose(a, b, atol=1e-5)
    assert gd.gated_rms_norm(o.astype(jnp.bfloat16), z.astype(jnp.bfloat16),
                             w, 1e-6).dtype == jnp.bfloat16


def test_the_gated_rule_offers_the_same_names_to_a_callers_remat():
    """One decay a head, the other branch of the kernel file: its forward
    rule names o and the entering states DELTA_RESIDUALS too, in the
    kernel's layouts ((B, S padded, Hv dv); (B, Hk, grid steps, grp, dk,
    dv)); a remat that keeps them holds one `gated_delta_fwd`, a remat with
    no policy (the family's own, which has no room) two; bit for bit."""
    seq, hv = 75, 4  # two grid steps, the second ragged
    args = inputs(seq, jnp.float32, hv)
    rule = lambda *a: jnp.sum(jnp.sin(gd.gated_delta_rule(*a, chunk=CHUNK)))  # noqa: E731
    step = CHUNK * kernel.CHUNKS_A_STEP
    tiles = -(-seq // step)
    assert checkpoint_names(rule, *args) == []
    assert checkpoint_names(jax.grad(rule, argnums=(0, 1, 2, 3, 4)), *args) == [
        ("delta_o", (B, tiles * step, hv * DV)),
        ("delta_states", (B, HK, tiles, hv // HK, DK, DV))]

    def grad_of(**remat):
        return jax.value_and_grad(
            jax.checkpoint(rule, prevent_cse=True, **remat),
            argnums=(0, 1, 2, 3, 4))

    kept = grad_of(policy=jax.checkpoint_policies.save_only_these_names(
        *kernel.DELTA_RESIDUALS))
    plain = grad_of()
    assert kernel_calls(kept, *args) == {
        "gated_delta_fwd": 1, "gated_delta_bwd": 1}
    assert kernel_calls(plain, *args) == {
        "gated_delta_fwd": 2, "gated_delta_bwd": 1}
    assert_same_bits(jax.jit(kept)(*args), jax.jit(plain)(*args))
