"""The delta rule with a decay per key channel (`ops/kda.py`, the kernels
of `kernels/gated_delta.py` interpreted): the chunked form of the timed
path against the rule token by token, values and all five gradients, at
random decays and at the strongest the initialisation can draw (a channel
that forgets everything within a token: the exponent no chunked form may
take positive), over one chunk, a ragged chunk, whole grid steps and
several grid steps with the state crossing them; and a decay that repeats
one number over the channels against the gated delta rule of
`ops/gated_delta.py`, both as the recurrence and as its kernels."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import (
    assert_same_bits, checkpoint_names, kernel_calls, two_remat_layers)

from solvingpapers_tpu.kernels import gated_delta as kernel
from solvingpapers_tpu.ops import gated_delta as gd
from solvingpapers_tpu.ops import kda

pytestmark = pytest.mark.fast

B, H, DK, DV = 2, 3, 16, 8
CHUNK, SUB = 16, 4
STEP = CHUNK * kernel.CHUNKS_A_STEP  # tokens a grid step holds


def inputs(seq, dtype=jnp.float32, decay="random", seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, seq, H, DK)).astype(dtype)
    k = jax.random.normal(ks[1], (B, seq, H, DK)).astype(dtype)
    v = jax.random.normal(ks[2], (B, seq, H, DV)).astype(dtype)
    if decay == "random":
        # heads that forget in a token to heads that hardly do, as A_log =
        # log U(1e-3, 16) draws them; the channels of a head differ
        a = jax.random.uniform(ks[3], (H, 1), minval=1e-3, maxval=16.0)
        pre = jax.random.normal(ks[4], (B, seq, H, DK)) + 1.0
    else:
        # the strongest start: every head at exp(A_log) = 16, softplus of
        # dt_bias 1 plus a large projection: g about -16 * 4 a token, e^-88
        # passed in two tokens and e^-4000 inside a chunk
        a = jnp.full((H, 1), 16.0)
        pre = jax.random.normal(ks[4], (B, seq, H, DK)) + 4.0
    g = -a * jax.nn.softplus(pre)
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, seq, H)))
    return q, k, v, g, beta


@jax.jit
def chunked(*args):
    return kda.kda_rule(*args, chunk=CHUNK, sub=SUB)


recurrent = jax.jit(kda.kda_rule_recurrent)


def grads(fn, args, mix):
    return jax.jit(jax.grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * mix),
        argnums=(0, 1, 2, 3, 4)))(*args)


# tokens: one chunk; a ragged chunk (two chunks a grid step, the second
# padded); one whole grid step; grid steps and a ragged tail (the state
# crosses them in VMEM, and in the backward dS does, the other way)
SEQS = [16, 23, STEP, 2 * STEP + 22]


@pytest.mark.parametrize("decay", ["random", "strongest"])
@pytest.mark.parametrize("seq", SEQS)
def test_chunked_rule_matches_the_recurrence(seq, decay):
    args = inputs(seq, decay=decay)
    want = recurrent(*args)
    got = chunked(*args)
    assert got.shape == want.shape == (B, seq, H, DV)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("decay", ["random", "strongest"])
@pytest.mark.parametrize("seq", SEQS)
def test_chunked_rule_gradients_match_the_recurrence(seq, decay):
    args = inputs(seq, decay=decay)
    mix = jax.random.normal(jax.random.key(9), (B, seq, H, DV))
    got, want = grads(chunked, args, mix), grads(recurrent, args, mix)
    for name, a, b in zip("qkvgb", got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, err_msg=name)


@pytest.mark.parametrize("sub", [1, 2, 8, 16])
def test_any_sub_block_size_is_the_same_function(sub):
    """From every pair through a reference point (sub-blocks of one token:
    only the diagonal is summed channel by channel) to none (one sub-block
    a chunk: the far product is not made at all)."""
    rule = jax.jit(lambda *a: kda.kda_rule(*a, chunk=CHUNK, sub=sub))
    for decay in ("strongest", "random"):
        args = inputs(STEP + 5, decay=decay, seed=sub)
        np.testing.assert_allclose(rule(*args), recurrent(*args),
                                   atol=2e-6, rtol=1e-5)
    mix = jax.random.normal(jax.random.key(9), (B, STEP + 5, H, DV))
    for name, a, b in zip("qkvgb", grads(rule, args, mix),
                          grads(recurrent, args, mix)):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, err_msg=name)


def test_the_product_form_would_overflow_where_this_one_does_not():
    """What the sub-blocks are for: at the strongest decay (k e^G)(k e^-G)^t
    needs e^(+4000) inside a chunk, which float32 does not hold."""
    _, _, _, g, _ = inputs(16, decay="strongest")
    total = jnp.cumsum(g, axis=1)
    assert float(jnp.min(total)) < -1000.0
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-total))))


@pytest.mark.parametrize("seq", [23, 64])
def test_one_decay_a_head_gives_the_gated_delta_rule_back(seq):
    """g_t repeated over the key channels is Gated DeltaNet's rule: the
    recurrences agree, and so do the two branches of the kernels (g's rank
    picks the sub-blocked system or the decay matrix)."""
    q, k, v, g, beta = inputs(seq)
    g_head = g[..., 0]
    want = jax.jit(gd.gated_delta_rule_recurrent)(q, k, v, g_head, beta)
    g_all = jnp.broadcast_to(g_head[..., None], q.shape)
    np.testing.assert_allclose(recurrent(q, k, v, g_all, beta), want,
                               atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(chunked(q, k, v, g_all, beta), want,
                               atol=2e-6, rtol=1e-5)
    a_head = jax.jit(lambda *a: gd.gated_delta_rule(*a, chunk=CHUNK))(
        q, k, v, g_head, beta)
    np.testing.assert_allclose(chunked(q, k, v, g_all, beta), a_head,
                               atol=2e-6, rtol=1e-5)


def test_chunked_rule_with_bfloat16_inputs():
    """bfloat16 q, k, v (the chip's dtype): the result is bfloat16 and lies
    within bfloat16's rounding of the float32 recurrence on the same
    (rounded) inputs; the gradients come in their arguments' dtypes."""
    args = inputs(2 * STEP + 22, jnp.bfloat16)
    want = recurrent(*(a.astype(jnp.float32) for a in args))
    got = chunked(*args)
    assert got.dtype == jnp.bfloat16
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(got.astype(jnp.float32), want,
                               atol=2e-2 * scale)
    mix = jax.random.normal(jax.random.key(9), want.shape)
    got_g = grads(chunked, args, mix)
    want_g = grads(recurrent, [a.astype(jnp.float32) for a in args], mix)
    assert [a.dtype for a in got_g] == [a.dtype for a in args]
    for name, a, b in zip("qkvgb", got_g, want_g):
        np.testing.assert_allclose(
            a.astype(jnp.float32), b, err_msg=name,
            atol=4e-2 * float(jnp.max(jnp.abs(b))))


def test_the_state_is_float32_across_grid_steps():
    """Under differentiation the forward kernel also writes the float32
    state that enters each grid step, (B, H, steps, 1, dk, dv): the
    backward starts each step from it."""
    args = inputs(3 * STEP)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(chunked(*a)), argnums=0))(*args))
    assert f"f32[{B},{H},3,1,{DK},{DV}]" in jaxpr
    assert jaxpr.count("pallas_call") == 2
    assert "while" not in jaxpr and "scan" not in jaxpr


def test_the_rule_refuses_what_it_does_not_compute():
    q, k, v, g, beta = inputs(16)
    with pytest.raises(ValueError, match="decay per key channel"):
        kda.kda_rule(q, k, v, g[..., 0], beta)
    with pytest.raises(ValueError, match="its own q, k and v"):
        kda.kda_rule(q, k, jnp.concatenate([v, v], axis=2), g, beta)
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_rule(q, k, v, g, beta, chunk=24, sub=4)
    with pytest.raises(ValueError, match="multiple of sub"):
        kda.kda_rule(q, k, v, g, beta, chunk=16, sub=3)


def test_decay_made_from_a_low_rank_input_is_the_same_function():
    """`decay=`: the log decays made inside `kda_rule` from a low-rank
    input, values and the gradients of what the function closes over."""
    seq = STEP + 11
    q, k, v, _, beta = inputs(seq)
    low = jax.random.normal(jax.random.key(3), (B, seq, 5))
    w = 0.5 * jax.random.normal(jax.random.key(4), (5, H * DK))

    def decay(low, w):
        return -2.0 * jax.nn.softplus((low @ w).reshape(
            low.shape[:2] + (H, DK)))

    def whole(low, w):
        return jnp.sum(chunked(q, k, v, decay(low, w), beta) ** 2)

    def handed_over(low, w):
        return jnp.sum(kda.kda_rule(
            q, k, v, low, beta, chunk=CHUNK, sub=SUB,
            decay=lambda x: decay(x, w)) ** 2)

    want, g_want = jax.jit(jax.value_and_grad(whole, (0, 1)))(low, w)
    got, g_got = jax.jit(jax.value_and_grad(handed_over, (0, 1)))(low, w)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=1e-4 * float(jnp.max(jnp.abs(b))))


# --- the forward kernel's results survive a caller's remat (DELTA_RESIDUALS)

def _layer_like(seq, width=12):
    """A layer as the family wraps one in remat: projections, the rule, an
    output projection; and its inputs."""
    keys = jax.random.split(jax.random.key(7), 7)
    x = jax.random.normal(keys[0], (B, seq, width))
    sizes = {"q": H * DK, "k": H * DK, "v": H * DV, "g": H * DK, "beta": H}
    w = {n: jax.random.normal(key, (width, m)) * 0.3
         for key, (n, m) in zip(keys[1:], sizes.items())}
    w["o"] = jax.random.normal(keys[6], (H * DV, width)) * 0.3

    def layer(w, x):
        heads = lambda n, d: (x @ w[n]).reshape(B, seq, H, d)  # noqa: E731
        o = kda.kda_rule(
            heads("q", DK), heads("k", DK), heads("v", DV),
            -jax.nn.softplus(heads("g", DK)), jax.nn.sigmoid(x @ w["beta"]),
            chunk=CHUNK, sub=SUB)
        return x + jnp.tanh(o.reshape(B, seq, H * DV) @ w["o"])

    return layer, w, x


# tokens: one grid step; grid steps and a ragged tail
KEPT = [STEP, 2 * STEP + 22]


@pytest.mark.parametrize("seq", KEPT)
def test_forward_kernel_runs_once_under_a_remat_that_keeps_its_results(seq):
    """Under `save_only_these_names(*DELTA_RESIDUALS)` the gradient of two
    rematerialised layers holds one forward kernel a layer; under a remat
    with no policy two (the names are identities there); the backward
    kernel one a layer either way; output and gradients bit for bit."""
    layer, w, x = _layer_like(seq)
    kept = two_remat_layers(layer, keep=kernel.DELTA_RESIDUALS)
    plain = two_remat_layers(layer)
    assert kernel_calls(kept, w, x) == {"kda_fwd": 2, "kda_bwd": 2}
    assert kernel_calls(plain, w, x) == {"kda_fwd": 4, "kda_bwd": 2}
    assert_same_bits(jax.jit(kept)(w, x), jax.jit(plain)(w, x))


@pytest.mark.parametrize("seq", KEPT)
def test_names_stand_in_the_forward_rule_only(seq):
    """`_rule`, the primal, names nothing; differentiated, o and the
    entering states carry DELTA_RESIDUALS, in the kernel's own layouts:
    (B, S padded, H dv) and (B, H, grid steps, 1, dk, dv)."""
    layer, w, x = _layer_like(seq)
    assert checkpoint_names(layer, w, x) == []
    assert checkpoint_names(jax.checkpoint(layer, prevent_cse=True), w, x) == []
    tiles = -(-seq // STEP)
    assert checkpoint_names(
        jax.grad(lambda w, x: jnp.sum(layer(w, x))), w, x) == [
            ("delta_o", (B, tiles * STEP, H * DV)),
            ("delta_states", (B, H, tiles, 1, DK, DV))]
