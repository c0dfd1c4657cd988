"""The Mamba-2 state-space recurrence of `ops/ssd.py` on the CPU: the
chunked form (the Pallas kernels of `kernels/ssd.py`, interpreted) against
the rule token by token (`ssd_recurrent`) and against its quadratic dual
written out here, values and every gradient, in float32 and with bfloat16
operands; a sequence that is no whole number of chunks or of grid steps;
heads that share their group's B and C, and heads that share a tile of
lanes; a step so large that the decay underflows; the state handed from one
call to the next and differentiated; one group of many heads cut into
blocks of heads that read the same B and C (16 and 32 heads at a tiny size,
and 64 heads of 64 at chunks of 256, the Granite-hybrid shape); the gated
norm."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import (
    assert_same_bits, checkpoint_names, kernel_calls, two_remat_layers)

from solvingpapers_tpu.kernels import ssd as kernel
from solvingpapers_tpu.ops import gated_delta, ssd

pytestmark = pytest.mark.fast

B, S, H, P, G, N = 2, 50, 8, 4, 2, 16


def inputs(seed=0, seq=S, dtype=jnp.float32, heads=H, groups=G, width=P):
    k = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(k[0], (B, seq, heads, width)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, seq, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=-2.0, maxval=2.7))
    b = jax.random.normal(k[3], (B, seq, groups, N)).astype(dtype)
    c = jax.random.normal(k[4], (B, seq, groups, N)).astype(dtype)
    d = jax.random.normal(k[5], (heads,))
    return x, dt, a, b, c, d


def dual(x, dt, a, b, c, d):
    """y_t = sum_{s<=t} exp(sum_{r=s+1..t} dt_r a) dt_s (C_t . B_s) x_s + d
    x_t, the whole (S, S) matrix a head at once."""
    rep = x.shape[2] // b.shape[2]
    b, c = jnp.repeat(b, rep, axis=2), jnp.repeat(c, rep, axis=2)
    cum = jnp.cumsum(dt * a, axis=1)  # (B, S, H)
    diff = cum[:, :, None] - cum[:, None, :]  # [t, s]
    keep = jnp.tril(jnp.ones((x.shape[1],) * 2, bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(keep, diff, -jnp.inf))
    scores = jnp.einsum("bthn,bshn->btsh", c, b, precision="highest")
    m = scores * decay * dt[:, None]
    return (jnp.einsum("btsh,bshp->bthp", m, x, precision="highest")
            + d[:, None] * x)


def close(got, want, tol=2e-5):
    """Within `tol` of the largest entry of `want`."""
    np.testing.assert_allclose(
        got, want, rtol=0, atol=tol * float(jnp.max(jnp.abs(want))))


def objective(fn, **kw):
    def f(x, dt, a, b, c, d):
        out = fn(x, dt, a, b, c, d, **kw)
        y = out[0] if isinstance(out, tuple) else out
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))
    return f


def both_outputs(fn, **kw):
    def f(*args7):
        y, last = fn(*args7[:6], state=args7[6], **kw)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))) + jnp.sum(
            jnp.cos(last))
    return f


@pytest.mark.parametrize("chunk, a_step", [(8, 2), (16, 1), (8, 8)])
def test_chunked_equals_recurrent_equals_dual_float32(
        chunk, a_step, monkeypatch):
    """50 tokens: no whole number of chunks of 8 or 16, nor of grid steps of
    2 chunks (four steps, the last half padding), of 1 (four steps) or of 8
    (one step of 7 chunks); 8 heads on 2 groups."""
    monkeypatch.setattr(kernel, "CHUNKS_A_STEP", a_step)
    args = inputs()
    y_rec, s_rec = ssd.ssd_recurrent(*args)
    y, s = ssd.ssd_chunked(*args, chunk=chunk)
    assert y.shape == (B, S, H, P) and s.shape == (B, H, P, N)
    close(y, y_rec)
    close(s, s_rec)
    close(dual(*args), y_rec)


def test_gradients_equal_the_recurrent_and_the_dual_ones(monkeypatch):
    """dS crosses chunks inside a grid step and grid steps (2 chunks a
    step, 50 tokens: four steps)."""
    monkeypatch.setattr(kernel, "CHUNKS_A_STEP", 2)
    args = inputs(seed=1)
    want = jax.grad(objective(ssd.ssd_recurrent), argnums=range(6))(*args)
    got = jax.grad(objective(ssd.ssd_chunked, chunk=8),
                   argnums=range(6))(*args)
    by_dual = jax.grad(objective(dual), argnums=range(6))(*args)
    for g, w, q in zip(got, want, by_dual):
        close(g, w)
        close(q, w)


def test_bfloat16_operands_stay_near_the_float32_rule():
    """Operands in bfloat16, sums, decays and state in float32: values and
    gradients within bfloat16's rounding of the float32 recurrence on the
    same (rounded) inputs."""
    args = inputs(seed=2, dtype=jnp.bfloat16)
    y, state = ssd.ssd_chunked(*args, chunk=8)
    assert y.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    y_rec, s_rec = ssd.ssd_recurrent(*args)
    close(y.astype(jnp.float32), y_rec, 2e-2)
    close(state, s_rec, 2e-2)
    got = jax.grad(objective(ssd.ssd_chunked, chunk=8),
                   argnums=(0, 1, 3))(*args)
    want = jax.grad(objective(ssd.ssd_recurrent), argnums=(0, 1, 3))(*args)
    for g, w in zip(got, want):
        rel = float(jnp.linalg.norm(g.astype(jnp.float32) - w)
                    / jnp.linalg.norm(w))
        assert rel < 3e-2, rel


def test_every_head_of_a_group_reads_its_groups_b_and_c():
    """8 heads on 2 groups equal 8 heads on 8 groups whose B and C repeat
    their group's rows."""
    x, dt, a, b, c, d = inputs(seed=3)
    wide = ssd.ssd_chunked(x, dt, a, jnp.repeat(b, 4, axis=2),
                           jnp.repeat(c, 4, axis=2), d, chunk=8)[0]
    close(ssd.ssd_chunked(x, dt, a, b, c, d, chunk=8)[0], wide)
    with pytest.raises(ValueError, match="heads over"):
        ssd.ssd_chunked(x, dt, a, b[:, :, :1].repeat(3, 2),
                        c[:, :, :1].repeat(3, 2), d)


def test_a_step_that_underflows_the_decay_gives_zeros_not_nan():
    """dt a = -4,000 a token: exp underflows, the state forgets everything
    within one token and what is left is the token's own write."""
    x, dt, a, b, c, d = inputs(seed=4)
    dt = jnp.full_like(dt, 250.0)
    a = jnp.full_like(a, -16.0)
    y, state = ssd.ssd_chunked(x, dt, a, b, c, None, chunk=8)
    assert bool(jnp.all(jnp.isfinite(y))) and bool(
        jnp.all(jnp.isfinite(state)))
    rep = H // G
    own = dt[..., None] * x * jnp.sum(
        jnp.repeat(b, rep, 2) * jnp.repeat(c, rep, 2), -1, keepdims=True)
    close(y, own)
    grads = jax.grad(objective(ssd.ssd_chunked, chunk=8),
                     argnums=(0, 1, 2, 3, 4))(x, dt, a, b, c, d)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in grads)


def test_state_handed_from_one_call_to_the_next():
    args = inputs(seed=5, seq=48)
    x, dt, a, b, c, d = args
    whole, s_whole = ssd.ssd_chunked(*args, chunk=8)
    cut = 20  # not a chunk's edge
    first, s_first = ssd.ssd_chunked(x[:, :cut], dt[:, :cut], a, b[:, :cut],
                                     c[:, :cut], d, chunk=8)
    rest, s_rest = ssd.ssd_chunked(x[:, cut:], dt[:, cut:], a, b[:, cut:],
                                   c[:, cut:], d, state=s_first, chunk=8)
    close(jnp.concatenate([first, rest], 1), whole)
    close(s_rest, s_whole)
    # and one token at a time, as a decode step would
    state, ys = s_first, []
    for t in range(cut, cut + 3):
        state, y_t = ssd.ssd_step(state, x[:, t], dt[:, t], a, b[:, t],
                                  c[:, t], d)
        ys.append(y_t)
    close(jnp.stack(ys, 1), whole[:, cut:cut + 3])


def test_shapes_that_do_not_fit_are_refused():
    x, dt, a, b, c, d = inputs()
    with pytest.raises(ValueError, match="state .* must be a head's"):
        ssd.ssd_chunked(x, dt, a, b, c, d, chunk=8,
                        state=jnp.zeros((B, H, N, P)))
    with pytest.raises(ValueError, match="one step a head"):
        ssd.ssd_chunked(x, dt[..., :4], a, b, c, d)


@pytest.mark.parametrize("a_step", [1, 4])
def test_every_gradient_with_a_state_handed_in(a_step, monkeypatch):
    """x, dt, a, b, c, d and the state an earlier call left, with a
    cotangent on the state that leaves too: the backward's carry starts
    from it and ends as d(state)."""
    monkeypatch.setattr(kernel, "CHUNKS_A_STEP", a_step)
    args = inputs(seed=6, seq=37)
    state = jax.random.normal(jax.random.key(7), (B, H, P, N))

    want = jax.grad(both_outputs(ssd.ssd_recurrent), argnums=range(7))(
        *args, state)
    got = jax.grad(both_outputs(ssd.ssd_chunked, chunk=8),
                   argnums=range(7))(*args, state)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        close(g, w, 5e-5)  # float32's rounding of e^L where |L| is tens


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
def test_heads_that_share_a_tile_of_lanes(dtype, tol):
    """Heads of 64 sit two to a tile of 128 lanes: their score matrices side
    by side multiply x's tile with each head's columns down a diagonal. 4
    heads of 64 on 2 groups, values, state and gradients."""
    args = inputs(seed=8, seq=21, dtype=dtype, heads=4, width=64)
    assert kernel._Plan(8, 1, 2, 64, True).hp == 2
    y, state = ssd.ssd_chunked(*args, chunk=8)
    y_rec, s_rec = ssd.ssd_recurrent(*args)
    close(y.astype(jnp.float32), y_rec, tol)
    close(state, s_rec, tol)
    got = jax.grad(objective(ssd.ssd_chunked, chunk=8),
                   argnums=range(6))(*args)
    want = jax.grad(objective(ssd.ssd_recurrent), argnums=range(6))(*args)
    for g, w in zip(got, want):
        rel = float(jnp.linalg.norm(g.astype(jnp.float32) - w)
                    / jnp.linalg.norm(w))
        assert rel < 50 * tol, rel


@pytest.mark.parametrize("heads, groups", [(16, 1), (32, 2)])
def test_a_group_of_many_heads_runs_as_blocks_of_heads(
        heads, groups, monkeypatch):
    """A group of 16 heads is two blocks of `HEADS_A_STEP` = 8 on the grid,
    both reading the group's B and C; dB and dC add up over the blocks
    outside the kernel. 50 tokens in grid steps of two chunks of 8; values,
    the state, and every gradient, the entering state's among them; and the
    same numbers as eight-head groups whose B and C repeat."""
    monkeypatch.setattr(kernel, "CHUNKS_A_STEP", 2)
    args = inputs(seed=9, heads=heads, groups=groups)
    state = jax.random.normal(jax.random.key(10), (B, heads, P, N))
    y, last = ssd.ssd_chunked(*args, state=state, chunk=8)
    y_rec, s_rec = ssd.ssd_recurrent(*args, state=state)
    close(y, y_rec)
    close(last, s_rec)
    x, dt, a, b, c, d = args
    wide = ssd.ssd_chunked(x, dt, a, jnp.repeat(b, 2, axis=2),
                           jnp.repeat(c, 2, axis=2), d, state=state, chunk=8)
    close(y, wide[0], 1e-6)
    want = jax.grad(both_outputs(ssd.ssd_recurrent), argnums=range(7))(
        *args, state)
    got = jax.grad(both_outputs(ssd.ssd_chunked, chunk=8),
                   argnums=range(7))(*args, state)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        close(g, w, 5e-5)


def test_the_plans_of_the_two_state_space_cells(monkeypatch):
    """nemotron3_nano_ep16's call (8 groups of 8 heads, chunks of 128) is
    built as it was: four chunks a grid step, a group's eight heads, one
    block a group. granite4_h_micro_pp4's (ONE group of 64, chunks of 256):
    two chunks a step (512 tokens, as there), eight blocks of eight."""
    plans = []

    def rule(plan, x, *rest):
        plans.append(plan)
        return x, rest[-1]

    monkeypatch.setattr(kernel, "_rule", rule)
    for groups, chunk in ((8, 128), (1, 256)):
        x, dt, a, b, c, d = inputs(seq=1024, heads=64, groups=groups,
                                   width=64)
        kernel.ssd_chunked(x, dt, a, b, c, d, jnp.zeros((B, 64, 64, N)),
                           chunk=chunk, interpret=True)
    assert plans == [kernel._Plan(128, 4, 8, 64, True, 1),
                     kernel._Plan(256, 2, 8, 64, True, 8)]


def test_one_group_of_64_heads_at_chunks_of_256(monkeypatch):
    """The Granite-hybrid mixer's shape: 64 heads of 64 on ONE group of
    state 128, chunks of 256, float32, 300 tokens in two grid steps of one
    chunk; values, state and every gradient against the rule token by
    token. A chunk of 256 carries running sums four times a chunk of 64's,
    and float32 rounds e^(L_t - L_s) at 6e-8 |L|: the gradients agree to
    3e-4 of their largest entry (1.5e-4 seen) where chunks of 8 reach
    5e-5."""
    monkeypatch.setattr(kernel, "TOKENS_A_STEP", 256)
    k = jax.random.split(jax.random.key(11), 7)
    heads, width, n, seq = 64, 64, 128, 300
    x = jax.random.normal(k[0], (1, seq, heads, width))
    dt = jax.nn.softplus(jax.random.normal(k[1], (1, seq, heads)) - 1.0)
    a = -jnp.exp(jax.random.uniform(k[2], (heads,), minval=-2.0, maxval=2.7))
    b = jax.random.normal(k[3], (1, seq, 1, n))
    c = jax.random.normal(k[4], (1, seq, 1, n))
    d = jax.random.normal(k[5], (heads,))
    state = jax.random.normal(k[6], (1, heads, width, n))
    args = (x, dt, a, b, c, d, state)
    (y, last), vjp = jax.vjp(
        lambda *v: ssd.ssd_chunked(*v[:6], state=v[6], chunk=256), *args)
    (y_rec, s_rec), vjp_rec = jax.vjp(
        lambda *v: ssd.ssd_recurrent(*v[:6], state=v[6]), *args)
    close(y, y_rec)
    close(last, s_rec)
    cts = (jnp.cos(y_rec), jnp.sin(s_rec))
    for g, w in zip(vjp(cts), vjp_rec(cts)):
        close(g, w, 3e-4)


def test_gate_then_group_norm_is_not_norm_then_gate():
    """u = y * SiLU(z) normalised over groups of channels, against the
    DeltaNet families' norm a head with the gate after it: a y whose group
    holds one loud head tells them apart."""
    y = jnp.concatenate([jnp.full((1, 3, 4), 10.0), jnp.ones((1, 3, 12))], -1)
    z = jax.random.normal(jax.random.key(0), (1, 3, 16))
    w = jnp.linspace(0.5, 1.5, 16)
    got = ssd.gate_then_group_norm(y, z, w, 2, 1e-5)
    u = (y * jax.nn.silu(z)).reshape(1, 3, 2, 8)
    want = (u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-5)
            ).reshape(1, 3, 16) * w
    np.testing.assert_allclose(got, want, atol=1e-6)
    heads = gated_delta.gated_rms_norm(
        y.reshape(1, 3, 4, 4), z.reshape(1, 3, 4, 4), jnp.ones(4), 1e-5
    ).reshape(1, 3, 16) * w
    assert float(jnp.max(jnp.abs(got - heads))) > 0.1
    # one group over everything is the plain gated RMS norm
    one = ssd.gate_then_group_norm(y, z, w, 1, 1e-5)
    u = y * jax.nn.silu(z)
    np.testing.assert_allclose(
        one, u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-5) * w,
        atol=1e-6)


# --- the forward kernel's results survive a caller's remat (SSD_RESIDUALS)

KEPT_CHUNK = 8  # S = 50: two grid steps of four chunks, the second ragged


def _layer_like(read_last=False, width=12):
    """A layer as the families wrap one in remat: projections, the rule,
    the gate-then-norm's gate and an output projection; and its inputs.
    `read_last`: the state after the last token is read too, as no training
    caller does."""
    keys = jax.random.split(jax.random.key(7), 8)
    x = jax.random.normal(keys[0], (B, S, width))
    sizes = {"x": H * P, "z": H * P, "dt": H, "b": G * N, "c": G * N}
    w = {n: jax.random.normal(key, (width, m)) * 0.3
         for key, (n, m) in zip(keys[1:], sizes.items())}
    w["o"] = jax.random.normal(keys[6], (H * P, width)) * 0.3
    _, _, a, _, _, d = inputs()

    def layer(w, x):
        to = lambda n, *shape: (x @ w[n]).reshape(B, S, *shape)  # noqa: E731
        y, last = ssd.ssd_chunked(
            to("x", H, P), jax.nn.softplus(x @ w["dt"]), a, to("b", G, N),
            to("c", G, N), d, chunk=KEPT_CHUNK)
        u = y.reshape(B, S, H * P) * jax.nn.silu(x @ w["z"])
        out = x + jnp.tanh(u @ w["o"])
        return out + jnp.mean(jnp.cos(last)) if read_last else out

    return layer, w, x


@pytest.mark.parametrize("read_last, forward_calls", [
    pytest.param(False, 2, id="last_unread"),
    pytest.param(True, 4, id="last_read")])
def test_forward_kernel_runs_once_under_a_remat_that_keeps_its_results(
        read_last, forward_calls):
    """Under `save_only_these_names(*SSD_RESIDUALS)` the gradient of two
    rematerialised layers holds one forward kernel a layer; under a remat
    with no policy two (the names are identities there); the backward
    kernel one a layer either way; output and gradients bit for bit. The
    state after the last token is not named: unread, as in training, it
    leaves no forward call in the remat; a caller that reads it pays the
    second run whatever it keeps (and is another program: only its calls
    are counted)."""
    layer, w, x = _layer_like(read_last)
    kept = two_remat_layers(layer, keep=kernel.SSD_RESIDUALS)
    plain = two_remat_layers(layer)
    assert kernel_calls(kept, w, x) == {
        "ssd_fwd": forward_calls, "ssd_bwd": 2}
    assert kernel_calls(plain, w, x) == {"ssd_fwd": 4, "ssd_bwd": 2}
    if not read_last:
        assert_same_bits(jax.jit(kept)(w, x), jax.jit(plain)(w, x))


def test_names_stand_in_the_forward_rule_only():
    """`_rule`, the primal, names nothing; differentiated, y and the
    entering states carry SSD_RESIDUALS, in the kernel's own layouts: (B, S
    padded, H P) and (B, G, grid steps, R P, N); the last state no name."""
    layer, w, x = _layer_like()
    assert checkpoint_names(layer, w, x) == []
    assert checkpoint_names(jax.checkpoint(layer, prevent_cse=True), w, x) == []
    step = KEPT_CHUNK * kernel.CHUNKS_A_STEP
    tiles = -(-S // step)
    assert checkpoint_names(
        jax.grad(lambda w, x: jnp.sum(layer(w, x))), w, x) == [
            ("ssd_states", (B, G, tiles, H // G * P, N)),
            ("ssd_y", (B, tiles * step, H * P))]
