"""The pair-following token map (`moe_held_dispatch_combine`: a token gathers
one row for each of its k routed pairs) against the existing (T, E) map
(`moe_dispatch_combine`: one row for each expert held): on DeepSeekV3's
shapes, where a device holds every expert, the two are the same function,
values and gradients, drops included; on a share of the experts the pair map
gives that share's part."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu.ops import moe

pytestmark = pytest.mark.fast

T, D, H = 96, 16, 24


def setup(e, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (T, D))
    logits = jax.random.normal(ks[1], (T, e))
    w1 = 0.3 * jax.random.normal(ks[2], (e, D, H))
    w2 = 0.3 * jax.random.normal(ks[3], (e, H, D))
    return x, logits, w1, w2


def expert_fn(w1, w2):
    # the held dispatch hands the experts' fills on; these einsums run over
    # every slot
    return lambda xe, fill=None: jnp.einsum(
        "ech,ehd->ecd", jnp.tanh(jnp.einsum("ecd,edh->ech", xe, w1)), w2)


# (experts, top_k, capacity factor): the notebook's 8 experts top-2 with
# room and with drops, and a wider layer
SHAPES = [(8, 2, 2.0), (8, 2, 0.5), (16, 4, 1.0)]


@pytest.mark.parametrize("e, k, cf", SHAPES)
def test_pair_map_equals_the_expert_map_where_every_expert_is_held(e, k, cf):
    x, logits, w1, w2 = setup(e)
    cap = moe.expert_capacity(T, e, k, cf)

    def by_experts(x, logits, w1, w2):
        # renormalised top-k weights as a (T, E) map, the existing path
        pw, pi, _ = moe.topk_renorm_weights(logits, k)
        probs = jnp.sum(jnp.where(
            pi[..., None] == jnp.arange(e), pw[..., None], 0.0), 1)
        return moe.moe_dispatch_combine(x, probs, expert_fn(w1, w2), cap)

    def by_pairs(x, logits, w1, w2):
        pw, pi, _ = moe.topk_renorm_weights(logits, k)
        out, held = moe.moe_held_dispatch_combine(
            x, pw, pi, expert_fn(w1, w2), cap, 0, e)
        return out

    np.testing.assert_allclose(jax.jit(by_pairs)(x, logits, w1, w2),
                               jax.jit(by_experts)(x, logits, w1, w2),
                               atol=1e-5)
    mix = jax.random.normal(jax.random.key(7), (T, D))
    grads = lambda fn: jax.jit(jax.grad(  # noqa: E731
        lambda *a: jnp.sum(fn(*a) * mix), argnums=(0, 1, 2, 3)
    ))(x, logits, w1, w2)
    for name, a, b in zip(("x", "logits", "w1", "w2"), grads(by_pairs),
                          grads(by_experts)):
        np.testing.assert_allclose(a, b, atol=2e-5, err_msg=name)
    # and the two count the same drops
    pw, pi, _ = moe.topk_renorm_weights(logits, k)
    held = moe.held_pair_probs(pw, pi, 0, e)
    dropped = float(moe.dispatch_drop_fraction(held, cap))
    assert (dropped > 0.0) == (cf <= 1.0)


def test_shares_of_the_experts_add_up_to_the_whole_layer():
    e, k, ranks = 16, 4, 4
    x, logits, w1, w2 = setup(e, seed=3)
    pw, pi, _ = moe.topk_renorm_weights(logits, k)
    cap = T  # no drops: the shares must add up exactly
    whole, _ = moe.moe_held_dispatch_combine(
        x, pw, pi, expert_fn(w1, w2), cap, 0, e)
    held = e // ranks
    parts = []
    for r in range(ranks):
        sl = slice(r * held, (r + 1) * held)
        part, probs = moe.moe_held_dispatch_combine(
            x, pw, pi, expert_fn(w1[sl], w2[sl]), cap, r * held, held)
        assert probs.shape == (T, held)
        parts.append(part)
    np.testing.assert_allclose(sum(parts), whole, atol=1e-5)
    # a rank far from the chosen experts computes nothing for a token
    counts = jnp.sum((pi >= 0) & (pi < held), axis=1)
    assert float(jnp.max(jnp.abs(parts[0][counts == 0]))) == 0.0


def test_no_tensor_of_tokens_by_experts_held_by_width_in_the_pair_map():
    """The compiled pair map, forward and backward, gathers k rows a token:
    nothing in it is shaped (T, experts held, D) or holds T * held rows."""
    e, k, held = 64, 2, 32
    x, logits, _, _ = setup(e)
    w1 = jnp.zeros((held, D, H))
    w2 = jnp.zeros((held, H, D))
    cap = moe.expert_capacity(T, e, k, 2.0)

    def loss(x, logits, w1, w2):
        pw, pi, _ = moe.topk_renorm_weights(logits, k)
        out, _ = moe.moe_held_dispatch_combine(
            x, pw, pi, expert_fn(w1, w2), cap, 0, held)
        return jnp.sum(out * out)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        x, logits, w1, w2).compile().as_text()
    assert f"[{T},{held},{D}]" not in text
    assert f"[{held},{T},{D}]" not in text
    assert f"[{T * held},{D}]" not in text
    assert f"[{T},{D}]" in text  # the rows a token does gather
