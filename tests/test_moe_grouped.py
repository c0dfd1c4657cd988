"""The routed experts' grouped kernels (`kernels/moe_grouped.py`) against
the einsums of `MoELayer.expert_body` and `HeldExpertsMoE.expert_fn`,
through Pallas' interpreter, for both units (gated SwiGLU, ungated squared
ReLU) and both forms of the backward (one kernel; the sums in blocks of H
and dx in a kernel of its own):

  * forward and every gradient through `moe_dispatch_combine`, for fills
    that are empty, one row, not whole tiles, exactly C with drops, and a
    row of tied logits;
  * y and dx exactly zero behind an expert's fill;
  * the invariant the kernels rest on: an expert's filled slots are the
    prefix [0, fill) of its C;
  * a whole `MoELayer` step, and a `HeldExpertsMoE` step of each of the
    three families that use it, with the kernels equals the step with the
    einsums, and counts its live row tiles;
  * where the kernels engage, and how the backward is cut into blocks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from solvingpapers_tpu import ops
from solvingpapers_tpu.kernels import moe_grouped
from solvingpapers_tpu.models import kimi_linear, nemotron_h, qwen3next
from solvingpapers_tpu.models.deepseekv3 import DeepSeekV3Config, MoELayer

pytestmark = pytest.mark.fast

TILE = 8
T, E, K, C, D, H = 32, 5, 2, 16, 16, 40  # H pads to 128 lanes
# the unit: (the activation, whether there is a gate)
UNITS = {"swiglu": (ops.swish, True), "relu2": (ops.relu2, False)}
# the backward: (H, `SUMS_VMEM`): one kernel, or two with the sums in two
# blocks of the 256 lanes that H = 200 pads to (the budget holds 128 rows of
# the widest case, three float32 weights of D, and not 256 of the narrowest);
# and both again at an H of whole lanes, where w1 and w2 go in untransposed
BACKWARDS = {"one_kernel": (H, None), "h_blocks": (200, 128 * 832),
             "one_kernel_whole_lanes": (128, None),
             "h_blocks_whole_lanes": (256, 128 * 832)}


@pytest.fixture(autouse=True)
def small_tile(monkeypatch):
    monkeypatch.setattr(moe_grouped, "ROW_TILE", TILE)


def _logits(first: np.ndarray) -> np.ndarray:
    """(T, E) logits: every token's first choice is expert 4 (32 pairs on 16
    slots: full, and it drops), its second `first[t]`."""
    logits = np.full((T, E), -4.0, np.float32)
    logits[:, 4] = 2.0
    logits[np.arange(T), first] = 1.0
    return logits


def _case(name: str):
    """(logits, the fills they must give) of a routing case."""
    if name == "empty_one_ragged_full":
        # expert 0 none, 1 one row, 2 thirteen (not whole tiles), 3 the
        # other eighteen on sixteen slots
        first = np.array([1] + [2] * 13 + [3] * 18)
        return _logits(first), [0, 1, 13, 16, 16]
    if name == "whole_tiles":
        first = np.array([0] * 8 + [1] * 8 + [2] * 16)
        return _logits(first), [8, 8, 16, 0, 16]
    if name == "tied_row":
        # token 0 ties experts 0 to 3 below its first choice: the gate
        # selects all five (`topk_gate_probs` keeps every logit >= the k-th)
        logits = _logits(np.array([0] + [1] * 15 + [2] * 16))
        logits[0, :4] = 1.0
        return logits, [1, 16, 16, 1, 16]
    if name == "all_to_one":
        logits = np.full((T, E), -4.0, np.float32)
        logits[:, 2], logits[:, 3] = 2.0, 1.0
        return logits, [0, 0, 16, 16, 0]
    assert name == "random"
    logits = np.random.default_rng(0).normal(size=(T, E)).astype(np.float32)
    return logits, None


CASES = ["empty_one_ragged_full", "whole_tiles", "tied_row", "all_to_one",
         "random"]


def _weights(unit="swiglu", dtype=jnp.float32, e=E, d=D, h=H):
    """{"w1", "w3"} and, where the unit has a gate, "w2"."""
    k = jax.random.split(jax.random.key(1), 3)
    ws = {name: (jax.random.normal(k[i], s) * 0.3).astype(dtype)
          for i, (name, s) in enumerate(
              [("w1", (e, d, h)), ("w2", (e, d, h)), ("w3", (e, h, d))])}
    if not UNITS[unit][1]:
        del ws["w2"]
    return ws


def _einsums(unit, xe, ws):
    a = UNITS[unit][0](jnp.einsum("ecd,edh->ech", xe, ws["w1"]))
    if "w2" in ws:
        a = a * jnp.einsum("ecd,edh->ech", xe, ws["w2"])
    return jnp.einsum("ech,ehd->ecd", a, ws["w3"])


def _grouped(unit, xe, ws, fill):
    return moe_grouped.grouped_glu(
        xe, ws["w1"], ws.get("w2"), ws["w3"], fill,
        activation=UNITS[unit][0])


def _set_backward(monkeypatch, backward, unit, dtype) -> int:
    """H of the case, `SUMS_VMEM` shrunk where it asks for blocks."""
    h, budget = BACKWARDS[backward]
    if budget is not None:
        monkeypatch.setattr(moe_grouped, "SUMS_VMEM", budget)
    blocks = moe_grouped._h_blocks(
        -(-h // 128) * 128, D, jnp.dtype(dtype).itemsize, 2 + UNITS[unit][1])
    assert blocks == (0 if budget is None else 2)
    return h


def _moe(unit, x, ws, probs, grouped: bool):
    def expert_fn(xe, fill):
        if grouped:
            return _grouped(unit, xe, ws, fill)
        return _einsums(unit, xe, ws)

    return ops.moe.moe_dispatch_combine(x, probs, expert_fn, C, pass_fill=True)


@pytest.mark.parametrize("backward", list(BACKWARDS))
@pytest.mark.parametrize("unit", list(UNITS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_forward_and_every_gradient_match_the_einsums(
        monkeypatch, case, dtype, unit, backward):
    h = _set_backward(monkeypatch, backward, unit, dtype)
    logits, fills = _case(case)
    probs = ops.moe.topk_gate_probs(jnp.asarray(logits), K)
    if fills is not None:
        np.testing.assert_array_equal(ops.moe._routes(probs, C).fill, fills)
    x = jax.random.normal(jax.random.key(2), (T, D)).astype(dtype)
    ws = _weights(unit, dtype, h=h)
    dout = jax.random.normal(jax.random.key(3), (T, D))

    def loss(grouped):
        def f(x, ws):
            out = _moe(unit, x, ws, probs, grouped)
            return jnp.sum(out.astype(jnp.float32) * dout), out
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

    (_, out), (dx, dws) = loss(True)(x, ws)
    (_, want), (want_dx, want_dws) = loss(False)(x, ws)
    # float32: both sum in float32, in another order. bfloat16: the kernels
    # round a, g, h, da, dg once where the einsums' chain rounds each
    # product's result, so they differ by roundings of the operands
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == jnp.float32 else \
        dict(rtol=5e-2, atol=5e-2)
    f32 = lambda a: np.asarray(a, np.float32)  # noqa: E731
    scale = max(1.0, float(np.abs(f32(want)).max()))
    np.testing.assert_allclose(f32(out) / scale, f32(want) / scale, **tol)
    assert set(dws) == set(ws)
    for name, got, ref in [("x", dx, want_dx)] + [
            (k, dws[k], want_dws[k]) for k in ws]:
        scale = max(1.0, float(np.abs(f32(ref)).max()))
        np.testing.assert_allclose(f32(got) / scale, f32(ref) / scale,
                                   err_msg=name, **tol)


@pytest.mark.parametrize("backward", list(BACKWARDS))
@pytest.mark.parametrize("unit", list(UNITS))
@pytest.mark.parametrize("fills", [[0, 1, 13, 16], [8, 16, 0, 9], [0, 0, 0, 0]],
                         ids=["ragged", "tile_edges", "all_empty"])
def test_output_and_dx_are_exactly_zero_behind_the_fill(
        monkeypatch, fills, unit, backward):
    h = _set_backward(monkeypatch, backward, unit, jnp.float32)
    fill = jnp.asarray(fills, jnp.int32)
    e = len(fills)
    behind = np.arange(C)[None, :] >= np.asarray(fills)[:, None]  # (E, C)
    xe = jax.random.normal(jax.random.key(4), (e, C, D))
    xe = jnp.where(behind[..., None], 0.0, xe)
    # a cotangent that is NOT zero behind the fill: dx still is, since
    # a = g = 0 there makes da = dg = 0 whatever arrives
    dye = jax.random.normal(jax.random.key(5), (e, C, D))
    ws = _weights(unit, e=e, h=h)
    ye, vjp = jax.vjp(lambda xe, ws: _grouped(unit, xe, ws, fill), xe, ws)
    dxe, dws = vjp(dye)
    assert not np.asarray(ye)[behind].any()
    assert not np.asarray(dxe)[behind].any()
    np.testing.assert_allclose(ye, _einsums(unit, xe, ws),
                               rtol=2e-5, atol=2e-5)
    # an expert that holds nothing gets no weight gradient
    for dw in dws.values():
        assert not np.asarray(dw)[np.asarray(fills) == 0].any()


@pytest.mark.parametrize("capacity", [8, C, 40], ids=["C8", "C16", "C40>T"])
@pytest.mark.parametrize("case", CASES)
def test_filled_slots_are_a_prefix(case, capacity):
    """`slot_tok[e, :fill[e]]` are tokens in ascending order, everything
    behind them the sentinel T; fill = min(routed pairs, C)."""
    logits, _ = _case(case)
    probs = ops.moe.topk_gate_probs(jnp.asarray(logits), K)
    routes = ops.moe._routes(probs, capacity)
    slot_tok, fill = np.asarray(routes.slot_tok), np.asarray(routes.fill)
    load = np.asarray(probs > 0).sum(axis=0)
    np.testing.assert_array_equal(fill, np.minimum(load, capacity))
    assert slot_tok.shape == (E, capacity)
    for e in range(E):
        head, tail = slot_tok[e, :fill[e]], slot_tok[e, fill[e]:]
        assert (head < T).all() and (np.diff(head) > 0).all()
        assert (tail == T).all()
    # so the dispatched rows behind the fill are zero rows
    xe = ops.moe._dispatch(jnp.ones((T, D)), probs, capacity)[1]
    filled = np.arange(capacity)[None, :] < fill[:, None]
    np.testing.assert_array_equal(np.asarray(xe).any(axis=-1), filled)


def _as_one_tpu(monkeypatch):
    """Steer `engages` as the one-chip TPU would (the kernels still run
    through the interpreter: `grouped_glu` asks the real platform)."""
    monkeypatch.setattr(moe_grouped, "is_tpu_backend", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda: 1)


@pytest.mark.parametrize("capacity_factor", [1.0, 4.0], ids=["drops", "roomy"])
def test_moe_layer_step_with_kernels_equals_the_einsums(monkeypatch,
                                                        capacity_factor):
    cfg = DeepSeekV3Config(
        vocab_size=64, block_size=32, dim=128, n_layers=1, n_heads=2,
        latent_dim=16, n_experts=4, top_experts=2, dropout=0.0,
        attn_dropout=0.0, capacity_factor=capacity_factor)
    layer = MoELayer(cfg)
    x = jax.random.normal(jax.random.key(6), (2, 32, cfg.dim))
    params = layer.init(jax.random.key(7), x)["params"]
    # a routing bias that crowds expert 0, so that capacity binds
    state = {"moe_state": {"routing_bias": jnp.array([1.5, 0.0, 0.0, -1.0])}}

    def step(params):
        def f(params, x):
            out, mut = layer.apply(
                {"params": params, **state}, x, deterministic=False,
                mutable=["moe_state", "moe_metrics"])
            return jnp.sum(out ** 2), mut["moe_metrics"]["stats"][0]
        return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(params, x)

    (want, want_stats), want_grads = step(params)
    _as_one_tpu(monkeypatch)
    cap = ops.moe.expert_capacity(64, 4, 2, capacity_factor)
    assert moe_grouped.engages(cap, cfg.dim)
    (got, stats), grads = step(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5),
        grads, want_grads)
    # same capacity, same drops; the einsums multiply every tile
    assert float(stats["drop_fraction"]) == float(want_stats["drop_fraction"])
    assert (float(stats["drop_fraction"]) > 0) == (capacity_factor == 1.0)
    assert float(want_stats["live_tile_fraction"]) == 1.0
    # a whole number of the layer's tiles; where C is twice the tokens, at
    # most half of them
    total = 4 * cap // TILE
    tiles = float(stats["live_tile_fraction"]) * total
    assert tiles == pytest.approx(round(tiles)) and 0 < tiles <= total
    if capacity_factor == 4.0:
        assert tiles <= total // 2


# a rank's layer of each family that uses `HeldExpertsMoE`, as its config
# words it, at a width of whole lanes: 96 tokens, 3 of 16 experts a token, 4
# held; softmax and gated, sigmoid and gated (its experts a whole number of
# lanes wide, as published), sigmoid and two matrices around a squared ReLU
HELD = dict(hidden_size=128, num_experts=4, router_experts=16, first_expert=4,
            moe_intermediate_size=24, dtype="float32")
FAMILIES = {
    "qwen3next": lambda cf: qwen3next.held_moe(qwen3next.Qwen3NextConfig(
        **HELD, num_experts_per_tok=3, shared_expert_intermediate_size=24,
        capacity_factor=cf)),
    "kimi_linear": lambda cf: kimi_linear.held_moe(
        kimi_linear.KimiLinearConfig(
            **{**HELD, "moe_intermediate_size": 128},
            num_experts_per_token=3, capacity_factor=cf)),
    "nemotron_h": lambda cf: nemotron_h.held_moe(nemotron_h.NemotronHConfig(
        **{k: v for k, v in HELD.items() if k != "num_experts"},
        n_routed_experts=4, num_experts_per_tok=3,
        moe_shared_expert_intermediate_size=48, capacity_factor=cf)),
}


def _held_step(layer, params, x):
    def f(params, x):
        out, mut = layer.apply({"params": params}, x, mutable=["moe_metrics"])
        return jnp.sum(out ** 2), mut["moe_metrics"]["stats"][0]
    return jax.value_and_grad(f, argnums=(0, 1), has_aux=True)(params, x)


@pytest.mark.parametrize("capacity_factor", [0.25, 2.0], ids=["drops", "roomy"])
@pytest.mark.parametrize("family", list(FAMILIES))
def test_held_experts_step_with_kernels_equals_the_einsums(
        monkeypatch, family, capacity_factor):
    """Output, every parameter's gradient and dx; the same capacity and the
    same drops; fewer row tiles multiplied than the layer has."""
    layer = FAMILIES[family](capacity_factor)
    assert layer.gated == (family != "nemotron_h")
    x = jax.random.normal(jax.random.key(8), (2, 48, 128))
    params = layer.init(jax.random.key(9), x)["params"]
    (want, want_stats), want_grads = _held_step(layer, params, x)
    _as_one_tpu(monkeypatch)
    cap = ops.moe.expert_capacity(96, 16, 3, capacity_factor)
    assert moe_grouped.engages(cap, 128)
    (got, stats), grads = _held_step(layer, params, x)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-5),
        grads, want_grads)
    assert float(stats["drop_fraction"]) == float(want_stats["drop_fraction"])
    assert (float(stats["drop_fraction"]) > 0) == (capacity_factor == 0.25)
    assert float(want_stats["live_tile_fraction"]) == 1.0
    # a whole number of the layer's tiles; where the slots are twice an
    # expert's share, not all of them
    total = 4 * cap // TILE
    tiles = float(stats["live_tile_fraction"]) * total
    assert tiles == pytest.approx(round(tiles)) and 0 < tiles <= total
    if capacity_factor == 2.0:
        assert tiles < total


@pytest.mark.parametrize("family", list(FAMILIES))
def test_held_experts_keep_the_einsums_at_a_ragged_capacity(monkeypatch,
                                                            family):
    """40 slots an expert are no whole number of 16-row tiles: on the one
    TPU too the einsums run over every slot, and say so."""
    layer = FAMILIES[family](2.0)
    x = jax.random.normal(jax.random.key(8), (2, 48, 128))
    params = layer.init(jax.random.key(9), x)["params"]
    want, want_grads = _held_step(layer, params, x)
    _as_one_tpu(monkeypatch)
    monkeypatch.setattr(moe_grouped, "ROW_TILE", 16)
    monkeypatch.setattr(
        moe_grouped, "grouped_glu",
        lambda *a, **k: pytest.fail("the kernels at a ragged capacity"))
    assert ops.moe.expert_capacity(96, 16, 3, 2.0) == 40
    got, grads = _held_step(layer, params, x)
    assert float(got[1]["live_tile_fraction"]) == 1.0
    jax.tree.map(np.testing.assert_array_equal, (got, grads),
                 (want, want_grads))


@pytest.mark.parametrize("tpu, devices, capacity, dim, want", [
    (True, 1, 64, 128, True),
    (False, 1, 64, 128, False),  # the CPU: einsums
    (True, 4, 64, 128, False),  # a mesh: a pallas_call is opaque to GSPMD
    (True, 1, 60, 128, False),  # not whole row tiles
    (True, 1, 0 + TILE // 2, 128, False),  # a decode call's few slots
    (True, 1, 64, 96, False),  # not whole lanes
], ids=["one_tpu", "cpu", "mesh", "ragged_capacity", "few_slots", "narrow"])
def test_where_the_kernels_engage(monkeypatch, tpu, devices, capacity, dim,
                                  want):
    monkeypatch.setattr(moe_grouped, "is_tpu_backend", lambda: tpu)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    assert moe_grouped.engages(capacity, dim) is want


def test_grouped_glu_refuses_a_ragged_capacity():
    with pytest.raises(ValueError, match="whole tiles"):
        _grouped("swiglu", jnp.zeros((2, TILE + 1, D)), _weights(e=2),
                 jnp.zeros((2,), jnp.int32))
