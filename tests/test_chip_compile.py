"""The flash-attention kernels compiled for the TPU v5e, without one.

libtpu's compiler is installed here and compiles for a chip that is
described, not attached (`get_topology_desc`), so what Mosaic refuses on the
real chip — a block not aligned to the tiling, too much VMEM — fails here,
at the shapes the main path uses. A compile is not a run: nothing executes
and no result is checked. The interpret-mode suites check results.

This is the only test file that may describe a TPU, and it does so inside a
fixture: only one process may hold libtpu, every xdist worker imports every
test file, and a module that touched the TPU while being imported would make
the workers collect different tests. Everything built from the topology is
built in fixtures or tests; the compiles run in the test's own process.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from solvingpapers_tpu.kernels import flash_attention


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an executable compiled for a described chip can be written to the
    # persistent cache but not read back without one: keep the cache off
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


# (batch, seq, q heads, kv heads, head dim, dropout rate)
SHAPES = {
    # dsv3_long: absorbed-query MLA is MQA over the latent stream
    "mla_16k": (1, 16_384, 8, 1, 128, 0.0),
    # gpt_shakespeare's attention: one 256-wide head, in-kernel dropout
    "gpt_dropout": (128, 256, 1, 1, 256, 0.1),
    # the 342M llama3 study point: GQA 16 q heads over 8 kv heads
    "llama_gqa_1k": (8, 1024, 16, 8, 64, 0.0),
    # a ragged prefill chunk: no 128-divisible block, one q block
    "ragged_2016": (1, 2016, 8, 1, 128, 0.0),
    # qwen3next's gated attention: 16 q heads on 2 kv heads of width 256.
    # With the long-sequence 1024-tiles the compiler refuses the backward-dq
    # kernel here (VMEM), so `auto_block` keeps 512 past width 128 for the
    # two backward kernels; the forward kernel takes its own 1,024-tiles
    "qwen3next_gqa_16k": (1, 16_384, 16, 2, 256, 0.0),
    # nemotron3_nano_ep16's attention layer: 32 q heads on 2 kv heads of
    # width 128, sixteen query heads a key-value head, no rotation; at width
    # 128 all three kernels run in 1,024-tiles (`flash_blocks`)
    "nemotron_gqa_32on2_16k": (1, 16_384, 32, 2, 128, 0.0),
    # ouro_2p6b_pp6's layers: plain multi-head attention, 16 q heads on 16
    # kv heads of width 128, two sequences of 4,096 (the one cell with
    # batch 2; under LONG_SEQ, so 512-tiles for the backward kernels; the
    # forward kernel's own are 1,024 x 1,024)
    "ouro_mha_16on16_2x4k": (2, 4096, 16, 16, 128, 0.0),
    # granite4_h_micro_pp4's attention layer: 32 q heads on 8 kv heads of
    # width 64 (half the lanes: every block's last dimension is the
    # array's), one sequence of 8,192: the 1,024-tiles
    "granite_gqa_32on8_w64_8k": (1, 8192, 32, 8, 64, 0.0),
}


def _abstract_qkv(shape, sharding):
    b, s, n, n_kv, d, _ = shape
    q = jax.ShapeDtypeStruct((b, s, n, d), jnp.bfloat16, sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, s, n_kv, d), jnp.bfloat16,
                              sharding=sharding)
    return q, kv, kv


def _attend(rate):
    def f(q, k, v):
        # interpret=False: the default would ask jax.devices(), which is
        # the CPU here, and interpret the kernel instead of lowering it
        return flash_attention(q, k, v, causal=True, dropout_rate=rate,
                               dropout_seed=3, interpret=False)
    return f


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_flash_forward_compiles_for_v5e(one_chip, name):
    shape = SHAPES[name]
    compiled = jax.jit(_attend(shape[-1])).lower(
        *_abstract_qkv(shape, one_chip)
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_flash_backward_compiles_for_v5e(one_chip, name):
    """Forward + both backward kernels (dq; dk/dv) through the custom VJP."""
    shape = SHAPES[name]
    attend = _attend(shape[-1])

    def loss(q, k, v):
        return jnp.sum(attend(q, k, v).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_abstract_qkv(shape, one_chip)
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_with_a_value_width_of_its_own_compiles_for_v5e(
        one_chip, backward):
    """kimi_linear_ep32's latent attention, decompressed: 32 heads, keys 192
    wide (128 + 64, no whole number of 128-lane tiles: 256 lanes in VMEM),
    values 128, one sequence of 16,384. `auto_block` counts 192 as 256 and
    keeps the 512-tiles for the backward kernels, the forward kernel runs
    in its own; Mosaic takes the blocks of all three kernels."""
    sds = lambda h, w: jax.ShapeDtypeStruct(  # noqa: E731
        (1, 16_384, h, w), jnp.bfloat16, sharding=one_chip)
    from solvingpapers_tpu.kernels.flash_attention import flash_blocks

    assert flash_blocks(16_384, 16_384, 192, 128) == (
        (1024, 1024), (512, 512))

    def loss(q, k, v):
        out = flash_attention(
            q, k, v, causal=True, scale=192 ** -0.5, interpret=False)
        assert out.shape == (1, 16_384, 32, 128)
        return jnp.sum(out.astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else loss
    compiled = jax.jit(fn).lower(
        sds(32, 192), sds(32, 192), sds(32, 128)).compile()
    assert compiled.as_text().count("tpu_custom_call") == (3 if backward
                                                           else 1)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_with_a_selection_mask_compiles_for_v5e(one_chip, backward):
    """keye_vl2_ep8's attention over the indexer's keys (`ops/dsa.py`): 32
    heads on 4 of width 128, one sequence of 16,384, a (1, S, S) int8
    selection mask whose (block_q, block_k) tile sits in VMEM beside K and
    V in the forward kernel and both backward kernels, and `selected_probs`
    (the heads' mean probability, a float32 output tile) from the
    forward's log-sum-exp: Mosaic takes all four in the pairs
    `flash_blocks` and `PROBS_BLOCKS` give them, at the scoped VMEM the
    masked calls ask for."""
    from solvingpapers_tpu.kernels.flash_attention import (
        MASKED_BWD_BLOCKS, MASKED_FWD_BLOCKS, flash_blocks, selected_probs)

    sds = lambda dtype, *dims: jax.ShapeDtypeStruct(  # noqa: E731
        dims, dtype, sharding=one_chip)
    mask = sds(jnp.int8, 1, 16_384, 16_384)
    assert flash_blocks(16_384, 16_384, 128, 128, mask=mask) == (
        MASKED_FWD_BLOCKS, MASKED_BWD_BLOCKS)

    def loss(q, k, v, mask):
        out, lse = flash_attention(q, k, v, causal=True, mask=mask,
                                   return_lse=True, interpret=False)
        probs = selected_probs(q, k, lse, mask, causal=True, interpret=False)
        assert probs.shape == (1, 16_384, 16_384)
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(probs[:, :, :128])

    # the value too: the probabilities pass no gradient
    fn = jax.value_and_grad(loss, argnums=(0, 1, 2)) if backward else loss
    compiled = jax.jit(fn).lower(
        sds(jnp.bfloat16, 1, 16_384, 32, 128),
        sds(jnp.bfloat16, 1, 16_384, 4, 128),
        sds(jnp.bfloat16, 1, 16_384, 4, 128), mask).compile()
    assert compiled.as_text().count("tpu_custom_call") == (4 if backward
                                                           else 2)


def test_flash_prefill_chunk_compiles_for_v5e(one_chip):
    """The serving prefill of `dsv3_long`: a chunk of 512 queries over the
    16,384 latent rows written so far, end-aligned causal, one shared
    key-value head: the forward kernel alone, in a pair that is not square
    (each side shrinks to its own length)."""
    from solvingpapers_tpu.kernels.flash_attention import flash_blocks

    assert flash_blocks(512, 16_384, 128, 128)[0] == (512, 1024)
    sds = lambda s, h: jax.ShapeDtypeStruct(  # noqa: E731
        (1, s, h, 128), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(_attend(0.0)).lower(
        sds(512, 8), sds(16_384, 1), sds(16_384, 1)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


def _compiled_kda(one_chip, qk_shape, dv, backward):
    """The per-channel rule's kernels compiled for the described chip: q, k
    (B, S, H, dk) and v (B, S, H, dv) bfloat16, g float32, beta a head."""
    from solvingpapers_tpu.kernels.gated_delta import gated_delta_rule

    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    args = (sds(qk_shape, jnp.bfloat16), sds(qk_shape, jnp.bfloat16),
            sds(qk_shape[:3] + (dv,), jnp.bfloat16),
            sds(qk_shape, jnp.float32), sds(qk_shape[:3], jnp.float32))

    def loss(q, k, v, g, beta):
        # interpret=False: the default would ask jax.devices(), the CPU here
        return jnp.sum(gated_delta_rule(
            q, k, v, g, beta, chunk=64, sub=16,
            interpret=False).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if backward else loss
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_kda_rule_compiles_for_v5e(one_chip, backward):
    """The delta rule with a decay per key channel at the cell's shape (32
    heads of 128, one sequence of 16,384, bfloat16 q, k, v, float32 g):
    Mosaic takes the kernels' blocks and their VMEM (the sub-blocked pair
    sums, the triangular system and both of their backwards in one grid
    step), the rule is a kernel call forward and one backward, and no
    `while` is left of it."""
    compiled = _compiled_kda(one_chip, (1, 16_384, 32, 128), 128, backward)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + backward
    assert " while(" not in text
    # besides the result in float32 and the gradients: the entering states
    # (64 grid steps x 32 heads x 128 x 128 float32, 0.125 GiB) and beta's
    # rows; nothing a chunk's system made
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * 2 ** 30


def test_kda_rule_pads_narrow_heads_for_v5e(one_chip):
    """Key and value widths that are no multiple of the 128 lanes and a
    ragged length are padded, not refused (the padded channels carry a log
    decay of 0 and a key of 0: they write nothing)."""
    compiled = _compiled_kda(one_chip, (2, 300, 4, 64), 96, True)
    assert compiled.as_text().count("tpu_custom_call") == 2


def _abstract_gdn(one_chip, dtype, seq=16_384):
    """qwen3next_ep16's Gated DeltaNet core: 16 key heads of 128 serving 32
    value heads of 128, one sequence of 16,384."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    return (sds((1, seq, 16, 128), dtype), sds((1, seq, 16, 128), dtype),
            sds((1, seq, 32, 128), dtype),
            sds((1, seq, 32), jnp.float32), sds((1, seq, 32), jnp.float32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_gated_delta_rule_compiles_for_v5e(one_chip, backward, dtype):
    """The gated delta rule's kernels at the cell's shape (bfloat16 as the
    cell runs it, and float32): Mosaic takes their blocks and their VMEM,
    the rule is a kernel call forward (the one that also writes the
    entering states, under differentiation) and one backward, and no
    `while` is left of it."""
    from solvingpapers_tpu.kernels.gated_delta import gated_delta_rule

    def loss(q, k, v, g, beta):
        # interpret=False: the default would ask jax.devices(), the CPU here
        return jnp.sum(gated_delta_rule(
            q, k, v, g, beta, chunk=64, interpret=False).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if backward else loss
    compiled = jax.jit(fn).lower(*_abstract_gdn(one_chip, dtype)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + backward
    assert " while(" not in text
    # besides gradients and cotangents of the arguments' size, the entering
    # states (256 chunks x 32 heads x 128 x 128): 0.27 GB in bfloat16
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2 ** 30


def test_gated_delta_rule_pads_narrow_heads_for_v5e(one_chip):
    """Widths that are no multiple of the 128 lanes (and one value head a
    key head, a ragged length) are padded, not refused."""
    from solvingpapers_tpu.kernels.gated_delta import gated_delta_rule

    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    args = (sds((2, 300, 4, 64), jnp.bfloat16), sds((2, 300, 4, 64), jnp.bfloat16),
            sds((2, 300, 4, 96), jnp.bfloat16),
            sds((2, 300, 4), jnp.float32), sds((2, 300, 4), jnp.float32))

    def loss(q, k, v, g, beta):
        return jnp.sum(gated_delta_rule(
            q, k, v, g, beta, chunk=64, interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2


def _compiled_ssd(one_chip, x_shape, groups, state, backward, chunk=128):
    """The state-space rule's kernels compiled for the described chip: x (B,
    S, H, P) bfloat16, B and C (B, S, groups, state) bfloat16, the step
    float32, every argument differentiated."""
    from solvingpapers_tpu.kernels.ssd import ssd_chunked

    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    b, s, h, p = x_shape
    args = (sds(x_shape, jnp.bfloat16), sds((b, s, h), jnp.float32),
            sds((h,), jnp.float32), sds((b, s, groups, state), jnp.bfloat16),
            sds((b, s, groups, state), jnp.bfloat16), sds((h,), jnp.float32),
            sds((b, h, p, state), jnp.float32))

    def loss(x, dt, a, b, c, d, entering):
        # interpret=False: the default would ask jax.devices(), the CPU here
        y, last = ssd_chunked(x, dt, a, b, c, d, entering, chunk=chunk,
                              interpret=False)
        return jnp.sum(y.astype(jnp.float32)) + jnp.sum(last)

    fn = jax.grad(loss, argnums=tuple(range(7))) if backward else loss
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_ssd_rule_compiles_for_v5e(one_chip, backward):
    """The Mamba-2 recurrence at the cell's shape (64 heads of 64 on 8
    groups of state 128, one sequence of 16,384, chunks of 128, bfloat16 x,
    B, C, float32 step): Mosaic takes the kernels' blocks and their VMEM (a
    group's eight (128, 128) decay matrices of four chunks, and in the
    backward their cotangents: 18 MiB, over the default 16), the rule is a
    kernel call forward and one backward, and no `while` is left of it."""
    compiled = _compiled_ssd(one_chip, (1, 16_384, 64, 64), 8, 128, backward)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + backward
    assert " while(" not in text
    # besides the gradients: the entering states (32 grid steps x 64 heads x
    # 64 x 128 float32, 64 MiB) and the step's rows; nothing of size Q x Q
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2 ** 30


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_ssd_rule_at_one_group_of_64_heads_compiles_for_v5e(
        one_chip, backward):
    """granite4_h_micro_pp4's mixer: 64 heads of 64 on ONE group of state
    128, one sequence of 8,192, chunks of 256. A grid step is two chunks of
    a block of eight heads (the group's 64 at once would be 64 MiB of decay
    matrices), eight blocks read the group's B and C; Mosaic takes the
    blocks and the backward's VMEM inside the 32 MiB the kernel asks for.
    Besides the gradients: the entering states (16 steps x 64 heads, 32 MiB)
    and every block's own dB and dC in float32 (64 MiB), summed outside."""
    compiled = _compiled_ssd(one_chip, (1, 8192, 64, 64), 1, 128, backward,
                             chunk=256)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 + backward
    assert " while(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.25 * 2 ** 30


def test_ssd_rule_pads_narrow_heads_for_v5e(one_chip):
    """Heads of 48 (padded to 64: two share a tile of lanes), a state of 16
    (padded to the 128 lanes) and a ragged length are padded, not refused;
    the padded channels hold zeros and write nothing."""
    compiled = _compiled_ssd(one_chip, (2, 300, 4, 48), 2, 16, True)
    assert compiled.as_text().count("tpu_custom_call") == 2


# (experts, slots an expert, width, hidden width, gated, activation, calls)
MOE_SHAPES = {
    # the two DeepSeekV3 cells: hidden 1,365 padded to 1,408; an expert's
    # weights, sums and gradient blocks fit VMEM: one kernel backward
    "train_64x256": (8, 16_384, 512, 1365, True, "swish", 2),
    "train_16k": (8, 8_192, 512, 1365, True, "swish", 2),
    # the published widths: the backward's sums in blocks of H, dx apart
    "kimi_linear_ep32": (8, 2_048, 2304, 1024, True, "silu", 3),
    "nemotron3_nano_ep16": (8, 6_144, 2688, 1856, False, "relu2", 3),
}


@pytest.mark.parametrize("shape", list(MOE_SHAPES))
def test_moe_grouped_glu_compiles_for_v5e(one_chip, shape):
    """The routed experts' kernels at the cells' shapes (bfloat16, forward
    and backward): Mosaic takes their blocks and the VMEM they ask for. At
    the two DeepSeekV3 cells' one call forward and one backward, and each
    returns three arrays or more (the benchmark's `flash_mla.kind_of` reads
    a Mosaic call with one or two results as a flash-attention kernel); at
    the published widths, gated and not, the backward is two calls."""
    import re

    from solvingpapers_tpu import ops
    from solvingpapers_tpu.kernels.moe_grouped import grouped_glu

    e, c, d, h, gated, act, n_calls = MOE_SHAPES[shape]
    sds = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    args = (sds((e, c, d)), sds((e, d, h)), sds((e, d, h)), sds((e, h, d)),
            sds((e,), jnp.int32))

    def loss(xe, w1, w2, w3, fill):
        # interpret=False: the default would ask jax.devices(), the CPU here
        return jnp.sum(grouped_glu(
            xe, w1, w2 if gated else None, w3, fill,
            activation=getattr(ops, act), interpret=False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3) if gated else (0, 1, 3))
                   ).lower(*args).compile().as_text()
    calls = [line.split(" custom-call(")[0].split("=", 1)[1]
             for line in text.splitlines()
             if " custom-call(" in line and "tpu_custom_call" in line]
    assert len(calls) == n_calls
    if n_calls == 2:
        for results in calls:
            assert len(re.findall(r"\b[a-z]+\d+\[", results)) >= 3, results


def test_described_chip_is_in_the_peak_tables(topo):
    """`metrics/mfu.py` and `metrics/mesh_obs.py` key their peak tables by
    `device_kind`; the v5e's must resolve to its published peaks, not to
    the NaN sentinel for unknown chips."""
    from solvingpapers_tpu.metrics.mesh_obs import link_bandwidth_bytes_per_s
    from solvingpapers_tpu.metrics.mfu import chip_peak_flops

    dev = topo.devices[0]
    assert dev.device_kind == "TPU v5 lite"
    assert chip_peak_flops(dev) == 197e12
    assert link_bandwidth_bytes_per_s(dev) > 0
