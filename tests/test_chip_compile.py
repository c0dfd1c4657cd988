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
    # bench.py's GPT row: one 256-wide head, in-kernel dropout
    "gpt_dropout": (128, 256, 1, 1, 256, 0.1),
    # the 342M llama3 study point: GQA 16 q heads over 8 kv heads
    "llama_gqa_1k": (8, 1024, 16, 8, 64, 0.0),
    # a ragged prefill chunk: no 128-divisible block, one q block
    "ragged_2016": (1, 2016, 8, 1, 128, 0.0),
    # qwen3next's gated attention: 16 q heads on 2 kv heads of width 256.
    # With the long-sequence 1024-tiles the compiler refuses the backward-dq
    # kernel here (VMEM), so `auto_block` keeps 512 past width 128
    "qwen3next_gqa_16k": (1, 16_384, 16, 2, 256, 0.0),
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


def _abstract_gdn(one_chip, seq=16_384):
    """qwen3next_ep16's Gated DeltaNet core: 16 key heads of 128 serving 32
    value heads of 128, one sequence of 16,384, bfloat16 q/k/v."""
    sds = lambda shape, dt: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one_chip)
    return (sds((1, seq, 16, 128), jnp.bfloat16),
            sds((1, seq, 16, 128), jnp.bfloat16),
            sds((1, seq, 32, 128), jnp.bfloat16),
            sds((1, seq, 32), jnp.float32), sds((1, seq, 32), jnp.float32))


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_gated_delta_rule_compiles_for_v5e(one_chip, backward):
    """The chunked gated delta rule at the cell's shape: a scan over
    segments whose body scans over chunks, and no loop over single tokens
    (no `while` of 16,384 trips: the trip counts are the 8 segments' and
    the 32 chunks' of a segment)."""
    from solvingpapers_tpu.ops.gated_delta import gated_delta_rule

    def loss(q, k, v, g, beta):
        return jnp.sum(gated_delta_rule(q, k, v, g, beta).astype(jnp.float32))

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if backward else loss
    args = _abstract_gdn(one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert " while(" in compiled.as_text()

    def scan_lengths(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                yield eqn.params["length"]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from scan_lengths(sub)

    trips = set(scan_lengths(jax.make_jaxpr(fn)(*args).jaxpr))
    assert trips == {8, 32}, trips
    # a segment's backward keeps a segment's intermediates, not 16k tokens'
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * 2 ** 30


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
