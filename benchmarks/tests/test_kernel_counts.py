"""Operation and byte counts against shapes worked by hand."""

import pytest

from benchmarks.kernels import deepseekv3_model, flash_mla
from benchmarks.reference.deepseekv3_ref import Sizes

FWD = ('%mla.27 = (bf16[8,16384,128]{2,1,0:T(8,128)(2,1)S(1)}, '
       'f32[8,1,16384]{2,1,0:T(1,128)}) custom-call(bf16[8,16384,128]{2,1,0} '
       '%q, bf16[1,16384,128]{2,1,0} %k), custom_call_target="tpu_custom_call"')
DQ = ('%mla.32 = bf16[8,16384,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call('
      'bf16[8,16384,128]{2,1,0} %q), custom_call_target="tpu_custom_call"')
DKV = ('%mla.41 = (bf16[8,16384,128]{2,1,0}, bf16[8,16384,128]{2,1,0}) '
       'custom-call(bf16[8,16384,128]{2,1,0} %q), '
       'custom_call_target="tpu_custom_call"')


def test_kernels_are_told_apart_by_what_they_return():
    assert flash_mla.kind_of(FWD) == "fwd"
    assert flash_mla.kind_of(DQ) == "bwd_dq"
    assert flash_mla.kind_of(DKV) == "bwd_dkv"
    assert flash_mla.kind_of('%fusion.1 = bf16[8]{0} fusion()') is None
    assert flash_mla.result_shapes(FWD) == [
        ("bf16", (8, 16384, 128)), ("f32", (8, 1, 16384))]


def test_flops_by_hand():
    # 4 queries, 1 head, width 2: QK^T is 4*4/2 = 8 live pairs * 2 MACs * 2
    assert flash_mla.flops("fwd", 4, 1, 2) == 2 * (2 * 8 * 2)
    assert flash_mla.flops("bwd_dq", 4, 1, 2) == 3 * (2 * 8 * 2)
    assert flash_mla.flops("bwd_dkv", 4, 1, 2) == 4 * (2 * 8 * 2)
    # the timed shape: 2 products * 2 * 8 heads * 16384^2/2 * 128
    assert flash_mla.flops("fwd", 16384, 8, 128) == pytest.approx(5.49756e11,
                                                                  rel=1e-5)


def test_bytes_by_hand():
    # fwd, S=4, n=1, w=2, bf16: q 16 + k,v 32 + o 16 + lse 16
    assert flash_mla.hbm_bytes("fwd", 4, 1, 2) == 16 + 32 + 16 + 16
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = flash_mla.least_seconds("fwd", 16384, 8, 128, peaks)
    assert bound == "compute" and t == pytest.approx(2.7906e-3, rel=1e-3)
    t, bound = flash_mla.least_seconds("fwd", 128, 8, 128, peaks)
    assert bound == "memory"


def test_model_flops_per_token():
    sz = Sizes(vocab=50257, block=256, dim=512, layers=6, heads=8, latent=64,
               experts=8, top_k=2)
    attn = 512 * 64 + 512 * 512 + 2 * 64 * 512 + 512 * 512
    moe = 512 * 8 + 3 * (3 * 512 * 1365)
    assert deepseekv3_model.active_params(sz) == 6 * (attn + moe) + 50257 * 512
    assert deepseekv3_model.train_flops_per_token(sz, 256) == (
        6 * deepseekv3_model.active_params(sz) + 12 * 6 * 512 * 256)
