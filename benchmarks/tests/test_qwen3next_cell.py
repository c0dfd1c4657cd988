"""The cell `qwen3next_ep16.train_16k` on the CPU: through `train_job` at a
tiny cut of its own (the widths shrink here and nowhere else), the two
planted faults of `benchmarks/faults.py` and the int8 control against the
same limits, the two count files against counts worked by hand, and every
new reader with nothing to read."""

import time

import pytest

from benchmarks import faults, harness
from benchmarks.kernels import flash_gqa, gated_delta_rule, qwen3next_model
from benchmarks.reference.qwen3next_ref import Sizes

CELL = "qwen3next_ep16.train_16k"
TINY_MODEL = dict(
    vocab_size=256, block_size=64, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, num_experts=4, router_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=32,
    shared_expert_intermediate_size=32, use_flash=False,
    capacity_factor=4.0)
# a tiny model's numbers, not the chip's
TINY_LIMITS = {"loss_gap": 5e-3, "grad_norm_gap": 2e-2,
               "first_grad_leaf_gap": 3e-2, "delta_leaf_gap": 1e-2}
NEW_READERS = ["gdn_ms", "gdn_core_ms", "gdn_core_roofline_pct",
               "moe_held_pair_pct", "mfu_pct.ep_share",
               "flash_gqa_fwd_roofline_pct", "flash_gqa_bwd_roofline_pct"]


def tiny_files():
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    changed = {k for k, v in TINY_MODEL.items() if config["model"][k] != v}
    config["model"].update(TINY_MODEL)
    config["reduced"] = sorted(set(config["reduced"]) | changed)
    config["limits"]["train"].update(TINY_LIMITS)
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    traffic.update(corpus_tokens=20000, reference_q_block=16)
    return bench, cell, config, traffic


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    monkeypatch.setattr(harness, "peak_bytes", lambda n: (1, 1))


def tiny_run(seed=2**31 + 11):
    bench, cell, config, traffic = tiny_files()
    run = harness.Run(
        workload=CELL, seed=seed, seconds=1.0, trace=False,
        t_start=time.perf_counter(), bench=bench, cell=cell, config=config,
        traffic=traffic,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        peaks=harness.peaks_for("TPU v5 lite"))
    run.watch_compiles()
    harness.load_module("drivers", traffic["driver"]).run(run)
    return run


def test_sound_run_is_correct_and_prints_the_contract_line():
    run = tiny_run()
    line = run.result()
    assert line["correct"] is True, run.checks
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] == run.obs["steps"] >= 2 and line["failed"] == 0
    rows = run.obs["rows"]
    assert all("train_moe_held_pair_fraction" in r
               and "train_moe_drop_fraction" in r for r in rows)
    # the program counter's reader, on what the run logged
    held = harness.load_module("metrics", "moe_held_pair_pct").read(run.obs)
    assert 0.0 < held < 100.0
    # on the CPU there is no trace: the trace readers find nothing
    for name in ("gdn_ms", "gdn_core_ms", "gdn_core_roofline_pct"):
        assert harness.load_module("metrics", name).read(run.obs) is None


@pytest.mark.parametrize("fault,fails", [
    ("frozen", {"delta_leaf_gap"}),
    ("half", {"loss_gap", "grad_norm_gap", "first_grad_leaf_gap"})])
def test_planted_fault_is_not_correct(monkeypatch, fault, fails):
    """A step that returns its state unchanged reads 1.0 where the weights'
    change is compared; a loss over half of the one sequence's tokens,
    counted twice, is what `loss_gap` is there for."""
    faults.plant(fault, monkeypatch.setattr)
    run = tiny_run()
    assert run.result()["correct"] is False
    failed = {c["check"] for c in run.checks if not c["ok"]}
    assert fails <= failed, run.checks


def test_int8_control_fails_where_a_sound_run_passes():
    _, _, config, traffic = tiny_files()
    driver = harness.load_module("drivers", traffic["driver"])
    got = driver.control_readings(config, traffic, seed=5)
    over = [k for k in TINY_LIMITS if got[k] > TINY_LIMITS[k]]
    assert {"grad_norm_gap", "first_grad_leaf_gap"} <= set(over), got


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_none_with_nothing_to_read(name):
    read = harness.load_module("metrics", name).read
    assert read({}) is None
    # an accepted cell's observations: DeepSeekV3's sizes, no GDN scopes
    from benchmarks.reference.deepseekv3_ref import Sizes as DSizes

    obs = {"rows": [{"train_loss": 1.0}], "trace": None,
           "sizes": DSizes(vocab=8, block=8, dim=8, layers=1, heads=1,
                           latent=4, experts=2, top_k=1),
           "tokens_per_step": 8, "steps": 2, "window_s": 1.0, "seq_len": 8}
    assert read(obs) is None


def sizes(**over):
    base = dict(vocab=100, block=64, dim=8, layers=4, interval=4, heads=2,
                kv_heads=1, head_dim=4, rotary_dim=2, rope_theta=1e4,
                gdn_k_heads=1, gdn_v_heads=2, gdn_k_dim=4, gdn_v_dim=6,
                conv=4, router=16, held=4, first=0, top_k=2,
                expert_hidden=3, shared_hidden=5)
    return Sizes(**{**base, **over})


def test_delta_rule_counts_by_hand():
    sz = sizes()
    # one chunk of 4 tokens: 8 pairs on or below... C^2/2 = 8
    # key head: k.k and q.k, 8 * dk(4) each -> 64 MACs
    # value head (2 of them): solve 8 * (6 + 4) = 80; state 3 * 4 * 4 * 6 =
    # 288; own part 8 * 6 = 48 -> 416 MACs each
    assert gated_delta_rule.forward_flops(sz, 4, 4) == 2 * (64 + 2 * 416)
    assert gated_delta_rule.forward_flops(sz, 8, 4) == 4 * (64 + 2 * 416)
    assert gated_delta_rule.flops("bwd", sz, 4, 4) == 4 * (64 + 2 * 416)
    # bytes, S=4, bf16: q,k 2*4*1*4*2 = 64; v 4*2*6*2 = 96; gates 2*4*2*4 = 64
    assert gated_delta_rule.hbm_bytes("fwd", sz, 4) == 64 + 96 + 64 + 96
    assert gated_delta_rule.hbm_bytes("bwd", sz, 4) == 320 + 64 + 96 + 64
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}
    assert gated_delta_rule.least_seconds(sz, 4, 4, peaks) == pytest.approx(
        max(1792 / 1e3, 320 / 1e2) + max(3584 / 1e3, 544 / 1e2))


def test_flash_gqa_counts_by_hand():
    # 2 query heads on 1 KV head of width 4, 8 tokens: 32 causal pairs
    assert flash_gqa.flops("flash_mla_fwd", 8, 2, 4) == 2 * 2 * 2 * 32 * 4
    assert flash_gqa.flops("flash_mla_bwd_dq", 8, 2, 4) == 3 * 2 * 2 * 32 * 4
    assert flash_gqa.flops("flash_mla_bwd_dkv", 8, 2, 4) == 4 * 2 * 2 * 32 * 4
    # bf16: q 2*8*4*2 = 128, k and v 2*(8*4*2) = 128, a float32 row 2*8*4 = 64
    assert flash_gqa.hbm_bytes("flash_mla_fwd", 8, 2, 1, 4) == 128 * 3 + 64
    assert flash_gqa.hbm_bytes("flash_mla_bwd_dq", 8, 2, 1, 4) == 128 * 4 + 128
    assert flash_gqa.hbm_bytes("flash_mla_bwd_dkv", 8, 2, 1, 4) == 128 * 4 + 128
    # one attention layer of four, one sequence: fwd needs 1.024 s at these
    # peaks by its operations (0.448 s by its bytes), the trace gives it 2 s
    obs = {"sizes": sizes(), "seq_len": 8, "batch_size": 1,
           "peaks": {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3},
           "layer_ms": {"flash_mla_fwd": 2000.0}}
    assert flash_gqa.roofline_share(obs, ("flash_mla_fwd",)) == pytest.approx(
        100 * 1.024 / 2.0)
    assert flash_gqa.roofline_share(obs, ("flash_mla_bwd_dq",)) is None


def test_rank_flops_per_token_by_hand():
    sz = sizes()
    p = qwen3next_model.rank_params(sz)
    # n_qk = 4, n_v = 12: qkvz 8*32, ba 8*4, conv 4*20, out 12*8
    assert p["gdn"] == 8 * 32 + 8 * 4 + 4 * 20 + 12 * 8
    # q (with gate) 8*16, k and v 8*4 each, o 8*8
    assert p["attn"] == 8 * 16 + 2 * 8 * 4 + 8 * 8
    # router 8*16, routed 2*4/16 = 0.5 experts of 3*8*3, shared 3*8*5 + 8
    assert p["moe"] == 8 * 16 + 0.5 * 72 + 120 + 8
    assert p["head"] == 800
    weights = 3 * p["gdn"] + p["attn"] + 4 * p["moe"] + 800
    scores = 2 * 2 * 4 * 10 / 2  # heads * 2 * head_dim * S / 2
    state = 3 * 3 * 2 * 4 * 6
    assert qwen3next_model.train_flops_per_token(sz, 10) == 6.0 * (
        weights + scores + state)
