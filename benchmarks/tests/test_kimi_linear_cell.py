"""The cell `kimi_linear_ep32.train_16k` on the CPU: through `train_job` at
a tiny cut of its own (the widths shrink here and nowhere else), the two
planted faults of `benchmarks/faults.py` and the int8 control against the
same limits, the three count files against counts worked by hand, and every
new reader with nothing to read."""

import time

import pytest

from benchmarks import faults, harness
from benchmarks.kernels import flash_mla_nope, kda_rule, kimi_linear_model
from benchmarks.reference.kimi_linear_ref import Sizes

CELL = "kimi_linear_ep32.train_16k"
TINY_MODEL = dict(
    vocab_size=256, block_size=64, hidden_size=64, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    linear_num_heads=4, linear_head_dim=16, intermediate_size=96,
    num_experts=4, router_experts=16, num_experts_per_token=3,
    moe_intermediate_size=32, use_flash=False, capacity_factor=4.0)
# a tiny model's numbers, not the chip's
TINY_LIMITS = {"loss_gap": 5e-3, "grad_norm_gap": 2e-2,
               "first_grad_leaf_gap": 3e-2, "delta_leaf_gap": 1e-2}
NEW_READERS = ["kda_ms", "kda_core_ms", "kda_core_roofline_pct",
               "dense_ffn_ms", "mfu_pct.kimi_ep_share",
               "flash_mla_nope_fwd_roofline_pct",
               "flash_mla_nope_bwd_roofline_pct"]


def tiny_files():
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    changed = {k for k, v in TINY_MODEL.items() if config["model"][k] != v}
    config["model"].update(TINY_MODEL)
    config["reduced"] = sorted(set(config["reduced"]) | changed)
    # a second of window is some thirty steps of a warm-up that starts at
    # zero: at this size the loss does not reliably fall in them
    config["limits"]["train"].update(TINY_LIMITS, window_loss_rise=0.5)
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


def test_the_file_keeps_every_published_width():
    """`reduced` is depth, experts held and vocabulary, and nothing else
    differs from the registry's published entry (`train_job.run_config`
    refuses it otherwise); the cell is listed where its readers read."""
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    assert conf["reduced"] == config["reduced"] == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    driver = harness.load_module("drivers", "train_job")
    cfg = driver.run_config(
        config, {"batch_size": 1, "corpus_tokens": 20000, "corpus_seed": 0,
                 "zipf_exponent": 1.1}, 3)
    m = cfg.model
    assert (m.num_hidden_layers, m.num_experts, m.vocab_size) == (5, 8, 20480)
    assert [m.is_attention_layer(i) for i in range(5)] == [
        False, False, False, True, False]
    assert (m.hidden_size, m.router_experts, m.num_experts_per_token) == (
        config["hidden_size"], 256, config["num_experts_per_token"])
    assert config["linear_attn_config"]["full_attn_layers"] == list(
        m.full_attn_layers)
    listed = {x["name"] for x in harness.metrics_of(bench, "per_layer", CELL)}
    assert set(NEW_READERS) <= listed and len(listed) == 20
    assert "flash_share_pct" not in listed


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
    for name in ("kda_ms", "kda_core_ms", "kda_core_roofline_pct",
                 "dense_ffn_ms", "flash_mla_nope_fwd_roofline_pct"):
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
    # an accepted cell's observations: DeepSeekV3's sizes, no KDA scopes
    from benchmarks.reference.deepseekv3_ref import Sizes as DSizes

    obs = {"rows": [{"train_loss": 1.0}], "trace": None,
           "sizes": DSizes(vocab=8, block=8, dim=8, layers=1, heads=1,
                           latent=4, experts=2, top_k=1),
           "tokens_per_step": 8, "steps": 2, "window_s": 1.0, "seq_len": 8}
    assert read(obs) is None


def sizes(**over):
    base = dict(vocab=100, block=64, dim=8, layers=5, attn_layers=(4, 8),
                dense_layers=1, heads=2, latent=4, nope_dim=4, rope_dim=2,
                v_dim=4, kda_heads=2, kda_dim=4, conv=4, dense_hidden=12,
                router=16, held=4, first=0, top_k=2, expert_hidden=3,
                shared_hidden=3)
    return Sizes(**{**base, **over})


def test_kda_rule_counts_by_hand():
    sz = sizes()
    # one chunk of 4 tokens: C^2/2 = 8 pairs. A head (2 of them): the
    # k-pairs and q-pairs 8 * dk(4) each = 64; solve 8 * (4 + 4) = 64;
    # state 3 * 4 * 4 * 4 = 192; own part 8 * 4 = 32 -> 352 MACs
    assert kda_rule.forward_flops(sz, 4, 4) == 2 * 2 * 352
    assert kda_rule.forward_flops(sz, 8, 4) == 4 * 2 * 352
    assert kda_rule.flops("bwd", sz, 4, 4) == 4 * 2 * 352
    # bytes, S=4, bf16: q,k 2*4*2*4*2 = 128; v 4*2*4*2 = 64; the decay a
    # channel 4*2*4*4 = 128 and beta 4*2*4 = 32 -> 160
    assert kda_rule.hbm_bytes("fwd", sz, 4) == 128 + 64 + 160 + 64
    assert kda_rule.hbm_bytes("bwd", sz, 4) == 416 + 128 + 64 + 160
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}
    assert kda_rule.least_seconds(sz, 4, 4, peaks) == pytest.approx(
        max(1408 / 1e3, 416 / 1e2) + max(2816 / 1e3, 768 / 1e2))


def test_flash_mla_nope_counts_by_hand():
    # 2 heads, keys 6 wide, values 4, 8 tokens: 32 causal pairs
    f = flash_mla_nope
    assert f.flops("flash_mla_fwd", 8, 2, 6, 4) == 2 * 2 * 32 * (6 + 4)
    assert f.flops("flash_mla_bwd_dq", 8, 2, 6, 4) == 2 * 2 * 32 * (12 + 4)
    assert f.flops("flash_mla_bwd_dkv", 8, 2, 6, 4) == 2 * 2 * 32 * (12 + 8)
    # bf16: q or k 2*8*6*2 = 192, v or o 2*8*4*2 = 128, a float32 row 64
    assert f.hbm_bytes("flash_mla_fwd", 8, 2, 6, 4) == 2 * 192 + 2 * 128 + 64
    assert f.hbm_bytes("flash_mla_bwd_dq", 8, 2, 6, 4) == (
        3 * 192 + 2 * 128 + 128)
    assert f.hbm_bytes("flash_mla_bwd_dkv", 8, 2, 6, 4) == (
        3 * 192 + 3 * 128 + 128)
    # one attention layer of five, one sequence: fwd needs 1.28 s at these
    # peaks by its operations (0.704 s by its bytes), the trace gives it 4 s
    obs = {"sizes": sizes(), "seq_len": 8, "batch_size": 1,
           "peaks": {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3},
           "layer_ms": {"flash_mla_fwd": 4000.0}}
    assert f.roofline_share(obs, ("flash_mla_fwd",)) == pytest.approx(
        100 * 1.28 / 4.0)
    assert f.roofline_share(obs, ("flash_mla_bwd_dq",)) is None


def test_rank_flops_per_token_by_hand():
    sz = sizes()
    p = kimi_linear_model.rank_params(sz)
    # n = 8: qkv 8*24, fob 8*(8+2), two up-projections 2*4*8, conv 4*24,
    # out 8*8
    assert p["kda"] == 8 * 24 + 8 * 10 + 64 + 4 * 24 + 8 * 8
    # q 8*2*6, down 8*(4+2), up 4*2*(4+4), o 2*4*8
    assert p["attn"] == 8 * 12 + 8 * 6 + 4 * 16 + 8 * 8
    assert p["dense"] == 3 * 8 * 12
    # router 8*16, routed 2*4/16 = 0.5 experts of 3*8*3, shared 3*8*3
    assert p["moe"] == 8 * 16 + 0.5 * 72 + 72
    assert p["head"] == 800
    weights = 4 * p["kda"] + p["attn"] + p["dense"] + 4 * p["moe"] + 800
    scores = 2 * (6 + 4) * 10 / 2  # heads * (wk + wv) * S / 2
    state = 4 * 3 * 2 * 4 * 4
    assert kimi_linear_model.train_flops_per_token(sz, 10) == 6.0 * (
        weights + scores + state)
