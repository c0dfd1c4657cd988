"""The cell `keye_vl2_ep8.train_16k` on the CPU: its configuration file
against the catalog's row and the registry, through `train_job` at a tiny
cut of its own (the widths shrink here and nowhere else), the two planted
faults of `benchmarks/faults.py`, the selection switched off, and the int8
control against the same limits, the two count files against counts worked
by hand, and every new reader on a table of layer times and with nothing to
read."""

import json
import math
import os
import time

import pytest

from benchmarks import faults, harness
from benchmarks.kernels import dsa_rule, keye_vl_model
from benchmarks.reference.keye_vl_ref import Sizes, weight_shapes

CELL = "keye_vl2_ep8.train_16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_experts", "num_hidden_layers", "num_local_experts",
           "vocab_size"]
TINY_MODEL = dict(
    vocab_size=256, block_size=64, hidden_size=64, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=4,
    num_local_experts=4, router_experts=16, num_experts_per_tok=3,
    moe_intermediate_size=32, indexer_num_heads=2, indexer_head_dim=8,
    topk=32, capacity_factor=4.0)
# a tiny model's numbers, not the chip's. A sound bfloat16 run reads at most
# loss_gap 6.2e-4, first_grad_leaf_gap 0.018, delta_leaf_gap 5.4e-3 on two
# seeds, the int8 control at least 1.8e-3, 0.056, 9.4e-3 on three; the
# gradient's norm does not tell them apart at this size (4.2e-3 and 1.5e-3)
TINY_LIMITS = {"loss_gap": 1.2e-3, "grad_norm_gap": 2e-2,
               "first_grad_leaf_gap": 3.5e-2, "delta_leaf_gap": 7.5e-3}
NEW_READERS = ["dsa_ms", "dsa_selected_pct", "dsa_core_roofline_pct",
               "mfu_pct.keye_ep_share"]
SHARED_READERS = [
    "data_wait_ms", "trainer_data_wait_ms", "train_step_device_ms",
    "device_idle_pct.train", "peak_hbm_gib.train", "unscoped_device_pct",
    "moe_drop_pct", "moe_route_ms", "moe_experts_ms", "moe_held_pair_pct",
    "attention_ms", "loss_head_ms", "optimizer_ms",
    "startup_import_s", "startup_build_s", "startup_init_state_s",
    "startup_trace_lower_s", "startup_compile_s", "startup_first_step_s",
    "startup_program_s", "compile_cache_miss_count",
    "train_dispatch_max_ms", "host_gap_max_ms", "idle_named_pct"]


def tiny_files():
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    changed = {k for k, v in TINY_MODEL.items() if config["model"][k] != v}
    config["model"].update(TINY_MODEL)
    config["reduced"] = sorted(set(config["reduced"]) | changed)
    config["limits"]["train"].update(TINY_LIMITS, window_loss_rise=0.5)
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    traffic.update(corpus_tokens=20000, reference_q_block=32)
    return bench, cell, config, traffic


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    """No device memory to read; 64 tokens in two spans of 32 keys, each two
    blocks of 16 queries: the first selects every causal key, the second 32
    of up to 64."""
    from solvingpapers_tpu.models import keye_vl
    from solvingpapers_tpu.ops import dsa

    monkeypatch.setattr(harness, "peak_bytes", lambda n: (1, 1))
    monkeypatch.setattr(dsa, "Q_BLOCK", 16)
    monkeypatch.setattr(dsa, "KEY_STEP", 32)
    monkeypatch.setattr(keye_vl, "SEGMENT", 32)


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


def test_every_key_of_the_file_is_the_catalogs_but_the_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    assert conf["source"] == config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if config.get(k, "missing") != v)
    assert differ == conf["reduced"] == config["reduced"] == REDUCED
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128,
        "num_local_experts": 128, "vocab_size": 151936}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_local_experts"], config["vocab_size"]) == (
                4, 16, 16, 18992)
    assert config["sa_config"] == row["config"]["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    for key in ("why_reduced", "assumed", "deployment", "stands_for"):
        assert config[key], key
    assert {"job", "q_norm_k_norm", "rope", "indexer_rotation",
            "indexer_scale", "q_chunk_size_kv_chunk_size", "tie_rule",
            "indexer_loss", "router_aux_loss_coef", "capacity_factor",
            "initialisation", "block_size", "left_out"} <= set(
                config["assumed"])
    assert config["deployment"]["expert_parallel"] == 8
    assert config["deployment"]["experts_held"] == "0-15 of 128"
    assert "465,390,592" in config["stands_for"]
    limits = config["limits"]["train"]
    assert {"loss_gap", "grad_norm_gap", "first_grad_leaf_gap",
            "delta_leaf_gap", "window_loss_rise", "reasons"} == set(limits)


def test_the_file_keeps_every_published_width():
    """`reduced` is the depth, the experts held (under both of the source's
    names) and the vocabulary's slice, and nothing else differs from the
    registry's published entry (`train_job.run_config` refuses it
    otherwise); the cell is listed where its readers read."""
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    driver = harness.load_module("drivers", "train_job")
    traffic = harness.load_json(harness.HERE, "traffic", "train_16k.json")
    cfg = driver.run_config(config, dict(traffic, corpus_tokens=20000), 3)
    m = cfg.model
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "vocab_size", "num_hidden_layers", "num_experts",
                "num_local_experts", "num_experts_per_tok",
                "moe_intermediate_size", "norm_topk_prob", "rms_norm_eps",
                "rope_theta"):
        assert getattr(m, key) == config[key], key
    for key in ("indexer_num_heads", "indexer_head_dim",
                "indexer_num_kv_heads", "topk"):
        assert getattr(m, key) == config["sa_config"][key], key
    assert (m.router_experts, m.first_expert, m.block_size) == (128, 0, 16384)
    assert (cfg.train.batch_size, cfg.train.tokens_per_step) == (1, 16384)
    assert (cell["traffic"], cell["chips"]) == ("train_16k", 1)
    # a width is refused
    wrong = json.loads(json.dumps(config))
    wrong["model"]["topk"] = 1024
    with pytest.raises(harness.BenchFailure, match="topk"):
        driver.run_config(wrong, dict(traffic, corpus_tokens=20000), 3)
    listed = {x["name"] for x in harness.metrics_of(bench, "per_layer", CELL)}
    assert listed == set(NEW_READERS) | set(SHARED_READERS)
    for name in NEW_READERS:
        entry = next(x for x in bench["per_layer"] if x["name"] == name)
        assert entry["workloads"] == [CELL], name
        assert entry["moves"] == "train_tokens_per_s"


def test_sound_run_is_correct_and_prints_the_contract_line():
    run = tiny_run()
    line = run.result()
    assert line["correct"] is True, run.checks
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] == run.obs["steps"] >= 2 and line["failed"] == 0
    rows = run.obs["rows"]
    assert all("train_dsa_selected_fraction" in r and "train_dsa_index_kl" in r
               and "train_moe_held_pair_fraction" in r for r in rows)
    # the program counter's reader, on what the run logged: 32 * 33 / 2 +
    # 32 * 32 selected pairs of 64 * 65 / 2
    selected = harness.load_module("metrics", "dsa_selected_pct").read(run.obs)
    assert selected == pytest.approx(100 * 1552 / 2080)
    # on the CPU there is no trace: the trace readers find nothing
    for name in ("dsa_ms", "dsa_core_roofline_pct"):
        assert harness.load_module("metrics", name).read(run.obs) is None
    mfu = harness.load_module("metrics", "mfu_pct.keye_ep_share")
    assert mfu.read(run.obs) > 0.0


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


def test_a_program_that_does_not_select_is_not_correct(monkeypatch):
    """The program with `topk` past the sequence (plain causal attention)
    against the reference's top-32: outside the limits, and the counter
    reads 100."""
    import dataclasses

    from solvingpapers_tpu.configs import factory

    real = factory.build_model

    def build_model(cfg):
        return real(dataclasses.replace(cfg, model=dataclasses.replace(
            cfg.model, topk=64)))

    monkeypatch.setattr(factory, "build_model", build_model)
    adapter = harness.load_module("adapters", "keye_vl")
    sizes_of = adapter.sizes_of
    monkeypatch.setattr(adapter, "sizes_of", lambda m: dataclasses.replace(
        sizes_of(m), topk=TINY_MODEL["topk"]))
    run = tiny_run()
    assert run.result()["correct"] is False, run.checks
    assert harness.load_module("metrics", "dsa_selected_pct").read(
        run.obs) == pytest.approx(100.0)


def test_int8_control_fails_where_a_sound_run_passes():
    _, _, config, traffic = tiny_files()
    driver = harness.load_module("drivers", traffic["driver"])
    got = driver.control_readings(config, traffic, seed=5)
    over = {k for k in TINY_LIMITS if got[k] > TINY_LIMITS[k]}
    assert over == {"loss_gap", "first_grad_leaf_gap", "delta_leaf_gap"}, got


def sizes(**over):
    base = dict(vocab=100, block=64, dim=8, layers=2, heads=4, kv_heads=2,
                head_dim=2, rope_theta=1e4, idx_heads=2, idx_dim=4, topk=4,
                router=16, held=4, first=0, top_k=2, expert_hidden=3)
    return Sizes(**{**base, **over})


def published(layers=4, held=16, vocab=18992):
    return Sizes(vocab=vocab, block=16384, dim=2048, layers=layers, heads=32,
                 kv_heads=4, head_dim=128, rope_theta=1e7, idx_heads=16,
                 idx_dim=64, topk=2048, router=128, held=held, first=0,
                 top_k=8, expert_hidden=768)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_none_with_nothing_to_read(name):
    read = harness.load_module("metrics", name).read
    assert read({}) is None
    # an accepted cell's observations: DeepSeekV3's sizes, no indexer
    from benchmarks.reference.deepseekv3_ref import Sizes as DSizes

    obs = {"rows": [{"train_loss": 1.0}], "trace": None,
           "sizes": DSizes(vocab=8, block=8, dim=8, layers=1, heads=1,
                           latent=4, experts=2, top_k=1),
           "tokens_per_step": 8, "steps": 2, "window_s": 1.0, "seq_len": 8,
           "layer_ms": {"L_attn_core": 5.0}}
    assert read(obs) is None


def test_mechanism_counts_by_hand():
    sz = sizes()
    # 8 tokens, top-4: 36 causal pairs; 10 + 4 * 4 = 26 selected
    assert dsa_rule.causal_pairs(8) == 36
    assert dsa_rule.selected_pairs(8, 4) == 26
    assert dsa_rule.selected_pairs(3, 4) == 6
    # index 2 * 2 * 4 = 16 a pair, attention 2 * 4 * 2 = 16 a pair a product
    assert dsa_rule.flops("fwd", sz, 8) == 16 * 36 + 2 * 16 * 26
    assert dsa_rule.flops("bwd", sz, 8) == (2 * 16 + 4 * 16) * 26
    # bf16: q 8*4*2*2 = 128, k and v 2*8*2*2*2 = 128, indexer 8*3*4*2 + 8*2*4
    # = 256, p_t 26 * 4 = 104
    assert dsa_rule.hbm_bytes("fwd", sz, 8) == 128 + 128 + 256 + 128 + 104
    assert dsa_rule.hbm_bytes("bwd", sz, 8) == 744 + 128 + 512
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert dsa_rule.least_seconds(sz, 8, peaks) == pytest.approx(
        max(1.408, 0.744) + max(2.496, 1.384))
    # the reader: two layers, one sequence, over the four scopes' time
    obs = {"sizes": sz, "seq_len": 8, "batch_size": 1, "peaks": peaks,
           "layer_ms": {"L_dsa_index": 4000.0, "L_dsa_attend": 11000.0,
                        "L_dsa_select": 1000.0, "L_attn_proj": 7.0}}
    read = lambda n: harness.load_module("metrics", n).read(obs)  # noqa: E731
    assert read("dsa_ms") == 16000.0
    assert read("dsa_core_roofline_pct") == pytest.approx(
        100 * 2 * 3.904 / 16.0)
    # at the cell's size: 23.4% of the causal pairs, 1.95 TFLOP a layer
    assert dsa_rule.selected_pairs(16384, 2048) / dsa_rule.causal_pairs(
        16384) == pytest.approx(0.23437, abs=1e-5)
    assert sum(dsa_rule.flops(p, published(), 16384)
               for p in dsa_rule.PASSES) == pytest.approx(1.950e12, rel=1e-3)


def test_rank_flops_per_token_by_hand():
    sz = sizes()
    p = keye_vl_model.rank_params(sz)
    # q, k, v 8 x (4 + 2 * 2) x 2, o 8 x 8; the indexer 8 x (3 * 4 + 2);
    # router 8 x 16, routed 2 * 4 / 16 = 0.5 experts of 3 * 8 * 3
    assert p == {"attn": 128 + 64, "indexer": 112, "moe": 128 + 36,
                 "head": 800}
    weights = 2 * (192 + 112 + 164) + 800
    mechanism = 2 * (16 * 36 + 32 * 26 + 96 * 26) / 8
    assert keye_vl_model.train_flops_per_token(sz, 8) == 6.0 * weights + (
        mechanism)


def test_published_size_counts():
    """The cell's 465,390,592 parameters and the whole model's
    30,640,650,240 from the reference's shapes."""
    def total(sz):
        return sum(math.prod(shape)
                   for shape, _ in weight_shapes(sz).values())

    assert total(published()) == 465_390_592
    assert total(published(1)) - total(published(0)) == 96_899_328
    assert total(published(48, 128, 151936)) == 30_640_650_240
