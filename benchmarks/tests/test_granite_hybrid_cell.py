"""The cell `granite4_h_micro_pp4.train_8k` on the CPU: its configuration
file against the catalog's row and the registry, through `train_job` at a
tiny cut of its own (the widths shrink here and nowhere else), the two
planted faults of `benchmarks/faults.py`, a program that takes
head_dim^-0.5 for the softmax or forgets the residual factor, and the int8
control against the same limits, the count files against counts worked by
hand, and every new reader on a table of layer times and with nothing to
read."""

import dataclasses
import json
import math
import os
import time

import pytest

from benchmarks import faults, harness
from benchmarks.kernels import (
    flash_gqa, flash_gqa_32on8_w64, granite_hybrid_model, ssd_rule,
)
from benchmarks.reference.granite_hybrid_ref import Sizes, weight_shapes

CELL = "granite4_h_micro_pp4.train_8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY_MODEL = dict(
    vocab_size=256, block_size=64, hidden_size=64, intermediate_size=96,
    shared_intermediate_size=96, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, attention_multiplier=1.0,
    mamba_n_heads=16, mamba_d_head=8, mamba_d_state=8, mamba_chunk_size=16,
    use_flash=False)
# two layers, the mixer and the attention layer: the published pattern's
# first two are both `mamba`
TINY_TYPES = ("mamba", "attention")
# a tiny model's numbers, not the chip's: with 0.22 on every add and the
# logits divided by 8 the tiny model is nearly linear in its layers, and
# the int8 control stands close by. A sound bfloat16 run on the test's seed
# reads grad_norm_gap 1.8e-4 and delta_leaf_gap 4.4e-4, the control on that
# seed 1.06e-3 and 2.27e-3 (5.2e-4 and 9.5e-4 at the least over four seeds)
TINY_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 4e-4,
               "first_grad_leaf_gap": 5e-2, "delta_leaf_gap": 8e-4}
NEW_READERS = ["mfu_pct.granite_pp4", "flash_gqa_32on8_w64_fwd_roofline_pct",
               "flash_gqa_32on8_w64_bwd_roofline_pct"]
SHARED_READERS = [
    "data_wait_ms", "trainer_data_wait_ms", "train_step_device_ms",
    "device_idle_pct.train", "peak_hbm_gib.train", "unscoped_device_pct",
    "attention_ms", "flash_share_pct", "loss_head_ms", "optimizer_ms",
    "dense_ffn_ms", "ssm_ms", "ssm_core_ms", "ssm_core_roofline_pct",
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
    # a second of window is some tens of steps of a warm-up that starts at
    # zero: at this size the loss does not reliably fall in them
    config["limits"]["train"].update(TINY_LIMITS, window_loss_rise=0.5)
    traffic = harness.load_json(harness.HERE, "traffic",
                                cell["traffic"] + ".json")
    traffic.update(corpus_tokens=20000, reference_q_block=64)
    return bench, cell, config, traffic


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    """No device memory to read; blocks of 32 tokens; and the registry's
    entry with the tiny pattern (the file's `model` names no kinds: the
    program reads them from the registry's published 40)."""
    from solvingpapers_tpu.configs import registry
    from solvingpapers_tpu.ops import ssd

    monkeypatch.setattr(harness, "peak_bytes", lambda n: (1, 1))
    monkeypatch.setattr(ssd, "SEGMENT", 32)
    published = registry._REGISTRY["granite4_h_micro"]

    def entry():
        cfg = published()
        return dataclasses.replace(cfg, model=dataclasses.replace(
            cfg.model, layer_types=TINY_TYPES + cfg.model.layer_types[2:]))

    monkeypatch.setitem(registry._REGISTRY, "granite4_h_micro", entry)


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


def catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not here")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "granite-4.0-h-micro")


def test_every_key_of_the_file_is_the_catalogs_but_the_reduced():
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    row = catalog_row()
    assert conf["source"] == config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if config.get(k, "missing") != v)
    assert differ == conf["reduced"] == config["reduced"] == [
        "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "vocab_size": 100352}
    assert (config["num_hidden_layers"], config["vocab_size"]) == (10, 12544)
    assert len(config["layer_types"]) == 40
    for key in ("why_reduced", "assumed", "deployment", "stands_for"):
        assert config[key], key
    assert {"job", "initialisation", "chunk_size", "mamba_expand",
            "rope_theta"} <= set(config["assumed"])
    assert config["deployment"]["pipeline_stages"] == 4
    assert "772,160,448" in config["stands_for"]
    limits = config["limits"]["train"]
    assert {"loss_gap", "grad_norm_gap", "first_grad_leaf_gap",
            "delta_leaf_gap", "window_loss_rise", "reasons"} == set(limits)


def test_the_file_keeps_every_published_width(monkeypatch):
    """`reduced` is the depth and the vocabulary's slice, and nothing else
    differs from the registry's published entry (`train_job.run_config`
    refuses it otherwise); the cell is listed where its readers read."""
    monkeypatch.undo()  # the published registry entry
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    driver = harness.load_module("drivers", "train_job")
    traffic = harness.load_json(harness.HERE, "traffic", "train_8k.json")
    cfg = driver.run_config(config, dict(traffic, corpus_tokens=20000), 3)
    m = cfg.model
    assert m.layer_pattern == tuple(config["layer_types"][:10])
    for key in ("hidden_size", "intermediate_size",
                "shared_intermediate_size", "num_attention_heads",
                "num_key_value_heads", "vocab_size", "num_hidden_layers",
                "attention_multiplier", "embedding_multiplier",
                "residual_multiplier", "logits_scaling", "mamba_n_heads",
                "mamba_d_head", "mamba_n_groups", "mamba_d_state",
                "mamba_d_conv", "mamba_chunk_size", "mamba_expand",
                "mamba_conv_bias", "mamba_proj_bias", "rms_norm_eps",
                "num_local_experts", "num_experts_per_tok",
                "tie_word_embeddings", "position_embedding_type"):
        assert getattr(m, key) == config[key], key
    assert (m.block_size, m.head_dim) == (8192, 64)
    assert (cfg.train.batch_size, cfg.train.tokens_per_step) == (1, 8192)
    # the traffic the issue gives, letter for letter
    assert traffic == {
        "driver": "train_job", "what": traffic["what"], "batch_size": 1,
        "corpus_tokens": 2000000, "corpus_seed": 0, "zipf_exponent": 1.1,
        "check_steps": 3, "calibration_steps": 4, "trace_seconds": 6,
        "reference_q_block": 4096}
    assert (cell["traffic"], cell["chips"]) == ("train_8k", 1)
    # a width is refused
    wrong = json.loads(json.dumps(config))
    wrong["model"]["intermediate_size"] = 4096
    wrong["model"]["shared_intermediate_size"] = 4096
    with pytest.raises(harness.BenchFailure, match="intermediate_size"):
        driver.run_config(wrong, dict(traffic, corpus_tokens=20000), 3)
    listed = {x["name"] for x in harness.metrics_of(bench, "per_layer", CELL)}
    assert listed == set(NEW_READERS) | set(SHARED_READERS)
    for name in NEW_READERS:
        entry = next(x for x in bench["per_layer"] if x["name"] == name)
        assert entry["workloads"] == [CELL], name
        assert entry["moves"] == "train_tokens_per_s"
    # the shares of a peak or roofline the benchmark had are the cell's too
    assert CELL in next(x for x in bench["per_layer"]
                        if x["name"] == "ssm_core_roofline_pct")["workloads"]


def test_sound_run_is_correct_and_prints_the_contract_line():
    run = tiny_run()
    line = run.result()
    assert line["correct"] is True, run.checks
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] == run.obs["steps"] >= 2 and line["failed"] == 0
    # on the CPU there is no trace: the trace readers find nothing
    for name in ("flash_gqa_32on8_w64_fwd_roofline_pct",
                 "flash_gqa_32on8_w64_bwd_roofline_pct",
                 "ssm_core_roofline_pct"):
        assert harness.load_module("metrics", name).read(run.obs) is None
    # the host-clock share reads the window it was given
    mfu = harness.load_module("metrics", "mfu_pct.granite_pp4")
    assert mfu.read(run.obs) > 0.0


@pytest.mark.parametrize("fault,fails", [
    ("frozen", {"delta_leaf_gap"}),
    ("half", {"loss_gap", "grad_norm_gap", "first_grad_leaf_gap"})])
def test_planted_fault_is_not_correct(monkeypatch, fault, fails):
    """A step that returns its state unchanged reads 1.0 where the weights'
    change is compared; a loss over the first half of the sequence, counted
    twice, is what `loss_gap` is there for."""
    faults.plant(fault, monkeypatch.setattr)
    run = tiny_run()
    assert run.result()["correct"] is False
    failed = {c["check"] for c in run.checks if not c["ok"]}
    assert fails <= failed, run.checks


@pytest.mark.parametrize("field, value", [
    ("attention_multiplier", 16 ** -0.5), ("residual_multiplier", 1.0)])
def test_a_multiplier_of_its_own_is_not_correct(monkeypatch, field, value):
    """The reference keeps the configuration's multipliers; a program whose
    softmax is scaled by head_dim^-0.5, or whose adds forget the 0.22, is
    outside the limits."""
    from solvingpapers_tpu.configs import factory

    real = factory.build_model

    def build_model(cfg):
        return real(dataclasses.replace(cfg, model=dataclasses.replace(
            cfg.model, **{field: value})))

    monkeypatch.setattr(factory, "build_model", build_model)
    adapter = harness.load_module("adapters", "granite_hybrid")
    sizes_of = adapter.sizes_of
    name = {"attention_multiplier": "attn_scale",
            "residual_multiplier": "res_scale"}[field]
    want = TINY_MODEL.get(field, 0.22)
    monkeypatch.setattr(adapter, "sizes_of", lambda m: dataclasses.replace(
        sizes_of(m), **{name: want}))
    run = tiny_run()
    assert run.result()["correct"] is False, run.checks


def test_int8_control_fails_where_a_sound_run_passes():
    _, _, config, traffic = tiny_files()
    driver = harness.load_module("drivers", traffic["driver"])
    got = driver.control_readings(config, traffic, seed=2**31 + 11)
    over = {k for k in TINY_LIMITS if got[k] > TINY_LIMITS[k]}
    assert over == {"grad_norm_gap", "delta_leaf_gap"}, got


def sizes(**over):
    base = dict(vocab=100, block=64, dim=8, layers=3, pattern="M*M", heads=4,
                kv_heads=2, head_dim=2, attn_scale=0.5, ssm_heads=4,
                ssm_head_dim=4, ssm_groups=1, ssm_state=3, conv=4, ffn=12)
    return Sizes(**{**base, **over})


def published(layers=10, vocab=12544):
    pattern = "".join("*" if i % 10 == 5 else "M" for i in range(layers))
    return Sizes(vocab=vocab, block=8192, dim=2048, layers=layers,
                 pattern=pattern, heads=32, kv_heads=8, head_dim=64,
                 attn_scale=1 / 64, ssm_heads=64, ssm_head_dim=64,
                 ssm_groups=1, ssm_state=128, conv=4, ffn=8192)


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_none_with_nothing_to_read(name):
    read = harness.load_module("metrics", name).read
    assert read({}) is None
    # an accepted cell's observations: DeepSeekV3's sizes
    from benchmarks.reference.deepseekv3_ref import Sizes as DSizes

    obs = {"rows": [{"train_loss": 1.0}], "trace": None,
           "sizes": DSizes(vocab=8, block=8, dim=8, layers=1, heads=1,
                           latent=4, experts=2, top_k=1),
           "tokens_per_step": 8, "steps": 2, "window_s": 1.0, "seq_len": 8}
    assert read(obs) is None


def test_new_readers_on_a_table_of_layer_times():
    """On a table of layer times that has the kernels, the shares are their
    counts over the times; another state-space family's cell (32-on-2 at
    width 128) is not these readers'."""
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    sz = published()
    obs = {"sizes": sz, "seq_len": 8, "batch_size": 1, "peaks": peaks,
           "layer_ms": {"flash_mla_fwd": 4000.0, "L_ssm_core": 5000.0},
           "config": {"chunk_size": 4}}
    read = lambda n: harness.load_module("metrics", n).read(obs)  # noqa: E731
    # 32 heads of 64, 32 causal pairs, QK^T and PV; one attention layer
    a_call = 2 * 2.0 * 32 * 32 * 64 / 1e3
    assert a_call == flash_gqa.least_seconds("flash_mla_fwd", 8, 32, 8, 64,
                                             peaks)
    assert read("flash_gqa_32on8_w64_fwd_roofline_pct") == pytest.approx(
        100 * a_call / 4.0)
    assert read("flash_gqa_32on8_w64_bwd_roofline_pct") is None
    # the accepted reader of the recurrence counts C B^T once for the ONE
    # group and nine layers
    assert harness.load_module("metrics", "ssm_core_roofline_pct").read(
        obs) == pytest.approx(
            100 * ssd_rule.least_seconds(sz, 8, 4, peaks) * 9 / 5.0)
    from benchmarks.reference.nemotron_h_ref import Sizes as NSizes

    other = dict(obs, sizes=NSizes(
        vocab=100, block=64, dim=8, layers=9, pattern="MEMEM*EME", heads=32,
        kv_heads=2, head_dim=128, ssm_heads=4, ssm_head_dim=2, ssm_groups=2,
        ssm_state=3, conv=4, router=16, held=4, first=0, top_k=2,
        expert_hidden=3, shared_hidden=5))
    assert flash_gqa_32on8_w64.roofline_share(
        other, ("flash_mla_fwd",)) is None
    assert harness.load_module("metrics", "mfu_pct.granite_pp4").read(
        dict(other, tokens_per_step=8, steps=2, window_s=1.0)) is None


def test_stage_flops_per_token_by_hand():
    sz = sizes()
    p = granite_hybrid_model.stage_params(sz)
    # in_proj 8 x (16 + 22 + 4), conv 4 x 22, out_proj 16 x 8; q, k, v
    # 8 x (4 + 2 * 2) x 2, o 8 x 8; three matrices of 8 x 12; the tied head
    assert p == {"mamba": 8 * 42 + 88 + 128, "attn": 128 + 64,
                 "ffn": 3 * 96, "head": 800}
    weights = 2 * 552 + 192 + 3 * 288 + 800
    scores = 4 * 2 * 2 * 10 / 2  # heads * 2 * head_dim * S / 2
    state = 2 * 2 * 4 * 4 * 3  # two mixers: 2 * heads * P * N
    assert granite_hybrid_model.train_flops_per_token(sz, 10) == 6.0 * (
        weights + scores + state)


def test_published_size_counts():
    """The cell's 772,160,448 parameters and the whole model's
    3,191,396,096 from the reference's shapes (the tied head is no weight
    of its own); a token's 4.79 GFLOP at the cell's size, 39.2 TFLOP a
    step."""
    def total(sz):
        return sum(math.prod(shape)
                   for shape, _ in weight_shapes(sz).values())

    assert total(published()) == 772_160_448
    assert total(published(40, 100352)) == 3_191_396_096
    assert "head" not in weight_shapes(published())
    per_token = granite_hybrid_model.train_flops_per_token(published(), 8192)
    assert per_token == 6.0 * (
        9 * 25_838_592 + 10_485_760 + 10 * 50_331_648 + 25_690_112
        + 32 * 2 * 64 * 8192 / 2 + 9 * 2 * 64 * 64 * 128)
    assert per_token * 8192 == pytest.approx(39.24e12, rel=1e-3)
