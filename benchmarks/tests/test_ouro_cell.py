"""The cell `ouro_2p6b_pp6.train_2x4k` on the CPU: its configuration file
against the catalog's row and the registry, through `train_job` at a tiny
cut of its own (the widths shrink here and nowhere else), the two planted
faults of `benchmarks/faults.py`, a program that runs a pass less or whose
loss forgets the gate, and the int8 control against the same limits, the
count files against counts worked by hand, and every new reader on a
recorded small trace and with nothing to read."""

import dataclasses
import json
import math
import os
import time

import pytest

from benchmarks import faults, harness
from benchmarks.kernels import flash_gqa, flash_mha_16on16, ouro_model
from benchmarks.reference.ouro_ref import Sizes, weight_shapes

CELL = "ouro_2p6b_pp6.train_2x4k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY_MODEL = dict(
    vocab_size=256, block_size=64, hidden_size=64, intermediate_size=96,
    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, use_flash=False)
# a tiny model's numbers, not the chip's
TINY_LIMITS = {"loss_gap": 1e-3, "grad_norm_gap": 1e-2,
               "first_grad_leaf_gap": 5e-2, "delta_leaf_gap": 2e-2}
NEW_READERS = ["mfu_pct.ouro_looped", "flash_mha_16on16_fwd_roofline_pct",
               "flash_mha_16on16_bwd_roofline_pct", "exit_gate_ms",
               "exit_entropy_nats"]
SHARED_READERS = [
    "data_wait_ms", "trainer_data_wait_ms", "train_step_device_ms",
    "device_idle_pct.train", "peak_hbm_gib.train", "unscoped_device_pct",
    "attention_ms", "flash_share_pct", "loss_head_ms", "optimizer_ms",
    "dense_ffn_ms", "startup_import_s", "startup_build_s",
    "startup_init_state_s", "startup_trace_lower_s", "startup_compile_s",
    "startup_first_step_s", "startup_program_s", "compile_cache_miss_count",
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
    from solvingpapers_tpu.models import ouro

    monkeypatch.setattr(harness, "peak_bytes", lambda n: (1, 1))
    monkeypatch.setattr(ouro, "SEGMENT", 32)


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
    return next(r for r in rows if r["name"] == "Ouro-2.6B")


def test_every_key_of_the_file_is_the_catalogs_but_the_reduced():
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    row = catalog_row()
    assert conf["source"] == config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if config.get(k, "missing") != v)
    assert differ == conf["reduced"] == config["reduced"] == [
        "num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] in (6, 8)
    for key in ("why_reduced", "assumed", "left_out", "deployment",
                "stands_for"):
        assert config[key], key
    assert {"exit_entropy_weight", "job", "initialisation",
            "gate_bias"} <= set(config["assumed"])


def test_the_file_keeps_every_published_width():
    """`reduced` is the depth, and nothing else differs from the registry's
    published entry (`train_job.run_config` refuses it otherwise); the cell
    is listed where its readers read."""
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    driver = harness.load_module("drivers", "train_job")
    traffic = harness.load_json(harness.HERE, "traffic", "train_2x4k.json")
    cfg = driver.run_config(config, dict(traffic, corpus_tokens=20000), 3)
    m = cfg.model
    assert m.num_hidden_layers == config["num_hidden_layers"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "vocab_size",
                "rope_theta", "rms_norm_eps", "total_ut_steps",
                "hidden_act", "tie_word_embeddings", "use_sliding_window",
                "sliding_window"):
        assert getattr(m, key) == config[key], key
    assert (m.block_size, m.exit_entropy_weight) == (4096, 0.1)
    assert (cfg.train.batch_size, cfg.train.tokens_per_step) == (2, 8192)
    # the traffic the issue gives, letter for letter
    assert traffic == {
        "driver": "train_job", "what": traffic["what"], "batch_size": 2,
        "corpus_tokens": 2000000, "corpus_seed": 0, "zipf_exponent": 1.1,
        "check_steps": 3, "calibration_steps": 4, "trace_seconds": 6,
        "reference_q_block": 4096}
    assert (cell["traffic"], cell["chips"]) == ("train_2x4k", 1)
    # another depth, or a width, is refused
    wrong = json.loads(json.dumps(config))
    wrong["model"]["intermediate_size"] = 4096
    with pytest.raises(harness.BenchFailure, match="intermediate_size"):
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
    assert all({"train_ce_ut1", "train_ce_ut4", "train_exit_entropy",
                "train_exit_mean_step"} <= set(r) for r in rows)
    entropy = harness.load_module("metrics", "exit_entropy_nats").read(
        run.obs)
    assert entropy == rows[-1]["train_exit_entropy"]
    assert 0.0 < entropy <= math.log(4)
    # on the CPU there is no trace: the trace readers find nothing
    for name in ("exit_gate_ms", "flash_mha_16on16_fwd_roofline_pct",
                 "flash_mha_16on16_bwd_roofline_pct"):
        assert harness.load_module("metrics", name).read(run.obs) is None
    # the host-clock share reads the window it was given
    mfu = harness.load_module("metrics", "mfu_pct.ouro_looped")
    assert mfu.read(run.obs) > 0.0


@pytest.mark.parametrize("fault,fails", [
    ("frozen", {"delta_leaf_gap"}),
    ("half", {"loss_gap", "grad_norm_gap", "first_grad_leaf_gap"})])
def test_planted_fault_is_not_correct(monkeypatch, fault, fails):
    """A step that returns its state unchanged reads 1.0 where the weights'
    change is compared; a loss over the first of the two sequences, counted
    twice, is what `loss_gap` is there for."""
    faults.plant(fault, monkeypatch.setattr)
    run = tiny_run()
    assert run.result()["correct"] is False
    failed = {c["check"] for c in run.checks if not c["ok"]}
    assert fails <= failed, run.checks


@pytest.mark.parametrize("fault", ["three_passes", "gate_dropped"])
def test_a_pass_less_or_a_loss_without_its_gate_is_not_correct(
        monkeypatch, fault):
    """The reference runs the configuration's four passes under the whole
    loss; a program that runs three, or whose loss is the last exit's
    cross-entropy alone, is outside the limits."""
    from solvingpapers_tpu import ops
    from solvingpapers_tpu.configs import factory

    if fault == "three_passes":
        real = factory.build_model

        def build_model(cfg):
            return real(dataclasses.replace(cfg, model=dataclasses.replace(
                cfg.model, total_ut_steps=3)))

        monkeypatch.setattr(factory, "build_model", build_model)
        # the reference keeps the configuration's four passes
        adapter = harness.load_module("adapters", "ouro")
        sizes_of = adapter.sizes_of
        monkeypatch.setattr(adapter, "sizes_of", lambda m: dataclasses.replace(
            sizes_of(m), ut_steps=4))
    else:
        def last_exit_only(model, params, batch, rng, model_state, train):
            (hidden, _), _ = model.apply({"params": params}, batch["x"],
                                         head=False)
            return ops.head_cross_entropy(
                hidden[-1], params["lm_head"]["kernel"],
                batch["y"]), {}, model_state

        monkeypatch.setattr(factory, "loss_fn_for",
                            lambda cfg: last_exit_only)
    run = tiny_run()
    assert run.result()["correct"] is False
    failed = {c["check"] for c in run.checks if not c["ok"]}
    assert "loss_gap" in failed, run.checks


def test_int8_control_fails_where_a_sound_run_passes():
    _, _, config, traffic = tiny_files()
    driver = harness.load_module("drivers", traffic["driver"])
    got = driver.control_readings(config, traffic, seed=5)
    over = [k for k in TINY_LIMITS if got[k] > TINY_LIMITS[k]]
    assert over, got


def sizes(**over):
    base = dict(vocab=100, block=64, dim=8, layers=2, ut_steps=4, heads=4,
                kv_heads=4, head_dim=4, ffn=12)
    return Sizes(**{**base, **over})


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_none_with_nothing_to_read(name):
    read = harness.load_module("metrics", name).read
    assert read({}) is None
    # an accepted cell's observations: DeepSeekV3's sizes, no gate's scope
    from benchmarks.reference.deepseekv3_ref import Sizes as DSizes

    obs = {"rows": [{"train_loss": 1.0}], "trace": None,
           "sizes": DSizes(vocab=8, block=8, dim=8, layers=1, heads=1,
                           latent=4, experts=2, top_k=1),
           "tokens_per_step": 8, "steps": 2, "window_s": 1.0, "seq_len": 8}
    assert read(obs) is None


def test_new_readers_on_a_recorded_small_trace():
    """The recorded trace of `benchmarks/tests/data` (a tiny DeepSeekV3
    step on a v5e) has no exit gate: the scope reader finds nothing in it
    whatever sizes stand beside it. On a table of layer times that has the
    scope and the kernels they read the sums, and the shares their
    counts."""
    xplane = harness.load_module("trace", "xplane")
    path = os.path.join(harness.HERE, "tests", "data", "small_v5e.xplane.pb")
    trace = xplane.reduce_trace(path, window_span=harness.WINDOW_SPAN,
                                host_spans=(), fallback="host")
    recorded = {"trace": trace, "sizes": sizes(), "seq_len": 8,
                "batch_size": 2, "peaks": harness.peaks_for("TPU v5 lite"),
                "train_step_module": "jit_train_step"}
    assert harness.load_module("metrics", "exit_gate_ms").read(
        recorded) is None
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    obs = {"sizes": sizes(), "seq_len": 8, "batch_size": 2, "peaks": peaks,
           "layer_ms": {"L_exit_gate": 3.0, "L_loss_head": 9.0,
                        "flash_mla_fwd": 4000.0}}
    read = lambda n: harness.load_module("metrics", n).read(obs)  # noqa: E731
    assert read("exit_gate_ms") == 3.0
    # 4 heads of 4, 32 causal pairs, QK^T and PV: 2.048 s a call by its
    # operations at these peaks; 4 passes x 2 layers x 2 sequences calls
    a_call = 2 * 2.0 * 4 * 32 * 4 / 1e3
    assert a_call == flash_gqa.least_seconds("flash_mla_fwd", 8, 4, 4, 4,
                                             peaks)
    assert read("flash_mha_16on16_fwd_roofline_pct") == pytest.approx(
        100 * a_call * 16 / 4.0)
    assert read("flash_mha_16on16_bwd_roofline_pct") is None
    # a grouped-query family that does not loop: not this reader's
    from benchmarks.reference.nemotron_h_ref import Sizes as NSizes

    other = dict(obs, sizes=NSizes(
        vocab=100, block=64, dim=8, layers=9, pattern="MEMEM*EME", heads=4,
        kv_heads=2, head_dim=4, ssm_heads=4, ssm_head_dim=2, ssm_groups=2,
        ssm_state=3, conv=4, router=16, held=4, first=0, top_k=2,
        expert_hidden=3, shared_hidden=5))
    assert flash_mha_16on16.roofline_share(other, ("flash_mla_fwd",)) is None
    # the window's LAST logged entropy
    rows = {"rows": [{"train_exit_entropy": 1.2}, {"train_loss": 1.0},
                     {"train_exit_entropy": 0.9}]}
    assert harness.load_module("metrics", "exit_entropy_nats").read(
        rows) == 0.9


def test_stage_flops_per_token_by_hand():
    sz = sizes()
    p = ouro_model.stage_params(sz)
    # q, k, v 8 * (4 + 2 * 4) * 4, o 4 * 4 * 8; three matrices of 8 x 12
    assert p == {"attn": 8 * 12 * 4 + 128, "ffn": 3 * 96, "head": 800,
                 "gate": 8}
    uses = 4 * 2
    weights = uses * (512 + 288) + 4 * 808
    scores = uses * 4 * 2 * 4 * 10 / 2  # heads * 2 * head_dim * S / 2
    assert ouro_model.train_flops_per_token(sz, 10) == 6.0 * (
        weights + scores)
    # a pass more costs a stack and a head more
    more = ouro_model.train_flops_per_token(
        dataclasses.replace(sz, ut_steps=5), 10)
    assert more - 6.0 * (weights + scores) == 6.0 * (
        2 * 800 + 808 + 2 * 4 * 2 * 4 * 10 / 2)


def test_published_size_counts():
    """The cell's 612,438,017 parameters (six layers: 509,661,185) and the
    whole model's 2,667,974,657 from the reference's shapes; a token's
    13.89 GFLOP at the cell's size."""
    def total(layers):
        sz = Sizes(vocab=49152, block=4096, dim=2048, layers=layers,
                   ut_steps=4, heads=16, kv_heads=16, head_dim=128, ffn=5632)
        return sz, sum(math.prod(shape)
                       for shape, _ in weight_shapes(sz).values())

    assert total(8)[1] == 612_438_017
    assert total(6)[1] == 509_661_185
    assert total(48)[1] == 2_667_974_657
    assert ouro_model.train_flops_per_token(total(8)[0], 4096) == 6.0 * (
        32 * 51_380_224 + 4 * 100_665_344 + 32 * 8_388_608)
