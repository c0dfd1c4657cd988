"""The cell `nemotron3_nano_ep16.train_16k` on the CPU: its configuration
file against the catalog's row, through `train_job` at a tiny cut of its own
(the widths shrink here and nowhere else), the two planted faults of
`benchmarks/faults.py` and the int8 control against the same limits, the
count files against counts worked by hand, and every new reader on a
recorded small trace and with nothing to read."""

import json
import math
import os
import time

import pytest

from benchmarks import faults, harness
from benchmarks.kernels import flash_gqa_32on2, nemotron_h_model, ssd_rule
from benchmarks.reference.nemotron_h_ref import Sizes, weight_shapes

CELL = "nemotron3_nano_ep16.train_16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
TINY_MODEL = dict(
    vocab_size=256, block_size=64, hidden_size=64, num_hidden_layers=9,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    chunk_size=16, n_routed_experts=4, router_experts=16,
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64, use_flash=False,
    capacity_factor=4.0)
# a tiny model's numbers, not the chip's
TINY_LIMITS = {"loss_gap": 4e-4, "grad_norm_gap": 5e-3,
               "first_grad_leaf_gap": 3e-2, "delta_leaf_gap": 1e-2}
NEW_READERS = ["ssm_ms", "ssm_core_ms", "ssm_core_roofline_pct",
               "flash_gqa_32on2_fwd_roofline_pct",
               "flash_gqa_32on2_bwd_roofline_pct",
               "mfu_pct.nemotron_ep_share"]
SHARED_READERS = [
    "data_wait_ms", "trainer_data_wait_ms", "train_step_device_ms",
    "device_idle_pct.train", "peak_hbm_gib.train", "unscoped_device_pct",
    "moe_drop_pct", "moe_held_pair_pct", "moe_route_ms", "moe_experts_ms",
    "attention_ms", "flash_share_pct", "loss_head_ms", "optimizer_ms"]


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
    from solvingpapers_tpu.ops import ssd

    monkeypatch.setattr(harness, "peak_bytes", lambda n: (1, 1))
    monkeypatch.setattr(ssd, "SEGMENT", 32)


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
    return next(r for r in rows
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")


def test_every_key_of_the_file_is_the_catalogs_but_the_reduced():
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    row = catalog_row()
    assert conf["source"] == config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items()
                    if config.get(k, "missing") != v)
    assert differ == conf["reduced"] == config["reduced"] == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in differ}
    assert (config["n_routed_experts"], config["num_hidden_layers"],
            config["vocab_size"]) == (8, 9, 16384)


def test_the_file_keeps_every_published_width():
    """`reduced` is depth, experts held and vocabulary, and nothing else
    differs from the registry's published entry (`train_job.run_config`
    refuses it otherwise); the cell is listed where its readers read."""
    bench, cell, conf = harness.find_cell(CELL)
    config = harness.load_json(harness.ROOT, conf["file"])
    driver = harness.load_module("drivers", "train_job")
    cfg = driver.run_config(
        config, {"batch_size": 1, "corpus_tokens": 20000, "corpus_seed": 0,
                 "zipf_exponent": 1.1}, 3)
    m = cfg.model
    assert (m.num_hidden_layers, m.n_routed_experts, m.vocab_size) == (
        9, 8, 16384)
    assert m.layer_pattern == "MEMEM*EME"
    assert m.hybrid_override_pattern == config["hybrid_override_pattern"]
    assert len(m.hybrid_override_pattern) == 52
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
                "ssm_state_size", "conv_kernel", "chunk_size",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "layer_norm_epsilon",
                "time_step_min", "time_step_max", "time_step_floor"):
        assert getattr(m, key) == config[key], key
    assert (m.router_experts, m.d_inner, m.conv_dim) == (128, 4096, 6144)
    assert (cell["traffic"], cell["chips"]) == ("train_16k", 1)
    assert len(bench["workloads"]) == 5
    listed = {x["name"] for x in harness.metrics_of(bench, "per_layer", CELL)}
    assert listed == set(NEW_READERS) | set(SHARED_READERS)
    for name in NEW_READERS:
        entry = next(x for x in bench["per_layer"] if x["name"] == name)
        assert entry["workloads"] == [CELL], name


def test_sound_run_is_correct_and_prints_the_contract_line():
    run = tiny_run()
    line = run.result()
    assert line["correct"] is True, run.checks
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert line["attempted"] == run.obs["steps"] >= 2 and line["failed"] == 0
    rows = run.obs["rows"]
    assert all("train_moe_held_pair_fraction" in r
               and "train_moe_drop_fraction" in r for r in rows)
    held = harness.load_module("metrics", "moe_held_pair_pct").read(run.obs)
    assert 0.0 < held < 100.0
    # on the CPU there is no trace: the trace readers find nothing
    for name in ("ssm_ms", "ssm_core_ms", "ssm_core_roofline_pct",
                 "flash_gqa_32on2_fwd_roofline_pct"):
        assert harness.load_module("metrics", name).read(run.obs) is None
    # the host-clock share reads the window it was given
    mfu = harness.load_module("metrics", "mfu_pct.nemotron_ep_share")
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


def test_int8_control_fails_where_a_sound_run_passes():
    _, _, config, traffic = tiny_files()
    driver = harness.load_module("drivers", traffic["driver"])
    got = driver.control_readings(config, traffic, seed=5)
    # at this size int8 and bfloat16 part clearly in the loss and in the
    # weights' change alone (sound runs read 1.7e-4 and 3e-3 at most); the
    # chip's limits, at the published widths, are the configuration file's
    over = [k for k in TINY_LIMITS if got[k] > TINY_LIMITS[k]]
    assert {"loss_gap", "delta_leaf_gap"} <= set(over), got


def sizes(**over):
    base = dict(vocab=100, block=64, dim=8, layers=9, pattern="MEMEM*EME",
                heads=4, kv_heads=2, head_dim=4, ssm_heads=4, ssm_head_dim=2,
                ssm_groups=2, ssm_state=3, conv=4, router=16, held=4,
                first=0, top_k=2, expert_hidden=3, shared_hidden=5)
    return Sizes(**{**base, **over})


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_return_none_with_nothing_to_read(name):
    read = harness.load_module("metrics", name).read
    assert read({}) is None
    # an accepted cell's observations: DeepSeekV3's sizes, no SSM scopes
    from benchmarks.reference.deepseekv3_ref import Sizes as DSizes

    obs = {"rows": [{"train_loss": 1.0}], "trace": None,
           "sizes": DSizes(vocab=8, block=8, dim=8, layers=1, heads=1,
                           latent=4, experts=2, top_k=1),
           "tokens_per_step": 8, "steps": 2, "window_s": 1.0, "seq_len": 8}
    assert read(obs) is None


def test_new_readers_on_a_recorded_small_trace():
    """The recorded trace of `benchmarks/tests/data` (a tiny DeepSeekV3
    step on a v5e) has no state-space scope: the scope readers find nothing
    in it whatever sizes stand beside it. On a table of layer times that
    has them they read the sums, and the shares their counts."""
    xplane = harness.load_module("trace", "xplane")
    path = os.path.join(harness.HERE, "tests", "data", "small_v5e.xplane.pb")
    trace = xplane.reduce_trace(path, window_span=harness.WINDOW_SPAN,
                                host_spans=(), fallback="host")
    recorded = {"trace": trace, "sizes": sizes(), "seq_len": 8,
                "batch_size": 1, "config": {"chunk_size": 4},
                "peaks": harness.peaks_for("TPU v5 lite"),
                "train_step_module": "jit_train_step"}
    for name in NEW_READERS[:5]:
        assert harness.load_module("metrics", name).read(recorded) is None
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    obs = {"sizes": sizes(), "seq_len": 8, "batch_size": 1,
           "config": {"chunk_size": 4}, "peaks": peaks,
           "layer_ms": {"L_ssm_proj": 5.0, "L_ssm_conv": 2.0,
                        "L_ssm_core": 4000.0, "flash_mla_fwd": 4000.0}}
    read = lambda n: harness.load_module("metrics", n).read(obs)  # noqa: E731
    assert read("ssm_ms") == 4007.0 and read("ssm_core_ms") == 4000.0
    least = ssd_rule.least_seconds(sizes(), 8, 4, peaks)
    assert read("ssm_core_roofline_pct") == pytest.approx(
        100 * least * 4 / 4.0)
    # one attention layer: 4 heads of 4, 32 causal pairs, QK^T and PV:
    # 2.048 s by its operations at these peaks (0.896 s by its bytes)
    assert read("flash_gqa_32on2_fwd_roofline_pct") == pytest.approx(
        100 * (2 * 2.0 * 4 * 32 * 4 / 1e3) / 4.0)
    assert read("flash_gqa_32on2_bwd_roofline_pct") is None
    # another grouped-query family's sizes: not this reader's
    from benchmarks.reference.qwen3next_ref import Sizes as QSizes

    other = dict(obs, sizes=QSizes(
        vocab=8, block=8, dim=8, layers=4, interval=4, heads=4, kv_heads=2,
        head_dim=4, rotary_dim=2, rope_theta=1e4, gdn_k_heads=2,
        gdn_v_heads=2, gdn_k_dim=4, gdn_v_dim=4, conv=4, router=4, held=4,
        first=0, top_k=2, expert_hidden=3, shared_hidden=3))
    assert flash_gqa_32on2.roofline_share(other, ("flash_mla_fwd",)) is None


def test_ssd_rule_counts_by_hand():
    sz = sizes()
    # one chunk of 4 tokens: Q^2/2 = 8 pairs. C B^T a group (2 of them):
    # 8 * N(3) = 24; a head (4 of them): scores times x 8 * P(2) = 16,
    # write and read 2 * 4 * 2 * 3 = 48 -> 2*24 + 4*64 = 304 MACs
    assert ssd_rule.forward_flops(sz, 4, 4) == 2 * 304
    assert ssd_rule.forward_flops(sz, 8, 4) == 2 * 2 * 304
    assert ssd_rule.forward_flops(sz, 6, 4) == 2 * 2 * 304  # a padded tail
    assert ssd_rule.flops("bwd", sz, 4, 4) == 4 * 304
    # bytes, S=4, bf16: x or y 4*4*2*2 = 64; B and C 2*4*2*3*2 = 96; the
    # step 4*4*4 = 64
    assert ssd_rule.hbm_bytes("fwd", sz, 4) == 64 + 96 + 64 + 64
    assert ssd_rule.hbm_bytes("bwd", sz, 4) == 288 + 64 + 96 + 64
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}
    assert ssd_rule.least_seconds(sz, 4, 4, peaks) == pytest.approx(
        max(608 / 1e3, 288 / 1e2) + max(1216 / 1e3, 512 / 1e2))


def test_rank_flops_per_token_by_hand():
    sz = sizes()
    p = nemotron_h_model.rank_params(sz)
    # d_inner 8, conv_dim 8 + 2*2*3 = 20: in_proj 8*(8 + 20 + 4), the
    # convolution 4*20, out_proj 8*8
    assert p["mamba"] == 8 * 32 + 80 + 64
    # q, k, v 8*(4 + 2*2)*4, o 4*4*8
    assert p["attn"] == 8 * 8 * 4 + 128
    # router 8*16, routed 2*4/16 = 0.5 experts of 2*8*3, shared 2*8*5
    assert p["moe"] == 128 + 0.5 * 48 + 80
    assert p["head"] == 800
    weights = 4 * p["mamba"] + p["attn"] + 4 * p["moe"] + 800
    scores = 4 * 2 * 4 * 10 / 2  # heads * 2 * head_dim * S / 2
    state = 4 * 2 * 4 * 2 * 3
    assert nemotron_h_model.train_flops_per_token(sz, 10) == 6.0 * (
        weights + scores + state)


def test_published_size_counts():
    """The cell's 666,963,456 parameters and the whole model's
    31,577,940,288 from the count file's per-layer weights plus what a
    token does not multiply with (norms, biases, the embedding)."""
    def total(layers, held, vocab):
        pattern = ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
                   [:layers])
        sz = Sizes(vocab=vocab, block=16384, dim=2688, layers=layers,
                   pattern=pattern, heads=32, kv_heads=2, head_dim=128,
                   ssm_heads=64, ssm_head_dim=64, ssm_groups=8,
                   ssm_state=128, conv=4, router=128, held=held, first=0,
                   top_k=6, expert_hidden=1856, shared_hidden=3712)
        return sum(math.prod(shape)
                   for shape, _ in weight_shapes(sz).values())

    assert total(9, 8, 16384) == 666_963_456
    assert total(52, 128, 131072) == 31_577_940_288
