"""`trace/layers.py` on a hand-made reduction and instruction map: a loop's
body is not counted beside the loop, an instruction the map does not hold is
unscoped, times are a step's, and every new reader returns None where there
is no trace or no map to read (a program from before the scopes)."""

import pytest

from benchmarks import harness
from benchmarks.trace import layers
from benchmarks.trace.xplane import Reduced

NEW_READERS = ["moe_route_ms", "moe_experts_ms", "attention_ms",
               "flash_kernels_ms", "loss_head_ms", "optimizer_ms",
               "unscoped_device_pct", "trainer_data_wait_ms"]

# (layer, pass, top_level), as `hlo_cost.device_scopes` gives them
SCOPES = {
    "fusion.1": ("L_moe_dispatch", "fwd", True),
    "fusion.2": ("L_moe_combine", "bwd", True),
    "flash_mla_fwd.3": ("flash_mla_fwd", "fwd", True),
    "flash_mla_fwd.4": ("flash_mla_fwd", "remat", True),
    "while.7": ("L_loss_head", "bwd", True),
    "reduce_fusion.2": ("L_loss_head", "bwd", False),  # body of while.7
    "while.8": ("L_loss_head", "bwd", False),  # a loop inside that body
    "add.9": ("L_loss_head", "bwd", False),  # body of the inner loop
    "copy-done.5": (None, "fwd", True),
    "convert.6": ("L_optimizer", "fwd", True),
}


def hand_made_obs():
    ms = 1e-3
    ops = {
        "%fusion.1 = bf16[8,64,16]{2,1,0} fusion(bf16[64,16] %p), kind=kOutput":
            [10 * ms, 10 * ms],
        "%fusion.2 = bf16[64,16,1]{1,0,2} fusion(%x), kind=kLoop": [6 * ms] * 2,
        '%flash_mla_fwd.3 = (bf16[8,64,16]{2,1,0}, f32[8,1,64]{2,1,0}) '
        'custom-call(%q), custom_call_target="tpu_custom_call"': [4 * ms] * 2,
        '%flash_mla_fwd.4 = (bf16[8,64,16]{2,1,0}, f32[8,1,64]{2,1,0}) '
        'custom-call(%q), custom_call_target="tpu_custom_call"': [4 * ms] * 2,
        "%while.7 = (s32[], f32[]) while(%t), condition=%c, body=%b":
            [8 * ms] * 2,
        "%reduce_fusion.2 = f32[] fusion(%y), kind=kInput": [1 * ms] * 8,
        "%while.8 = (s32[]) while(%u), condition=%c2, body=%b2": [3 * ms] * 2,
        "%add.9 = s32[] add(%i, %one)": [1 * ms] * 6,
        "%copy-done.5 = f32[4]{0} copy-done(%copy-start.5)": [1 * ms] * 2,
        "%convert.6 = f32[4]{0} convert(%g)": [2 * ms] * 2,
        "%fusion.77 = f32[4]{0} fusion(%z), kind=kLoop": [1 * ms] * 2,
    }
    tr = Reduced(window_s=0.1, busy_s=0.072, n_devices=1,
                 modules={"jit_train_step(123)": [36 * ms] * 2,
                          "jit_other(9)": [1 * ms]},
                 ops=ops, gaps=[])
    return {"trace": tr, "train_step_module": "jit_train_step",
            "device_scopes": dict(SCOPES),
            "rows": [{"data_wait_ms": 0.04}, {"data_wait_ms": 0.06}]}


def test_instruction_name():
    assert layers.instruction_name(
        "%fusion.55 = bf16[16384,512,1]{1,0,2:T(8,128)(2,1)} fusion(") \
        == "fusion.55"
    assert layers.instruction_name("%while.7 = (s32[]{:T(128)}) while(") \
        == "while.7"
    assert layers.instruction_name("fusion.3") == "fusion.3"


def test_layer_ms_sums_top_level_events_a_step(capsys):
    obs = hand_made_obs()
    table = layers.layer_ms(obs)
    assert table == {
        "L_moe_dispatch": pytest.approx(10.0),
        "L_moe_combine": pytest.approx(6.0),
        "flash_mla_fwd": pytest.approx(8.0),
        # while.7 alone: its body, the loop inside it and that loop's body
        # are events inside while.7's own and are not counted again
        "L_loss_head": pytest.approx(8.0),
        "L_optimizer": pytest.approx(2.0),
        # copy-done.5 has no layer; fusion.77 is not in the map at all
        layers.UNSCOPED: pytest.approx(2.0),
    }
    # the layers add up to the program's time on the XLA Modules line
    assert sum(table.values()) == pytest.approx(36.0)
    assert obs["layer_pass_ms"]["flash_mla_fwd/remat"] == pytest.approx(4.0)
    assert obs["layer_pass_ms"]["L_loss_head/bwd"] == pytest.approx(8.0)
    # computed once, kept on obs; the detail line goes out once
    assert layers.layer_ms(obs) is table
    assert capsys.readouterr().out.count('"layer_ms"') == 1


def test_new_readers_on_the_hand_made_trace():
    obs = hand_made_obs()
    read = {n: harness.load_module("metrics", n).read(obs)
            for n in NEW_READERS}
    assert read["moe_route_ms"] == pytest.approx(16.0)
    assert read["moe_experts_ms"] is None  # no such event in this trace
    assert read["attention_ms"] == pytest.approx(8.0)
    assert read["flash_kernels_ms"] == pytest.approx(8.0)
    assert read["loss_head_ms"] == pytest.approx(8.0)
    assert read["optimizer_ms"] == pytest.approx(2.0)
    assert read["unscoped_device_pct"] == pytest.approx(100 * 2 / 36)
    assert read["trainer_data_wait_ms"] == pytest.approx(0.05)


@pytest.mark.parametrize("name", NEW_READERS)
@pytest.mark.parametrize("obs", [
    {},  # an untraced run, rows of a trainer without the counters
    {"trace": None, "train_step_module": "jit_train_step", "rows": [{}]},
    # a trace of a program that registered nothing (the parent commit)
    {"trace": hand_made_obs()["trace"], "rows": [{"train_loss": 1.0}],
     "train_step_module": "jit_a_program_nobody_registered"},
])
def test_new_readers_find_nothing_and_do_not_raise(name, obs):
    assert harness.load_module("metrics", name).read(dict(obs)) is None


def test_every_new_reader_is_in_benchmark_json_with_its_cells():
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_READERS:
        assert entries[name]["moves"] == "train_tokens_per_s"
        assert entries[name]["workloads"], name
    assert entries["flash_kernels_ms"]["workloads"] == ["dsv3_long.train_16k"]
