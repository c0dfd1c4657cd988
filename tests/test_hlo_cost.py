"""Program-anatomy observatory tests (metrics/hlo_cost.py + the
CompileRegistry anatomy/hlo_dir integration).

The contracts under test:
  * `parse_hlo_costs` classifies defining ops into the documented
    categories with output-shape bytes and XLA-convention flops, skips
    sub-computation parameters, ranks top ops, and keeps the jax-level
    op_name source;
  * on a PINNED known program the ledger's flops/bytes totals reconcile
    with the executable's own `cost_analysis()` within tolerance
    (flops tight — the conventions match; bytes within the documented
    output-shape-proxy factor);
  * the anatomy surface is present IFF the observatory parses it:
    `CompileRegistry(anatomy=True)` -> per-program `anatomy` in
    `snapshot()["programs"]` / `anatomy_stats()`; a registry without
    the flag has NO anatomy key anywhere;
  * an engine with `xla_obs` exposes `compile.programs.<name>.anatomy`
    through /statusz with a paged decode program whose ledger actually
    names gather ops, and the category totals reconcile with the
    program's recorded cost_analysis flops;
  * traces: compile events carry the anatomy ledger, `summarize_trace`
    rebuilds an "anatomy" section present IFF the events carry it —
    PR-4/5-era traces (no anatomy args) summarize with the key ABSENT;
  * `obs_hlo_dir` dumps one HLO text file per TRUE compile, atomically,
    with sanitized names;
  * `ServeMetrics.snapshot()` survives a raising gauge provider: warn
    once, skip its keys, keep every healthy provider reporting.
"""

import json
import os
import re
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from solvingpapers_tpu.metrics.hlo_cost import (
    CATEGORIES,
    classify_op,
    format_anatomy,
    parse_hlo_costs,
)
from solvingpapers_tpu.metrics.trace import summarize_trace
from solvingpapers_tpu.metrics.xla_obs import CompileRegistry, clear_aot_cache
from solvingpapers_tpu.models.gpt import GPT, GPTConfig
from solvingpapers_tpu.serve import ServeConfig, ServeEngine

pytestmark = pytest.mark.fast

GPT_TINY = GPTConfig(vocab_size=64, block_size=64, dim=32, n_layers=2,
                     n_heads=2, dropout=0.0)


@pytest.fixture(scope="module")
def gpt_tiny():
    model = GPT(GPT_TINY)
    rng = jax.random.key(0)
    params = model.init({"params": rng},
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompts(n, seed=0, lo=4, hi=24):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, GPT_TINY.vocab_size,
                     size=int(rng.integers(lo, hi))).astype(np.int32)
        for _ in range(n)
    ]


# ------------------------------------------------------------- the parser


CRAFTED_HLO = """\
HloModule jit_f, entry_computation_layout={(f32[8,16]{1,0})->f32[8,4]{1,0}}

%fused_computation (param_0.1: f32[8,16]) -> f32[8,16] {
  %param_0.1 = f32[8,16]{1,0} parameter(0)
  %constant.1 = f32[] constant(0)
  %broadcast.1 = f32[8,16]{1,0} broadcast(f32[] %constant.1), dimensions={}
  ROOT %maximum.1 = f32[8,16]{1,0} maximum(f32[8,16]{1,0} %param_0.1, f32[8,16]{1,0} %broadcast.1), metadata={op_name="jit(f)/relu/max" source_file="x.py" source_line=3}
}

ENTRY %main.9 (Arg_0.1: f32[8,16]) -> f32[8,4] {
  %Arg_0.1 = f32[8,16]{1,0} parameter(0)
  %idx = s32[3]{0} constant({0, 1, 2})
  %relu_fusion = f32[8,16]{1,0} fusion(f32[8,16]{1,0} %Arg_0.1), kind=kLoop, calls=%fused_computation
  %weights = f32[16,4]{1,0} constant({...})
  %dot.3 = f32[8,4]{1,0} dot(f32[8,16]{1,0} %relu_fusion, f32[16,4]{1,0} %weights), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/dot_general"}
  %gather.2 = f32[3,4]{1,0} gather(f32[8,4]{1,0} %dot.3, s32[3]{0} %idx), offset_dims={1}, collapsed_slice_dims={0}
  %convert.5 = bf16[8,4]{1,0} convert(f32[8,4]{1,0} %dot.3)
  %dus.6 = f32[8,4]{1,0} dynamic-update-slice(f32[8,4]{1,0} %dot.3, f32[3,4]{1,0} %gather.2, s32[] %idx, s32[] %idx)
  %cc.7 = f32[8,4]{1,0} custom-call(f32[8,4]{1,0} %dus.6), custom_call_target="foo"
  ROOT %scatter.8 = f32[8,4]{1,0} scatter(f32[8,4]{1,0} %cc.7, s32[3]{0} %idx, f32[3,4]{1,0} %gather.2), to_apply=%add_comp
}
"""


# jaxlib 0.9 prints operands as bare names (`dot(%a, %b)`), older text
# carried their shapes inline: the same module in both spellings
BARE_OPERAND_HLO = re.sub(
    r"[a-z]+\d*\[[\d,]*\](?:\{[\d,]*\})? (?=%)", "", CRAFTED_HLO
)


@pytest.mark.parametrize("hlo", [CRAFTED_HLO, BARE_OPERAND_HLO],
                         ids=["typed_operands", "bare_operands"])
def test_parser_categories_flops_bytes(hlo):
    assert ("dot(%relu_fusion" in hlo) == (hlo is BARE_OPERAND_HLO)
    led = parse_hlo_costs(hlo)
    cats = led["categories"]
    # one op per named category surfaced from the crafted module
    assert cats["dot"]["ops"] == 1
    assert cats["gather"]["ops"] == 1
    assert cats["scatter"]["ops"] == 1
    assert cats["convert"]["ops"] == 1
    assert cats["fusion"]["ops"] == 1
    assert cats["dynamic-slice"]["ops"] == 1
    assert cats["custom-call"]["ops"] == 1
    # dot flops = 2 * out(8*4) * contraction(16) = 1024, parsed from the
    # operand shape + lhs_contracting_dims
    assert cats["dot"]["flops"] == 2 * 8 * 4 * 16
    # data movement is zero-flop; elementwise counts output elements
    assert cats["gather"]["flops"] == 0
    assert cats["scatter"]["flops"] == 0
    assert cats["convert"]["flops"] == 8 * 4
    # output-shape bytes: gather (3,4) f32 = 48; convert (8,4) bf16 = 64
    assert cats["gather"]["bytes"] == 48
    assert cats["convert"]["bytes"] == 64
    # ENTRY parameter counted (argument traffic), fused-computation
    # parameter skipped (it aliases an operand)
    assert cats["parameter"]["ops"] == 1
    assert cats["parameter"]["bytes"] == 8 * 16 * 4
    # the fused maximum is counted in "other" with its flops
    assert cats["other"]["flops"] >= 8 * 16
    assert led["ops"] == sum(c["ops"] for c in cats.values())
    assert led["flops"] == sum(c["flops"] for c in cats.values())
    assert led["bytes"] == sum(c["bytes"] for c in cats.values())
    # every category name is a documented one
    assert set(cats) <= set(CATEGORIES)


def test_parser_top_ops_ranked_with_source():
    led = parse_hlo_costs(CRAFTED_HLO, top_k=3)
    top = led["top_ops"]
    assert len(top) == 3
    weights = [max(t["flops"], t["bytes"]) for t in top]
    assert weights == sorted(weights, reverse=True)
    # the dot is the heaviest (1024 flops) and carries its op_name
    assert top[0]["name"] == "dot.3"
    assert top[0]["source"] == "jit(f)/dot_general"


def test_parser_empty_and_format():
    led = parse_hlo_costs("")
    assert led == {"ops": 0, "flops": 0, "bytes": 0, "categories": {},
                   "top_ops": []}
    assert format_anatomy({}) == ""
    text = format_anatomy({"decode_block": parse_hlo_costs(CRAFTED_HLO)})
    assert "decode_block" in text and "gather" in text
    assert "heaviest ops" in text


def test_classify_op_mapping():
    assert classify_op("gather") == "gather"
    assert classify_op("dynamic-update-slice") == "dynamic-slice"
    assert classify_op("convolution") == "dot"
    assert classify_op("maximum") == "other"


def test_ledger_reconciles_with_cost_analysis_on_pinned_program():
    """The acceptance pin: on a known program (matmul + relu + gather —
    the categories the decomposition cares about), the ledger's flops
    total matches cost_analysis() within 10% and the bytes total is
    within the documented output-shape-proxy factor [0.5x, 2x]."""

    def f(a, b, t):
        x = jnp.dot(a, b)
        return x[t], jax.nn.relu(x).astype(jnp.bfloat16)

    a = jnp.zeros((64, 32))
    b = jnp.zeros((32, 128))
    t = jnp.zeros((8,), jnp.int32)
    compiled = jax.jit(f).lower(a, b, t).compile()
    led = parse_hlo_costs(compiled.as_text())
    ca = compiled.cost_analysis()
    d = ca[0] if isinstance(ca, (list, tuple)) else (ca or {})
    flops = float(d.get("flops", 0.0))
    nbytes = float(d.get("bytes accessed", 0.0))
    if flops <= 0 or nbytes <= 0:
        pytest.skip("backend reports no cost_analysis totals")
    assert abs(led["flops"] - flops) <= 0.10 * flops, (led["flops"], flops)
    assert 0.5 * nbytes <= led["bytes"] <= 2.0 * nbytes, (
        led["bytes"], nbytes)
    # the dot dominates and is categorized as such
    assert led["categories"]["dot"]["flops"] >= 0.9 * flops


# ------------------------------------------- registry anatomy key surface


def _run_registry(anatomy: bool, hlo_dir=None):
    clear_aot_cache()
    reg = CompileRegistry(anatomy=anatomy, hlo_dir=hlo_dir)

    def f(a, b):
        return jnp.dot(a, b)

    jitted = jax.jit(f)
    args = (jnp.ones((8, 16)), jnp.ones((16, 4)))
    reg.call("matmul", (8,), jitted, args)
    reg.call("matmul", (8,), jitted, args)
    return reg


def test_registry_anatomy_present_iff_enabled():
    reg = _run_registry(anatomy=True)
    snap = reg.snapshot()
    anatomy = snap["programs"]["matmul"].get("anatomy")
    assert anatomy, "anatomy missing with the flag on"
    assert anatomy["categories"]["dot"]["flops"] == 2 * 8 * 4 * 16
    stats = reg.anatomy_stats()
    assert "matmul" in stats and stats["matmul"]["ops"] > 0

    off = _run_registry(anatomy=False)
    snap_off = off.snapshot()
    assert "anatomy" not in snap_off["programs"]["matmul"]
    assert off.anatomy_stats() == {}


def test_registry_hlo_dir_dumps_one_file_per_signature(tmp_path):
    hlo_dir = tmp_path / "hlo"
    _run_registry(anatomy=True, hlo_dir=str(hlo_dir))
    files = sorted(os.listdir(hlo_dir))
    assert len(files) == 1, files  # one signature, one TRUE compile
    assert files[0].startswith("matmul__") and files[0].endswith(".hlo.txt")
    text = (hlo_dir / files[0]).read_text()
    assert "HloModule" in text
    assert not [f for f in files if f.startswith(".hlo_tmp_")]


# --------------------------------------------- engine + statusz + trace


def test_engine_statusz_carries_paged_anatomy(gpt_tiny):
    """A paged engine's decode program must expose an anatomy ledger
    through the statusz document that actually NAMES the paged tax:
    gather ops present, and the ledger flops reconciling with the
    program's recorded cost_analysis flops (within the elementwise-
    convention tolerance)."""
    model, params = gpt_tiny
    clear_aot_cache()
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=32, decode_block=4, bucket=8,
        paged=True, page_size=8, xla_obs=True,
    ))
    for p in _prompts(2, lo=4, hi=8):
        eng.submit(p, max_new_tokens=8)
    eng.run()
    doc = eng.statusz()
    progs = doc["compile"]["programs"]
    decode = progs.get("decode_block")
    assert decode is not None
    anatomy = decode.get("anatomy")
    assert anatomy, "paged decode program has no anatomy ledger"
    assert anatomy["categories"].get("gather", {}).get("ops", 0) > 0, (
        "the paged decode gather does not appear in the ledger")
    cost_flops = decode["flops_per_call"]
    if cost_flops > 0:
        assert 0.5 * cost_flops <= anatomy["flops"] <= 2.0 * cost_flops, (
            anatomy["flops"], cost_flops)
    # the document is JSON-serializable end to end (the statusz wire
    # contract)
    json.dumps(doc, default=str)
    eng.close()


def test_trace_anatomy_section_present_iff_recorded(gpt_tiny, tmp_path):
    model, params = gpt_tiny
    clear_aot_cache()
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=32, decode_block=4, bucket=8,
        trace=True, xla_obs=True,
    ))
    for p in _prompts(2, lo=4, hi=8):
        eng.submit(p, max_new_tokens=6)
    eng.run()
    path = tmp_path / "trace.json"
    eng.trace.export_chrome(str(path))
    eng.close()
    summary = summarize_trace(str(path))
    assert "anatomy" in summary
    assert "decode_block" in summary["anatomy"]
    assert summary["anatomy"]["decode_block"]["ops"] > 0

    # PR-4/5-era trace: same events with the anatomy args stripped must
    # summarize with the key ABSENT — pinned backward compat
    events = json.loads(path.read_text())["traceEvents"]
    for e in events:
        if e.get("cat") == "xla" and (e.get("args") or {}).get("anatomy"):
            del e["args"]["anatomy"]
    old = summarize_trace(events)
    assert "anatomy" not in old


def test_trace_summary_cli_prints_anatomy(gpt_tiny, tmp_path, capsys):
    from solvingpapers_tpu.cli import main as cli_main

    model, params = gpt_tiny
    clear_aot_cache()
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=32, decode_block=4, bucket=8,
        trace=True, xla_obs=True,
    ))
    for p in _prompts(2, lo=4, hi=8):
        eng.submit(p, max_new_tokens=6)
    eng.run()
    path = tmp_path / "trace.json"
    eng.trace.export_chrome(str(path))
    eng.close()
    rc = cli_main(["trace-summary", str(path)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "program anatomy" in out
    assert "gather" in out


# ------------------------------------------ snapshot provider hardening


def test_snapshot_survives_raising_provider():
    from solvingpapers_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise RuntimeError("boom")

    m.add_gauge_provider(lambda: {"ok/first": 1.0})
    m.add_gauge_provider(broken)
    m.add_gauge_provider(lambda: {"ok/second": 2.0})

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        snap = m.snapshot()
    assert snap["ok/first"] == 1.0 and snap["ok/second"] == 2.0
    assert not any(k.startswith("broken") for k in snap)
    assert sum("gauge provider" in str(x.message) for x in w) == 1

    # second snapshot: still healthy, NO second warning (warn once per
    # provider), the broken provider still polled (self-heal on
    # transient failures)
    with warnings.catch_warnings(record=True) as w2:
        warnings.simplefilter("always")
        snap2 = m.snapshot()
    assert snap2["ok/first"] == 1.0
    assert calls["n"] == 2
    assert not any("gauge provider" in str(x.message) for x in w2)

    # prom_snapshot rides the same hardened path
    assert m.prom_snapshot()["ok/second"] == 2.0
