"""Per-op HLO cost ledger: the program-anatomy half of the observatory.

`metrics/xla_obs.py` records each compiled program's `cost_analysis()`
TOTALS — one flops number, one bytes number per program. That is enough
to rank programs against each other but useless for the question ROADMAP
item 1 (the fused paged-attention kernel) has to answer: of the paged
decode program's cost, how much is the full-lane page GATHER, how much
the int8 dequant CONVERTs, how much the written-page SCATTER, and how
much the attention/MLP dots the kernel must keep? This module parses the
compiled program's HLO text (`compiled.as_text()`, the same line-scan
discipline as `metrics.mesh_obs.parse_hlo_collectives`) into a per-op-
CATEGORY ledger:

    gather / scatter / dot / convert / fusion / dynamic-slice /
    custom-call / parameter / other

with three numbers per category — op count, estimated flops, and
output-shape bytes — plus the top-k heaviest NAMED ops (with their
jax-level `metadata op_name` source when the compiler kept it), so an
"opaque 27% tax" becomes "%gather.12, 5.2 MB output, from
jit(decode)/gather_lanes/gather".

Conventions (shared with the collective ledger, documented here once):

* Counts are STATIC — an op inside a `while` body (the decode scan)
  counts once, not per trip. The ledger answers "which ops, how big",
  not cycle-exact totals.
* Bytes are the op's OUTPUT shape bytes (tuple outputs summed) — a
  uniform traffic proxy across op kinds. `parameter` ops in the ENTRY
  computation are counted (their "output" is the argument the program
  reads), so the all-category bytes total approximates cost_analysis's
  operand+output "bytes accessed"; parameters of fused/sub-computations
  alias an already-counted operand and are skipped.
* Flops follow XLA's own cost-analysis conventions closely enough to
  reconcile on simple programs (pinned in tests/test_hlo_cost.py):
  elementwise/transcendental ops count one flop per output element,
  `dot` counts ``2 * output_elems * contraction_size`` (contraction
  parsed from the first operand's shape — printed inline, or looked up
  by the operand's name — + `lhs_contracting_dims`), `reduce`
  counts its input elements, and pure data movement (gather, scatter,
  slice, broadcast, copy, bitcast, parameter, ...) counts zero. A
  `fusion` op's flops live on the INNER ops of its fused computation
  (which the scan also walks); the fusion line itself contributes only
  its output bytes — the buffer the fusion materializes.

The same line scan also maps each instruction to the LAYER of the program
that emitted it (`device_scopes`): the train step wraps its layers in
`jax.named_scope`s from a fixed vocabulary (`LAYER_SCOPES`) and names its
Pallas kernels (`KERNEL_SCOPES`), both reach the optimised HLO as
`metadata={op_name=...}`, and the profiler's `XLA Ops` events carry the
instruction's name but not its metadata. `program_scopes` compiles a
program the `Trainer` registered and returns that map, so a device trace
can be summed by layer.

Nothing here imports jax while the module is imported: the parsers take
a string, so they are unit-testable on crafted HLO and usable offline on
`obs_hlo_dir` dumps; `register_program`/`program_scopes` import it when
called.
"""

from __future__ import annotations

import contextlib
import re
from typing import NamedTuple

# category order is the display order everywhere (statusz, trace
# summary, README table) — the paged-tax story first, remainder last
CATEGORIES = (
    "gather",
    "scatter",
    "dot",
    "convert",
    "fusion",
    "dynamic-slice",
    "custom-call",
    "parameter",
    "other",
)

_CATEGORY_OF = {
    "gather": "gather",
    "scatter": "scatter",
    "select-and-scatter": "scatter",
    "dot": "dot",
    "convolution": "dot",
    "convert": "convert",
    "fusion": "fusion",
    "dynamic-slice": "dynamic-slice",
    "dynamic-update-slice": "dynamic-slice",
    "custom-call": "custom-call",
    "parameter": "parameter",
}

# data movement / bookkeeping: zero flops (the XLA cost-analysis
# convention the reconciliation test pins). Everything not listed and
# not special-cased (dot, reduce) counts one flop per output element.
_ZERO_FLOP_OPS = frozenset({
    "parameter", "constant", "broadcast", "bitcast", "bitcast-convert",
    "reshape", "transpose", "copy", "copy-start", "copy-done", "tuple",
    "get-tuple-element", "gather", "scatter", "dynamic-slice",
    "dynamic-update-slice", "slice", "concatenate", "pad", "iota",
    "reverse", "after-all", "all-reduce", "all-gather", "reduce-scatter",
    "all-to-all", "collective-permute", "fusion", "custom-call", "call",
    "while", "conditional", "optimization-barrier", "domain", "send",
    "recv", "send-done", "recv-done", "infeed", "outfeed",
    "partition-id", "replica-id", "rng-bit-generator", "get-dimension-size",
})

# "%name = <output shape(s)> <op>(" — defining occurrences only, the
# parse_hlo_collectives discipline: operand references live inside the
# parens of another op's definition and never follow " = ".
_DEF_HEAD_RE = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[^\s=]+)\s*=\s*")
_DEF_OP_RE = re.compile(r"\s+(?P<op>[a-z][a-z0-9\-]*)\(")


class _Def(NamedTuple):
    """One defining line: `%name = out op(` and where its operands start."""

    name: str
    out: str
    op: str
    end: int


def _match_def(line: str) -> _Def | None:
    head = _DEF_HEAD_RE.match(line)
    if head is None:
        return None
    start = head.end()
    if line.startswith("(", start):
        # a tuple shape; TPU layouts nest parentheses inside it
        # ("{2,1,0:T(8,128)(2,1)S(1)}"), so count them
        depth = 0
        for stop in range(start, len(line)):
            depth += (line[stop] == "(") - (line[stop] == ")")
            if depth == 0:
                break
        else:
            return None
        stop += 1
    else:
        stop = start
        while stop < len(line) and not line[stop].isspace():
            stop += 1
    op = _DEF_OP_RE.match(line, stop)
    if op is None:
        return None
    return _Def(head.group("name"), line[start:stop], op.group("op"), op.end())

_SHAPE_RE = re.compile(
    r"(?P<dt>[a-z]\d*[a-z0-9]*|pred)\[(?P<dims>[\d,]*)\]"
)

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e3m4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{(?P<dims>[\d,]*)\}")
_OP_NAME_RE = re.compile(r'op_name="(?P<src>[^"]*)"')
_OPERAND_NAMES_RE = re.compile(r"%([^\s,()]+)")
_CALLS_RE = re.compile(r"\bcalls=%?(?P<comp>[^\s,}]+)")


def _atom_elems_bytes(dt: str, dims: str) -> tuple[int, int]:
    nbytes = _DTYPE_BYTES.get(dt)
    if nbytes is None:
        digits = re.search(r"(\d+)$", dt)
        nbytes = max(int(digits.group(1)) // 8, 1) if digits else 4
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n, n * nbytes


def _shape_elems_bytes(text: str) -> tuple[int, int]:
    """(total elements, total bytes) of every shape atom in `text` —
    a single shape, or a tuple shape summed."""
    elems = 0
    nbytes = 0
    for m in _SHAPE_RE.finditer(text):
        e, b = _atom_elems_bytes(m.group("dt"), m.group("dims"))
        elems += e
        nbytes += b
    return elems, nbytes


def classify_op(op: str) -> str:
    """HLO opcode -> ledger category (CATEGORIES)."""
    return _CATEGORY_OF.get(op, "other")


_OPERAND_NAME_RE = re.compile(r"\s*%?(?P<name>[^\s,()]+)")


def _first_operand(tail: str, shapes: dict[str, str]):
    """Shape atom (a `_SHAPE_RE` match) of an op's FIRST operand. Older
    HLO text prints operands with their shapes (``dot(f32[8,4]{1,0}
    %a, ...)``); jaxlib 0.9 prints bare names (``dot(%a, %b)``), so the
    name is resolved through `shapes`, the module's name -> output-shape
    table. None when neither is there (minimized dumps)."""
    inline = _SHAPE_RE.match(tail.lstrip())
    if inline is not None:
        return inline
    name = _OPERAND_NAME_RE.match(tail)
    if name is None:
        return None
    return _SHAPE_RE.search(shapes.get(name.group("name"), ""))


def _dot_flops(line: str, lhs, out_elems: int) -> int:
    """``2 * output_elems * contraction_size`` with the contraction
    parsed from the first operand's shape atom `lhs` +
    lhs_contracting_dims; falls back to ``2 * output_elems`` when
    either is absent (elided operand shapes in minimized dumps)."""
    contract = _CONTRACT_RE.search(line)
    if lhs is None or contract is None:
        return 2 * out_elems
    dims_txt = lhs.group("dims")
    lhs_dims = [int(d) for d in dims_txt.split(",")] if dims_txt else []
    k = 1
    for i in contract.group("dims").split(","):
        if i == "":
            continue
        idx = int(i)
        if idx < len(lhs_dims):
            k *= lhs_dims[idx]
    return 2 * out_elems * k


def _scan_defs(hlo_text: str):
    """(in_entry, computation, `_Def`, line) for each defining line of an
    HLO module's text — the one line scan `parse_hlo_costs` and
    `device_scopes` share. `computation` is the name of the computation
    the line sits in."""
    in_entry = False
    comp = ""
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{") and " = " not in stripped:
            # a computation header ("%fused_computation (...) -> ... {",
            # "ENTRY %main (...) {", while/reduce region bodies)
            in_entry = stripped.startswith("ENTRY")
            words = stripped.split()
            comp = words[1 if in_entry else 0].lstrip("%") if words else ""
            continue
        m = _match_def(line)
        if m is not None:
            yield in_entry, comp, m, line


def parse_hlo_costs(hlo_text: str, top_k: int = 5) -> dict:
    """Scan an HLO module's text into the per-op-category cost ledger.

    Returns::

        {"ops": N, "flops": F, "bytes": B,
         "categories": {category: {"ops": n, "flops": f, "bytes": b}},
         "top_ops": [{"name", "op", "category", "flops", "bytes"
                      [, "source"]}, ...]}   # heaviest first

    ``top_ops`` ranks by ``max(flops, bytes)`` — a zero-flop gather
    moving megabytes is exactly as interesting as a dot burning them —
    and carries the jax-level ``metadata op_name`` as ``source`` when
    present. Categories with no ops are ABSENT, never zero-filled; an
    empty module returns zero totals and an empty category dict.
    """
    categories: dict[str, dict[str, int]] = {}
    ops_list: list[dict] = []
    total_ops = 0
    total_flops = 0
    total_bytes = 0
    shapes: dict[str, str] = {}  # op name -> its output shape text
    for in_entry, _, m, line in _scan_defs(hlo_text):
        # only the entry computation's parameters are argument traffic
        op = m.op
        shapes[m.name] = m.out
        if op == "parameter" and not in_entry:
            # a sub-computation's parameter aliases an operand the
            # caller already counted — skipping it keeps the bytes
            # total an operand+output traffic proxy, not double counts
            continue
        out_elems, out_bytes = _shape_elems_bytes(m.out)
        tail = line[m.end:]
        if op in ("dot", "convolution"):
            flops = _dot_flops(line, _first_operand(tail, shapes), out_elems)
        elif op in ("reduce", "reduce-window"):
            first = _first_operand(tail, shapes)
            flops = (
                _atom_elems_bytes(first.group("dt"), first.group("dims"))[0]
                if first is not None else out_elems
            )
        elif op in _ZERO_FLOP_OPS:
            flops = 0
        else:
            flops = out_elems
        cat = classify_op(op)
        d = categories.setdefault(cat, {"ops": 0, "flops": 0, "bytes": 0})
        d["ops"] += 1
        d["flops"] += flops
        d["bytes"] += out_bytes
        total_ops += 1
        total_flops += flops
        total_bytes += out_bytes
        entry = {
            "name": m.name,
            "op": op,
            "category": cat,
            "flops": flops,
            "bytes": out_bytes,
        }
        src = _OP_NAME_RE.search(line)
        if src is not None:
            entry["source"] = src.group("src")
        ops_list.append(entry)
    ops_list.sort(key=lambda e: -max(e["flops"], e["bytes"]))
    return {
        "ops": total_ops,
        "flops": total_flops,
        "bytes": total_bytes,
        "categories": categories,
        "top_ops": ops_list[:top_k],
    }


def best_anatomy(candidates) -> dict | None:
    """Pick the representative ledger from an iterable of per-signature
    candidates: the heaviest-output-bytes NON-EMPTY parse (the
    steady-state variant — the collective-ledger convention), or None
    when nothing parsed. ONE implementation shared by the live registry
    (statusz + anatomy_stats) and the offline trace join, so the three
    surfaces can never pick differently."""
    best = None
    for a in candidates:
        if not a or not a.get("ops"):
            continue
        if best is None or a.get("bytes", 0) > best.get("bytes", 0):
            best = a
    return best


def format_anatomy(anatomy: dict) -> str:
    """Human-readable per-program anatomy report (the `anatomy` section
    of `summarize_trace` / the statusz `programs.<name>.anatomy` dicts:
    {program: parse_hlo_costs result}), or "" when empty."""
    if not anatomy:
        return ""
    lines = ["program anatomy (per-op HLO ledger: static counts, "
             "output-shape bytes):"]
    for prog, d in sorted(anatomy.items(),
                          key=lambda kv: -kv[1].get("bytes", 0)):
        lines.append(
            f"  {prog}: {d.get('ops', 0)} ops, "
            f"{d.get('flops', 0):.3g} flops, {d.get('bytes', 0)} bytes"
        )
        cats = d.get("categories") or {}
        for cat in CATEGORIES:
            c = cats.get(cat)
            if not c:
                continue
            lines.append(
                f"    {cat:<14} x{c['ops']:<4} flops {c['flops']:>12.3g} "
                f"bytes {c['bytes']:>12}"
            )
        top = d.get("top_ops") or []
        if top:
            lines.append("    heaviest ops:")
            for t in top:
                src = t.get("source")
                lines.append(
                    f"      {t['name']:<24} {t['category']:<14} "
                    f"flops {t['flops']:>12.3g} bytes {t['bytes']:>12}"
                    + (f"  [{src}]" if src else "")
                )
    return "\n".join(lines)


# ------------------------------------------------------- layers of a program

# The layer scopes of the train step, one `jax.named_scope` at each layer
# boundary (a family's models/<family>.py, models/mixers.py, models/layers.py,
# ops/moe.py, ops/losses.py, train/objectives.py, train/engine.py; a family
# adds one only with a new layer). Single tokens that no Flax module is named. `L_gdn_*` are a Gated DeltaNet layer's: its projections,
# norms and gate; its causal convolution; the chunked gated delta rule.
# `L_kda_*` are the same three of a Kimi Delta Attention layer (the decay a
# key channel is made in `L_kda_proj`); `L_dense_ffn` a dense SwiGLU layer
# with its norm and residual add. `L_exit_gate` is a looped model's exit
# gate: the gate's product, the exit distribution, the loss's weighted sum
# and entropy.
LAYER_SCOPES = (
    "L_embed",
    "L_attn_proj",
    "L_attn_core",
    "L_gdn_proj",
    "L_gdn_conv",
    "L_gdn_core",
    "L_kda_proj",
    "L_kda_conv",
    "L_kda_core",
    "L_ssm_proj",
    "L_ssm_conv",
    "L_ssm_core",
    "L_dense_ffn",
    "L_moe_gate",
    "L_moe_dispatch",
    "L_moe_experts",
    "L_moe_combine",
    "L_moe_shared",
    "L_moe_stats",
    "L_loss_head",
    "L_optimizer",
    "L_exit_gate",
    "L_dsa_index",
    "L_dsa_select",
    "L_dsa_attend",
    "L_dsa_loss",
)
# `name=` of the three `pallas_call`s of kernels/flash_attention.py
KERNEL_SCOPES = ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv")

_SCOPE_RE = re.compile(
    r"(?<![A-Za-z0-9_])(" + "|".join(LAYER_SCOPES + KERNEL_SCOPES)
    + r")(?![A-Za-z0-9_])"
)


class DeviceScope(NamedTuple):
    """Where one HLO instruction comes from in the program."""

    layer: str | None  # innermost of LAYER_SCOPES / KERNEL_SCOPES
    pass_: str  # "fwd", "bwd" or "remat"
    top_level: bool  # defined in the ENTRY computation


def device_scopes(hlo_text: str) -> dict[str, DeviceScope]:
    """{instruction name: DeviceScope} of a compiled program's text.

    The layer is the innermost vocabulary token of the instruction's own
    `op_name` (`jit(train_step)/transpose(jvp(DeepSeekV3))/layer_0/moe/
    L_moe_combine/jit(_take)/gather` -> `L_moe_combine`; a
    `tpu_custom_call` carries its kernel's name there, inside the layer
    that called it). A fusion's line holds its root's metadata; where it
    holds none the fusion takes the last `op_name` of the computation it
    calls. An instruction the compiler made, with no metadata at all (a
    layout copy, a reduce split in two, a prefetch into fast memory),
    takes the layer and pass of its first operand that has a layer, else
    of the first instruction that uses it; next to a kernel that is the
    layer the kernel is called in, not the kernel's name, which stays
    with the kernel's own call. The pass is `remat` under
    `rematted_computation` (the forward that `jax.checkpoint` runs again),
    `bwd` under `transpose(`, else `fwd`.

    `top_level`: the profiler records a `while`, a `conditional` or a
    `call` as one event and the instructions of its body as events inside
    it, so device time is summed over top-level instructions only.
    Parameters and constants run nothing and are left out."""
    out: dict[str, DeviceScope] = {}
    lends: dict[str, DeviceScope] = {}  # what a neighbour inherits
    last_path: dict[str, str] = {}  # computation -> its last op_name
    bare: dict[str, list[str]] = {}  # no metadata, no layer -> operands
    for in_entry, comp, m, line in _scan_defs(hlo_text):
        if m.op in ("parameter", "constant"):
            continue
        src = _OP_NAME_RE.search(line)
        path = src.group("src") if src is not None else ""
        if path:
            last_path[comp] = path
        elif m.op == "fusion":
            called = _CALLS_RE.search(line)
            if called is not None:
                path = last_path.get(called.group("comp"), "")
        found = _SCOPE_RE.findall(path)
        if "rematted_computation" in path:
            pass_ = "remat"
        elif "transpose(" in path:
            pass_ = "bwd"
        else:
            pass_ = "fwd"
        scope = DeviceScope(found[-1] if found else None, pass_, in_entry)
        lend = scope._replace(layer=next(
            (t for t in reversed(found) if t in LAYER_SCOPES), None
        ))
        operands = _OPERAND_NAMES_RE.findall(
            line[m.end:].split(")", 1)[0]
        )
        if not path:
            for name in operands:
                if name in lends and lends[name].layer is not None:
                    scope = lend = lends[name]._replace(top_level=in_entry)
                    break
            else:
                bare[m.name] = operands
        out[m.name], lends[m.name] = scope, lend
        if lend.layer is not None:
            # hand the layer up to what the compiler made to feed this
            todo = [o for o in operands if o in bare]
            while todo:
                name = todo.pop()
                todo += [o for o in bare.pop(name, ()) if o in bare]
                out[name] = lends[name] = lend._replace(
                    top_level=out[name].top_level
                )
    return out


# program name as the profiler shows it ("jit_train_step") -> (the jitted
# function, the abstract arguments of its first dispatch). Filled by
# `Trainer`; holds shapes, no device buffer.
_PROGRAMS: dict[str, tuple] = {}
_SCOPES: dict[str, dict[str, DeviceScope]] = {}  # name -> its map, once made


def register_program(name: str, jitted, args: tuple) -> None:
    """Remember how to compile `jitted` again: `args` are the arguments of
    a dispatch, kept as `ShapeDtypeStruct`s with their shardings."""
    import jax

    _PROGRAMS[name] = (jitted, jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=getattr(a, "sharding", None)
        ),
        args,
    ))
    _SCOPES.pop(name, None)


@contextlib.contextmanager
def _persistent_cache_off():
    """Compile without JAX's persistent compilation cache. Its key leaves
    metadata out (`jax_compilation_cache_include_metadata_in_key` is
    False), so after a change that only moves a scope a hit returns an
    executable whose text still holds the old `op_name`s
    (tests/test_device_scopes.py pins it)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def program_scopes(name: str) -> dict[str, DeviceScope] | None:
    """`device_scopes` of the registered program `name`, from a compile of
    its current lowering (tens of seconds for a train step: call it after
    the measured work, never inside it). None for a name no `Trainer` of
    this process has dispatched. Kept until the name is registered again."""
    if name not in _PROGRAMS:
        return None
    if name not in _SCOPES:
        jitted, args = _PROGRAMS[name]
        with _persistent_cache_off():
            text = jitted.lower(*args).compile().as_text()
        _SCOPES[name] = device_scopes(text)
    return _SCOPES[name]
