#!/usr/bin/env python3
"""The quickest proof that solvingpapers_tpu still starts on the chip.

    python chip_smoke.py            # one chip: device, train, train_flash, profile, serve
    python chip_smoke.py --chips 4  # four chips: sharded vs one-device training only

One process drives the package's normal entry points at the registry width
of the flagship, `dsv3_tinystories` (DeepSeekV3: vocab 50,257, dim 512, 6
layers, 8 heads, latent 64, 8 experts top-2 + shared, block 256, batch 16,
bf16; 196M parameters by the reference notebook's count, 142.7M in this
implementation's tree, whose head is weight-tied and whose MLA keeps one
latent per layer) — a `Trainer` built the way `cli train` builds it,
the Pallas flash-MLA path through `dsv3_long` (16,384-token context), and a
`ServeEngine` behind the `ApiServer` the way `cli serve` assembles them,
answering HTTP requests that are checked against an uncached float32
full-prefix forward. The `profile` phase trains `dsv3_long` under
`TrainConfig.profile_dir` and holds the trace to what the trainer promises:
whole steps, the loop's annotations, and a layer for the device's time.
Weights are random (from `--seed`), and so are the
token ids the runs train on: the file is generated here, not shipped.

There is no CPU mode: a device that is not a TPU fails the `device` phase,
and any failed check raises, so the process exits non-zero with the reason.
Each phase prints one JSON line when it ends; the last line of stdout is
`{"ok": true, "device": {...}}` and is printed only when every phase passed.
"""

from __future__ import annotations

import argparse
import dataclasses
import http.client
import importlib.metadata
import json
import math
import os
import sys
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

FLAGSHIP = "dsv3_tinystories"
LONG = "dsv3_long"
TRAIN_STEPS = 30
# random-init logits are ~N(0, 0.45^2) over the vocabulary, which puts the
# first loss at ln(V) + var/2 ~= ln(V) + 0.1; anything further off means the
# model or the loss is wrong, not unlucky
FIRST_LOSS_TOL = 0.5
# a generated token's float32 reference logit may trail that position's
# maximum by at most this much. The engine computes in bf16 (its logits
# resolve ~0.01 at this scale; the worst gap seen on the v5e is 0.011) and
# random-init logits sit ~0.1 apart at the top, so near-ties flip; a wrong
# cache or position lands ~2 below the max
SERVE_LOGIT_MARGIN = 0.05
# sharded vs one-device loss, same seed, batches and dropout masks: only
# the bf16 reduction order differs
SHARDED_LOSS_TOL = 2e-2
# the steps `profile_phase` traces, counted from the start of `fit`
PROFILE_STEPS = (4, 9)
# the top-level operations of a step against the step's own duration
PROFILE_SUM_TOL = 0.01
# prompt lengths of the concurrent requests: two prefill buckets of 32
PROMPT_LENGTHS = (12, 20, 28, 40, 52, 60)
MAX_NEW_TOKENS = 32


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ------------------------------------------------------------------ device


def device_phase(n_chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    require(dev["platform"] == "tpu",
            f"device: JAX found no TPU (platform {dev['platform']!r})")
    require(dev["count"] >= n_chips,
            f"device: need {n_chips} chip(s), JAX reports {dev['count']}")
    return dev


def environment() -> dict:
    import flax
    import jaxlib

    from solvingpapers_tpu import native

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None  # a label on the line, not a check
    return {
        "jax": jax.__version__, "jaxlib": jaxlib.__version__,
        "flax": flax.__version__, "libtpu": libtpu,
        "python": sys.version.split()[0],
        "native_available": native.available(),
        "native_load_error": native.load_error(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }


# ------------------------------------------------------------------- data


def write_token_file(path: str, n_tokens: int, vocab: int, seed: int) -> str:
    """Seeded token ids over the WHOLE vocabulary, as a `.npy` the `tokens`
    data kind memory-maps. Zipf-distributed under a seeded permutation, so a
    few steps of training have a unigram distribution to learn (uniform ids
    would leave the loss at ln V whatever the model did)."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab + 1) ** 1.1
    ranks = rng.choice(vocab, size=n_tokens, p=p / p.sum())
    ids = rng.permutation(vocab)[ranks]
    dtype = np.uint16 if vocab <= 1 << 16 else np.uint32
    np.save(path, ids.astype(dtype))
    return path


def tokens_config(name: str, token_path: str, **train_overrides):
    """The registry config `name`, fed from a token file: the `tokens` data
    kind keeps `vocab_size` as configured (the char pipeline would shrink
    it to the corpus alphabet)."""
    from solvingpapers_tpu.configs import get_config

    cfg = get_config(name, **train_overrides)
    return dataclasses.replace(cfg, data={
        "kind": "tokens", "path": token_path,
        "block_size": cfg.data["block_size"],
    })


# ------------------------------------------------------------------ train


def run_training(cfg, jsonl_path: str, devices=None):
    """Build the trainer as `cli train` does (`cmd_train`) and `fit`.
    Returns (trainer, final state, logged rows)."""
    from solvingpapers_tpu.configs.factory import (
        build_char_lm_run, init_fn_for, loss_fn_for, rules_for,
    )
    from solvingpapers_tpu.metrics import (
        ConsoleWriter, JSONLWriter, MultiWriter,
    )
    from solvingpapers_tpu.sharding import batch_sharding, create_mesh
    from solvingpapers_tpu.train import Trainer

    mesh = create_mesh(cfg.train.mesh, devices=devices)
    cfg, model, _, train_iter, eval_iter_fn = build_char_lm_run(
        cfg, sharding=batch_sharding(mesh)
    )
    trainer = Trainer(
        model, cfg.train, loss_fn=loss_fn_for(cfg), init_fn=init_fn_for(cfg),
        mesh=mesh, rules=rules_for(cfg),
    )
    writer = MultiWriter(ConsoleWriter(stream=sys.stderr),
                         JSONLWriter(jsonl_path))
    state = trainer.fit(train_iter, eval_iter_fn, writer=writer)
    writer.close()
    with open(jsonl_path) as f:
        rows = [json.loads(line) for line in f]
    return trainer, state, [r for r in rows if "train_loss" in r]


def widths(model_cfg, train_cfg) -> dict:
    """The numbers that make the run 'registry width', as the model and
    trainer that ran hold them."""
    return {
        "vocab": model_cfg.vocab_size, "dim": model_cfg.dim,
        "layers": model_cfg.n_layers, "heads": model_cfg.n_heads,
        "latent": model_cfg.latent_dim, "experts": model_cfg.n_experts,
        "top_experts": model_cfg.top_experts, "block": model_cfg.block_size,
        "batch": train_cfg.batch_size, "dtype": model_cfg.dtype,
    }


def compile_summary(registry) -> dict:
    return {
        name: {"compilations": p["compilations"],
               "compile_s": round(p["compile_time_s"], 2),
               "calls": p["calls"]}
        for name, p in registry.snapshot()["programs"].items()
    }


def fit_phase(phase: str, cfg, jsonl_path: str):
    """`run_training` plus what every training phase reports and checks:
    the widths that ran, the step count, finite losses, compile counts.
    Returns (trainer, losses, line)."""
    t0 = time.perf_counter()
    trainer, state, rows = run_training(cfg, jsonl_path)
    losses = [r["train_loss"] for r in rows]
    out = {
        "config": cfg.name, **widths(trainer.model.cfg, cfg.train),
        "steps": int(jax.device_get(state.step)),
        "n_params": sum(int(np.prod(p.shape))
                        for p in jax.tree.leaves(state.params)),
        "losses": losses,
        "drop_fraction": rows[-1].get("train_moe_drop_fraction"),
        "compiles": compile_summary(trainer._registry),
        "seconds": round(time.perf_counter() - t0, 2),
    }
    require(out["steps"] == cfg.train.steps == len(losses),
            f"{phase}: {out['steps']} steps and {len(losses)} loss rows for "
            f"{cfg.train.steps} asked")
    require(all(math.isfinite(x) for x in losses),
            f"{phase}: non-finite loss in {losses}")
    return trainer, losses, out


def train_phase(cfg, jsonl_path: str) -> dict:
    trainer, losses, out = fit_phase("train", cfg, jsonl_path)
    ln_vocab = math.log(trainer.model.cfg.vocab_size)
    out.update(first_loss=losses[0], last_loss=losses[-1], ln_vocab=ln_vocab)
    require(abs(losses[0] - ln_vocab) < FIRST_LOSS_TOL,
            f"train: first loss {losses[0]} is not within {FIRST_LOSS_TOL} "
            f"of ln(vocab) = {ln_vocab:.4f}")
    require(losses[-1] < losses[0],
            f"train: last loss {losses[-1]} is not below the first "
            f"{losses[0]}")
    return out


def flash_train_phase(cfg, jsonl_path: str) -> dict:
    """`train_phase` through the Pallas kernel: finite losses, and the step
    that RAN holds the kernel as a Mosaic custom call (an interpreted
    kernel lowers to plain HLO and has none)."""
    trainer, _, out = fit_phase("train_flash", cfg, jsonl_path)
    out["mosaic_calls"] = sum(
        t.count("tpu_custom_call")
        for t in trainer._registry.hlo_texts("train_step")
    )
    require(trainer.model.cfg.use_flash, "train_flash: config has no flash")
    require(out["mosaic_calls"] > 0,
            "train_flash: no tpu_custom_call in the compiled train step — "
            "the flash kernel did not run as a Mosaic kernel")
    return out


def profile_phase(cfg, workdir: str) -> dict:
    """Train under `TrainConfig.profile_dir` and read the trace back: the
    window holds exactly `profile_steps[1] - profile_steps[0]` executions
    of the train step, the loop's annotations are on the host plane of the
    same file, `device_scopes.json` lies beside it, a loop's body runs
    inside the loop's own event, and the top-level operations of a step add
    up to the step."""
    import collections
    import glob

    from jax.profiler import ProfileData

    start, stop = cfg.train.profile_steps
    _, _, rows = run_training(cfg, os.path.join(workdir, "profile.jsonl"))
    prof = cfg.train.profile_dir
    with open(os.path.join(prof, "device_scopes.json")) as f:
        scopes = json.load(f)["jit_train_step"]
    found = sorted(glob.glob(
        os.path.join(prof, "plugins", "profile", "*", "*.xplane.pb")))
    require(len(found) == 1, f"profile: expected one trace, found {found}")
    planes = {p.name: p for p in ProfileData.from_file(found[0]).planes}
    require("/device:TPU:0" in planes,
            f"profile: no /device:TPU:0 plane among {sorted(planes)}")
    lines = {ln.name: list(ln.events) for ln in planes["/device:TPU:0"].lines}
    steps = [e for e in lines.get("XLA Modules", [])
             if e.name.startswith("jit_train_step(")]
    require(len(steps) == stop - start,
            f"profile: {len(steps)} executions of jit_train_step in the "
            f"trace, profile_steps={start, stop} promises {stop - start}")
    host = collections.Counter(
        e.name for ln in planes["/host:CPU"].lines for e in ln.events)
    for name in ("train", "data_wait", "train_dispatch"):
        require(host[name] == stop - start,
                f"profile: {host[name]} host annotations {name!r}, "
                f"expected {stop - start}")
    require(host["log_fetch"] >= 1, "profile: no log_fetch annotation")

    def name_of(e):
        return e.name.split(" = ", 1)[0].strip().lstrip("%")

    ops = sorted(lines["XLA Ops"], key=lambda e: e.start_ns)
    lo, hi = steps[0].start_ns, steps[-1].start_ns + steps[-1].duration_ns
    ops = [e for e in ops if lo <= e.start_ns < hi]
    unknown = sorted({name_of(e) for e in ops} - set(scopes))
    require(not unknown, f"profile: operations the map lacks: {unknown[:8]}")
    layer_ms, inside, parent_end, nested_outside = {}, 0, 0, []
    for e in ops:
        layer, _, top_level = scopes[name_of(e)]
        if top_level:
            key = layer or "unscoped"
            layer_ms[key] = layer_ms.get(key, 0.0) + e.duration_ns / 1e6
            parent_end = max(parent_end, e.start_ns + e.duration_ns)
        else:
            inside += 1
            # starts and durations are rounded to the nanosecond each
            over_ns = e.start_ns + e.duration_ns - parent_end
            if over_ns > 2:
                nested_outside.append((name_of(e), over_ns))
    require(not nested_outside,
            "profile: operations of a loop's or branch's body that end "
            f"(ns) after every top-level event so far: {nested_outside[:8]}")
    n = stop - start
    step_ms = sum(e.duration_ns for e in steps) / 1e6 / n
    top_ms = sum(layer_ms.values()) / n
    require(abs(top_ms - step_ms) <= PROFILE_SUM_TOL * step_ms,
            f"profile: top-level operations take {top_ms:.3f} ms a step, "
            f"the step {step_ms:.3f} ms")
    return {
        "config": cfg.name, "profile_steps": [start, stop],
        "train_step_executions": len(steps), "step_ms": step_ms,
        "top_level_ms": top_ms, "events_inside_loops": inside,
        "layer_ms": {k: v / n for k, v in sorted(layer_ms.items())},
        "host_annotations": {k: host[k] for k in (
            "train", "data_wait", "train_dispatch", "log_fetch")},
        "trace_bytes": os.path.getsize(found[0]),
        "data_wait_ms": rows[-1].get("data_wait_ms"),
        "host_loop_ms": rows[-1].get("host_loop_ms"),
    }


def flash_dropout_check(seed: int) -> dict:
    """In-kernel dropout draws from the hardware PRNG: equal seeds give
    equal outputs, different seeds different ones, and a rate > 0 really
    drops (the output differs from the rate-0 one)."""
    from solvingpapers_tpu.kernels import flash_attention

    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (2, 512, 8, 128), jnp.bfloat16)
    k = jax.random.normal(kk, (2, 512, 1, 128), jnp.bfloat16)
    v = jax.random.normal(kv, (2, 512, 1, 128), jnp.bfloat16)

    def run(rate, s):
        return np.asarray(flash_attention(
            q, k, v, causal=True, dropout_rate=rate, dropout_seed=s,
        ).astype(jnp.float32))

    a, b, c, plain = run(0.1, 5), run(0.1, 5), run(0.1, 6), run(0.0, 0)
    require(np.isfinite(a).all(), "flash dropout: non-finite output")
    require(np.array_equal(a, b), "flash dropout: equal seeds differ")
    require(not np.array_equal(a, c), "flash dropout: seeds 5 and 6 agree")
    require(not np.array_equal(a, plain),
            "flash dropout: rate 0.1 equals rate 0 — nothing was dropped")
    return {"dropout_equal_seed": True, "dropout_other_seed_differs": True}


# ------------------------------------------------------------------ serve


def build_serving(cfg, seed: int):
    """Model, params and id-token text codec, as `cli serve`'s
    `_serve_model` builds them for a token-file config."""
    from solvingpapers_tpu.configs.factory import build_char_lm_run

    cfg, model, _, _, _ = build_char_lm_run(cfg)
    dummy = jnp.zeros((1, 8), jnp.int32)
    variables = model.init({"params": jax.random.key(seed)}, dummy)
    params = variables["params"]
    extra = {k: v for k, v in variables.items() if k != "params"}
    # ids-only text: every token renders as its id and a space, so a
    # streamed text splits back into exactly the ids that were generated
    table = [f"{i} " for i in range(model.cfg.vocab_size)]

    def encode(s: str):
        return [int(t) for t in s.split()]

    def decode(ids):
        return "".join(table[int(i)] for i in ids)

    return model, params, extra, table, encode, decode


def _post(host: str, port: int, body: dict) -> dict:
    """One /v1/completions request; returns {"text", "finish_reason",
    "completion_tokens"} from the JSON body or the SSE stream."""
    conn = http.client.HTTPConnection(host, port, timeout=600)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
    finally:
        conn.close()
    require(resp.status == 200, f"serve: HTTP {resp.status}: {raw[:300]}")
    if not body.get("stream"):
        doc = json.loads(raw)
        ch = doc["choices"][0]
        return {"text": ch["text"], "finish_reason": ch["finish_reason"],
                "completion_tokens": doc["usage"]["completion_tokens"]}
    text, reason, usage, done = [], None, None, False
    for frame in raw.split("\n\n"):
        for line in frame.splitlines():
            if not line.startswith("data: "):
                continue
            if line[6:] == "[DONE]":
                done = True
                continue
            ev = json.loads(line[6:])
            require("error" not in ev, f"serve: stream error event {ev}")
            ch = ev["choices"][0]
            text.append(ch.get("text") or "")
            reason = ch.get("finish_reason") or reason
            usage = ev.get("usage") or usage
    require(done, "serve: SSE stream ended without [DONE]")
    return {"text": "".join(text), "finish_reason": reason,
            "completion_tokens": (usage or {}).get("completion_tokens")}


def serve_requests(prompts: list[list[int]]) -> list[dict]:
    """Greedy completion bodies over `prompts`: alternately streamed and
    not, alternately a token-id list and a string prompt."""
    return [
        {"prompt": p if i % 4 < 2 else " ".join(map(str, p)),
         "max_tokens": MAX_NEW_TOKENS, "temperature": 0,
         "stream": i % 2 == 0}
        for i, p in enumerate(prompts)
    ]


def serve_pool(model, params, extra, table, encode, decode, *,
               paged: bool, prompts: list[list[int]]) -> dict:
    """Assemble ServeEngine + ApiServer as `cmd_serve` does (its parser's
    defaults: 8 slots, decode block 8, bucket 32, sample cap 64) on an
    ephemeral port in this process, send `prompts` concurrently over
    HTTP, and return each stream's ids with the engine's end state."""
    from solvingpapers_tpu.serve.api import ApiServer
    from solvingpapers_tpu.serve.engine import ServeConfig, ServeEngine

    max_len = min(512, model.max_positions)
    scfg = ServeConfig(
        n_slots=8, max_len=max_len, decode_block=8, bucket=min(32, max_len),
        sample_cap=64, paged=paged, api_port=0,
    )
    eng = ServeEngine(model, params, scfg, extra_variables=extra or None,
                      detokenize=decode)
    server = ApiServer(eng, encode=encode, decode=decode, token_table=table,
                       model_name=FLAGSHIP)
    bodies = serve_requests(prompts)
    answers: list = [None] * len(bodies)
    errors: list = []

    def client(i):
        try:
            answers[i] = _post(server.host, server.port, bodies[i])
        except Exception as e:  # noqa: BLE001 — re-raised on the main thread
            errors.append(e)

    try:
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(bodies))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        snap = eng.metrics.snapshot()
        health = eng.health
        peak_slots = float(eng.metrics.occupancy.values().max()) * scfg.n_slots
    finally:
        server.close()
    for a, p in zip(answers, prompts):
        a["prompt"] = p
        a["ids"] = encode(a["text"])
    return {"answers": answers, "snapshot": snap, "health": health,
            "peak_slots": peak_slots}


def reference_gaps(model, params, extra, streams: list[dict]) -> np.ndarray:
    """For every generated token, how far its logit trails the position's
    maximum in ONE uncached full-prefix forward over prompt + generated
    tokens — a float32 twin of the model (same parameters, float32
    compute, full matmul precision), no cache, no engine code."""
    ref = type(model)(dataclasses.replace(model.cfg, dtype="float32"))
    longest = max(len(s["prompt"]) + len(s["ids"]) for s in streams)
    width = -(-longest // 64) * 64
    toks = np.zeros((len(streams), width), np.int32)
    for i, s in enumerate(streams):
        seq = s["prompt"] + s["ids"]
        toks[i, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(
            lambda v, t: ref.apply(v, t, deterministic=True)
        )({"params": params, **extra}, jnp.asarray(toks))
    logits = np.asarray(logits.astype(jnp.float32))
    gaps = []
    for i, s in enumerate(streams):
        p = len(s["prompt"])
        for j, tok in enumerate(s["ids"]):
            row = logits[i, p + j - 1]  # predicts the token at p + j
            gaps.append(float(row.max() - row[tok]))
    return np.asarray(gaps)


def serve_phase(cfg, seed: int) -> dict:
    t0 = time.perf_counter()
    model, params, extra, table, encode, decode = build_serving(cfg, seed)
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(seed)
    limit = min(512, model.max_positions) - MAX_NEW_TOKENS
    prompts = [rng.integers(0, vocab, size=min(limit, n)).tolist()
               for n in PROMPT_LENGTHS]
    out = {"config": cfg.name, **widths(model.cfg, cfg.train),
           "requests": len(prompts), "margin": SERVE_LOGIT_MARGIN}
    ids = {}
    for paged in (False, True):
        pool = "paged" if paged else "lane"
        res = serve_pool(model, params, extra, table, encode, decode,
                         paged=paged, prompts=prompts)
        answers, snap = res["answers"], res["snapshot"]
        gaps = reference_gaps(model, params, extra, answers)
        out[pool] = {
            "finish_reasons": sorted({a["finish_reason"] for a in answers}),
            "tokens": sum(len(a["ids"]) for a in answers),
            "health": res["health"],
            "peak_slots": res["peak_slots"],
            "fault_retries": snap.get("serve/fault_retries", 0.0),
            "fault_quarantined": snap.get("serve/fault_quarantined"),
            "max_logit_gap": float(gaps.max()),
            "exact_match_rate": float((gaps == 0.0).mean()),
        }
        for a in answers:
            require(a["finish_reason"] not in (None, "error"),
                    f"serve[{pool}]: a request finished "
                    f"{a['finish_reason']!r}")
            require(len(a["ids"]) == a["completion_tokens"] > 0,
                    f"serve[{pool}]: text holds {len(a['ids'])} ids, usage "
                    f"says {a['completion_tokens']}")
            require(all(0 <= t < vocab for t in a["ids"]),
                    f"serve[{pool}]: token id outside the vocabulary")
        require(res["health"] == "healthy",
                f"serve[{pool}]: engine is {res['health']!r} at the end")
        require(not snap.get("serve/fault_retries"),
                f"serve[{pool}]: the fault boundary retried "
                f"{snap.get('serve/fault_retries')} time(s)")
        require("serve/fault_quarantined" not in snap,
                f"serve[{pool}]: a slot was quarantined")
        require(res["peak_slots"] > 1.5,
                f"serve[{pool}]: never more than one slot decoding "
                f"(peak {res['peak_slots']})")
        require(float(gaps.max()) <= SERVE_LOGIT_MARGIN,
                f"serve[{pool}]: a generated token's float32 reference "
                f"logit trails the maximum by {gaps.max():.4f} > "
                f"{SERVE_LOGIT_MARGIN}")
        ids[pool] = [t for a in answers for t in a["ids"]]
    # informational: both pools are held to the reference above, not to
    # each other (their programs fuse differently, so bf16 near-ties may
    # resolve differently)
    out["lane_paged_token_agreement"] = float(
        np.mean(np.asarray(ids["lane"]) == np.asarray(ids["paged"])))
    out["seconds"] = round(time.perf_counter() - t0, 2)
    return out


# ------------------------------------------------------------ four chips


def sharded_phase(cfg, workdir: str) -> dict:
    """`cfg` on a (data=2, fsdp=2) mesh over four devices against the
    same steps on one device: same seed, same batches (the token-file
    iterator is seeded on the host), same dropout masks (threefry keys
    are sharding-invariant; the registry's default `rbg` stream is not,
    so both runs take the jax default here). Then the mesh once more with
    the PRNG as registered — what `cli train` runs on a mesh — held to
    finite losses only."""
    from solvingpapers_tpu.sharding import MeshConfig

    t0 = time.perf_counter()
    devs = jax.devices()
    base = dataclasses.replace(cfg.train, prng_impl=None, mesh_obs=True)
    one = dataclasses.replace(
        cfg, train=dataclasses.replace(base, mesh=MeshConfig(data=1)))
    four = dataclasses.replace(
        cfg, train=dataclasses.replace(base, mesh=MeshConfig(data=2, fsdp=2)))

    _, state, rows = run_training(one, os.path.join(workdir, "one.jsonl"),
                                  devices=devs[:1])
    one_losses = [r["train_loss"] for r in rows]
    del state
    trainer, state, rows = run_training(
        four, os.path.join(workdir, "four.jsonl"), devices=devs[:4])
    four_losses = [r["train_loss"] for r in rows]
    as_registered = dataclasses.replace(
        four, train=dataclasses.replace(four.train,
                                        prng_impl=cfg.train.prng_impl))
    _, _, reg_rows = run_training(
        as_registered, os.path.join(workdir, "registered.jsonl"),
        devices=devs[:4])
    reg_losses = [r["train_loss"] for r in reg_rows]

    sizes = dict(zip(trainer.mesh.axis_names, trainer.mesh.devices.shape))
    on = set()
    whole_on_first = []
    n_sharded = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(state.params):
        on |= {s.device for s in leaf.addressable_shards}
        if any(sizes[a] > 1 for ax in leaf.sharding.spec if ax is not None
               for a in (ax if isinstance(ax, tuple) else (ax,))):
            n_sharded += 1
            if leaf.addressable_shards[0].data.shape == leaf.shape:
                whole_on_first.append(jax.tree_util.keystr(path))
    coll = trainer._registry.collective_stats().get("train_step", {})
    by_type = {k: v["ops"] for k, v in coll.get("by_type", {}).items()}
    out = {
        "config": cfg.name, **widths(trainer.model.cfg, cfg.train),
        "mesh": sizes,
        "one_device_losses": one_losses, "sharded_losses": four_losses,
        "max_loss_diff": max(abs(a - b)
                             for a, b in zip(one_losses, four_losses)),
        "tolerance": SHARDED_LOSS_TOL,
        "registered_prng": cfg.train.prng_impl,
        "registered_prng_losses": reg_losses,
        "param_devices": sorted(d.id for d in on),
        "sharded_params": n_sharded, "collectives": by_type,
        "comm_bytes": coll.get("bytes"),
        "seconds": round(time.perf_counter() - t0, 2),
    }
    require(len(one_losses) == len(four_losses) == cfg.train.steps,
            f"sharded: {len(one_losses)} and {len(four_losses)} loss rows")
    require(all(math.isfinite(x) for x in one_losses + four_losses),
            "sharded: non-finite loss")
    require(len(reg_losses) == cfg.train.steps
            and all(math.isfinite(x) for x in reg_losses),
            f"sharded: with prng_impl={cfg.train.prng_impl!r} the mesh gave "
            f"{reg_losses}")
    require(out["max_loss_diff"] <= SHARDED_LOSS_TOL,
            f"sharded: losses differ by {out['max_loss_diff']} > "
            f"{SHARDED_LOSS_TOL}: {one_losses} vs {four_losses}")
    require(len(on) == 4, f"sharded: parameters sit on {len(on)} device(s)")
    require(n_sharded > 0, "sharded: no parameter has a sharded axis")
    require(not whole_on_first,
            f"sharded: whole on the first device: {whole_on_first[:5]}")
    require(by_type.get("all-gather", 0) > 0,
            f"sharded: fsdp=2 implies all-gathers, ledger has {by_type}")
    require(by_type.get("all-reduce", 0) + by_type.get("reduce-scatter", 0)
            > 0, f"sharded: no gradient reduction in the ledger: {by_type}")
    return out


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)

    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    t0 = time.perf_counter()
    dev = device_phase(args.chips)
    emit("device", **dev, **environment())

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        tokens = write_token_file(os.path.join(work, "tokens.npy"),
                                  2_000_000, 50257, args.seed)
        common = dict(log_every=1, eval_every=0, seed=args.seed)
        if args.chips == 4:
            cfg = tokens_config(FLAGSHIP, tokens, steps=3, **common)
            emit("train_sharded", **sharded_phase(cfg, work))
        else:
            cfg = tokens_config(FLAGSHIP, tokens, steps=TRAIN_STEPS,
                                xla_obs=True, **common)
            emit("train", **train_phase(
                cfg, os.path.join(work, "train.jsonl")))
            long_cfg = tokens_config(LONG, tokens, steps=3, xla_obs=True,
                                     **common)
            emit("train_flash", **flash_train_phase(
                long_cfg, os.path.join(work, "flash.jsonl")),
                **flash_dropout_check(args.seed))
            prof_cfg = tokens_config(
                LONG, tokens, steps=PROFILE_STEPS[1] + 3,
                profile_dir=os.path.join(work, "profile"),
                profile_steps=PROFILE_STEPS, **common)
            emit("profile", **profile_phase(prof_cfg, work))
            emit("serve", **serve_phase(cfg, args.seed))
    emit("done", seconds=round(time.perf_counter() - t0, 2))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
