"""CLI entrypoints — `python -m solvingpapers_tpu.cli <cmd>`.

Replaces the reference's notebook cells with commands (every notebook's
train() cell becomes a CLI entrypoint):

    cli list
    cli train  --config gpt_shakespeare [--steps N] [--data-path f.txt]
               [--checkpoint-dir ckpts] [--jsonl metrics.jsonl]
    cli sample --config gpt_shakespeare --checkpoint-dir ckpts
               [--prompt "ROMEO:"] [--max-new-tokens 200] [--top-k 50]
    cli serve  --config gpt_shakespeare [--checkpoint-dir ckpts]
               [--port 8000] — OpenAI-compatible /v1/completions +
               /v1/chat/completions (SSE streaming, json_object mode)
    cli replay --config gpt_shakespeare --journal serve.jsonl
               [--config-overrides kv_quant=int8] [--out report.json]
               — config-canary divergence gate (exit 2 on divergence)
    cli trace-summary serve_trace.json [--top 10]
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", required=True)
    p.add_argument("--data-path", default=None)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument(
        "--platform",
        default=None,
        choices=["cpu", "tpu"],
        help="force a JAX platform ('cpu' enables local debugging and "
        "virtual multi-device meshes)",
    )
    p.add_argument(
        "--virtual-devices",
        type=int,
        default=None,
        help="with --platform cpu: number of virtual host devices to "
        "provision (xla_force_host_platform_device_count), so multi-axis "
        "meshes run without hardware; must be set before any JAX "
        "computation, i.e. only works as a process entry flag",
    )


def _apply_platform(args) -> None:
    """Apply --platform/--virtual-devices. Called from main() BEFORE any
    command code touches jax attributes: XLA reads XLA_FLAGS at backend
    initialization, so mutating it after a backend exists is a silent no-op
    — fail loudly instead of quietly running on the wrong device count."""
    n = getattr(args, "virtual_devices", None)
    if n:
        import os

        from jax._src import xla_bridge

        from solvingpapers_tpu.hostenv import virtual_cpu_env

        if xla_bridge.backends_are_initialized():
            raise RuntimeError(
                "--virtual-devices must be applied before any JAX "
                "backend initializes, but one already has; re-exec with "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={n}"
            )
        os.environ["XLA_FLAGS"] = virtual_cpu_env(n)["XLA_FLAGS"]
    if getattr(args, "platform", None):
        jax.config.update("jax_platforms", args.platform)


def cmd_list(_args) -> int:
    from solvingpapers_tpu.configs import list_configs

    for name in list_configs():
        print(name)
    return 0


def cmd_train(args) -> int:
    from solvingpapers_tpu.configs import get_config
    from solvingpapers_tpu.configs.factory import (
        build_char_lm_run,
        build_image_run,
        init_fn_for,
        loss_fn_for,
    )
    from solvingpapers_tpu.configs.families import FAMILIES
    from solvingpapers_tpu.metrics import ConsoleWriter, JSONLWriter, MultiWriter
    from solvingpapers_tpu.sharding import batch_sharding, create_mesh
    from solvingpapers_tpu.train import Trainer

    overrides = {}
    if args.steps is not None:
        overrides["steps"] = args.steps
        # keep the LR schedule aligned with the actual horizon
    if args.checkpoint_dir:
        overrides["checkpoint_dir"] = args.checkpoint_dir
        overrides["ckpt_every"] = args.ckpt_every
    cfg = get_config(args.config, **overrides)
    if args.data_path:
        cfg = dataclasses.replace(cfg, data={**cfg.data, "path": args.data_path})

    cp = getattr(cfg.model, "context_parallel", False)
    if cp and not cfg.train.context_parallel:
        # a CP model demands the CP train step; keep the two flags in sync
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, context_parallel=True)
        )
    mesh = create_mesh(cfg.train.mesh)
    writer = ConsoleWriter()  # fit() gates cadence by log_every
    if args.jsonl:
        writer = MultiWriter(writer, JSONLWriter(args.jsonl))

    kind = cfg.data.get("kind", "char")
    if kind in ("char", "bpe", "tokens"):
        from solvingpapers_tpu.configs.factory import rules_for

        cfg, model, tok, train_iter, eval_iter_fn = build_char_lm_run(
            cfg, sharding=batch_sharding(mesh, context=cp)
        )
        count_flops = FAMILIES[cfg.model_family].flops_per_token
        if count_flops is not None and not cfg.train.flops_per_token:
            # the row's `mfu`, where the family counts its own operations
            cfg = dataclasses.replace(cfg, train=dataclasses.replace(
                cfg.train, flops_per_token=count_flops(
                    cfg.model, cfg.data.get("block_size", 256))))
        trainer = Trainer(
            model, cfg.train, loss_fn=loss_fn_for(cfg),
            init_fn=init_fn_for(cfg), mesh=mesh, rules=rules_for(cfg),
        )
        callbacks = None
        can_sample = False
        # CP samples through a dense twin (params are replicated at rest);
        # PP stage-stacked params still need the export conversion first
        no_decode = cfg.train.pipeline_parallel
        sample_model = model
        if cp:
            sample_model = type(model)(
                dataclasses.replace(model.cfg, context_parallel=False)
            )
        if args.artifacts_dir and no_decode:
            print("[sample] disabled: decode caches are unsupported under "
                  "pipeline parallelism (export stage params first)",
                  file=sys.stderr)
        elif args.artifacts_dir:
            try:  # token-file runs have no text tokenizer to build prompts
                can_sample = len(tok.encode("\n")) > 0
                if not can_sample:
                    print("[sample] disabled: tokenizer yields an empty "
                          "prompt", file=sys.stderr)
            except Exception as e:
                print(f"[sample] disabled: {e}", file=sys.stderr)
        if can_sample:
            # deepseekv3 cell 54: sample + save generated_{step}.txt each eval
            from solvingpapers_tpu import ops
            from solvingpapers_tpu.infer import generate
            from solvingpapers_tpu.metrics.viz import save_text_sample

            # one sampler object: it is a static jit arg of generate, and a
            # fresh partial per call would retrace + recompile every sample
            sampler = functools.partial(ops.sample_top_k, k=50)

            def sample_cb(state, step, _tok=tok, _model=sample_model, _cp=cp):
                prompt = jnp.asarray(_tok.encode("\n"), jnp.int32)[None, :]
                extra = state.model_state or None
                # CP state lives on the training mesh; pull the replicated
                # params to host so the dense twin decodes on one device
                params = jax.device_get(state.params) if _cp else state.params
                if _cp and extra:
                    extra = jax.device_get(extra)
                limit = getattr(_model, "max_positions", None) or 1_000_000
                out = generate(
                    _model, params, prompt, jax.random.key(step),
                    max_new_tokens=min(200, limit - prompt.shape[1]),
                    sampler=sampler,
                    extra_variables=extra,
                )
                path = save_text_sample(
                    _tok.decode(np.asarray(out[0])), args.artifacts_dir, step
                )
                print(f"[sample] wrote {path}")

            every = cfg.train.eval_every or cfg.train.log_every
            callbacks = [(every, sample_cb)]
        trainer.fit(train_iter, eval_iter_fn, writer=writer, callbacks=callbacks)
        return 0
    if kind == "images":
        if cfg.model_family == "kd":
            return _train_kd(cfg, mesh, writer)
        model, train_iter, eval_iter_fn, loss_fn = build_image_run(cfg, mesh=mesh)
        trainer = Trainer(model, cfg.train, loss_fn=loss_fn, mesh=mesh)
        state = trainer.fit(train_iter, eval_iter_fn, writer=writer)
        if args.artifacts_dir and cfg.model_family in ("ae", "vae"):
            # autoencoder.ipynb cell 9 / vae cell 9: reconstruction grid
            from solvingpapers_tpu.metrics.viz import save_reconstruction_grid

            batch = next(eval_iter_fn())
            out = model.apply(
                {"params": state.params}, batch["x"], deterministic=True
            )
            recon = out[0] if isinstance(out, tuple) else out
            path = save_reconstruction_grid(
                np.asarray(batch["x"]), np.asarray(jax.device_get(recon)),
                f"{args.artifacts_dir}/reconstructions.png",
            )
            print(f"[viz] wrote {path}")
        return 0
    raise ValueError(f"unknown data kind {kind!r}")


def _train_kd(cfg, mesh, writer) -> int:
    """kd.py pipeline: pretrain teacher, freeze, distill student."""
    import jax as _jax

    from solvingpapers_tpu.configs.factory import build_image_run
    from solvingpapers_tpu.models.kd import MLPClassifier, teacher_config
    from solvingpapers_tpu.train import Trainer, make_kd_loss_fn

    _, train_iter, eval_iter_fn, cls_loss = build_image_run(cfg, mesh=mesh)
    teacher_steps = cfg.data.get("teacher_steps", 1200)
    t_cfg = dataclasses.replace(
        cfg.train, steps=teacher_steps, checkpoint_dir=None, ckpt_every=0
    )
    teacher = MLPClassifier(teacher_config(dtype=cfg.model.dtype))
    print(f"[kd] pretraining teacher for {teacher_steps} steps")
    t_trainer = Trainer(teacher, t_cfg, loss_fn=cls_loss, mesh=mesh)
    t_state = t_trainer.fit(train_iter, eval_iter_fn, writer=writer)

    print(f"[kd] distilling student for {cfg.train.steps} steps")
    student = MLPClassifier(cfg.model)
    kd_loss = make_kd_loss_fn(
        teacher,
        _jax.device_get(t_state.params),
        temperature=cfg.data.get("temperature", 7.0),
        alpha=cfg.data.get("alpha", 0.3),
    )
    s_trainer = Trainer(student, cfg.train, loss_fn=kd_loss, mesh=mesh)
    s_trainer.fit(train_iter, eval_iter_fn, writer=writer)
    return 0


def cmd_sample(args) -> int:
    from solvingpapers_tpu import ops
    from solvingpapers_tpu.configs import get_config
    from solvingpapers_tpu.configs.factory import build_char_lm_run
    from solvingpapers_tpu.infer import generate

    cfg = get_config(args.config)
    if cfg.train.pipeline_parallel:
        print(
            "sampling is unsupported for pipeline-parallel configs; "
            "export the stage-stacked params to the dense family first",
            file=sys.stderr,
        )
        return 2
    if getattr(cfg.model, "context_parallel", False):
        # Single-chip path: CP params are replicated at rest, so a non-CP
        # twin of the same architecture decodes them directly (tested:
        # tests/test_infer_prefill.py::test_cp_trained_weights_export_to_plain_decode).
        # On a real multi-chip mesh, `infer.generate_cp` decodes UNDER CP
        # instead — context-sharded caches, ring prefill, prompts beyond
        # one chip's HBM (tests/test_deepseekv3.py::test_cp_decode_*).
        from solvingpapers_tpu.sharding import MeshConfig

        cfg = dataclasses.replace(
            cfg,
            model=dataclasses.replace(cfg.model, context_parallel=False),
            train=dataclasses.replace(
                cfg.train, context_parallel=False, mesh=MeshConfig()
            ),
        )
    if args.data_path:
        cfg = dataclasses.replace(cfg, data={**cfg.data, "path": args.data_path})
    cfg, model, tok, _, _ = build_char_lm_run(cfg)

    rng = jax.random.key(args.seed)
    if getattr(args, "prompt_file", None):
        with open(args.prompt_file, "r", encoding="utf-8") as f:
            prompt_text = f.read()
    else:
        prompt_text = args.prompt or "\n"
    ids = tok.encode(prompt_text)
    limit = getattr(model, "max_positions", None)
    if limit is not None and len(ids) + args.max_new_tokens > limit:
        # keep a multiple of 128 so every flash prefill chunk keeps a
        # Mosaic-legal q block (kernels/flash_attention._pick_block_q);
        # floor at 1 token — tiny contexts truncate unaligned rather than
        # keeping nothing (ids[-0:] would silently keep everything)
        keep = (limit - args.max_new_tokens) // 128 * 128
        if keep <= 0:
            keep = limit - args.max_new_tokens
        if keep <= 0:
            print(f"[sample] max-new-tokens {args.max_new_tokens} >= model "
                  f"max positions {limit}: no room for a prompt",
                  file=sys.stderr)
            return 2
        print(f"[sample] prompt of {len(ids)} tokens truncated to its last "
              f"{keep} (model max positions {limit} - "
              f"{args.max_new_tokens} new)", file=sys.stderr)
        ids = ids[-keep:]
    prompt = jnp.asarray(ids, jnp.int32)[None, :]
    # init on a short dummy: param shapes are seq-independent, and a full
    # uncached forward over a 16k prompt just to initialize would run the
    # single-shot attention the chunked prefill exists to avoid
    init_toks = prompt[:, : min(prompt.shape[1], 128)]
    init_kwargs = {}
    if getattr(args, "speculative", False):
        n_drafts = getattr(args, "spec_drafts", 1)
        if getattr(cfg.model, "mtp_heads", 0) < n_drafts:
            print(
                f"--speculative with --spec-drafts {n_drafts} needs a model "
                f"with mtp_heads >= {n_drafts} "
                f"(config {cfg.name!r} has {getattr(cfg.model, 'mtp_heads', 0)})",
                file=sys.stderr,
            )
            return 1
        if not args.greedy:
            print(
                "--speculative decodes greedily (exact-match draft "
                "verification); pass --greedy — temperature/top-k are "
                "not supported",
                file=sys.stderr,
            )
            return 1
        if prompt.shape[1] < 2:
            print(
                "--speculative needs a prompt of at least 2 tokens "
                "(pass --prompt)",
                file=sys.stderr,
            )
            return 1
        # trace the MTP branch so the head params / routing state exist
        # even without a checkpoint
        init_kwargs["return_mtp"] = True
    variables = model.init({"params": rng}, init_toks, **init_kwargs)
    params = variables["params"]
    extra = {k: v for k, v in variables.items() if k != "params"}

    if args.checkpoint_dir:
        restored = _restore_for_inference(
            cfg, model, args.checkpoint_dir, {"x": prompt, "y": prompt}
        )
        if restored is None:
            print(f"no checkpoint found in {args.checkpoint_dir}", file=sys.stderr)
            return 1
        _, params, extra_restored = restored
        if extra_restored:
            extra = extra_restored

    sampler = (
        ops.sample_greedy
        if args.greedy
        else functools.partial(ops.sample_top_k, k=args.top_k, temperature=args.temperature)
    )
    # long prompts prefill in chunks (static end-aligned flash/causal calls
    # into the cache) so activation memory stays bounded; "auto" = one chunk
    # for short prompts, 2048-token chunks past that
    chunk = args.prefill_chunk
    if chunk is None and prompt.shape[1] > 4096:
        chunk = 2048
    if getattr(args, "speculative", False):
        # MTP self-speculative greedy decode (infer/speculative.py):
        # output identical to --greedy, fewer forwards
        from solvingpapers_tpu.infer import generate_speculative

        out, stats = generate_speculative(
            model, params, prompt, max_new_tokens=args.max_new_tokens,
            extra_variables=extra or None, prefill_chunk=chunk,
            n_drafts=getattr(args, "spec_drafts", 1),
        )
        f, a = int(stats["forwards"]), int(stats["accepted"])
        print(
            f"[speculative] forwards={f} accepted={a} "
            f"tokens/forward={(f + a) / max(f, 1):.2f}",
            file=sys.stderr,
        )
    else:
        out = generate(
            model, params, prompt, rng, max_new_tokens=args.max_new_tokens,
            sampler=sampler, extra_variables=extra or None, prefill_chunk=chunk,
        )
    print(tok.decode(np.asarray(out[0])))
    return 0


def _serve_model(args, *, quiet_random_init: bool = False):
    """Build the serving model EXACTLY as `cli serve` does — config
    densification, `jax.random.key(args.seed)` init, optional
    checkpoint restore, and the full-vocab token table. `cli replay`
    reuses this so a journal recorded by a serving process replays
    byte-exactly in a different process: same seed -> same params ->
    same logits. Returns (model, params, extra, table, encode, decode)
    or an int exit code on a usage error."""
    from solvingpapers_tpu.configs import get_config
    from solvingpapers_tpu.configs.factory import build_char_lm_run
    from solvingpapers_tpu.configs.families import FAMILIES
    from solvingpapers_tpu.serve.openai import extend_token_table

    cfg = get_config(args.config)
    if cfg.train.pipeline_parallel:
        print("serving is unsupported for pipeline-parallel configs; "
              "export the stage-stacked params to the dense family first",
              file=sys.stderr)
        return 2
    unservable = FAMILIES[cfg.model_family].unservable
    if unservable is not None:
        print(f"serving is unsupported for the {cfg.model_family} family: "
              f"{unservable}; `cli train` runs it", file=sys.stderr)
        return 2
    if getattr(cfg.model, "context_parallel", False):
        # params are replicated at rest: serve the dense twin, exactly
        # like cmd_sample's single-chip path
        from solvingpapers_tpu.sharding import MeshConfig

        cfg = dataclasses.replace(
            cfg,
            model=dataclasses.replace(cfg.model, context_parallel=False),
            train=dataclasses.replace(
                cfg.train, context_parallel=False, mesh=MeshConfig()
            ),
        )
    if args.data_path:
        cfg = dataclasses.replace(cfg, data={**cfg.data, "path": args.data_path})
    cfg, model, tok, _, _ = build_char_lm_run(cfg)

    dummy = jnp.zeros((1, 8), jnp.int32)
    variables = model.init({"params": jax.random.key(args.seed)}, dummy)
    params = variables["params"]
    extra = {k: v for k, v in variables.items() if k != "params"}
    if args.checkpoint_dir:
        restored = _restore_for_inference(
            cfg, model, args.checkpoint_dir, {"x": dummy, "y": dummy}
        )
        if restored is None:
            print(f"no checkpoint found in {args.checkpoint_dir}",
                  file=sys.stderr)
            return 1
        _, params, extra_restored = restored
        if extra_restored:
            extra = extra_restored
    elif not quiet_random_init:
        print("[serve] no --checkpoint-dir: serving RANDOM-INIT params "
              "(endpoint/latency demo, not a language model)",
              file=sys.stderr)

    # token table over the FULL model vocab: corpus tokenizer ids decode
    # normally, spare ids (model vocab_size > corpus charset) map to the
    # missing JSON structural chars so json_object mode is expressible
    vocab = getattr(model.cfg, "vocab_size", tok.vocab_size)
    table = []
    for i in range(vocab):
        try:
            table.append(tok.decode([i]))
        except (KeyError, IndexError):
            table.append(None)
    table = extend_token_table(table, vocab)
    stoi = {}
    for i, t in enumerate(table):
        if t is not None and len(t) == 1 and t not in stoi:
            stoi[t] = i

    def encode(s: str):
        return [stoi[c] for c in s]

    def decode(ids):
        return "".join(table[int(i)] or "" for i in ids)

    return model, params, extra, table, encode, decode


def cmd_serve(args) -> int:
    """Serve a model over the OpenAI-compatible HTTP front door
    (serve/api.py): POST /v1/completions + /v1/chat/completions (SSE
    streaming, json_object mode) plus /healthz /metrics /statusz on ONE
    port. Ctrl-C / SIGTERM shuts down in order: drain active streams,
    close the engine, stop the HTTP threads."""
    import signal
    import threading

    from solvingpapers_tpu.serve.api import ApiServer
    from solvingpapers_tpu.serve.engine import ServeConfig, ServeEngine

    built = _serve_model(args)
    if isinstance(built, int):
        return built
    model, params, extra, table, encode, decode = built
    slo_targets = None
    if args.slo:
        from solvingpapers_tpu.serve.slo import DEFAULT_SLO_TARGETS

        slo_targets = DEFAULT_SLO_TARGETS
    limit = getattr(model, "max_positions", None) or 512
    max_len = args.max_len or min(512, limit)
    serve_cfg = ServeConfig(
        n_slots=args.slots,
        max_len=max_len,
        decode_block=args.decode_block,
        bucket=min(args.bucket, max_len),
        sample_cap=args.sample_cap,
        paged=args.paged,
        kv_quant=args.kv_quant,
        kv_quant_block=args.kv_quant_block,
        kv_exact_lanes=args.kv_exact_lanes,
        speculative=args.speculative,
        spec_k=args.spec_k,
        spec_rounds=args.spec_rounds,
        api_port=args.port,
        api_host=args.host,
        json_mode=not args.no_json_mode,
        max_waiting=args.max_waiting,
        trace=args.trace,
        slo_targets=slo_targets,
        degrade=args.degrade,
        fault_step_deadline_s=args.step_deadline,
        journal_path=args.journal,
        journal_strict=args.journal_strict,
        timeseries=args.timeseries_interval > 0,
        timeseries_interval_s=args.timeseries_interval or 1.0,
        timeseries_capacity=args.timeseries_capacity,
    )
    n_replicas = max(1, args.replicas)
    engines = []
    for i in range(n_replicas):
        rep_cfg = serve_cfg
        if args.journal and n_replicas > 1:
            # each replica needs its own write-ahead journal — a shared
            # file would interleave records from independent engines
            rep_cfg = dataclasses.replace(
                serve_cfg, journal_path=f"{args.journal}.r{i}"
            )
        eng = ServeEngine(model, params, rep_cfg,
                          extra_variables=extra or None, detokenize=decode)
        if rep_cfg.journal_path:
            # crash-safe warm restart: replay the journal's unfinished
            # entries BEFORE the front door starts stepping — recovered
            # greedy/seeded streams continue token-exactly
            resumed = eng.recover()
            print(f"[serve] journal {rep_cfg.journal_path}: recovered "
                  f"{len(resumed)} in-flight request(s)", file=sys.stderr)
        engines.append(eng)
    router = None
    if n_replicas > 1:
        from solvingpapers_tpu.serve.fleet import FleetRouter

        router = FleetRouter(engines)
        server = ApiServer(encode=encode, decode=decode,
                           token_table=table, model_name=args.config,
                           router=router)
        fleet_note = f" — fleet of {n_replicas} replicas"
    else:
        server = ApiServer(engines[0], encode=encode, decode=decode,
                           token_table=table, model_name=args.config)
        fleet_note = ""
    print(f"[serve] {args.config} on http://{server.host}:{server.port} "
          f"— POST /v1/completions /v1/chat/completions, "
          f"GET /healthz /metrics /statusz{fleet_note}", file=sys.stderr)

    stop = threading.Event()

    def _sig(signum, frame):
        stop.set()

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    try:
        while not stop.wait(0.5):
            pass
    finally:
        print("[serve] shutting down: draining streams, closing engine",
              file=sys.stderr)
        server.close()
        if args.trace_out:
            # export AFTER close so the drain/shutdown spans make the
            # file; the recorders outlive their engines
            try:
                if router is not None:
                    router.export_chrome_fleet(args.trace_out)
                elif engines[0].trace is not None:
                    engines[0].trace.export_chrome(args.trace_out)
                else:
                    raise ValueError("tracing is off — pass --trace")
                print(f"[serve] trace -> {args.trace_out}",
                      file=sys.stderr)
            except ValueError as e:
                print(f"[serve] --trace-out skipped: {e}",
                      file=sys.stderr)
    return 0


def cmd_replay(args) -> int:
    """Replay a request journal against a candidate serving config and
    gate on the divergence report (serve/replay.py) — the config-canary
    check: journal production traffic, replay it under the proposed
    knobs, ship only if the streams still match.

    Builds the model exactly as `cli serve` does (same --config /
    --seed / --checkpoint-dir => same params), loads the journal's
    finished streams, re-serves them on a fresh engine shaped by the
    engine flags + --config-overrides, and prints the report JSON.
    With overrides, the un-overridden config is re-served too for
    paired latency/throughput deltas.

    Exit codes: 0 = gate passed; 2 = divergence beyond
    --byte-exact-min / --agreement-min (the CI-able canary signal);
    1 = operational failure (unreadable journal, nothing comparable)."""
    from solvingpapers_tpu.serve.engine import ServeConfig
    from solvingpapers_tpu.serve.journal import JournalError
    from solvingpapers_tpu.serve.replay import ReplayHarness, apply_overrides

    built = _serve_model(args, quiet_random_init=True)
    if isinstance(built, int):
        return built
    model, params, extra, _, _, decode = built
    if not args.checkpoint_dir:
        print("[replay] no --checkpoint-dir: random-init params — fine "
              "iff the journal was recorded by the same seed's "
              "random-init server", file=sys.stderr)

    limit = getattr(model, "max_positions", None) or 512
    max_len = args.max_len or min(512, limit)
    base_cfg = ServeConfig(
        n_slots=args.slots,
        max_len=max_len,
        decode_block=args.decode_block,
        bucket=min(args.bucket, max_len),
        sample_cap=args.sample_cap,
        paged=args.paged,
        kv_quant=args.kv_quant,
        kv_quant_block=args.kv_quant_block,
        kv_exact_lanes=args.kv_exact_lanes,
        speculative=args.speculative,
        spec_k=args.spec_k,
        spec_rounds=args.spec_rounds,
        max_waiting=args.max_waiting,
    )
    overrides = {}
    for kv in args.config_overrides or []:
        if "=" not in kv:
            print(f"[replay] --config-overrides takes KEY=VALUE pairs, "
                  f"got {kv!r}", file=sys.stderr)
            return 2
        k, v = kv.split("=", 1)
        overrides[k] = v
    try:
        candidate = apply_overrides(base_cfg, overrides)
    except (ValueError, TypeError) as e:
        print(f"[replay] {e}", file=sys.stderr)
        return 2

    harness = ReplayHarness(model, params, extra_variables=extra or None,
                            detokenize=decode)
    try:
        entries = harness.load(args.journal)
    except FileNotFoundError:
        print(f"[replay] journal not found: {args.journal}",
              file=sys.stderr)
        return 1
    except JournalError as e:
        print(f"[replay] {e}", file=sys.stderr)
        return 1
    print(f"[replay] {args.journal}: {len(entries)} journaled "
          f"request(s)", file=sys.stderr)

    report = harness.run(
        entries, candidate,
        baseline=base_cfg if overrides else None,
        cut_stride=args.cut_stride,
        max_cuts=args.max_cuts,
        max_requests=args.max_requests,
        pace=args.pace,
        journal_path=args.journal,
    )
    line = json.dumps(report)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
        print(f"[replay] wrote {args.out}", file=sys.stderr)

    if report["streams_compared"] == 0:
        print("[replay] no byte-comparable streams (greedy or seeded) "
              "in the journal — the gate is undecidable", file=sys.stderr)
        return 1
    bex = report["byte_exact_rate"]
    agr = report["agreement_rate"]
    print(f"[replay] byte_exact_rate={bex} agreement_rate={agr} "
          f"compared={report['streams_compared']} "
          f"skipped={len(report['skipped'])} "
          f"wall={report['replay_wall_s']}s", file=sys.stderr)
    failed = []
    if bex < args.byte_exact_min:
        failed.append(f"byte_exact_rate {bex} < {args.byte_exact_min}")
    if args.agreement_min and (agr is None or agr < args.agreement_min):
        failed.append(f"agreement_rate {agr} < {args.agreement_min}")
    if failed:
        print(f"[replay] DIVERGENCE GATE FAILED: {'; '.join(failed)}",
              file=sys.stderr)
        return 2
    return 0


def cmd_trace_summary(args) -> int:
    """Rebuild per-request timelines from a Chrome trace-event JSON the
    flight recorder exported (`serve --trace --trace-out`,
    `engine.trace.export_chrome`, or TrainConfig.trace_path) and print
    phase breakdowns plus the slowest requests (metrics/trace.py)."""
    import os

    from solvingpapers_tpu.metrics.trace import (
        format_summary,
        format_train_summary,
        summarize_trace,
        summarize_train_trace,
    )

    if not os.path.exists(args.trace):
        print(f"no trace file at {args.trace}", file=sys.stderr)
        return 2
    try:
        summary = summarize_trace(args.trace)
    except json.JSONDecodeError as e:
        # truncated exports (a killed run mid-write) and non-JSON files
        # are operator input errors, not tracebacks: say what and where
        print(
            f"{args.trace} is not valid JSON (truncated export?): "
            f"{e.msg} at line {e.lineno} column {e.colno}",
            file=sys.stderr,
        )
        return 2
    except (ValueError, TypeError, AttributeError, KeyError) as e:
        if isinstance(e, ValueError) and "partial fleet export" in str(e):
            # the stitcher's own diagnosis is the clearest message we
            # could print — a truncated fleet file must not masquerade
            # as a generic parse failure
            print(f"{args.trace}: {e}", file=sys.stderr)
            return 2
        print(
            f"{args.trace} does not parse as a Chrome trace-event JSON "
            f"({type(e).__name__}: {e}) — expected the flight recorder's "
            "export format",
            file=sys.stderr,
        )
        return 2
    except OSError as e:
        print(f"cannot read {args.trace}: {e}", file=sys.stderr)
        return 2
    if getattr(args, "fleet", False) and "fleet" not in summary:
        print(
            f"{args.trace} holds no fleet events: --fleet expects the "
            "stitched export (FleetRouter.export_chrome_fleet or "
            "`serve --replicas N --trace --trace-out`); this looks like a "
            "single-engine trace — rerun without --fleet",
            file=sys.stderr,
        )
        return 2
    if summary["n_requests"] or summary["rejected"]:
        print(format_summary(summary, top=args.top))
        return 0
    # request-less traces: a train trace keeps its per-phase summary even
    # when the observatory also recorded compile events — the roofline
    # and mesh (bubble/comm) sections ride along instead of displacing it
    from solvingpapers_tpu.metrics.hlo_cost import format_anatomy
    from solvingpapers_tpu.metrics.trace import format_mesh, format_roofline

    train = summarize_train_trace(args.trace)
    roofline = format_roofline(summary.get("programs") or {})
    anatomy = format_anatomy(summary.get("anatomy") or {})
    mesh = format_mesh(summary.get("mesh"))
    if train is not None:
        print(format_train_summary(train))
        for section in (roofline, anatomy, mesh):
            if section:
                print()
                print(section)
        return 0
    if roofline or anatomy or mesh:
        print("\n\n".join(s for s in (roofline, anatomy, mesh) if s))
        return 0
    print(
        f"{args.trace} holds neither request lifecycle events "
        "(ServeConfig(trace=True)) nor train spans "
        "(TrainConfig.trace_path) — was it exported by the flight "
        "recorder?",
        file=sys.stderr,
    )
    return 1


def _restore_for_inference(cfg, model, checkpoint_dir, example_batch, trainer=None):
    """Shared restore path: returns (state, params, extra_variables) from
    the newest checkpoint, or None if the directory is empty."""
    from solvingpapers_tpu.checkpoint import CheckpointManager
    from solvingpapers_tpu.configs.factory import init_fn_for, rules_for
    from solvingpapers_tpu.train import Trainer
    from solvingpapers_tpu.train.engine import _apply_pure, _pure_state

    if trainer is None:
        trainer = Trainer(model, cfg.train, init_fn=init_fn_for(cfg),
                          rules=rules_for(cfg))
    state = trainer.init_state(example_batch)
    mgr = CheckpointManager(checkpoint_dir, save_every=0)
    restored = mgr.restore_latest(_pure_state(state))
    if restored is None:
        return None
    state = _apply_pure(state, restored[0])
    extra = restored[0].get("model_state") or {}
    return state, restored[0]["params"], extra


def cmd_eval(args) -> int:
    """estimate_loss over the held-out split (gpt cell 14 / gemma cell 17 /
    dsv3 cell 48) or accuracy for classifiers (ViT cell 15, kd.py:145)."""
    from solvingpapers_tpu.configs import get_config
    from solvingpapers_tpu.configs.factory import (
        build_char_lm_run,
        build_image_run,
        init_fn_for,
        loss_fn_for,
        rules_for,
    )
    from solvingpapers_tpu.sharding import batch_sharding, create_mesh
    from solvingpapers_tpu.train import Trainer

    cfg = get_config(args.config)
    if args.data_path:
        cfg = dataclasses.replace(cfg, data={**cfg.data, "path": args.data_path})
    mesh = create_mesh(cfg.train.mesh)
    cp = getattr(cfg.model, "context_parallel", False)
    if cfg.data.get("kind", "char") == "images":
        model, _, eval_iter_fn, loss_fn = build_image_run(cfg, mesh=mesh)
    else:
        cfg, model, _, _, eval_iter_fn = build_char_lm_run(
            cfg, sharding=batch_sharding(mesh, context=cp)
        )
        loss_fn = loss_fn_for(cfg)
    trainer = Trainer(model, cfg.train, loss_fn=loss_fn,
                      init_fn=init_fn_for(cfg), mesh=mesh, rules=rules_for(cfg))
    eval_iter = eval_iter_fn()
    first = next(eval_iter)
    if args.checkpoint_dir:
        restored = _restore_for_inference(
            cfg, model, args.checkpoint_dir, first, trainer=trainer
        )
        if restored is None:
            print(f"no checkpoint found in {args.checkpoint_dir}", file=sys.stderr)
            return 1
        state = restored[0]
    else:
        state = trainer.init_state(first)
    import itertools

    metrics = trainer.evaluate(state, itertools.chain([first], eval_iter))
    print(json.dumps({k: round(float(v), 6) for k, v in metrics.items()}))
    return 0


def cmd_export(args) -> int:
    """Params-only export (the reference publishes bare weights to HF)."""
    from solvingpapers_tpu.checkpoint import export_params
    from solvingpapers_tpu.configs import get_config
    from solvingpapers_tpu.configs.factory import (
        build_char_lm_run,
        build_image_run,
    )
    from solvingpapers_tpu.sharding import create_mesh

    if not args.checkpoint_dir:
        print("export requires --checkpoint-dir", file=sys.stderr)
        return 2
    cfg = get_config(args.config)
    if args.data_path:
        cfg = dataclasses.replace(cfg, data={**cfg.data, "path": args.data_path})
    mesh = create_mesh(cfg.train.mesh)
    if cfg.data.get("kind", "char") == "images":
        model, train_iter, _, _ = build_image_run(cfg, mesh=mesh)
    else:
        cfg, model, _, train_iter, _ = build_char_lm_run(cfg)
    first = next(train_iter)
    restored = _restore_for_inference(cfg, model, args.checkpoint_dir, first)
    if restored is None:
        print(f"no checkpoint found in {args.checkpoint_dir}", file=sys.stderr)
        return 1
    _, params, _ = restored
    export_params(args.out, params)
    print(f"exported params to {args.out}")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="solvingpapers_tpu")
    sub = parser.add_subparsers(dest="cmd", required=True)

    sub.add_parser("list")

    p_train = sub.add_parser("train")
    _add_common(p_train)
    p_train.add_argument("--steps", type=int, default=None)
    p_train.add_argument("--ckpt-every", type=int, default=1000)
    p_train.add_argument("--jsonl", default=None)
    p_train.add_argument(
        "--artifacts-dir",
        default=None,
        help="write qualitative artifacts here: generated_{step}.txt each "
        "eval for LMs, reconstructions.png after AE/VAE training",
    )

    p_sample = sub.add_parser("sample")
    _add_common(p_sample)
    p_sample.add_argument("--prompt", default=None)
    p_sample.add_argument("--prompt-file", default=None,
                          help="read the prompt text from a file (long-"
                               "context prompts, e.g. 16k tokens)")
    p_sample.add_argument("--prefill-chunk", type=int, default=None,
                          help="prefill the prompt in chunks of this many "
                               "tokens (default: auto — 2048 past 4096)")
    p_sample.add_argument("--max-new-tokens", type=int, default=200)
    p_sample.add_argument("--top-k", type=int, default=50)
    p_sample.add_argument("--temperature", type=float, default=1.0)
    p_sample.add_argument("--greedy", action="store_true")
    p_sample.add_argument(
        "--speculative", action="store_true",
        help="MTP self-speculative greedy decode (models with mtp_heads "
             ">= 1): identical output to --greedy in fewer forwards; "
             "prints acceptance stats to stderr",
    )
    p_sample.add_argument(
        "--spec-drafts", type=int, default=1, choices=(1, 2),
        help="[--speculative] chained MTP heads to draft with (2 needs "
             "mtp_heads >= 2; commits up to 3 tokens per forward)",
    )
    p_sample.add_argument("--seed", type=int, default=0)

    p_srv = sub.add_parser("serve")
    _add_common(p_srv)
    p_srv.add_argument("--port", type=int, default=8000,
                       help="API port (0 = ephemeral, printed to stderr)")
    p_srv.add_argument("--host", default="127.0.0.1",
                       help="bind address (loopback by default — front "
                            "with a real proxy to expose it)")
    p_srv.add_argument("--slots", type=int, default=8)
    p_srv.add_argument("--max-len", type=int, default=None,
                       help="engine sequence capacity (default: min(512, "
                            "model max positions))")
    p_srv.add_argument("--decode-block", type=int, default=8)
    p_srv.add_argument("--bucket", type=int, default=32)
    p_srv.add_argument("--sample-cap", type=int, default=64)
    p_srv.add_argument("--max-waiting", type=int, default=256)
    p_srv.add_argument("--paged", action="store_true",
                       help="serve over the paged KV pool")
    p_srv.add_argument("--kv-quant", default=None, choices=["int8"],
                       help="hold the KV pool as symmetric int8 with "
                            "per-block absmax scales (~half the resident "
                            "KV bytes vs bf16, a quarter vs f32; output "
                            "is close to the exact pool's, not equal: "
                            "`cli replay` scores the agreement)")
    p_srv.add_argument("--kv-quant-block", type=int, default=16,
                       help="[--kv-quant] lane-pool scale block length "
                            "in tokens (must divide max-len; the paged "
                            "pool scales per page)")
    p_srv.add_argument("--kv-exact-lanes", type=int, default=0,
                       help="[--kv-quant] full-precision sidecar lanes "
                            "for SamplingParams.kv_exact requests "
                            "(byte-identical streams inside the "
                            "quantized engine; 0 rejects kv_exact "
                            "submissions)")
    p_srv.add_argument("--speculative", default=None,
                       choices=["ngram", "mtp"],
                       help="speculative decoding: n-gram prompt-lookup "
                            "self-drafting (any family) or MTP heads "
                            "(deepseekv3 with mtp_heads >= 1, lane "
                            "pool); greedy streams stay token-exact, "
                            "stochastic distributions unchanged")
    p_srv.add_argument("--spec-k", type=int, default=4,
                       help="[--speculative] draft tokens per round")
    p_srv.add_argument("--spec-rounds", type=int, default=None,
                       help="[--speculative] draft-verify rounds per "
                            "decode call (default: decode-block)")
    p_srv.add_argument("--no-json-mode", action="store_true",
                       help="reject response_format json_object instead "
                            "of grammar-constraining the decode")
    p_srv.add_argument("--slo", action="store_true",
                       help="account every request under an SLO class "
                            "(serve/slo.py DEFAULT_SLO_TARGETS: "
                            "interactive/standard/batch; requests tag "
                            "one via the 'slo' body field, default "
                            "standard) — per-class attainment, burn "
                            "rate and goodput ride /metrics + /statusz")
    p_srv.add_argument("--degrade", action="store_true",
                       help="arm the degradation ladder "
                            "(serve/faults.py): under page exhaustion, "
                            "HBM-projection breach or SLO burn the "
                            "engine sheds prefix-cache leaves, holds "
                            "speculation, then load-sheds admissions "
                            "by class (batch first) with a jittered "
                            "Retry-After; pair with --slo for the "
                            "burn signal and class-aware shedding")
    p_srv.add_argument("--step-deadline", type=float, default=None,
                       help="watchdog: flag engine steps exceeding this "
                            "absolute wall deadline in seconds "
                            "(serve/watchdog_stalls + anomaly dump)")
    p_srv.add_argument("--journal", default=None, metavar="PATH",
                       help="request write-ahead journal "
                            "(ServeConfig.journal_path): fsync'd JSONL "
                            "of submit/commit/finish events; an "
                            "existing file is REPLAYED on boot "
                            "(engine.recover) so a crashed server's "
                            "in-flight streams resume token-exactly, "
                            "and SSE clients reconnect with "
                            "Last-Event-ID")
    p_srv.add_argument("--journal-strict", action="store_true",
                       help="[--journal] journal I/O failures kill "
                            "serving instead of degrading to "
                            "journal-off with a warning (for "
                            "deployments that REQUIRE durability)")
    p_srv.add_argument("--replicas", type=int, default=1,
                       help="serve a FLEET of N identical engine "
                            "replicas behind one port (serve/fleet.py "
                            "FleetRouter): prefix-affinity + SLO-aware "
                            "routing, merged /metrics, fleet /statusz; "
                            "with --journal each replica journals to "
                            "PATH.rN and FleetRouter.drain can migrate "
                            "live streams between replicas")
    p_srv.add_argument("--trace", action="store_true",
                       help="flight recorder on (ServeConfig.trace): "
                            "HTTP accept/parse/handoff/drain spans join "
                            "engine lifecycle spans per request; "
                            "GET /v1/requests/<id> works either way")
    p_srv.add_argument("--trace-out", default=None, metavar="PATH",
                       help="[--trace] on shutdown write the Chrome "
                            "trace-event JSON here — with --replicas > 1 "
                            "the STITCHED fleet export (router + every "
                            "replica as its own Perfetto process, flows "
                            "following requests across reroutes and "
                            "migrations), the single-engine export "
                            "otherwise; feed `cli trace-summary --fleet`")
    p_srv.add_argument("--timeseries-interval", type=float, default=1.0,
                       help="rolling time-series snapshot cadence in "
                            "seconds (ServeConfig.timeseries_interval_s; "
                            "0 disables the store and /timeseriesz)")
    p_srv.add_argument("--timeseries-capacity", type=int, default=120,
                       help="time-series ring capacity in windows — the "
                            "retrospective spans capacity x interval "
                            "seconds at O(capacity x series) memory")
    p_srv.add_argument("--seed", type=int, default=0)

    p_rep = sub.add_parser(
        "replay",
        help="replay a request journal against a candidate config and "
             "gate on stream divergence (serve/replay.py): exit 0 = "
             "match, exit 2 = divergence beyond the thresholds, exit "
             "1 = operational failure",
    )
    _add_common(p_rep)
    p_rep.add_argument("--journal", required=True, metavar="PATH",
                       help="journal to replay — the live file a "
                            "`cli serve --journal` wrote (a concurrent "
                            "rotation mid-read is tolerated) or a "
                            "copied snapshot")
    p_rep.add_argument("--config-overrides", nargs="*", default=None,
                       metavar="KEY=VALUE",
                       help="ServeConfig fields for the CANDIDATE "
                            "(e.g. kv_quant=int8 paged=true "
                            "decode_block=16); values parse as JSON "
                            "then fall back to raw strings; when "
                            "given, the un-overridden config is "
                            "re-served too for paired latency/"
                            "throughput deltas")
    p_rep.add_argument("--out", default=None,
                       help="also write the report JSON here")
    p_rep.add_argument("--byte-exact-min", type=float, default=1.0,
                       help="exit 2 if byte_exact_rate over the "
                            "greedy+seeded streams falls below this "
                            "(default 1.0 — identical configs must "
                            "match exactly)")
    p_rep.add_argument("--agreement-min", type=float, default=0.0,
                       help="exit 2 if the teacher-forced greedy "
                            "agreement_rate falls below this — the "
                            "graded gate for deliberately-lossy "
                            "candidates like kv_quant=int8 (0 "
                            "disables; pair with --byte-exact-min 0)")
    p_rep.add_argument("--max-requests", type=int, default=None,
                       help="replay only the first N journaled "
                            "requests")
    p_rep.add_argument("--cut-stride", type=int, default=8,
                       help="token stride of the teacher-forced "
                            "agreement cuts (0 disables the "
                            "agreement pass)")
    p_rep.add_argument("--max-cuts", type=int, default=512,
                       help="total agreement-cut budget (overflow is "
                            "disclosed as cuts_dropped, never "
                            "silently truncated)")
    p_rep.add_argument("--pace", action="store_true",
                       help="re-serve at the recorded arrival offsets "
                            "instead of submitting upfront (realistic "
                            "latency deltas, slower wall clock)")
    p_rep.add_argument("--slots", type=int, default=8)
    p_rep.add_argument("--max-len", type=int, default=None,
                       help="engine sequence capacity (default: "
                            "min(512, model max positions)) — match "
                            "the recording server's")
    p_rep.add_argument("--decode-block", type=int, default=8)
    p_rep.add_argument("--bucket", type=int, default=32)
    p_rep.add_argument("--sample-cap", type=int, default=64)
    p_rep.add_argument("--max-waiting", type=int, default=256)
    p_rep.add_argument("--paged", action="store_true")
    p_rep.add_argument("--kv-quant", default=None, choices=["int8"])
    p_rep.add_argument("--kv-quant-block", type=int, default=16)
    p_rep.add_argument("--kv-exact-lanes", type=int, default=0)
    p_rep.add_argument("--speculative", default=None,
                       choices=["ngram", "mtp"])
    p_rep.add_argument("--spec-k", type=int, default=4)
    p_rep.add_argument("--spec-rounds", type=int, default=None)
    p_rep.add_argument("--seed", type=int, default=0,
                       help="model-init seed — must match the "
                            "recording server's for byte-exactness "
                            "without a checkpoint")

    p_tsum = sub.add_parser("trace-summary")
    p_tsum.add_argument("trace",
                        help="Chrome trace-event JSON exported by the "
                             "flight recorder (serve --trace --trace-out, "
                             "engine.trace.export_chrome, "
                             "TrainConfig.trace_path)")
    p_tsum.add_argument("--top", type=int, default=5,
                        help="how many slowest requests to print")
    p_tsum.add_argument("--fleet", action="store_true",
                        help="require the stitched fleet section: exit 2 "
                             "with a clear message when the trace holds "
                             "no fleet events (a single-engine export) "
                             "— a manifest that declares replicas the "
                             "file is missing (truncated/partial "
                             "export) is exit 2 with or without this "
                             "flag")

    p_eval = sub.add_parser("eval")
    _add_common(p_eval)

    p_export = sub.add_parser("export")
    _add_common(p_export)
    p_export.add_argument("--out", required=True)

    args = parser.parse_args(argv)
    from solvingpapers_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    if args.cmd not in ("list", "trace-summary"):
        # before any command code touches jax (see _apply_platform docstring)
        _apply_platform(args)
    return {
        "list": cmd_list,
        "train": cmd_train,
        "sample": cmd_sample,
        "serve": cmd_serve,
        "replay": cmd_replay,
        "trace-summary": cmd_trace_summary,
        "eval": cmd_eval,
        "export": cmd_export,
    }[args.cmd](args)


if __name__ == "__main__":
    sys.exit(main())
