"""Build model / data / trainer objects from a RunConfig; what differs by
family is read from the family table (`configs/families.py`)."""

from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np

from solvingpapers_tpu.data import load_char_corpus
from solvingpapers_tpu.data.batches import lm_batch_iterator, prefetch_batches
from solvingpapers_tpu.configs.families import FAMILIES, resolve
from solvingpapers_tpu.configs.registry import RunConfig
from solvingpapers_tpu.metrics.trace import run_span


def build_model(cfg: RunConfig):
    return resolve(FAMILIES[cfg.model_family].model)(cfg.model)


def loss_fn_for(cfg: RunConfig):
    """Objective for a RunConfig's family (kd's teacher phase uses
    classification; its student phase is built in train.kd_pipeline)."""
    return resolve(FAMILIES[cfg.model_family].objective)


def rules_for(cfg: RunConfig):
    """Partition-rule table for a RunConfig — every Trainer construction
    site (train/eval/export/sample-restore) must agree on it, or restored
    states land in a layout that mismatches training."""
    from solvingpapers_tpu.sharding import LM_RULES, PP_RULES

    return PP_RULES if cfg.train.pipeline_parallel else LM_RULES


def init_fn_for(cfg: RunConfig):
    """Trainer init_fn override (None = default params-only init)."""
    init_fn = FAMILIES[cfg.model_family].init_fn
    return None if init_fn is None else resolve(init_fn)


def build_image_run(cfg: RunConfig, mesh=None):
    """Returns (model, train_iter, eval_iter_fn, loss_fn) for image workloads."""
    from solvingpapers_tpu.data.images import image_batch_iterator, load_image_dataset

    d = cfg.data
    tx, ty, vx, vy = load_image_dataset(
        path=d.get("path"),
        n_train=d.get("n_train", 8192),
        n_test=d.get("n_test", 2048),
        side=d.get("side", 28),
        n_classes=d.get("n_classes", 10),
        seed=cfg.train.seed,
        source=d.get("source", "separable"),
        snr=d.get("snr", 2.8),
    )
    flatten = d.get("flatten", False)
    bsz = cfg.train.batch_size
    model = build_model(cfg)
    train_iter = image_batch_iterator(
        tx, ty, bsz, seed=cfg.train.seed, flatten=flatten, mesh=mesh
    )

    def eval_iter_fn():
        return image_batch_iterator(
            vx, vy, bsz, seed=10_000, flatten=flatten, mesh=mesh, loop=False
        )

    return model, train_iter, eval_iter_fn, loss_fn_for(cfg)


def _load_corpus(cfg: RunConfig):
    """(tokenizer, train tokens, validation tokens) of `cfg.data`.

    data.kind 'char' builds a char vocab (gpt/gemma pipelines); 'bpe' trains
    a byte-level BPE on the corpus (the offline stand-in for the reference's
    tiktoken/HF GPT-2 tables — llama3 cell 6, deepseekv3 cell 6), or loads
    GPT-2-format tables from data['vocab_path']/data['merges_path'].
    """
    if cfg.data.get("kind") == "bpe":
        from solvingpapers_tpu.data.bpe import ByteBPETokenizer
        from solvingpapers_tpu.data.char import load_text, split_train_val

        # synthetic_chars: long-context configs need a corpus larger than
        # one block AFTER tokenization (BPE compresses ~4.5x — a 65k block
        # needs ~300k+ chars minimum; lm_batch_iterator raises otherwise)
        text = load_text(
            cfg.data.get("path"),
            synthetic_chars=cfg.data.get("synthetic_chars", 200_000),
        )
        if cfg.data.get("vocab_path") and cfg.data.get("merges_path"):
            tok = ByteBPETokenizer.from_files(
                cfg.data["vocab_path"], cfg.data["merges_path"]
            )
        else:
            tok = ByteBPETokenizer.train(
                text, cfg.data.get("bpe_vocab_size", 1024)
            )
        train_toks, val_toks = split_train_val(tok.encode(text))
    elif cfg.data.get("kind") == "tokens":
        # pre-tokenized stream (deepseekv3 cells 8-14: tokenize once, train
        # from saved tokens); model.vocab_size must match the tokenizer that
        # wrote the file; decode-side tokenizer is not reconstructable here
        from solvingpapers_tpu.data.char import split_train_val
        from solvingpapers_tpu.data.tokens import load_token_file

        toks = load_token_file(cfg.data["path"])

        class _IdTok:
            """Ids-only tokenizer: prompts are space-separated integer ids
            (the text tokenizer that wrote the file is not reconstructable)."""

            vocab_size = cfg.model.vocab_size

            def encode(self, s):
                try:
                    return np.asarray([int(t) for t in s.split()], np.int32)
                except ValueError:
                    raise RuntimeError(
                        "token-file runs carry no text tokenizer; prompts "
                        f"must be space-separated integer ids, got {s!r}"
                    ) from None

            def decode(self, ids):
                return " ".join(str(int(i)) for i in ids)

        from solvingpapers_tpu.data.tokens import token_file_max_id

        max_id = token_file_max_id(cfg.data["path"], toks)
        if max_id >= cfg.model.vocab_size:
            raise ValueError(
                f"token file {cfg.data['path']} holds id {max_id} but "
                f"model.vocab_size is {cfg.model.vocab_size}; XLA gathers "
                "clamp silently, so this must match the writing tokenizer"
            )
        tok = _IdTok()
        train_toks, val_toks = split_train_val(toks)
    elif cfg.data.get("source") == "markov":
        # entropy-calibrated corpus: val loss has an absolute target
        # (MarkovSource.entropy_rate_nats) that memorization cannot reach;
        # markov_text shares chain defaults with markov_entropy_nats so the
        # trained-on corpus and the gating floor come from the same chain
        from solvingpapers_tpu.data.char import CharTokenizer, split_train_val
        from solvingpapers_tpu.data.synthetic import markov_text

        text = markov_text(cfg.data)
        tok = CharTokenizer(text)
        train_toks, val_toks = split_train_val(tok.encode(text))
    else:
        tok, train_toks, val_toks = load_char_corpus(path=cfg.data.get("path"))
    return tok, train_toks, val_toks


@run_span("build_run")
def build_char_lm_run(cfg: RunConfig, sharding=None):
    """Returns (run_cfg_with_vocab, model, tokenizer, train_iter, eval_iter_fn)
    for the corpus `_load_corpus` reads."""
    block = cfg.data.get("block_size", 256)
    bsz = cfg.train.batch_size
    with run_span("data_open"):
        tok, train_toks, val_toks = _load_corpus(cfg)
        train_iter = lm_batch_iterator(train_toks, bsz, block, seed=cfg.train.seed, sharding=sharding)
        if isinstance(train_toks, np.memmap):
            # host-side gathers (native, GIL-releasing) overlap the device step;
            # in-memory corpora crop device-side so there is nothing to overlap
            train_iter = prefetch_batches(train_iter, depth=2)
    # the char vocab comes from the corpus; resize the model to match
    model_cfg = dataclasses.replace(cfg.model, vocab_size=max(tok.vocab_size, 2))
    cfg = dataclasses.replace(cfg, model=model_cfg)
    with run_span("model_build"):
        model = build_model(cfg)

    def eval_iter_fn() -> Iterator[dict]:
        return lm_batch_iterator(val_toks, bsz, block, seed=10_000, sharding=sharding)

    return cfg, model, tok, train_iter, eval_iter_fn
