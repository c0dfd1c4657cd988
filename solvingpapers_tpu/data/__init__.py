"""Data pipelines (L4): tokenizers, LM streams, image datasets, sharded batches."""

from solvingpapers_tpu.metrics.trace import begin as _begin

_imported = _begin("import:data")
from solvingpapers_tpu.data.char import CharTokenizer, load_char_corpus
from solvingpapers_tpu.data.batches import (
    prefetch_batches,
    random_crop_batch,
    sliding_window_split,
)
from solvingpapers_tpu.data.synthetic import synthetic_text, synthetic_images
from solvingpapers_tpu.data.bpe import ByteBPETokenizer, gpt2_tokenizer
from solvingpapers_tpu.data.tokens import load_token_file, tokenize_to_file

_imported()
