"""solvingpapers_tpu — a TPU-native (JAX/Flax/optax/pjit/Pallas) framework
with the capabilities of the `prashantpandeygit/solvingpapers` reference
collection (GPT, LLaMA3, Gemma, DeepSeekV3 MLA+MoE+MTP, ViT, AlexNet,
autoencoder/VAE, knowledge distillation, attention primitives), rebuilt as
one shared framework: a single ops library, one training engine, jitted
cached inference, and mesh/sharding parallelism over TPU ICI/DCN.

Layout (see SURVEY.md §7):
    ops/        shared primitives: norms, RoPE, activations, attention, losses, sampling
    kernels/    Pallas TPU kernels + pure-jnp references
    sharding/   mesh construction, partition rules, collective wrappers
    models/     Flax model zoo
    data/       tokenizers + dataset/batch pipelines
    train/      the single training engine
    infer/      jitted prefill/decode with KV caches
    serve/      continuous-batching engine: slot pool, FIFO scheduler, mixed step, radix prefix cache
    checkpoint/ Orbax checkpoint manager + params-only export
    metrics/    console/JSONL metrics writers, MFU accounting
    configs/    typed run configs for every workload
"""

from solvingpapers_tpu.metrics.trace import begin as _begin  # no JAX, no NumPy

_imported = _begin("import:solvingpapers_tpu")
__version__ = "0.1.0"

_SERVE_API = ("ServeEngine", "ServeConfig", "KVSlotPool", "FIFOScheduler",
              "Request", "ServeMetrics", "PrefixCache", "PrefixMatch",
              "SamplingParams", "ApiServer", "EngineLoop", "JsonStepper",
              "serve_api")


def __getattr__(name):
    # serve API re-exported lazily (PEP 562): `solvingpapers_tpu.ServeEngine`
    # works without `import solvingpapers_tpu` dragging in jax/flax for
    # consumers that only want metadata
    if name in _SERVE_API:
        from solvingpapers_tpu import serve

        return getattr(serve, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


_imported()
