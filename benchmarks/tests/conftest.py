"""The benchmark's own tests: run by hand on the CPU,
`python -m pytest benchmarks/tests -q`. Not part of the repo's tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
