"""Activation primitives.

Covers the reference's `activation functions/` directory (ReLU.ipynb
cells 1-4: relu/leakyrelu/prelu/elu; GELU.ipynb cell 4: tanh-approx GELU)
plus the gated activations used by the LMs (silu/swish for SwiGLU —
llama3/LLaMA-jax.ipynb cell 25, deepseekv3/deepseekv3.ipynb cell 21;
gelu for GeGLU — gemma/gemma.ipynb cell 9).

All are pure elementwise functions; XLA fuses them into adjacent matmuls
so there is no reason to hand-write kernels for these on TPU.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def relu(x: jax.Array) -> jax.Array:
    return jax.nn.relu(x)


def relu2(x: jax.Array) -> jax.Array:
    """Squared ReLU, max(x, 0)^2: the activation of the `nemotron_h`
    family's ungated feed-forward units (`mlp_hidden_act: "relu2"`)."""
    return jnp.square(jax.nn.relu(x))


def leaky_relu(x: jax.Array, negative_slope: float = 0.01) -> jax.Array:
    return jax.nn.leaky_relu(x, negative_slope)


def prelu(x: jax.Array, alpha: jax.Array) -> jax.Array:
    """Parametric ReLU; `alpha` is a learned scalar or per-channel array."""
    return jnp.where(x >= 0, x, alpha * x)


def elu(x: jax.Array, alpha: float = 1.0) -> jax.Array:
    # expm1 for numerical accuracy near 0; where() keeps the positive branch exact.
    safe = jnp.minimum(x, 0.0)
    return jnp.where(x >= 0, x, alpha * jnp.expm1(safe))


_GELU_C = math.sqrt(2.0 / math.pi)


def gelu_tanh(x: jax.Array) -> jax.Array:
    """Tanh-approximation GELU: 0.5*x*(1 + tanh(sqrt(2/pi)*(x + 0.044715*x^3)))."""
    return 0.5 * x * (1.0 + jnp.tanh(_GELU_C * (x + 0.044715 * jnp.power(x, 3))))


def silu(x: jax.Array) -> jax.Array:
    return jax.nn.silu(x)


def swish(x: jax.Array, beta: float = 1.0) -> jax.Array:
    """Swish with temperature beta; beta=1 is SiLU."""
    return x * jax.nn.sigmoid(beta * x)
