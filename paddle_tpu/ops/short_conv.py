"""Gated short convolution — the token mixer of LFM2's ``conv`` blocks
(``models/lfm2.py``), between its two projections.

    z = b * x;   c_t = sum_j w[:, j] * z_{t-(K-1)+j};   out = g * c

``b``, ``g``, ``x`` [B, L, C] (the three thirds of the in-projection: the
source's B, C and x), ``w`` [C, K]: one K-tap filter a channel (depthwise),
causal — position t reads z at t-(K-1) .. t, and z is ZERO before a row's
first position, so no tap crosses from one sequence of the batch into the
next. No activation: the two gates are the non-linearity. This is
``torch.nn.Conv1d(C, C, K, groups=C, padding=K-1, bias=False)`` cut to the
first L outputs, as the source applies it.

Plain ``jax.numpy``: K shifted slices of the left-padded product, each
times its tap, summed — element-wise work bound by memory bandwidth that
XLA fuses into one pass; no ``lax.conv`` (a depthwise convolution of 3
taps would be handed to the MXU as a [C, 1, K] filter) and no kernel.
Autodiff gives the backward pass: the pad's transpose is the slice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["causal_depthwise_conv", "gated_short_conv"]


def causal_depthwise_conv(z: jax.Array, w: jax.Array) -> jax.Array:
    """``c[:, t] = sum_j w[:, j] * z[:, t-(K-1)+j]``, z zero before t = 0.
    ``z`` [B, L, C], ``w`` [C, K]."""
    L, K = z.shape[1], w.shape[1]
    zp = jnp.pad(z, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(zp[:, j:j + L] * w[:, j] for j in range(K))


def gated_short_conv(b: jax.Array, g: jax.Array, x: jax.Array,
                     w: jax.Array) -> jax.Array:
    """``g * causal_depthwise_conv(b * x, w)``: both gates and the taps."""
    return g * causal_depthwise_conv(b * x, w)
