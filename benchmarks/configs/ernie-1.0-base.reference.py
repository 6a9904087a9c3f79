"""Plain reference for ``ernie-1.0-base``: encoder forward, token
cross-entropy and gradients — ``jax.numpy``, float32, matmul precision
``highest``, einsum attention over the full [L, L] score matrix; no flash
kernel, no mixed precision, no trainer.

The architecture is the one the repo trains under this name (see the
configuration file's ``departures``): word + position embeddings and a
LayerNorm; ``num_hidden_layers`` pre-LN blocks of multi-head self-attention
(a fused QKV projection whose columns are laid out head-major,
``[head][q|k|v][head_dim]``, scores scaled by 1/sqrt(head_dim), softmax over
all positions) and a GELU (tanh form) feed-forward; a final LayerNorm and a
bias-free projection to the vocabulary; the loss is the mean over all
tokens of -log softmax(logits)[label]. LayerNorm epsilon 1e-5.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping

import numpy as np

#: Tolerances, with their reasons. The system's step is the configured one:
#: bf16 operands in every dense contraction (``amp``) and in the flash
#: kernel, f32 accumulation, f32 parameters. Against this f32 reference PR
#: 21 measured 7.7e-3 per gradient leaf for the bf16-operand flash kernel
#: alone (relative to the leaf's largest entry). For the whole step this PR
#: measured, on the v5e over 13 runs, at most 1.8e-5 on the loss and 0.85e-2
#: to 1.37e-2 on the worst of the 151 gradient leaves (chip runs, PR 23); the
#: bounds leave 10x and 3x. A step in a lower precision than bf16 (fp8's 3-4
#: mantissa bits against bf16's 8) has ~16x the rounding error and fails
#: both.
TOL = {"loss_rel": 2e-4, "grad_leaf_rel": 4e-2}


def _layer_norm(x, w, b):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5) * w + b


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def loss_fn(p: Mapping[str, Any], ids, labels, *, layers: int, heads: int):
    import jax
    import jax.numpy as jnp

    B, L = ids.shape
    x = p["embed.word_emb"][ids] + p["embed.pos_emb"][jnp.arange(L)]
    x = _layer_norm(x, p["embed.ln.weight"], p["embed.ln.bias"])
    h = x.shape[-1]
    D = h // heads
    for i in range(layers):
        q = f"blocks.{i}."
        y = _layer_norm(x, p[q + "ln1.weight"], p[q + "ln1.bias"])
        qkv = (y @ p[q + "attn.qkv_w"] + p[q + "attn.qkv_b"]).reshape(
            B, L, heads, 3, D)
        qh, kh, vh = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        s = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / math.sqrt(D)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, vh).reshape(B, L, h)
        x = x + o @ p[q + "attn.proj_w"] + p[q + "attn.proj_b"]
        y = _layer_norm(x, p[q + "ln2.weight"], p[q + "ln2.bias"])
        y = _gelu_tanh(y @ p[q + "ffn.w_in"] + p[q + "ffn.b_in"])
        x = x + y @ p[q + "ffn.w_out"] + p[q + "ffn.b_out"]
    x = _layer_norm(x, p["head.ln.weight"], p["head.ln.bias"])
    logits = x @ p["head.w"]
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(picked)


def loss_and_grads(params: Mapping[str, Any], ids, labels, *, layers: int,
                   heads: int) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, i, l: loss_fn(p, i, l, layers=layers, heads=heads)))(
                p, jnp.asarray(ids), jnp.asarray(labels))
    return {"loss": float(loss),
            "grads": {k: np.asarray(v) for k, v in grads.items()}}


def compare(got: Mapping[str, Any], ref: Mapping[str, Any]) -> Dict[str, Any]:
    loss_rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    worst, worst_leaf = 0.0, None
    for k, r in ref["grads"].items():
        top = float(np.max(np.abs(r)))
        if top == 0.0:
            continue
        e = float(np.max(np.abs(np.asarray(got["grads"][k], np.float64) - r))
                  ) / top
        if e > worst:
            worst, worst_leaf = e, k
    ok = (np.isfinite(got["loss"]) and loss_rel <= TOL["loss_rel"]
          and worst <= TOL["grad_leaf_rel"])
    return {"ok": bool(ok), "loss": [got["loss"], ref["loss"]],
            "loss_rel": loss_rel, "grad_leaf_rel": worst,
            "worst_leaf": worst_leaf, "leaves": len(ref["grads"]),
            "tol": TOL}
