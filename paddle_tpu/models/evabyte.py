"""EvaByte decoder — a byte-level model (320 ids) whose attention sees its
own aligned window exactly and every earlier window as chunk summaries,
under one softmax (EVA: Zheng et al., ICLR 2023, arXiv:2302.04542), and
which predicts the next ``num_pred_heads`` bytes from each position.

``EvaByte/EvaByte`` (6.5B, 2025-01). ``x`` is the residual stream [B, L,
hidden]; ``N`` is RMSNorm with a unit offset (``x / rms(x) · (1 + w)``, w
from 0); no bias anywhere; embedding and heads are separate matrices.

    x = embed[ids]
    layer:  h = x + W_o Eva(N_1(x))
            y = h + W_down(silu(W_gate u) * (W_up u)),   u = N_2(h)
    logits[r] = N_f(y_last) @ heads[:, r]                  r = 0..P-1

- ``Eva``: ``num_heads`` heads of ``head_dim``, rotary positions on q and
  k (half-split, ``transformer.rotary``), then ``ops.eva``: per head a
  learned ``adaptive_phi`` pools each ``chunk_size`` keys (and values) by
  the softmax of ``k·φ / sqrt(d)`` over the chunk, ``adaptive_mu_k`` is
  added to the pooled key, and query ``i`` attends to the keys ``j <= i``
  of its own aligned ``window_size`` window and to the summaries of the
  chunks of every EARLIER window — one running max and sum in the flash
  kernels (``flash_attention(mask=ops.eva.eva_mask(...))``).
- Head ``r`` at position ``t`` predicts byte ``t + 1 + r``
  (``evabyte_loss`` makes the ``P`` shifted targets from the one label row
  of next bytes); the loss is the mean over the heads of each head's mean
  cross-entropy over the positions whose target exists.

Every layer is alike, so ``first_layer`` only names which published layers
these stand for. Matmuls go through ``nn.functional.linear``:
``Trainer(amp=True)`` means bf16 operands with float32 accumulation; norms,
rotary, the pooling softmax, the kernels' statistics, the residual adds
(``fp32_skip_add``) and the loss stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..core.profiler import RecordEvent
from ..nn import functional as F
from ..nn.layer import Layer
from ..ops.eva import eva_attention, eva_attention_einsum
from .transformer import (SwiGLU, attention_impl, normal_init,
                          residual_out_std, rotary)

__all__ = ["EvaByteConfig", "EvaByteAttention", "EvaByteBlock", "EvaByte",
           "evabyte_loss"]


@dataclasses.dataclass
class EvaByteConfig:
    vocab_size: int = 320
    hidden_size: int = 4096
    num_heads: int = 32
    intermediate_size: int = 11008
    num_layers: int = 32
    first_layer: int = 0
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 8
    max_seq_len: int = 32768
    rope_theta: float = 100000.0
    rms_eps: float = 1e-5
    init_std: float = 0.01275
    # layers of the WHOLE model where this one is a slice of it; None =
    # ``num_layers``. It sets ``out_std``.
    total_layers: Optional[int] = None
    # attention impl: "auto" = Pallas flash kernels on TPU, einsum elsewhere
    attn_impl: str = "auto"
    # ``flash_attention``'s ``precision`` for the kernels' operands: bf16,
    # or with "highest" float32 (a float32 check of the kernels' path)
    attn_precision: str = "default"
    # what the backward pass rebuilds (``jax.checkpoint``) instead of
    # keeping: "none", or "blocks": every block whole, from its input
    recompute: str = "none"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def out_std(self) -> float:
        """std of W_o and W_down (``residual_out_std``)."""
        return residual_out_std(self.init_std,
                                self.total_layers or self.num_layers)

    def parameter_count(self) -> int:
        """Parameters of the model as configured, from the shapes alone."""
        h, f = self.hidden_size, self.intermediate_size
        layer = 4 * h * h + 2 * self.num_heads * self.head_dim \
            + 3 * h * f + 2 * h
        return (self.num_layers * layer
                + (1 + self.num_pred_heads) * self.vocab_size * h + h)


class EvaByteAttention(Layer):
    """EVA: the row's own window exactly, earlier windows as summaries."""

    def __init__(self, cfg: EvaByteConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h, H, d = cfg.hidden_size, cfg.num_heads, cfg.head_dim
        init = normal_init(cfg.init_std)
        for name in ("wq", "wk", "wv"):
            self.create_parameter(name, (h, h), initializer=init)
        self.create_parameter("wo", (h, h),
                              initializer=normal_init(cfg.out_std))
        self.create_parameter("adaptive_phi", (H, d), initializer=init)
        self.create_parameter("adaptive_mu_k", (H, d), initializer=init)

    def forward(self, u: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, L, h = u.shape
        heads = (B, L, cfg.num_heads, cfg.head_dim)
        with jax.named_scope("pt.eva.qkv"):
            q = F.linear(u, self.wq).reshape(heads)
            k = F.linear(u, self.wk).reshape(heads)
            v = F.linear(u, self.wv).reshape(heads)
        with jax.named_scope("pt.rope"):
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        impl = attention_impl(cfg.attn_impl)
        operands = (q, k, v, self.adaptive_phi, self.adaptive_mu_k,
                    cfg.window_size, cfg.chunk_size)
        out = eva_attention(*operands, precision=cfg.attn_precision) \
            if impl == "flash" else eva_attention_einsum(*operands)
        return F.linear(out.reshape(B, L, h), self.wo)


class EvaByteBlock(Layer):
    """EVA attention, then the dense SwiGLU; each sublayer's scope takes
    its norm and its residual add."""

    def __init__(self, cfg: EvaByteConfig) -> None:
        super().__init__()
        self.norm_attn = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps,
                                    unit_offset=True)
        self.attn = EvaByteAttention(cfg)
        self.norm_ffn = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps,
                                   unit_offset=True)
        self.mlp = SwiGLU(cfg.hidden_size, cfg.intermediate_size,
                           cfg.init_std, cfg.out_std)

    def forward(self, x: jax.Array) -> jax.Array:
        with jax.named_scope("pt.attn"):
            x = x + self.attn(self.norm_attn(x))
        with jax.named_scope("pt.ffn.dense"):
            return x + self.mlp(self.norm_ffn(x))


class EvaByte(Layer):
    """Whole model. ``forward(ids)`` returns the logits [B, L,
    num_pred_heads, vocab]."""

    def __init__(self, cfg: EvaByteConfig) -> None:
        super().__init__()
        enforce(cfg.num_layers >= 1 and cfg.first_layer >= 0,
                f"layers {cfg.first_layer}..+{cfg.num_layers}")
        enforce_eq(cfg.hidden_size % cfg.num_heads, 0,
                   "heads must divide hidden")
        enforce_eq(cfg.head_dim % 2, 0, "rotary halves")
        enforce(cfg.chunk_size >= 1
                and cfg.window_size % cfg.chunk_size == 0,
                f"a window of {cfg.window_size} keys in chunks of "
                f"{cfg.chunk_size}: whole chunks")
        enforce(cfg.num_pred_heads >= 1, "at least the next byte's head")
        enforce(cfg.recompute in ("none", "blocks"),
                f"recompute {cfg.recompute!r}: none or blocks")
        self.cfg = cfg
        init = normal_init(cfg.init_std)
        self.create_parameter("embed", (cfg.vocab_size, cfg.hidden_size),
                              initializer=init)
        self.blocks = nn.LayerList(
            [EvaByteBlock(cfg) for _ in range(cfg.num_layers)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps,
                                 unit_offset=True)
        # head r in columns r * vocab .. (r + 1) * vocab
        self.create_parameter(
            "heads", (cfg.hidden_size, cfg.num_pred_heads * cfg.vocab_size),
            initializer=init)

    def forward(self, ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, L = ids.shape
        enforce(L <= cfg.max_seq_len,
                f"sequence of {L} over max_seq_len {cfg.max_seq_len}")
        enforce(L % cfg.window_size == 0,
                f"sequence of {L}: whole windows of {cfg.window_size} (the "
                "published code pads; here the caller packs)")
        # what the stack is made of, read off the configuration and the
        # sequence: one host span a trace (``profiler.host_spans()``), none
        # on the step path. ``summaries``: the chunk summaries a layer's
        # kernels are handed (the last window's chunks are no one's past)
        with RecordEvent("pt.eva.layers", layers=cfg.num_layers,
                         window=cfg.window_size, chunk=cfg.chunk_size,
                         summaries=(L - cfg.window_size) // cfg.chunk_size,
                         pred_heads=cfg.num_pred_heads):
            pass
        with jax.named_scope("pt.embed"):
            x = jnp.take(self.embed, ids, axis=0)
        for block in self.blocks:
            if cfg.recompute == "blocks":
                # a function of its own each call: ``jax.checkpoint`` keeps
                # a function's trace (``models/smallthinker.py``)
                x = jax.checkpoint(lambda x, block=block: block(x))(x)
            else:
                x = block(x)
        with jax.named_scope("pt.head_loss"):
            # ``linear``, not ``lm_head``: the parent already reads a bf16
            # cotangent buffer here (eight softmaxes of 320 behind a
            # reshape do not fuse into the matmul), so the statement only
            # adds passes: -0.23% (PERF.md section 6, PRs 48 and 49)
            logits = F.linear(self.norm_f(x), self.heads)
        return logits.reshape(B, L, cfg.num_pred_heads, cfg.vocab_size)


def evabyte_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """``Trainer``'s ``loss_fn``. ``labels`` [B, L] is the next byte of
    each position; head ``r``'s target at ``t`` is byte ``t + 1 + r`` =
    ``labels[t + r]``, where the sequence still has it. The mean over the
    heads of each head's mean float32 cross-entropy over its positions."""
    L, P = logits.shape[1], logits.shape[2]
    targets = jnp.stack(
        [jnp.pad(labels[:, r:], ((0, 0), (0, r)), constant_values=-1)
         for r in range(P)], axis=-1)                          # [B, L, P]
    per = F.cross_entropy(logits.astype(jnp.float32), targets,
                          reduction="none", ignore_index=-1)
    count = jnp.sum(targets != -1, axis=(0, 1))
    return jnp.mean(jnp.sum(per, axis=(0, 1)) / count)
