"""LFM2-8B-A1B decoder — blocks that differ in their token mixer (a gated
short convolution or grouped-query attention, by the configuration's own
``layer_types``), leading dense layers, then a sigmoid bias-routed expert
layer that holds a share of its experts, on the dense path.

``LiquidAI/LFM2-8B-A1B`` (``model_type`` ``lfm2_moe``, 8.3B total / 1.5B
active). ``x`` is the residual stream [B, L, hidden]; ``N`` is RMSNorm
with a learned weight; no bias anywhere; the head is the embedding
transposed (tied).

    x = embed[ids]
    block i:  h = x + Op_i(N_op(x));   y = h + FFN_i(N_ffn(h))
    logits = N_f(y_last) @ embed^T

- ``Op_i`` by ``layer_types[i]``.
  ``conv``: ``[b | g | x~] = u W_in`` (hidden -> 3 hidden: the source's B,
  C, x); ``c_t = sum_j w[:, j] * (b * x~)_{t-(K-1)+j}``, a depthwise causal
  filter of ``conv_kernel`` taps a channel, zero before a row's first
  position (``ops/short_conv.py``); ``(g * c) W_out``. No activation.
  ``full_attention``: q = u W_q (``num_heads`` x ``head_dim``), k = u W_k,
  v = u W_v (``num_kv_heads`` x ``head_dim``); RMSNorm over each head of q
  and of k (one learned weight of ``head_dim`` each); rotary positions in
  the half-split convention (``transformer.rotary``), positions 0..L-1;
  key-value head j serves the ``num_heads / num_kv_heads`` consecutive
  query heads from ``j * that``; causal softmax at ``1/sqrt(head_dim)``;
  W_o. The kernel (``ops/flash_attention``) takes one k and one v a query
  head, so k and v are repeated to ``num_heads`` BEFORE it
  (``pt.gqa.repeat``).
- ``FFN_i``: a dense SwiGLU of ``dense_size`` for ``i < num_dense_layers``,
  else ``parallel.moe.held_moe``: ``s = sigmoid(u W_r)`` float32 over all
  ``num_experts``; the top ``experts_per_token`` of ``s + b`` (``b``: the
  buffer ``expert_bias``, never differentiated); weights ``s`` at the
  chosen experts, normalised, times ``routed_scale``; the sum over the
  chosen experts THAT THIS LAYER HOLDS (``cfg.held = (first, count)``: one
  expert-parallel rank's part, nothing standing in for the others). No
  shared expert. After the forward ``b <- b + bias_update_rate *
  sign(mean(c) - c)``, ``c`` this step's assignment counts over all
  experts: a buffer update the train step carries out (DeepSeek-V3's
  rule, ``transformer.HeldExperts``; the source's own rule and rate are not
  published with its configuration).

Matmuls go through ``nn.functional.linear`` and
``parallel.moe.grouped_matmul``: ``Trainer(amp=True)`` means bf16 operands
with float32 accumulation; norms, the convolution's gates and taps, rotary,
softmax and the router stay float32. Counters leave the forward in buffers
(``transformer.RoutingRecord``, one row an expert layer).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..core.profiler import RecordEvent
from ..nn import functional as F
from ..nn.layer import Layer
from ..ops.short_conv import gated_short_conv
from .transformer import (GroupedQueryAttention, HeldExperts, RoutingRecord,
                          SwiGLU, next_token_loss, normal_init, record_held,
                          residual_out_std, routing_outputs)

__all__ = ["Lfm2Config", "Lfm2ShortConv", "Lfm2Attention", "Lfm2Experts",
           "Lfm2Block", "Lfm2", "lfm2_loss", "LAYER_TYPES"]

#: the published mixer of each of the 24 layers
LAYER_TYPES = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


@dataclasses.dataclass
class Lfm2Config:
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    layer_types: Tuple[str, ...] = LAYER_TYPES
    num_dense_layers: int = 2
    dense_size: int = 7168             # ``intermediate_size``
    conv_kernel: int = 3               # ``conv_L_cache``
    num_experts: int = 32              # the router's width
    experts_per_token: int = 4
    expert_size: int = 1792            # ``moe_intermediate_size``
    routed_scale: float = 1.0          # ``routed_scaling_factor``
    held: Tuple[int, int] = (0, 32)    # (first, count) of the experts held
    max_seq_len: int = 128000
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    bias_update_rate: float = 0.001
    init_std: float = 0.02
    # layers of the WHOLE model where ``layer_types`` is one pipeline
    # stage's slice of it; None = ``len(layer_types)``. It sets ``out_std``.
    total_layers: Optional[int] = None
    # attention impl: "auto" = Pallas flash kernel on TPU, einsum elsewhere
    attn_impl: str = "auto"

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def out_std(self) -> float:
        """std of W_out, W_o and every FFN's down (``residual_out_std``)."""
        return residual_out_std(self.init_std,
                                self.total_layers or self.num_layers)

    def parameter_count(self) -> int:
        """Parameters of the model as configured (the held experts' banks,
        not the absent ones'), from the shapes alone."""
        h, d = self.hidden_size, self.head_dim
        conv = h * 3 * h + h * h + h * self.conv_kernel
        attn = (2 * h * self.num_heads * d + 2 * h * self.num_kv_heads * d
                + 2 * d)
        experts = h * self.num_experts + self.held[1] * 3 * h * self.expert_size
        total = self.vocab_size * h + h          # tied head, final norm
        for i, kind in enumerate(self.layer_types):
            total += (conv if kind == "conv" else attn) + 2 * h
            total += 3 * h * self.dense_size if i < self.num_dense_layers \
                else experts
        return total


class Lfm2ShortConv(Layer):
    """The gated short convolution between its two projections."""

    def __init__(self, cfg: Lfm2Config) -> None:
        super().__init__()
        h, K = cfg.hidden_size, cfg.conv_kernel
        bound = 1.0 / math.sqrt(K)
        self.create_parameter("w_in", (h, 3 * h),
                              initializer=normal_init(cfg.init_std))
        self.create_parameter(
            "w_conv", (h, K), initializer=lambda key, shape, dtype:
            jax.random.uniform(key, shape, dtype, -bound, bound))
        self.create_parameter("w_out", (h, h),
                              initializer=normal_init(cfg.out_std))

    def forward(self, u: jax.Array) -> jax.Array:
        with jax.named_scope("pt.conv.in"):
            b, g, x = jnp.split(F.linear(u, self.w_in), 3, axis=-1)
        with jax.named_scope("pt.conv.mix"):
            y = gated_short_conv(b, g, x, self.w_conv)
        with jax.named_scope("pt.conv.out"):
            return F.linear(y, self.w_out)


class Lfm2Attention(GroupedQueryAttention):
    """Causal grouped-query attention with a norm over each head of q and
    of k, and rotary positions."""

    def __init__(self, cfg: Lfm2Config) -> None:
        super().__init__(cfg, qk_norm=True, rope=True, window=None)


class Lfm2Experts(HeldExperts):
    """Router over all ``num_experts`` with its ``expert_bias`` and the
    banks of the experts held; no shared expert."""

    def __init__(self, cfg: Lfm2Config) -> None:
        super().__init__(cfg, cfg.num_experts, bias="expert_bias",
                         shared=None)


class Lfm2Block(Layer):
    """A token mixer of the kind ``layer_types`` names, then a
    feed-forward: the dense SwiGLU or the experts."""

    def __init__(self, cfg: Lfm2Config, kind: str, dense: bool) -> None:
        super().__init__()
        self.norm_op = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        if kind == "conv":
            self.conv = Lfm2ShortConv(cfg)
        else:
            self.attn = Lfm2Attention(cfg)
        self.norm_ffn = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        if dense:
            self.mlp = SwiGLU(cfg.hidden_size, cfg.dense_size, cfg.init_std,
                               cfg.out_std)
        else:
            self.moe = Lfm2Experts(cfg)
        self.kind, self.dense = kind, dense

    def forward(self, x: jax.Array):
        # each sublayer's scope takes its norm and its residual add; the
        # projections, the mix, the rotary, the repeat, the kernels and the
        # expert layer's stages sit in scopes of their own inside
        if self.kind == "conv":
            with jax.named_scope("pt.conv"):
                x = x + self.conv(self.norm_op(x))
        else:
            with jax.named_scope("pt.attn"):
                x = x + self.attn(self.norm_op(x))
        if self.dense:
            with jax.named_scope("pt.ffn.dense"):
                return x + self.mlp(self.norm_ffn(x)), None
        with jax.named_scope("pt.ffn"):
            y, route = self.moe(self.norm_ffn(x))
            return x + y, route


class Lfm2(Layer):
    """Whole model. ``forward(ids)`` returns the logits [B, L, vocab]; with
    ``output_routing`` also the routers' ``logits`` [expert layers, B*L,
    num_experts] and ``index``."""

    def __init__(self, cfg: Lfm2Config) -> None:
        super().__init__()
        kinds = set(cfg.layer_types)
        enforce(kinds <= {"conv", "full_attention"},
                f"layer_types {sorted(kinds)}: 'conv' and 'full_attention' "
                "are the mixers there are")
        enforce(cfg.num_layers > cfg.num_dense_layers >= 0,
                "at least one expert layer")
        enforce_eq(cfg.hidden_size % cfg.num_heads, 0,
                   "heads must divide hidden")
        enforce_eq(cfg.num_heads % cfg.num_kv_heads, 0,
                   "key-value heads must divide the query heads")
        enforce_eq(cfg.head_dim % 2, 0, "rotary halves")
        enforce(cfg.experts_per_token <= cfg.num_experts,
                "more experts a token than experts")
        self.cfg = cfg
        self.create_parameter("embed", (cfg.vocab_size, cfg.hidden_size),
                              initializer=normal_init(cfg.init_std))
        self.blocks = nn.LayerList(
            [Lfm2Block(cfg, kind, i < cfg.num_dense_layers)
             for i, kind in enumerate(cfg.layer_types)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        RoutingRecord.register(self, cfg.expert_layers, cfg.num_experts)

    def forward(self, ids: jax.Array, output_routing: bool = False):
        cfg = self.cfg
        enforce(ids.shape[-1] <= cfg.max_seq_len,
                f"sequence of {ids.shape[-1]} over max_seq_len {cfg.max_seq_len}")
        # what the stack is made of and what its expert layers hold, read
        # off the configuration: one host span each a trace
        # (``profiler.host_spans()``), none on the step path
        convs = cfg.layer_types.count("conv")
        with RecordEvent("pt.lfm2.layers", conv=convs,
                         attention=cfg.num_layers - convs,
                         dense=cfg.num_dense_layers,
                         experts=cfg.expert_layers):
            pass
        record_held(cfg.held, cfg.num_experts)
        with jax.named_scope("pt.embed"):
            x = jnp.take(self.embed, ids, axis=0)
        routes = []
        for block in self.blocks:
            x, route = block(x)
            if route is not None:
                routes.append(route)
        with jax.named_scope("pt.head_loss"):
            # ``linear``, not ``lm_head``: this step has no recomputation
            # and compiles through XLA's own rematerialisation pass, which
            # stops 0.39 GiB higher with the head's backward stated (13.479
            # -> 13.870 GiB, 1% allowed; PERF.md section 6, PR 49) for a
            # tied head's 3 ms
            logits = F.linear(self.norm_f(x), self.embed.T)
        RoutingRecord.store(self, routes)
        if output_routing:
            return logits, routing_outputs(routes, ("logits", "index"))
        return logits


#: ``Trainer``'s ``loss_fn``
lfm2_loss = next_token_loss
