"""OLMoE decoder — a sparse-expert causal language model on the dense path.

The block of ``allenai/OLMoE-1B-7B`` (arXiv:2409.02060; ``model_type``
``olmoe``), as its ``modeling_olmoe`` computes it:

    x = embed[ids]                                   (no scaling, no position table)
    h = x + Attn(RMSNorm_1(x));  y = h + MoE(RMSNorm_2(h))       per layer
    logits = RMSNorm_f(y) @ W_head                   (untied, no bias anywhere)

- ``Attn(u)``: q, k, v = u Wq, u Wk, u Wv; RMSNorm over the WHOLE q and k
  projections (QK-norm, before the split into heads); rotary positions
  (rotate-half form, positions 0..L-1) on q and k; causal softmax attention
  scaled by 1/sqrt(head_dim); Wo. The kernel is ``ops/flash_attention``
  (``attn_impl="auto"``: the Pallas kernel on TPU, einsum elsewhere).
- ``MoE(u)``: ``parallel.moe.dropless_moe`` — float32 softmax router, the
  ``experts_per_token`` largest probabilities as they are, every
  assignment computed by its expert's gated-SiLU FFN, no capacity.
- The router losses ``lb_coef * sum_layers LB + z_coef * sum_layers Z``
  leave the forward in the ``aux_loss`` buffer, which
  ``executor.make_train_step`` adds to the loss it differentiates; the
  counters ``expert_counts`` [layers, experts] and ``tokens_dropped`` (0
  by construction) leave it in buffers of those names.

Matmuls go through ``nn.functional.linear`` and
``parallel.moe.grouped_matmul``, so ``Trainer(amp=True)`` means bf16
operands with float32 accumulation; norms, rotary, softmax and the router
stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..nn import functional as F
from ..nn.layer import Layer
from ..ops.flash_attention import flash_attention
from ..parallel.moe import dropless_moe
from ..parallel.ring_attention import local_attention
from .transformer import (attention_impl, normal_init, rotary,
                          routing_outputs, stack_routes)

__all__ = ["OlmoeConfig", "OlmoeAttention", "OlmoeExperts", "OlmoeBlock",
           "Olmoe"]


@dataclasses.dataclass
class OlmoeConfig:
    vocab_size: int = 50304
    hidden_size: int = 2048
    num_heads: int = 16
    num_layers: int = 16
    num_experts: int = 64
    experts_per_token: int = 8
    expert_size: int = 1024           # the source's ``intermediate_size``
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    lb_coef: float = 0.01             # load-balancing loss (arXiv:2409.02060)
    z_coef: float = 0.001             # router z-loss
    init_std: float = 0.02
    # attention impl: "auto" = Pallas flash kernel on TPU, einsum elsewhere
    attn_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


class OlmoeAttention(Layer):
    """Causal multi-head attention with QK-norm and rotary positions."""

    def __init__(self, cfg: OlmoeConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        for name in ("wq", "wk", "wv", "wo"):
            self.create_parameter(name, (h, h),
                                  initializer=normal_init(cfg.init_std))
        self.q_norm = nn.RMSNorm(h, cfg.rms_eps)
        self.k_norm = nn.RMSNorm(h, cfg.rms_eps)

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, L, h = x.shape
        heads = (B, L, cfg.num_heads, cfg.head_dim)
        q, k = F.linear(x, self.wq), F.linear(x, self.wk)
        v = F.linear(x, self.wv).reshape(heads)
        with jax.named_scope("pt.rope"):
            q = rotary(self.q_norm(q).reshape(heads), cfg.rope_theta)
            k = rotary(self.k_norm(k).reshape(heads), cfg.rope_theta)
        if attention_impl(cfg.attn_impl) == "flash":
            out = flash_attention(q, k, v, causal=True)
        else:
            out = local_attention(q, k, v, causal=True)
        return F.linear(out.reshape(B, L, h), self.wo)


class OlmoeExperts(Layer):
    """Router and the three expert banks; ``forward`` returns the layer's
    output and the router's record (``parallel.moe.dropless_moe``)."""

    def __init__(self, cfg: OlmoeConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h, f, E = cfg.hidden_size, cfg.expert_size, cfg.num_experts
        init = normal_init(cfg.init_std)
        self.create_parameter("router_w", (h, E), initializer=init)
        self.create_parameter("w_gate", (E, h, f), initializer=init)
        self.create_parameter("w_up", (E, h, f), initializer=init)
        self.create_parameter("w_down", (E, f, h), initializer=init)

    def forward(self, x: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        lead = x.shape[:-1]
        out, route = dropless_moe(
            x.reshape(-1, x.shape[-1]), self.router_w, self.w_gate,
            self.w_up, self.w_down, self.cfg.experts_per_token)
        return out.reshape(*lead, out.shape[-1]), route


class OlmoeBlock(Layer):
    def __init__(self, cfg: OlmoeConfig) -> None:
        super().__init__()
        self.norm1 = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.attn = OlmoeAttention(cfg)
        self.norm2 = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.moe = OlmoeExperts(cfg)

    def forward(self, x: jax.Array) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        # each sublayer's scope takes its norm and its residual add; the
        # expert layer's own work sits in the pt.moe.* scopes inside pt.ffn
        with jax.named_scope("pt.attn"):
            x = x + self.attn(self.norm1(x))
        with jax.named_scope("pt.ffn"):
            y, route = self.moe(self.norm2(x))
            return x + y, route


class Olmoe(Layer):
    """Whole model: embed -> blocks -> final norm -> head. ``forward``
    returns the logits [B, L, vocab]; with ``output_routing`` also the
    routers' ``logits`` [layers, B*L, experts] and ``index``
    [layers, B*L, experts_per_token]."""

    def __init__(self, cfg: OlmoeConfig) -> None:
        super().__init__()
        enforce_eq(cfg.hidden_size % cfg.num_heads, 0, "heads must divide hidden")
        enforce(cfg.experts_per_token <= cfg.num_experts,
                "more experts a token than experts")
        self.cfg = cfg
        init = normal_init(cfg.init_std)
        self.create_parameter("embed", (cfg.vocab_size, cfg.hidden_size),
                              initializer=init)
        self.blocks = nn.LayerList([OlmoeBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.create_parameter("head_w", (cfg.hidden_size, cfg.vocab_size),
                              initializer=init)
        self.register_buffer("aux_loss", jnp.zeros(()))
        self.register_buffer("expert_counts", jnp.zeros(
            (cfg.num_layers, cfg.num_experts), jnp.int32))
        self.register_buffer("tokens_dropped", jnp.zeros((), jnp.int32))

    def forward(self, ids: jax.Array, output_routing: bool = False):
        cfg = self.cfg
        enforce(ids.shape[-1] <= cfg.max_seq_len,
                f"sequence of {ids.shape[-1]} over max_seq_len {cfg.max_seq_len}")
        with jax.named_scope("pt.embed"):
            x = jnp.take(self.embed, ids, axis=0)
        routes = []
        for block in self.blocks:
            x, route = block(x)
            routes.append(route)
        self._buffers["aux_loss"] = (
            cfg.lb_coef * jnp.sum(stack_routes(routes, "lb"))
            + cfg.z_coef * jnp.sum(stack_routes(routes, "z")))
        self._buffers["expert_counts"] = stack_routes(routes, "counts")
        self._buffers["tokens_dropped"] = jnp.sum(
            stack_routes(routes, "dropped")).astype(jnp.int32)
        with jax.named_scope("pt.head_loss"):
            # ``linear``, not ``lm_head``: the stated backward pays only
            # with its bf16 cotangent as a buffer, here 786 MiB beside the
            # float32 logits at the step's memory peak (+6% compiled);
            # left to choose, XLA rebuilds it inside both backward matmuls
            # and the weight gradient reads 17.1 -> 21.7 ms, the cell
            # -1.27% (PERF.md section 6, PR 49)
            logits = F.linear(self.norm_f(x), self.head_w)
        if output_routing:
            return logits, routing_outputs(routes, ("logits", "index"))
        return logits
