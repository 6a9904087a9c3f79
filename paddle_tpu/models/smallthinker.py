"""SmallThinker-21BA3B decoder — attention that differs by layer from the
configuration's own two lists (a sliding window or every earlier key; rotary
positions or none at all), a router that reads the layer's INPUT, before
attention and before its norm, and ReLU-gated experts of which this layer
holds a share, on the dense path.

``PowerInfer/SmallThinker-21BA3B-Instruct`` (arXiv:2507.20984; 21B total /
3B active). ``x`` is the residual stream [B, L, hidden] entering layer l;
``N`` is RMSNorm with a learned weight; no bias anywhere; embedding and
head are two matrices (untied).

    x = embed[ids]
    layer l:  r = x W_r                               (float32, 64 wide)
              h = x + W_o Attn_l(N_attn(x))
              y = h + sum_{e in top6(r), held} w_e W_down,e(relu(W_gate,e u)
                                                            * (W_up,e u)),
                                                       u = N_ffn(h)
    logits = N_f(y_last) @ head

- The router: the ``experts_per_token`` largest of ``r``; weights = the
  softmax over those chosen logits (``parallel.moe.topk_route`` with
  ``renormalise``: softmax over all, the chosen ones, divided by their
  sum). No bias, no scale. It reads the un-normed stream so that a
  deployment can fetch experts while attention runs; here it only means
  that the route is made from ANOTHER tensor than the rows
  ``parallel.moe.held_moe`` dispatches, and that its gradient reaches the
  layer's input past the attention block.
- ``Attn_l``: ``num_heads`` query / ``num_kv_heads`` key-value heads of
  ``head_dim``, no QK-norm; key-value head j serves the ``num_heads /
  num_kv_heads`` consecutive query heads from ``j * that`` (repeated to
  ``num_heads`` before the kernel, ``transformer.repeat_kv``).
  ``sliding_window_layout[l]`` 1: query i sees the keys ``i -
  sliding_window_size < j <= i`` (``ops.flash_attention(window=...)``: the
  pair list leaves out what lies below the band); 0: every ``j <= i``.
  ``rope_layout[l]`` 1: rotary positions on q and k, half-split
  (``transformer.rotary``); 0: NO positional encoding at all.
- The sum is over the chosen experts THAT THIS LAYER HOLDS (``cfg.held =
  (first, count)``: one expert-parallel rank's part, nothing standing in
  for the others).

Matmuls go through ``nn.functional.linear`` (the head: ``lm_head``) and
``parallel.moe.grouped_matmul``: ``Trainer(amp=True)`` means bf16 operands
with float32 accumulation; norms, rotary, softmax and the router stay
float32. Counters leave the forward in buffers
(``transformer.RoutingRecord``, one row a layer).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..core.profiler import RecordEvent
from ..nn import functional as F
from ..nn.layer import Layer
from ..parallel.moe import held_moe, router_logits, topk_route
from .transformer import (GroupedQueryAttention, HeldExperts, RoutingRecord,
                          next_token_loss, normal_init, record_held,
                          residual_out_std, routing_outputs)

__all__ = ["SmallThinkerConfig", "SmallThinkerAttention",
           "SmallThinkerExperts", "SmallThinkerBlock", "SmallThinker",
           "smallthinker_loss", "LAYOUT"]

#: the published ``sliding_window_layout`` and ``rope_layout`` alike: every
#: fourth layer, from layer 0, is global and position-free
LAYOUT = tuple(int(i % 4 != 0) for i in range(52))


@dataclasses.dataclass
class SmallThinkerConfig:
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    # the published lists, whole; this model runs ``num_layers`` of their
    # layers from ``first_layer`` on (one pipeline stage's slice)
    sliding_window_layout: Tuple[int, ...] = LAYOUT
    rope_layout: Tuple[int, ...] = LAYOUT
    sliding_window_size: int = 4096
    num_layers: int = 52
    first_layer: int = 0
    router_width: int = 64             # ``moe_num_primary_experts``
    experts_per_token: int = 6         # ``moe_num_active_primary_experts``
    expert_size: int = 768             # ``moe_ffn_hidden_size``
    held: Tuple[int, int] = (0, 64)    # (first, count) of the experts held
    max_seq_len: int = 16384
    rope_theta: float = 1500000.0
    rms_eps: float = 1e-6
    init_std: float = 0.02
    # layers of the WHOLE model where this one is a slice of it; None =
    # ``num_layers``. It sets ``out_std``.
    total_layers: Optional[int] = None
    # attention impl: "auto" = Pallas flash kernel on TPU, einsum elsewhere
    attn_impl: str = "auto"
    # what the backward pass rebuilds (``jax.checkpoint``) instead of
    # keeping: "none"; "experts": each layer's expert sublayer (its norm,
    # the held experts, the residual add), from the stream and the route;
    # "blocks": every block whole (a float32 pass over 16,384 tokens keeps
    # 2.4 GB a layer otherwise)
    recompute: str = "none"

    @property
    def layer_kinds(self) -> List[Tuple[Optional[int], bool]]:
        """(the window or None, whether q and k turn) of each layer run."""
        rows = slice(self.first_layer, self.first_layer + self.num_layers)
        return [(self.sliding_window_size if w else None, bool(r))
                for w, r in zip(self.sliding_window_layout[rows],
                                self.rope_layout[rows])]

    @property
    def out_std(self) -> float:
        """std of W_o and every expert's down (``residual_out_std``)."""
        return residual_out_std(self.init_std,
                                self.total_layers or self.num_layers)

    def parameter_count(self) -> int:
        """Parameters of the model as configured (the held experts' banks,
        not the absent ones'), from the shapes alone."""
        h, d = self.hidden_size, self.head_dim
        attn = 2 * h * self.num_heads * d + 2 * h * self.num_kv_heads * d
        experts = h * self.router_width \
            + self.held[1] * 3 * h * self.expert_size
        return (self.num_layers * (attn + experts + 2 * h)
                + 2 * self.vocab_size * h + h)


class SmallThinkerAttention(GroupedQueryAttention):
    """Causal grouped-query attention without QK-norm: under ``window`` a
    sliding one, with ``rope`` rotary positions, else none."""

    def __init__(self, cfg: SmallThinkerConfig, window: Optional[int],
                 rope: bool) -> None:
        super().__init__(cfg, qk_norm=False, rope=rope, window=window)


class SmallThinkerExperts(HeldExperts):
    """Router over all ``router_width``, no bias, and the banks of the
    ReLU-gated experts held. ``route`` is made from the layer's input;
    ``forward`` takes it with the rows to dispatch and returns the held
    experts' part and the router's record (``parallel.moe.held_moe``)."""

    def __init__(self, cfg: SmallThinkerConfig) -> None:
        super().__init__(cfg, cfg.router_width, bias=None, shared=None)

    def route(self, x: jax.Array) -> Dict[str, jax.Array]:
        with jax.named_scope("pt.moe.route"):
            logits = router_logits(x.reshape(-1, x.shape[-1]), self.router_w)
            route = topk_route(logits, self.cfg.experts_per_token,
                               renormalise=True)
            route["logits"] = logits
        return route

    def forward(self, u: jax.Array, route: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        # through THIS module's ``held_moe``: the benchmark's fault control
        # plants its wrong gate there (benchmarks/tests/swa_fault_control.py)
        cfg = self.cfg
        out, route = held_moe(
            u.reshape(-1, u.shape[-1]), None, None, self.w_gate, self.w_up,
            self.w_down, cfg.experts_per_token, cfg.held, route=route,
            activation=jax.nn.relu)
        return out.reshape(*u.shape[:-1], out.shape[-1]), route


class SmallThinkerBlock(Layer):
    """The router on the layer's input, attention of the layer's kind,
    then the held experts on the route made before it."""

    def __init__(self, cfg: SmallThinkerConfig, window: Optional[int],
                 rope: bool) -> None:
        super().__init__()
        self.norm_attn = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.attn = SmallThinkerAttention(cfg, window, rope)
        self.norm_ffn = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.moe = SmallThinkerExperts(cfg)
        # the kind's scope sits OUTSIDE ``pt.attn``: an operation's scope is
        # the last token of its name, so ``pt.attn``'s readers read these
        # blocks as they read every other model's, and the kind is there
        # for a reader that looks at the whole name
        self.scope = "pt.attn.full" if window is None else "pt.attn.window"
        self.cfg = cfg

    def forward(self, x: jax.Array):
        route = self.moe.route(x)
        with jax.named_scope(self.scope), jax.named_scope("pt.attn"):
            x = x + self.attn(self.norm_attn(x))
        with jax.named_scope("pt.ffn"):
            def experts(x, route):
                y, route = self.moe(self.norm_ffn(x), route)
                return x + y, route

            if self.cfg.recompute == "experts":
                experts = jax.checkpoint(experts)
            return experts(x, route)


class SmallThinker(Layer):
    """Whole model. ``forward(ids)`` returns the logits [B, L, vocab]; with
    ``output_routing`` also the routers' ``logits`` [layers, B*L,
    router_width], ``index`` and load-balance terms ``lb`` [layers]
    (``topk_route``'s; no loss of this model reads them)."""

    def __init__(self, cfg: SmallThinkerConfig) -> None:
        super().__init__()
        last = cfg.first_layer + cfg.num_layers
        enforce(cfg.num_layers >= 1 and cfg.first_layer >= 0
                and last <= len(cfg.sliding_window_layout)
                and last <= len(cfg.rope_layout),
                f"layers {cfg.first_layer}..{last} outside the layouts")
        enforce_eq(cfg.num_heads % cfg.num_kv_heads, 0,
                   "key-value heads must divide the query heads")
        enforce_eq(cfg.head_dim % 2, 0, "rotary halves")
        enforce(cfg.sliding_window_size >= 1, "a window of at least one key")
        enforce(cfg.recompute in ("none", "experts", "blocks"),
                f"recompute {cfg.recompute!r}: none, experts or blocks")
        enforce(cfg.experts_per_token <= cfg.router_width,
                "more experts a token than experts")
        self.cfg = cfg
        self.create_parameter("embed", (cfg.vocab_size, cfg.hidden_size),
                              initializer=normal_init(cfg.init_std))
        self.blocks = nn.LayerList(
            [SmallThinkerBlock(cfg, window, rope)
             for window, rope in cfg.layer_kinds])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.create_parameter("head", (cfg.hidden_size, cfg.vocab_size),
                              initializer=normal_init(cfg.init_std))
        RoutingRecord.register(self, cfg.num_layers, cfg.router_width)

    def forward(self, ids: jax.Array, output_routing: bool = False):
        cfg = self.cfg
        enforce(ids.shape[-1] <= cfg.max_seq_len,
                f"sequence of {ids.shape[-1]} over max_seq_len {cfg.max_seq_len}")
        # what the stack is made of and what its expert layers hold, read
        # off the configuration: one host span each a trace
        # (``profiler.host_spans()``), none on the step path
        windows = sum(w is not None for w, _ in cfg.layer_kinds)
        with RecordEvent("pt.smallthinker.layers",
                         full=cfg.num_layers - windows, window=windows,
                         experts=cfg.num_layers,
                         window_size=cfg.sliding_window_size):
            pass
        record_held(cfg.held, cfg.router_width)
        with jax.named_scope("pt.embed"):
            x = jnp.take(self.embed, ids, axis=0)
        routes = []
        for block in self.blocks:
            if cfg.recompute == "blocks":
                # a function of its own each call: ``jax.checkpoint`` keeps
                # a function's trace, and the parameters a Layer closes
                # over are another trace's the next time it is called
                x, route = jax.checkpoint(lambda x, block=block: block(x))(x)
            else:
                x, route = block(x)
            routes.append(route)
        with jax.named_scope("pt.head_loss"):
            logits = F.lm_head(self.norm_f(x), self.head)
        RoutingRecord.store(self, routes)
        if output_routing:
            return logits, routing_outputs(routes, ("logits", "index", "lb"))
        return logits


#: ``Trainer``'s ``loss_fn``: next-token cross-entropy ALONE; the published
#: configuration names no auxiliary loss
smallthinker_loss = next_token_loss
