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
  ``num_heads`` before the kernel, ``models.lfm2.repeat_kv``).
  ``sliding_window_layout[l]`` 1: query i sees the keys ``i -
  sliding_window_size < j <= i`` (``ops.flash_attention(window=...)``: the
  pair list leaves out what lies below the band); 0: every ``j <= i``.
  ``rope_layout[l]`` 1: rotary positions on q and k, half-split
  (``models.olmoe.rotary``); 0: NO positional encoding at all.
- The sum is over the chosen experts THAT THIS LAYER HOLDS (``cfg.held =
  (first, count)``: one expert-parallel rank's part, nothing standing in
  for the others).

Matmuls go through ``nn.functional.linear`` (the head: ``lm_head``) and
``parallel.moe.grouped_matmul``: ``Trainer(amp=True)`` means bf16 operands
with float32 accumulation; norms, rotary, softmax and the router stay
float32. Counters leave the forward in buffers as ``models/lfm2.py``'s do:
``expert_counts`` [layers, router_width], ``held_assignments``,
``dispatch_rung``, ``dispatch_rows_walked`` [layers], ``tokens_dropped``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..core.profiler import RecordEvent
from ..nn import functional as F
from ..nn.layer import Layer
from ..ops.flash_attention import flash_attention
from ..parallel.moe import held_moe, router_logits, topk_route
from .joyai import _normal
from .lfm2 import repeat_kv
from .olmoe import rotary

__all__ = ["SmallThinkerConfig", "SmallThinkerAttention",
           "SmallThinkerExperts", "SmallThinkerBlock", "SmallThinker",
           "smallthinker_loss", "LAYOUT"]

#: the published ``sliding_window_layout`` and ``rope_layout`` alike: every
#: fourth layer, from layer 0, is global and position-free
LAYOUT = tuple(int(i % 4 != 0) for i in range(52))

#: queries a block, and heads a group, of the einsum attention
_QUERY_BLOCK = 2048
_HEAD_GROUP = 7


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
        """std of the projections that write into the residual stream (W_o
        and every expert's down matrix): ``init_std / sqrt(2 * layers)``,
        as ``Lfm2Config.out_std``."""
        return self.init_std / math.sqrt(
            2 * (self.total_layers or self.num_layers))

    def parameter_count(self) -> int:
        """Parameters of the model as configured (the held experts' banks,
        not the absent ones'), from the shapes alone."""
        h, d = self.hidden_size, self.head_dim
        attn = 2 * h * self.num_heads * d + 2 * h * self.num_kv_heads * d
        experts = h * self.router_width \
            + self.held[1] * 3 * h * self.expert_size
        return (self.num_layers * (attn + experts + 2 * h)
                + 2 * self.vocab_size * h + h)


def _banded_attention(q, k, v, window):
    """Einsum attention, causal and under ``window`` banded: the off-TPU
    stand-in for the kernel, and the float32 side of the benchmark's
    check. [B, L, H, d]. A group of heads and a block of queries at a
    time, rebuilt in the backward pass: seven heads' [2048, 16384] scores
    are 0.9 GB, 28 heads' [16384, 16384] would be 30."""
    B, L, H, d = q.shape
    scale = float(d) ** -0.5
    bq = _QUERY_BLOCK if L % _QUERY_BLOCK == 0 else L
    cols = jnp.arange(L)[None, :]

    def group(q, k, v):
        g = q.shape[2]

        @jax.checkpoint
        def block(args):
            qb, start = args
            rows = start + jnp.arange(bq)[:, None]
            mask = cols <= rows
            if window is not None:
                mask = mask & (cols > rows - window)
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * scale
            p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p, v)

        blocks = jnp.moveaxis(q.reshape(B, L // bq, bq, g, d), 1, 0)
        out = lax.map(block, (blocks, jnp.arange(L // bq) * bq))
        return jnp.moveaxis(out, 0, 1).reshape(B, L, g, d)

    return jnp.concatenate(
        [group(q[:, :, g:g + _HEAD_GROUP], k[:, :, g:g + _HEAD_GROUP],
               v[:, :, g:g + _HEAD_GROUP])
         for g in range(0, H, _HEAD_GROUP)], axis=2)


class SmallThinkerAttention(Layer):
    """Causal grouped-query attention: under ``window`` a sliding one,
    with ``rope`` rotary positions, else none."""

    def __init__(self, cfg: SmallThinkerConfig, window: Optional[int],
                 rope: bool) -> None:
        super().__init__()
        self.cfg, self.window, self.rope = cfg, window, rope
        h, d = cfg.hidden_size, cfg.head_dim
        init = _normal(cfg.init_std)
        self.create_parameter("wq", (h, cfg.num_heads * d), initializer=init)
        self.create_parameter("wk", (h, cfg.num_kv_heads * d),
                              initializer=init)
        self.create_parameter("wv", (h, cfg.num_kv_heads * d),
                              initializer=init)
        self.create_parameter("wo", (cfg.num_heads * d, h),
                              initializer=_normal(cfg.out_std))

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, L, _ = x.shape
        H, G, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        with jax.named_scope("pt.gqa.qkv"):
            q = F.linear(x, self.wq).reshape(B, L, H, d)
            k = F.linear(x, self.wk).reshape(B, L, G, d)
            v = F.linear(x, self.wv).reshape(B, L, G, d)
        if self.rope:
            with jax.named_scope("pt.rope"):
                q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        k, v = repeat_kv(k, v, H)
        impl = cfg.attn_impl
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" else "einsum"
        if impl == "flash":
            out = flash_attention(q, k, v, causal=True, window=self.window)
        else:
            out = _banded_attention(q, k, v, self.window)
        return F.linear(out.reshape(B, L, H * d), self.wo)


class SmallThinkerExperts(Layer):
    """Router over all ``router_width`` and the banks of the experts held.
    ``route`` is made from the layer's input; ``forward`` takes it with
    the rows to dispatch and returns the held experts' part and the
    router's record (``parallel.moe.held_moe``)."""

    def __init__(self, cfg: SmallThinkerConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h, f, count = cfg.hidden_size, cfg.expert_size, cfg.held[1]
        init = _normal(cfg.init_std)
        self.create_parameter("router_w", (h, cfg.router_width),
                              initializer=init)
        self.create_parameter("w_gate", (count, h, f), initializer=init)
        self.create_parameter("w_up", (count, h, f), initializer=init)
        self.create_parameter("w_down", (count, f, h),
                              initializer=_normal(cfg.out_std))

    def route(self, x: jax.Array) -> Dict[str, jax.Array]:
        with jax.named_scope("pt.moe.route"):
            logits = router_logits(x.reshape(-1, x.shape[-1]), self.router_w)
            route = topk_route(logits, self.cfg.experts_per_token,
                               renormalise=True)
            route["logits"] = logits
        return route

    def forward(self, u: jax.Array, route: Dict[str, jax.Array]
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
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
        first, count = cfg.held
        enforce(0 <= first and count >= 1
                and first + count <= cfg.router_width,
                f"held experts {cfg.held} outside 0..{cfg.router_width}")
        self.cfg = cfg
        self.create_parameter("embed", (cfg.vocab_size, cfg.hidden_size),
                              initializer=_normal(cfg.init_std))
        self.blocks = nn.LayerList(
            [SmallThinkerBlock(cfg, window, rope)
             for window, rope in cfg.layer_kinds])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.create_parameter("head", (cfg.hidden_size, cfg.vocab_size),
                              initializer=_normal(cfg.init_std))
        n = cfg.num_layers
        self.register_buffer("expert_counts",
                             jnp.zeros((n, cfg.router_width), jnp.int32))
        self.register_buffer("held_assignments", jnp.zeros((n,), jnp.int32))
        self.register_buffer("dispatch_rung", jnp.zeros((n,), jnp.int32))
        self.register_buffer("dispatch_rows_walked",
                             jnp.zeros((n,), jnp.int32))
        self.register_buffer("tokens_dropped", jnp.zeros((), jnp.int32))

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
        with RecordEvent("pt.moe.held", first=cfg.held[0], count=cfg.held[1],
                         experts=cfg.router_width):
            pass
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
        stack = lambda key: jnp.stack([r[key] for r in routes])
        self._buffers["expert_counts"] = stack("counts")
        self._buffers["held_assignments"] = stack(
            "held_assignments").astype(jnp.int32)
        self._buffers["dispatch_rung"] = stack("rung").astype(jnp.int32)
        self._buffers["dispatch_rows_walked"] = stack("rows_walked").astype(
            jnp.int32)
        self._buffers["tokens_dropped"] = jnp.sum(stack("dropped")).astype(
            jnp.int32)
        if output_routing:
            return logits, {"logits": stack("logits"),
                            "index": stack("index"), "lb": stack("lb")}
        return logits


def smallthinker_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """``Trainer``'s ``loss_fn``: next-token cross-entropy ALONE, mean over
    the positions (a label of -1 is no position); the published
    configuration names no auxiliary loss."""
    return F.cross_entropy(logits, labels, ignore_index=-1)
