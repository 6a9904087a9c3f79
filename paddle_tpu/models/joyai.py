"""JoyAI-LLM-Flash decoder — latent attention, a sigmoid bias-routed
expert layer that holds a share of its experts, a shared expert, a leading
dense layer and a multi-token-prediction module, on the dense path.

``jdopensource/JoyAI-LLM-Flash`` (``model_type`` ``joyai_llm_flash``,
48B-A2.7B); its configuration keys are DeepSeek-V3's (arXiv:2412.19437),
so the equations below are that paper's. ``x`` is [tokens, hidden];
RMSNorm everywhere, no bias anywhere, untied embedding and head.

    x = embed[ids]
    layer < first_dense:  h = x + MLA(N(x));  y = h + SwiGLU_dense(N(h))
    other layers:         h = x + MLA(N(x));  y = h + MoE(N(h))
    logits = N_f(y) @ W_head

- ``MLA(u)``: ``c_q = N(u W_qa)``; ``[q_nope | q_rope] = c_q W_qb`` a head;
  ``[c_kv | k_rope] = u W_kva``; ``[k_nope | v] = N(c_kv) W_kvb`` a head.
  Rotary on ``q_rope`` (each head) and on the ONE ``k_rope`` all heads
  share; ``q = [q_nope | rot q_rope]``, ``k = [k_nope | rot k_rope]``,
  scores ``q.k / sqrt(nope + rope)``, causal softmax, ``o = P v`` at v's
  own width, ``o W_o``. The key's rotary part is broadcast to the heads
  BEFORE the kernel (``ops/flash_attention`` takes one k a head).
- Rotary (``rotary_pairs``): adjacent pairs (2i, 2i+1) turn by
  ``pos * theta^(-2i/D)`` (``rope_interleave`` true). The source
  de-interleaves first — channel 2i to i, 2i+1 to D/2+i — and then rotates
  halves: the same rotation followed by ONE fixed permutation of the D
  channels, applied to q and k alike, so every q.k is unchanged. This file
  leaves the permutation out.
- ``MoE(u)`` (``parallel.moe.held_moe``): ``s = sigmoid(u W_r)`` float32;
  the top k of ``s + b`` (``b``: the buffer ``e_score_correction_bias``,
  never differentiated); weights ``s`` at the chosen experts without ``b``,
  normalised, times ``routed_scale``; ``sum_i g_i SwiGLU_i(u)`` over the
  chosen experts THAT THIS LAYER HOLDS (``cfg.held = (first, count)`` of
  ``num_experts``: one expert-parallel rank's part, nothing standing in for
  the others; ``(0, num_experts)`` is the whole layer) plus the shared
  expert's ``SwiGLU(u)``, which every rank computes alike. ``n_group`` and
  ``topk_group`` must be 1: group-limited choice is then the identity, and
  there is no group code. After the forward ``b <- b + bias_update_rate *
  sign(mean(c) - c)``, ``c`` this step's assignment counts over all
  experts (DeepSeek-V3 section 2.1.2): a buffer update the train step
  carries out through ``new_state["buffers"]``. No auxiliary loss.
- Prediction module (``num_mtp`` 1; DeepSeek-V3 section 2.2): for position
  i, ``h'_i = [N_e(embed[ids[i+1]]) | N_h(y_i)] W_eh`` with ``y`` the main
  model's final hidden state AFTER ``N_f`` (as this family's public
  implementations hand it over; the paper's figure leaves that open: a
  reading, listed in the configuration's ``departures``), one block of the
  expert kind with weights of its own, and ``logits'_i = N'(.) W_head``
  with the model's own head: it predicts token i+2. The last position has
  no ``ids[i+1]``: it is computed on ``ids[i]`` and masked in the loss, so
  shapes stay static. ``forward(ids)`` returns ``(logits, logits')``;
  ``joyai_loss`` is the loss over both.

Xing4.0-29B-A4B (``XingChen-AGI/Xing4.0-29B-A4B``, ``model_type``
``xing4_0``) is this file under other keys — the same DeepSeek-V3 block at
3584 / 9216 / 1024, two leading dense layers, 4 of 64 experts — with three
things JoyAI has not, each an option that leaves JoyAI's program what it is:

- ``hc_mult`` > 1: the state between sublayers is ``hc_mult`` residual
  streams a token (manifold-constrained hyper-connections, arXiv:2512.24880;
  ``transformer.HyperConnected``, ``ops/hyper_connection.py``). Entry:
  every stream is ``embed[ids]``; each sublayer reads ``u = H_pre X``,
  computes ``y = F(N(u))`` with the norm, attention and feed-forward above,
  and leaves ``X' = H_res X + H_postᵀ y``; exit: ``N_f(sum_i X_i) W_head``.
  The streams are float32 between blocks, the step's activation dtype
  (bfloat16 streams would let two sequences fit: PERF.md section 7), the
  mappings float32 whatever ``amp`` says. They travel side by side in the
  last axis, [B, L, n·C] with stream j in columns jC..(j+1)C — the form
  the path's kernels read, so that no block's boundary lays them out anew
  (a [.., n, C] array with n = 4 is tiled (4, 128) on the chip and every
  way to or from [tokens, n·C] is a copy of the four streams).
- ``rope_scaling`` of ``type`` ``yarn`` (arXiv:2309.00071, as DeepSeek-V3's
  public rotary embedding computes it): blended frequencies
  (``transformer.yarn_frequencies``) at EVERY length, and the softmax scale
  ``1/sqrt(nope + rope)`` times ``m²``, ``m = yarn_mscale(factor,
  mscale_all_dim)`` — applied to q where it is assembled (q.k is bilinear),
  so the kernels and the einsum stand-in keep their own ``1/sqrt(D)``.
- ``num_mtp`` 0: no prediction module; ``forward(ids)`` returns ``logits``
  alone and the loss is ``transformer.next_token_loss``. How a module meets
  several streams is in no source: ``hc_mult`` > 1 wants ``num_mtp`` 0.

``recompute`` ``"blocks"`` rebuilds every block in the backward pass
(``jax.checkpoint``); a rebuilt block writes no buffer, so its route, its
moved router bias and its ``H_res`` error leave it as outputs and the model
stores them, once (``HeldExperts``' text). Why one file and not a model of
its own: a model file imports no other model's (``tests/test_layering.py``)
and there is to be ONE latent attention and one expert layer.

Matmuls go through ``nn.functional.linear`` (the head: ``lm_head``) and
``parallel.moe.grouped_matmul``: ``Trainer(amp=True)`` means bf16 operands
with float32 accumulation; norms, rotary, softmax and the router stay
float32. Counters leave the forward in buffers
(``transformer.RoutingRecord``); the layer axis runs over the expert layers,
the prediction module's last.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..nn import functional as F
from ..nn.layer import Layer
from ..ops.flash_attention import flash_attention
from ..core.profiler import RecordEvent
from .transformer import (HeldExperts, HyperConnected, RoutingRecord, SwiGLU,
                          _causal_attention, attention_impl, next_token_loss,
                          normal_init, record_held, residual_out_std,
                          rotary_pairs, routing_outputs, yarn_mscale)

__all__ = ["JoyaiConfig", "JoyaiAttention", "JoyaiExperts", "JoyaiBlock",
           "Joyai", "joyai_loss", "joyai_losses", "MTP_LOSS_WEIGHT"]

#: weight of the prediction module's loss (DeepSeek-V3 section 4.2: 0.3
#: for most of pre-training)
MTP_LOSS_WEIGHT = 0.3


@dataclasses.dataclass
class JoyaiConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    num_heads: int = 32
    num_layers: int = 40
    first_dense: int = 1               # ``first_k_dense_replace``
    dense_size: int = 7168             # ``intermediate_size``
    q_rank: int = 1536                 # ``q_lora_rank``
    kv_rank: int = 512                 # ``kv_lora_rank``
    nope_dim: int = 128                # ``qk_nope_head_dim``
    rope_dim: int = 64                 # ``qk_rope_head_dim``
    v_dim: int = 128                   # ``v_head_dim``
    num_experts: int = 256             # the router's width
    experts_per_token: int = 8
    expert_size: int = 768             # ``moe_intermediate_size``
    num_shared: int = 1                # ``n_shared_experts``
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 2.5          # ``routed_scaling_factor``
    held: Tuple[int, int] = (0, 256)   # (first, count) of the experts held
    num_mtp: int = 1                   # ``num_nextn_predict_layers``
    max_seq_len: int = 131072
    rope_theta: float = 32000000.0
    rms_eps: float = 1e-6
    bias_update_rate: float = 0.001
    init_std: float = 0.006
    # layers of the WHOLE model where ``num_layers`` is one pipeline
    # stage's slice of it (the published ``num_hidden_layers``); None =
    # ``num_layers``. It sets ``out_std``.
    total_layers: Optional[int] = None
    # attention impl: "auto" = Pallas flash kernel on TPU, einsum elsewhere
    attn_impl: str = "auto"
    # ``rope_scaling``: None, or a dict of ``type`` "yarn" with ``factor``,
    # ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
    # ``mscale``, ``mscale_all_dim``
    rope_scaling: Optional[Dict[str, Any]] = None
    # residual streams a token (``hc_mult``): 1 = the plain block
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)   # ``mhc_h_res_clamp_*``
    # what the backward pass rebuilds (``jax.checkpoint``): "none", or
    # "blocks": every block whole
    recompute: str = "none"

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.first_dense + self.num_mtp

    @property
    def softmax_gain(self) -> float:
        """What the scores are multiplied by beside ``1/sqrt(nope +
        rope)``: YaRN's ``m²``, 1 without scaling."""
        if self.rope_scaling is None:
            return 1.0
        return yarn_mscale(self.rope_scaling["factor"],
                           self.rope_scaling["mscale_all_dim"]) ** 2

    def parameter_count(self) -> int:
        """Parameters of the model as configured (the held experts' banks,
        not the absent ones'), from the shapes alone."""
        h, H = self.hidden_size, self.num_heads
        attn = (h * self.q_rank + self.q_rank
                + self.q_rank * H * (self.nope_dim + self.rope_dim)
                + h * (self.kv_rank + self.rope_dim) + self.kv_rank
                + self.kv_rank * H * (self.nope_dim + self.v_dim)
                + H * self.v_dim * h)
        n = self.hc_mult
        paths = 0 if n == 1 else 2 * (n * h * (2 * n + n * n)
                                      + 2 * n + n * n + 3)
        block = attn + 2 * h + paths
        expert = 3 * h * self.expert_size
        dense = block + 3 * h * self.dense_size
        experts = block + h * self.num_experts \
            + (self.num_shared + self.held[1]) * expert
        return (2 * self.vocab_size * h + h
                + self.first_dense * dense
                + (self.num_layers - self.first_dense) * experts
                + self.num_mtp * (2 * h * h + 3 * h + experts))

    @property
    def out_std(self) -> float:
        """std of W_o and every FFN's down matrix (``residual_out_std``)."""
        return residual_out_std(self.init_std,
                                self.total_layers or self.num_layers)


class JoyaiAttention(Layer):
    """Multi-head latent attention: low-rank q and kv projections with
    their norms, one rotary key for all heads."""

    def __init__(self, cfg: JoyaiConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h, H = cfg.hidden_size, cfg.num_heads
        init = normal_init(cfg.init_std)
        qk = cfg.nope_dim + cfg.rope_dim
        self.create_parameter("w_qa", (h, cfg.q_rank), initializer=init)
        self.q_norm = nn.RMSNorm(cfg.q_rank, cfg.rms_eps)
        self.create_parameter("w_qb", (cfg.q_rank, H * qk), initializer=init)
        self.create_parameter("w_kva", (h, cfg.kv_rank + cfg.rope_dim),
                              initializer=init)
        self.kv_norm = nn.RMSNorm(cfg.kv_rank, cfg.rms_eps)
        self.create_parameter("w_kvb", (cfg.kv_rank,
                                        H * (cfg.nope_dim + cfg.v_dim)),
                              initializer=init)
        self.create_parameter("w_o", (H * cfg.v_dim, h),
                              initializer=normal_init(cfg.out_std))

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, L, _ = x.shape
        H, nope, rope = cfg.num_heads, cfg.nope_dim, cfg.rope_dim
        with jax.named_scope("pt.mla.q"):
            q = F.linear(self.q_norm(F.linear(x, self.w_qa)), self.w_qb)
            q = q.reshape(B, L, H, nope + rope)
        with jax.named_scope("pt.mla.kv"):
            kva = F.linear(x, self.w_kva)
            kv = F.linear(self.kv_norm(kva[..., :cfg.kv_rank]), self.w_kvb)
            kv = kv.reshape(B, L, H, nope + cfg.v_dim)
            v = kv[..., nope:]
        with jax.named_scope("pt.rope"):
            q_rope = rotary_pairs(q[..., nope:], cfg.rope_theta,
                                  cfg.rope_scaling)
            k_rope = rotary_pairs(kva[..., None, cfg.kv_rank:],
                                  cfg.rope_theta, cfg.rope_scaling)
        with jax.named_scope("pt.mla.q"):
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
            if cfg.rope_scaling is not None:
                q = q * cfg.softmax_gain
        with jax.named_scope("pt.mla.kv"):
            k = jnp.concatenate(
                [kv[..., :nope], jnp.broadcast_to(k_rope, (B, L, H, rope))],
                axis=-1)
        if attention_impl(cfg.attn_impl) == "flash":
            out = flash_attention(q, k, v, causal=True)
        else:
            out = _causal_attention(q, k, v)
        return F.linear(out.reshape(B, L, H * cfg.v_dim), self.w_o)


class JoyaiExperts(HeldExperts):
    """Router over all ``num_experts`` with its ``e_score_correction_bias``,
    the banks of the experts held, and the shared expert."""

    def __init__(self, cfg: JoyaiConfig) -> None:
        super().__init__(cfg, cfg.num_experts,
                         bias="e_score_correction_bias",
                         shared=cfg.expert_size * cfg.num_shared)


class JoyaiBlock(Layer):
    """Attention then a feed-forward: the dense SwiGLU or the experts. On
    one stream ``forward(x)`` is ``(x', route)``, ``route`` None for the
    dense kind; with ``cfg.hc_mult`` streams ``x`` is [B, L, n·C], each
    sublayer runs inside its ``HyperConnected`` (``hc_attn``, ``hc_ffn``)
    and ``forward`` returns ``(x', route, err)``, ``err`` the larger of the
    two sublayers' ``H_res`` errors. ``keep_bias``: ``HeldExperts``'."""

    def __init__(self, cfg: JoyaiConfig, dense: bool) -> None:
        super().__init__()
        self.norm1 = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        self.attn = JoyaiAttention(cfg)
        self.norm2 = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        if dense:
            self.mlp = SwiGLU(cfg.hidden_size, cfg.dense_size, cfg.init_std,
                               cfg.out_std)
        else:
            self.moe = JoyaiExperts(cfg)
        self.dense = dense
        self.streams = cfg.hc_mult > 1
        if self.streams:
            self.hc_attn = HyperConnected(cfg)
            self.hc_ffn = HyperConnected(cfg)

    def _over_streams(self, x: jax.Array, keep_bias: bool):
        with jax.named_scope("pt.attn"):
            x, _, err_attn = self.hc_attn(
                x, lambda u: self.attn(self.norm1(u)))
        if self.dense:
            with jax.named_scope("pt.ffn.dense"):
                x, route, err = self.hc_ffn(
                    x, lambda u: self.mlp(self.norm2(u)))
        else:
            with jax.named_scope("pt.ffn"):
                x, route, err = self.hc_ffn(
                    x, lambda u: self.moe(self.norm2(u), keep_bias))
        return x, route, jnp.maximum(err_attn, err)

    def forward(self, x: jax.Array, keep_bias: bool = False):
        if self.streams:
            return self._over_streams(x, keep_bias)
        # each sublayer's scope takes its norm and its residual add; the
        # latent projections, the rotary, the kernels and the expert
        # layer's stages sit in scopes of their own inside
        with jax.named_scope("pt.attn"):
            x = x + self.attn(self.norm1(x))
        if self.dense:
            with jax.named_scope("pt.ffn.dense"):
                return x + self.mlp(self.norm2(x)), None
        with jax.named_scope("pt.ffn"):
            y, route = self.moe(self.norm2(x), keep_bias)
            return x + y, route


class JoyaiPredictor(Layer):
    """One multi-token-prediction module: the projection of the next
    token's embedding beside the trunk's state, and a block of its own."""

    def __init__(self, cfg: JoyaiConfig) -> None:
        super().__init__()
        h = cfg.hidden_size
        self.norm_e = nn.RMSNorm(h, cfg.rms_eps)
        self.norm_h = nn.RMSNorm(h, cfg.rms_eps)
        self.create_parameter("w_eh", (2 * h, h),
                              initializer=normal_init(cfg.init_std))
        self.block = JoyaiBlock(cfg, dense=False)
        self.norm_f = nn.RMSNorm(h, cfg.rms_eps)

    def forward(self, next_embed: jax.Array, trunk: jax.Array):
        with jax.named_scope("pt.mtp"):
            x = F.linear(jnp.concatenate(
                [self.norm_e(next_embed), self.norm_h(trunk)], axis=-1),
                self.w_eh)
        x, route = self.block(x)
        with jax.named_scope("pt.mtp"):
            return self.norm_f(x), route


class Joyai(Layer):
    """Whole model. ``forward(ids)`` returns ``(logits, logits')``, both
    [B, L, vocab]: the next token's and, from the prediction module, the
    one after (its last position is no prediction: ``joyai_loss`` masks
    it); with ``num_mtp`` 0 it returns ``logits`` alone
    (``next_token_loss``). With ``output_routing`` also the routers'
    ``logits`` [expert layers, B*L, num_experts] and ``index``. With
    ``hc_mult`` streams the buffer ``hc_res_err`` holds the step's largest
    ``H_res`` error (``ops.hyper_connection.hc_res_err``) over every token
    and sublayer."""

    def __init__(self, cfg: JoyaiConfig) -> None:
        super().__init__()
        enforce(cfg.n_group == 1 and cfg.topk_group == 1,
                f"group-limited routing (n_group {cfg.n_group}, topk_group "
                f"{cfg.topk_group}) is not implemented: both must be 1")
        enforce(cfg.num_mtp in (0, 1),
                f"num_mtp {cfg.num_mtp}: one prediction module (JoyAI) or "
                "none (Xing4.0 as it is cut)")
        enforce(cfg.hc_mult >= 1 and (cfg.hc_mult == 1 or cfg.num_mtp == 0),
                f"hc_mult {cfg.hc_mult} with num_mtp {cfg.num_mtp}: 1 is the "
                "plain block, 2 and more are that many residual streams — "
                "and then no prediction module, for how one is handed "
                "several streams is in no source")
        enforce(cfg.rope_scaling is None
                or cfg.rope_scaling.get("type") == "yarn",
                f"rope_scaling {cfg.rope_scaling!r}: None, or a dict of type "
                "yarn (linear, dynamic, llama3 and longrope are not "
                "implemented)")
        enforce(cfg.recompute in ("none", "blocks"),
                f"recompute {cfg.recompute!r}: none or blocks")
        enforce(cfg.num_layers > cfg.first_dense >= 0,
                "at least one expert layer")
        enforce(cfg.experts_per_token <= cfg.num_experts,
                "more experts a token than experts")
        enforce_eq(cfg.rope_dim % 2, 0, "rotary pairs")
        self.cfg = cfg
        init = normal_init(cfg.init_std)
        self.create_parameter("embed", (cfg.vocab_size, cfg.hidden_size),
                              initializer=init)
        self.blocks = nn.LayerList([JoyaiBlock(cfg, i < cfg.first_dense)
                                    for i in range(cfg.num_layers)])
        self.norm_f = nn.RMSNorm(cfg.hidden_size, cfg.rms_eps)
        if cfg.num_mtp:
            self.mtp = JoyaiPredictor(cfg)
        self.create_parameter("head_w", (cfg.hidden_size, cfg.vocab_size),
                              initializer=init)
        RoutingRecord.register(self, cfg.expert_layers, cfg.num_experts)
        if cfg.hc_mult > 1:
            self.register_buffer("hc_res_err", jnp.zeros((), jnp.float32))

    def _run_blocks(self, x: jax.Array):
        """``x`` through every block: ``(x', routes, errs)``. Under
        ``recompute: "blocks"`` each block is a ``jax.checkpoint``ed
        function of the stream — a function of its own each call:
        ``jax.checkpoint`` keeps a function's trace, and the parameters a
        Layer closes over are another trace's the next time it is called
        (``models/smallthinker.py``) — whose route, with the moved bias in
        it, and error are its OUTPUTS; the bias is stored here."""
        rebuilt = self.cfg.recompute == "blocks"
        routes, errs = [], []
        for block in self.blocks:
            run = lambda x, block=block: block(x, keep_bias=rebuilt)
            x, route, *err = (jax.checkpoint(run) if rebuilt else run)(x)
            errs += err
            if route is not None:
                if rebuilt:
                    block.moe.store_bias(route)
                routes.append(route)
        return x, routes, errs

    def forward(self, ids: jax.Array, output_routing: bool = False):
        cfg = self.cfg
        enforce(ids.shape[-1] <= cfg.max_seq_len,
                f"sequence of {ids.shape[-1]} over max_seq_len {cfg.max_seq_len}")
        record_held(cfg.held, cfg.num_experts)
        streams = cfg.hc_mult > 1
        if streams:
            # what the residual path is made of, read off the
            # configuration: one host span a trace, none on the step path
            with RecordEvent("pt.hc.layers", layers=cfg.num_layers,
                             streams=cfg.hc_mult,
                             sinkhorn_iters=cfg.hc_sinkhorn_iters,
                             sublayers=2 * cfg.num_layers):
                pass
        with jax.named_scope("pt.embed"):
            x = jnp.take(self.embed, ids, axis=0)
            if cfg.num_mtp:
                # the next token's embedding; the last position repeats
                # its own
                nxt = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
            if streams:         # every stream starts as the embedding
                x = jnp.concatenate([x] * cfg.hc_mult, axis=-1)
        x, routes, errs = self._run_blocks(x)
        with jax.named_scope("pt.head_loss"):
            if streams:         # and the streams leave as their sum
                x = sum(s.astype(jnp.float32)
                        for s in jnp.split(x, cfg.hc_mult, axis=-1))
                self._buffers["hc_res_err"] = jnp.max(jnp.stack(errs))
            trunk = self.norm_f(x)
            logits = F.lm_head(trunk, self.head_w)
        outputs = logits
        if cfg.num_mtp:
            y, route = self.mtp(nxt, trunk)
            routes.append(route)
            with jax.named_scope("pt.head_loss"):
                outputs = (logits, F.lm_head(y, self.head_w))
        RoutingRecord.store(self, routes)
        if output_routing:
            return outputs, routing_outputs(routes, ("logits", "index"))
        return outputs


def joyai_losses(outputs, labels: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(next-token cross-entropy of ``logits``, cross-entropy of
    ``logits'[:, :L-1]`` against ``labels[:, 1:]``): position i of the
    module predicts token i+2, which is label i+1; its last position has
    none and is masked, not dropped."""
    logits, logits_mtp = outputs
    ahead = jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], axis=1)
    return next_token_loss(logits, labels), next_token_loss(logits_mtp, ahead)


def joyai_loss(outputs, labels: jax.Array) -> jax.Array:
    """``Trainer``'s ``loss_fn``: main loss + ``MTP_LOSS_WEIGHT`` x the
    prediction module's."""
    main, mtp = joyai_losses(outputs, labels)
    return main + MTP_LOSS_WEIGHT * mtp
