"""What the decoder models share — OLMoE, JoyAI-LLM-Flash (and Xing4.0,
which is ``models/joyai.py`` under other keys), LFM2, SmallThinker and
EvaByte (``models/<name>.py``: a configuration and an assembly, nothing
another model imports) — each thing ONCE, under a public name. Arrows point
one way: ``ops/`` (kernels) <- ``nn/`` <- ``parallel/`` (layers over
kernels) <- this module <- the models (``tests/test_layering.py``).

A block is ``x -> x + f(N(x))`` on ONE residual stream in every model but
one: ``HyperConnected`` is the residual path as a thing of its own, ``n``
streams a token mixed round a sublayer by mappings made from the streams
(``ops/hyper_connection.py``); a model whose configuration asks for it
hands its sublayers to it instead of adding their results itself.

The einsum stand-ins for the flash kernels, ``_causal_attention`` (JoyAI's:
q.k at 192, v at 128) and ``_banded_attention`` (grouped-query attention's),
are the off-TPU path and the float32 side of the benchmark's ``correct``:
reference code, so they sit here side by side, NOT merged (ROADMAP D13).

A layer reads its model's configuration by field name and names the fields
it reads. ``cfg.attn_impl`` is read at every forward, so a caller may set it
between two (the benchmark's float32 check does).
"""

from __future__ import annotations

import math
from typing import (Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import nn
from ..core.enforce import enforce
from ..core.profiler import RecordEvent
from ..nn import functional as F
from ..nn.layer import Layer
from ..ops.flash_attention import flash_attention
from ..ops.hyper_connection import hc_gates, hc_post, hc_pre, hc_res_err
from ..parallel.moe import held_moe

__all__ = ["normal_init", "residual_out_std", "rotary", "rotary_pairs",
           "yarn_frequencies", "yarn_mscale",
           "repeat_kv", "SwiGLU", "attention_impl", "GroupedQueryAttention",
           "HeldExperts", "RoutingRecord", "record_held", "stack_routes",
           "routing_outputs", "next_token_loss", "HyperConnected",
           "HC_ALPHA_INIT", "hc_res_bias_init"]


def normal_init(std: float):
    return lambda key, shape, dtype: jax.random.normal(key, shape, dtype) * std


def residual_out_std(init_std: float, layers: int) -> float:
    """std of the projections that write into the residual stream (W_o and
    every FFN's down matrix): ``init_std / sqrt(2 * layers)``, the scaled
    initialisation of GPT-2 / Megatron-LM, so that the stream's scale does
    not grow with depth; ``layers`` are those of the WHOLE model, also
    where a slice of it runs. The rest is ``init_std``."""
    return init_std / math.sqrt(2 * layers)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding, rotate-half form, positions 0..L-1.
    ``x`` [B, L, H, D]: pair (i, i + D/2) of every head turns by
    ``pos * theta^(-2i/D)``. Float32; the cos / sin tables are constants
    of the traced step, computed in float64 — a float32 angle at position
    4095 is already off by 2e-4 rad."""
    L, D = x.shape[1], x.shape[-1]
    inv_freq = float(theta) ** (-np.arange(0, D, 2, dtype=np.float64) / D)
    angle = np.arange(L, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.tile(np.cos(angle), 2), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.tile(np.sin(angle), 2), jnp.float32)[None, :, None]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor (arXiv:2309.00071 section 3.4, as
    DeepSeek-V3's ``yarn_get_mscale``): ``0.1 * mscale * ln(factor) + 1``
    past a factor of 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(theta: float, dim: int, scaling: Mapping[str, float]
                     ) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies under YaRN (``rope_scaling`` of
    ``type`` ``yarn``), float64, as DeepSeek-V3's public
    ``DeepseekV3YarnRotaryEmbedding`` blends them: pair ``i`` turns by
    ``pos * theta^(-2i/dim) * ((1 - r_i) + r_i / factor)``, the ramp ``r_i =
    clip((i - low) / (high - low), 0, 1)`` between the pairs that make
    ``beta_fast`` (``low``, rounded down) and ``beta_slow`` (``high``,
    rounded up) turns over ``original_max_position_embeddings``. It holds
    at every length, not only past the original one."""
    base = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_making(turns):
        return dim * math.log(
            scaling["original_max_position_embeddings"]
            / (turns * 2 * math.pi)) / (2 * math.log(float(theta)))

    low = max(math.floor(pair_making(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_making(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 0.001), 0.0, 1.0)
    return base * ((1.0 - ramp) + ramp / float(scaling["factor"]))


def rotary_pairs(x: jax.Array, theta: float,
                 scaling: Optional[Mapping[str, float]] = None) -> jax.Array:
    """Rotary position embedding on adjacent pairs, positions 0..L-1.
    ``x`` [B, L, H, D]: pair (2i, 2i+1) of every head turns by
    ``pos * theta^(-2i/D)``. Float32; the cos / sin tables are constants of
    the traced step, computed in float64. ``scaling``: a ``rope_scaling``
    of ``type`` ``yarn`` — the frequencies are ``yarn_frequencies``' and cos
    and sin are times ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)``; None is no scaling, and no other type is known."""
    L, D = x.shape[1], x.shape[-1]
    gain = 1.0
    if scaling is None:
        inv_freq = float(theta) ** (-np.arange(0, D, 2, dtype=np.float64) / D)
    else:
        enforce(scaling.get("type") == "yarn",
                f"rope_scaling type {scaling.get('type')!r}: yarn or none")
        inv_freq = yarn_frequencies(theta, D, scaling)
        gain = yarn_mscale(scaling["factor"], scaling["mscale"]) \
            / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    angle = np.arange(L, dtype=np.float64)[:, None] * inv_freq[None, :]
    cos = jnp.asarray(np.repeat(np.cos(angle) * gain, 2, axis=1),
                      jnp.float32)[None, :, None]
    sin = jnp.asarray(np.repeat(np.sin(angle) * gain, 2, axis=1),
                      jnp.float32)[None, :, None]
    pairs = x.reshape(*x.shape[:-1], D // 2, 2)
    turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], axis=-1)
    return x * cos + turned.reshape(x.shape) * sin


def attention_impl(impl: str) -> str:
    """A configuration's ``attn_impl`` as "flash" or "einsum": "auto" is the
    Pallas flash kernels on TPU and the einsum stand-in elsewhere."""
    if impl == "auto":
        return "flash" if jax.default_backend() == "tpu" else "einsum"
    return impl


#: heads whose [L, L] scores are alive at once in the einsum attention
_HEAD_GROUP = 8


def _causal_attention(q, k, v):
    """Einsum attention over the full score matrix, q.k and v at their own
    widths: the off-TPU stand-in for the kernel, and the float32 side of
    the benchmark's check. [B, L, H, .]. A group of heads at a time,
    rebuilt in the backward pass: 32 heads' [4096, 4096] scores and
    probabilities of six blocks would be 25 GB of residuals."""
    L, H = q.shape[1], q.shape[2]
    causal = jnp.tril(jnp.ones((L, L), bool))[None, None]
    scale = float(q.shape[-1]) ** -0.5

    @jax.checkpoint
    def group(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    return jnp.concatenate(
        [group(q[:, :, g:g + _HEAD_GROUP], k[:, :, g:g + _HEAD_GROUP],
               v[:, :, g:g + _HEAD_GROUP])
         for g in range(0, H, _HEAD_GROUP)], axis=2)


#: queries a block, and heads a group, of the banded einsum attention
_QUERY_BLOCK = 2048
_BAND_GROUP = 7


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
        [group(q[:, :, g:g + _BAND_GROUP], k[:, :, g:g + _BAND_GROUP],
               v[:, :, g:g + _BAND_GROUP])
         for g in range(0, H, _BAND_GROUP)], axis=2)


def repeat_kv(k: jax.Array, v: jax.Array,
              heads: int) -> Tuple[jax.Array, jax.Array]:
    """k and v [B, L, G, d] copied to ``heads`` heads, key-value head j to
    the ``heads / G`` consecutive query heads from ``j * that``, under
    ``pt.gqa.repeat``: the kernels take one k and one v a query head
    (ROADMAP R8). The one repeat of the grouped-query models (LFM2's 8 ->
    32, SmallThinker's 4 -> 28)."""
    with jax.named_scope("pt.gqa.repeat"):
        groups = heads // k.shape[2]
        return (jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2))


class SwiGLU(Layer):
    """``down(silu(gate(u)) * up(u))``, no bias."""

    def __init__(self, hidden: int, width: int, std: float,
                 out_std: float) -> None:
        super().__init__()
        init = normal_init(std)
        self.create_parameter("w_gate", (hidden, width), initializer=init)
        self.create_parameter("w_up", (hidden, width), initializer=init)
        self.create_parameter("w_down", (width, hidden),
                              initializer=normal_init(out_std))

    def forward(self, u: jax.Array) -> jax.Array:
        return F.linear(jax.nn.silu(F.linear(u, self.w_gate))
                        * F.linear(u, self.w_up), self.w_down)


class GroupedQueryAttention(Layer):
    """Causal grouped-query attention, ``num_heads`` query heads on
    ``num_kv_heads`` key-value heads of ``head_dim``: with ``qk_norm`` an
    RMSNorm over each head of q and of k (one learned weight of
    ``head_dim`` each), with ``rope`` rotary positions (half-split) on q and
    k, else none at all; under ``window`` query i sees the keys ``i - window
    < j <= i``, else every ``j <= i``. Reads ``hidden_size``, ``num_heads``,
    ``num_kv_heads``, ``head_dim``, ``init_std``, ``out_std``,
    ``rope_theta``, ``rms_eps``, ``attn_impl``."""

    def __init__(self, cfg, qk_norm: bool, rope: bool,
                 window: Optional[int]) -> None:
        super().__init__()
        self.cfg, self.qk_norm = cfg, qk_norm
        self.rope, self.window = rope, window
        h, d = cfg.hidden_size, cfg.head_dim
        init = normal_init(cfg.init_std)
        self.create_parameter("wq", (h, cfg.num_heads * d), initializer=init)
        self.create_parameter("wk", (h, cfg.num_kv_heads * d),
                              initializer=init)
        self.create_parameter("wv", (h, cfg.num_kv_heads * d),
                              initializer=init)
        self.create_parameter("wo", (cfg.num_heads * d, h),
                              initializer=normal_init(cfg.out_std))
        if qk_norm:
            self.q_norm = nn.RMSNorm(d, cfg.rms_eps)
            self.k_norm = nn.RMSNorm(d, cfg.rms_eps)

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        B, L, _ = x.shape
        H, G, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        with jax.named_scope("pt.gqa.qkv"):
            q = F.linear(x, self.wq).reshape(B, L, H, d)
            if self.qk_norm:
                q = self.q_norm(q)
            k = F.linear(x, self.wk).reshape(B, L, G, d)
            if self.qk_norm:
                k = self.k_norm(k)
            v = F.linear(x, self.wv).reshape(B, L, G, d)
        if self.rope:
            with jax.named_scope("pt.rope"):
                q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        k, v = repeat_kv(k, v, H)
        if attention_impl(cfg.attn_impl) == "flash":
            out = flash_attention(q, k, v, causal=True, window=self.window)
        else:
            out = _banded_attention(q, k, v, self.window)
        return F.linear(out.reshape(B, L, H * d), self.wo)


class HeldExperts(Layer):
    """An expert layer that holds ``cfg.held = (first, count)`` of its
    ``experts`` (one expert-parallel rank's part): the router over all of
    them and the banks of the experts held. ``forward`` is the bias-routed
    layer (``parallel.moe.held_moe``) and returns the held experts' part and
    the router's record.

    ``bias``: the NAME of the router's bias buffer (each model keeps its
    published one). The route is made by the sigmoid rule at
    ``cfg.routed_scale``, the bias moving the choice, and every forward
    moves the bias by ``cfg.bias_update_rate * sign(mean(c) - c)``, ``c``
    this step's assignment counts over all experts (DeepSeek-V3 section
    2.1.2; the train step carries it out through ``new_state["buffers"]``).
    None: a router without one, whose model makes the route elsewhere and
    states its own ``forward`` (SmallThinker). ``shared``: the width of a
    shared expert's SwiGLU, which every rank computes alike, or None. Reads
    ``hidden_size``, ``expert_size``, ``experts_per_token``, ``held``,
    ``init_std``, ``out_std``.

    Who stores the moved bias: ``forward`` itself, in its buffer, where the
    block runs in the step's own trace (JoyAI, LFM2). A block that the
    backward pass rebuilds (``jax.checkpoint``) may write no buffer — the
    value would be a tracer of the rebuilt function, leaked — so its model
    calls ``forward(x, keep_bias=True)``: the moved bias then leaves with the
    route (``route["bias"]``), an output of the checkpointed function like
    the rest of the record, and the model hands the route to ``store_bias``
    once, outside (``models/joyai.py`` under ``recompute: "blocks"``)."""

    def __init__(self, cfg, experts: int, bias: Optional[str],
                 shared: Optional[int]) -> None:
        super().__init__()
        first, count = cfg.held
        enforce(0 <= first and count >= 1 and first + count <= experts,
                f"held experts {cfg.held} outside 0..{experts}")
        self.cfg, self.bias = cfg, bias
        h, f = cfg.hidden_size, cfg.expert_size
        init = normal_init(cfg.init_std)
        self.create_parameter("router_w", (h, experts), initializer=init)
        self.create_parameter("w_gate", (count, h, f), initializer=init)
        self.create_parameter("w_up", (count, h, f), initializer=init)
        self.create_parameter("w_down", (count, f, h),
                              initializer=normal_init(cfg.out_std))
        self.shared = None if shared is None else SwiGLU(
            h, shared, cfg.init_std, cfg.out_std)
        if bias is not None:
            self.register_buffer(bias, jnp.zeros((experts,), jnp.float32))

    def forward(self, x: jax.Array, keep_bias: bool = False
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        cfg = self.cfg
        lead = x.shape[:-1]
        bias = self._buffers[self.bias]
        out, route = held_moe(
            x.reshape(-1, x.shape[-1]), self.router_w, bias, self.w_gate,
            self.w_up, self.w_down, cfg.experts_per_token, cfg.held,
            cfg.routed_scale)
        counts = route["counts"].astype(jnp.float32)
        moved = bias + cfg.bias_update_rate * jnp.sign(
            jnp.mean(counts) - counts)
        if keep_bias:
            route = dict(route, bias=moved)
        else:
            self._buffers[self.bias] = moved
        if self.shared is None:
            return out.reshape(*lead, out.shape[-1]), route
        with jax.named_scope("pt.moe.shared"):
            out = out.reshape(*lead, out.shape[-1]) + self.shared(x)
        return out, route

    def store_bias(self, route: Dict[str, jax.Array]) -> None:
        """The moved bias a ``forward(x, keep_bias=True)`` left in its
        route, into the buffer: the caller's, outside what is rebuilt."""
        self._buffers[self.bias] = route["bias"]


def record_held(held: Tuple[int, int], experts: int) -> None:
    """What a model's expert layers hold, read off its configuration: one
    host span a trace (``profiler.host_spans()``), none on the step path."""
    with RecordEvent("pt.moe.held", first=held[0], count=held[1],
                     experts=experts):
        pass


def stack_routes(routes: List[Dict[str, jax.Array]], key: str) -> jax.Array:
    """``key`` of every expert layer's record, the layer axis first."""
    return jnp.stack([r[key] for r in routes])


def routing_outputs(routes: List[Dict[str, jax.Array]],
                    keys: Sequence[str]) -> Dict[str, jax.Array]:
    """What ``forward(ids, output_routing=True)`` returns beside the logits:
    the routers' ``keys``, [expert layers, ...] each."""
    return {key: stack_routes(routes, key) for key in keys}


class RoutingRecord:
    """The counters a model of ``n`` held expert layers leaves its forward
    in, as buffers of the model: ``expert_counts`` [n, width] (as routed,
    all experts), ``held_assignments`` [n], ``dispatch_rung`` [n] (rows of
    the form that ran: the bounded buffer, or every held expert on every
    token), ``dispatch_rows_walked`` [n] (the rows that form's row movement
    passed over: whole chunks up to the buffer's last live row,
    ``parallel.moe.held_moe``) and ``tokens_dropped`` (held assignments
    less those the form that ran counted as computed: 0 unless the dispatch
    is at fault)."""

    @staticmethod
    def register(layer: Layer, n: int, width: int) -> None:
        layer.register_buffer("expert_counts",
                              jnp.zeros((n, width), jnp.int32))
        layer.register_buffer("held_assignments", jnp.zeros((n,), jnp.int32))
        layer.register_buffer("dispatch_rung", jnp.zeros((n,), jnp.int32))
        layer.register_buffer("dispatch_rows_walked",
                              jnp.zeros((n,), jnp.int32))
        layer.register_buffer("tokens_dropped", jnp.zeros((), jnp.int32))

    @staticmethod
    def store(layer: Layer, routes: List[Dict[str, jax.Array]]) -> None:
        """``routes``: one forward's ``HeldExperts`` records, layer order."""
        layer._buffers["expert_counts"] = stack_routes(routes, "counts")
        layer._buffers["held_assignments"] = stack_routes(
            routes, "held_assignments").astype(jnp.int32)
        layer._buffers["dispatch_rung"] = stack_routes(
            routes, "rung").astype(jnp.int32)
        layer._buffers["dispatch_rows_walked"] = stack_routes(
            routes, "rows_walked").astype(jnp.int32)
        layer._buffers["tokens_dropped"] = jnp.sum(
            stack_routes(routes, "dropped")).astype(jnp.int32)


def next_token_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """``Trainer``'s ``loss_fn`` of a decoder: next-token cross-entropy,
    mean over the positions (a label of -1 is no position)."""
    return F.cross_entropy(logits, labels, ignore_index=-1)


#: the three gates ``alpha`` of a sublayer's mappings start here: the
#: mappings then begin as their biases say, a hundredth of the streams'
#: projection on top (hyper-connections section 3 starts its dynamic part
#: small; the row gives no value)
HC_ALPHA_INIT = 0.01


def hc_res_bias_init(n: int) -> np.ndarray:
    """``b_res`` [n, n] at step 0: 2 on the diagonal, 1 above it, 0 below.
    ``H_res = SK(b_res)`` is then 0.594 on the diagonal and 0.103 / 0.133 /
    0.170 off it at n = 4: a stream mostly keeps itself, and the matrix is
    neither uniform, nor the identity, nor symmetric, nor one step from
    doubly stochastic — one Sinkhorn step leaves it 0.060 from where twenty
    do (a symmetric start such as ``c * I`` is doubly stochastic after ONE
    step, and a check on it could not tell 1 step from 20, nor ``H_res``
    from its transpose). The row gives no initialisation."""
    return 2.0 * np.eye(n) + np.triu(np.ones((n, n)), 1)


class HyperConnected(Layer):
    """One sublayer's residual path over ``cfg.hc_mult`` streams
    (``ops/hyper_connection.py`` has the equations and the kernels): owns
    the sublayer's ``phi`` [nC, 2n + n²] (at ``init_std``), ``b`` (zeros,
    but ``b_res`` = ``hc_res_bias_init``) and ``alpha``
    (``HC_ALPHA_INIT``), and wraps a callable. ``forward(X, sublayer)``:
    ``X`` [..., n·C], the streams side by side (stream j in columns
    jC..(j+1)C: the kernels' own form, so nothing is laid out anew between
    two sublayers) -> ``(X', route, err)`` where ``sublayer(u)`` maps
    [..., C] float32 to ``y`` or to ``(y, route)`` — its own norm inside it
    —, ``route`` (None without one) is carried through, and ``err`` is
    ``hc_res_err`` of this call's ``H_res``. What is the size of a stream
    runs in the four token-tiled kernels, at every shape: ``hc_pre`` (the
    norm, the projection and ``u``; ``pt.hc.collect``) and ``hc_post``
    (``X'``; ``pt.hc.scatter``), which open their scopes themselves,
    forward and in their stated backward; ``pt.hc.map``, opened here, keeps
    the [tokens, 2n + n²] work — gates, sigmoids, the Sinkhorn steps, the
    error — which JAX differentiates. All inside whatever scope the caller
    has open (the sublayer's ``pt.attn`` / ``pt.ffn`` / ``pt.ffn.dense``).
    Reads ``hidden_size``, ``hc_mult``, ``hc_sinkhorn_iters``, ``hc_eps``,
    ``hc_clamp``, ``rms_eps``, ``init_std``."""

    def __init__(self, cfg) -> None:
        super().__init__()
        n, c = cfg.hc_mult, cfg.hidden_size
        enforce(n >= 2, f"hc_mult {n}: a hyper-connected sublayer has at "
                "least two streams (1 is the plain block, not a case of "
                "this one)")
        self.cfg = cfg
        self.create_parameter("phi", (n * c, 2 * n + n * n),
                              initializer=normal_init(cfg.init_std))
        bias = np.concatenate([np.zeros(2 * n), hc_res_bias_init(n).ravel()])
        self.create_parameter("b", bias.shape, init_value=bias)
        self.create_parameter("alpha", (3,),
                              init_value=np.full(3, HC_ALPHA_INIT))

    def forward(self, x: jax.Array, sublayer: Callable):
        cfg = self.cfg
        side_by_side = x.shape
        x = x.reshape(*x.shape[:-1], cfg.hc_mult, cfg.hidden_size)
        u, z, x = hc_pre(x, self.phi, self.b, self.alpha, cfg.rms_eps)
        with jax.named_scope("pt.hc.map"):
            _, h_post, h_res = hc_gates(
                z, self.b, self.alpha, cfg.hc_mult, cfg.hc_sinkhorn_iters,
                cfg.hc_eps, cfg.hc_clamp)
            err = hc_res_err(h_res)
        out = sublayer(u)
        y, route = out if isinstance(out, tuple) else (out, None)
        return (hc_post(x, y, h_post, h_res).reshape(side_by_side), route,
                err)
