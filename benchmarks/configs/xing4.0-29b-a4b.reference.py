"""Plain reference for ``xing4.0-29b-a4b``: decoder forward over four
residual streams a token, the next-token loss, its gradients and the
router-bias update — ``jax.numpy``, float32, matmul precision ``highest``;
einsum attention over the full [L, L] score matrix with an explicit causal
mask; the held experts applied to every token and masked by the choice; the
Sinkhorn normalisation as its twenty written-out steps; YaRN from its
formulas (no sort, no grouped matmul, no kernel, no mixed precision, no
trainer). Independent of ``paddle_tpu``.

The architecture: ``XingChen-AGI/Xing4.0-29B-A4B`` (``model_type``
``xing4_0``). Its attention, router, shared expert and dense-layer keys are
DeepSeek-V3's (arXiv:2412.19437); ``hc_mult`` / ``hc_sinkhorn_iters`` /
``hc_eps`` / ``mhc_h_res_clamp_*`` are manifold-constrained
hyper-connections (arXiv:2512.24880, on arXiv:2409.19606); ``rope_scaling``
is YaRN (arXiv:2309.00071). ``N`` is RMSNorm (eps ``rms_norm_eps``, a
learned weight), no bias anywhere; ``n`` = ``hc_mult``, ``C`` =
``hidden_size``; the state of a token between sublayers is X [n, C]:

    X_i = embed[id]  for every i                                  (entry)
    each sublayer F of each block (attention, then the feed-forward), with
    its own Phi [nC, 2n + n^2], b [2n + n^2], alpha [3]:
      xbar   = vec(X) / sqrt(mean(vec(X)^2) + rms_norm_eps)
      z      = xbar Phi
      H_pre  = sigmoid(alpha_0 z[:n] + b[:n])
      H_post = 2 sigmoid(alpha_1 z[n:2n] + b[n:2n])
      H_res  = SK(clip(alpha_2 mat(z[2n:]) + mat(b[2n:]), clamp_min, clamp_max))
      SK(A): M = exp(A); hc_sinkhorn_iters times: every column of M over
             (its sum + hc_eps), then every row over (its sum + hc_eps)
      u = sum_i H_pre[i] X_i;  y = F(N(u));  X'_i = sum_j H_res[i,j] X_j + H_post[i] y
    layer < first_k_dense_replace: F = MLA, then F = SwiGLU
    other layers:                  F = MLA, then F = MoE
    t = N_f(sum_i X_i);  logits = t @ head_w                       (exit)

``MLA(u)``: c_q = N(u W_qa); [q_nope | q_rope] = c_q W_qb a head;
[c_kv | k_rope] = u W_kva; [k_nope | v] = N(c_kv) W_kvb a head; rotary on
q_rope (each head) and on the one k_rope all heads share: adjacent pairs
(2i, 2i+1) turn by pos * f_i, positions 0..L-1, with YaRN's
f_i = theta^(-2i/64) * ((1 - r_i) + r_i / factor), r_i = clip((i - low) /
(high - low), 0, 1), low = floor(p(beta_fast)), high = ceil(p(beta_slow)),
p(turns) = 64 ln(original_max_position_embeddings / (2 pi turns)) /
(2 ln theta) (10 and 23 for the published values), cos and sin times
m(mscale) / m(mscale_all_dim), m(s) = 0.1 s ln(factor) + 1;
softmax([q_nope | q_rope] . [k_nope | k_rope] * m(mscale_all_dim)^2 /
sqrt(192) + causal) v; W_o.
``MoE(u)``: as ``joyai-llm-flash.reference``: z = u W_r; s = sigmoid(z);
the ``num_experts_per_tok`` largest of s + b (b: ``e_score_correction_bias``,
a buffer, no gradient); g = s at those experts, g / (sum g + 1e-20) *
``routed_scaling_factor``; out = sum over the chosen experts IN THE HELD
RANGE ``(held_first, n_routed_experts)`` of the ``router_width`` experts of
g_i SwiGLU_i(u) + SwiGLU_shared(u): what the absent experts would add is
left out, here as in the system. After the forward b <- b +
``bias_update_rate`` * sign(mean(c) - c), c this step's assignment counts
over all ``router_width`` experts. Loss: CE(logits, labels), mean over the
positions. No prediction module (``num_nextn_predict_layers`` 0 as cut).

Departures and readings, each also in the configuration's file: the
rotary pairs in place (the source de-interleaves first: one fixed
permutation of q's and k's rotary channels alike); ``hc_eps`` in both
denominators, columns before rows; the clamp on H_res' logits before the
exp; xbar without a learned weight; entry by copy, exit by sum; attention a
group of heads at a time and every block recomputed in the backward pass
(``jax.checkpoint``): memory, not arithmetic.

``operand_dtype``, when given, rounds both operands of every matmul but
the router's and the mappings' projection (which the configuration states
float32) to that dtype first (float32 accumulation): this reference "in
the nearest precision below" bf16 is ``float8_e4m3fn``, the reading that
the ``amp`` tolerances must refuse.

``expert_index`` [expert layers, T, k], when given, fixes which experts
every token uses (the weights are still this reference's own s at those
experts): that is how a step in lower precision, whose router flips
near-ties, is held to the same function.
"""

from __future__ import annotations

import importlib.util
import math
import os
from typing import Any, Dict, Mapping, Optional

import numpy as np

#: Tolerances, with their reasons.
#:
#: ``f32``: the system's step with ``amp`` off, einsum attention and matmul
#: precision ``highest`` computes the same float32 function by another
#: route (the mappings' projection with the norm's factor applied after
#: it, the n x n mixing written out stream by stream, held assignments
#: sorted into a bounded buffer, grouped matmuls); only summation order
#: differs. ``score_abs``, ``gap`` and the near-tie rule are
#: ``joyai-llm-flash.reference``'s, for its reasons (scores are sigmoids
#: in 0.3..0.7; ``harness/near_tie.py``). ``hc_abs`` is on the largest
#: ``|row sum - 1|`` / ``|column sum - 1|`` of H_res the step counted
#: (``hc_res_err``), against this reference's own: both are ``hc_eps`` =
#: 1e-6 and float32 rounding of sums of four (1e-7), so they agree to
#: 2e-6 (read 1.2e-7 to 2.4e-7), while ONE Sinkhorn step for twenty leaves
#: columns 0.15 from 1 and the mappings in bf16 3.9e-3.
#: ``grad_leaf_rel`` is a leaf's widest error over the leaf's largest entry
#: OR ``GRADIENT_FLOOR[mode]``, whichever is larger. Read on the chip at full
#: widths (PERF.md section 6, PR 51; three seeds): the sound program 1.3e-5,
#: 7.8e-6 and 4.4e-5; the planted faults 1.4e-2 (H_res transposed: 0.105 of
#: a leaf of 1.4e-6, 3.8e-3 of the next), 0.57 (the mappings in bf16), 1.0
#: and more (one Sinkhorn step, H_post without its 2, plain rotary); at a
#: fourth seed the sound program 1.8e-5 and H_res transposed 5.4e-3: 3e-4
#: lies 7x over the one and 18x under the other. ``logit_rel`` read 7e-7;
#: one Sinkhorn step 4.2e-4, the mappings in bf16 4.8e-3 (a transposed H_res
#: changes no logit while the streams are still equal: the gradients are
#: what refuses it).
#:
#: ``amp``: the step as measured — bf16 operands in every dense and grouped
#: matmul and in the flash kernels, float32 accumulation; float32 router,
#: norms, rotary, softmax statistics AND mappings. The bounds sit between
#: the step as measured (below) and this reference in ``float8_e4m3fn``
#: (above): ``loss_rel`` read 1.3e-5 against 8.5e-4 -> 3e-4 (JoyAI's);
#: ``grad_leaf_rel`` read 0.037 to 0.142 over eight runs at seven seeds
#: (0.057 on ``blocks.1.hc_ffn.b`` and 0.070 on ``blocks.4.moe.w_up``, the
#: bank of eight experts that saw 250 tokens each, on ONE seed's two runs;
#: then 0.037, 0.101, 0.142, 0.072, 0.063, 0.044), against 9 (fp8, over a
#: floor of 1e-5; 2 to 9 on the eight widest leaves) -> 0.5. Six runs
#: more read 0.047, 0.043, 0.303, 0.101, 0.035, 0.058 over that floor: the
#: 0.303 is why ``GRADIENT_FLOOR["amp"]`` is 1e-4 (below), over which the
#: fourteen read 0.142 at most and fp8 4.9: 3.5x over the one, 10x under
#: the other. JoyAI's 6e-2 is NOT kept: its readings sat at
#: 0.009-0.015, this cell's sit AT it and past it (a held expert sees a
#: sixteenth of 4096 tokens, and the mappings' ``b`` sum to almost nothing
#: over the tokens: the widest entry of such a leaf is what the seed draws).
#: ``topk_overlap``: the least mean share of a token's 4 experts that are
#: also this reference's own: read 0.995; a wrong router reads 4 / 64.
#:
#: ``update``: as ``joyai-llm-flash.reference``: the parameters and second
#: moments the system's AdamW step leaves against ``adamw_first_step`` here
#: on the SAME gradient, an ulp of each weight allowed for.
TOL = {
    "f32": {"loss_rel": 3e-5, "grad_leaf_rel": 3e-4, "logit_rel": 1e-4,
            "score_abs": 1e-5, "gap": 3e-5, "hc_abs": 2e-6},
    "amp": {"loss_rel": 3e-4, "grad_leaf_rel": 0.5, "topk_overlap": 0.7,
            "hc_abs": 2e-6},
    "update": {"param_rel": 1e-4, "moment_rel": 1e-5},
}

#: The least scale a leaf's error is judged against, by mode. A gradient
#: is a sum over 4096 tokens x 3584 channels, and what rounding leaves in an
#: entry of the mappings' ``b`` or ``alpha`` does not shrink with the leaf:
#: where a sublayer's per-token terms cancel, the leaf is nought beside its
#: neighbours and the rounding is all of it.
#: ``f32``: float32 leaves 1e-10 to 4e-10 an entry of ``b`` (three seeds
#: on the chip, PR 51: 1.5e-10, 1.0e-10, 4.4e-10); ``blocks.2.hc_attn.b`` is
#: 1.4e-6 at its largest entry at seed 1234567891 (its neighbours 2e-5 to
#: 7e-4) and that rounding 3.2e-4 OF IT: the sound program read not correct.
#: Judged against 1e-5 it reads 4.4e-5, and a transposed H_res still reads
#: 1.4e-2 there. The leaves at work are larger than the floor (the
#: smallest, a ``phi``, 4e-6 to 1e-5: it reads half of what it did).
#: ``amp``: the step as measured leaves 2e-7 to 1.4e-5 an entry of a ``b``
#: or an ``alpha`` (those among the eight widest leaves of eight runs on the
#: chip, PR 51), whose largest entry is 3.6e-5 to 7e-4 as the seed draws
#: it: against 1e-5 the eleventh of fourteen runs read 0.303 on
#: ``blocks.0.hc_ffn.alpha`` (1.1e-5 of 3.6e-5) where the others read 0.035
#: to 0.142 — a reading that goes as one over what the sum happens to come
#: to — and the limit is 0.5. This reference in ``float8_e4m3fn`` leaves
#: 9e-5 to 2.7e-3 an entry of those leaves (its printed leaves, PR 51):
#: judged against 1e-4 it reads 4.9 (``blocks.2.hc_ffn.b``; 3.9 on a leaf
#: of 7e-4 that the floor does not touch), that run 0.110, and no run more
#: than it did (0.142 the largest; of the eight whose leaves were printed,
#: 0.110).
GRADIENT_FLOOR = {"f32": 1e-5, "amp": 1e-4}

#: heads whose [L, L] scores are alive at once
_HEAD_GROUP = 8


def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def yarn_mscale(factor: float, mscale: float) -> float:
    """m(s) = 0.1 s ln(factor) + 1 past a factor of 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(theta: float, dim: int, scaling: Mapping[str, float]):
    """The dim / 2 rotary frequencies f_i under YaRN, float64."""
    def pair_making(turns):
        return dim * math.log(scaling["original_max_position_embeddings"]
                              / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_making(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_making(scaling["beta_slow"])), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return theta ** (-2.0 * i / dim) * ((1.0 - ramp)
                                        + ramp / scaling["factor"])


def _rotary_pairs(x, theta, scaling):
    """x [B, L, H, D]; pair (2i, 2i+1) rotated by pos * f_i."""
    import jax.numpy as jnp

    L, D = x.shape[1], x.shape[-1]
    freq = yarn_frequencies(float(theta), D, scaling)
    gain = yarn_mscale(scaling["factor"], scaling["mscale"]) \
        / yarn_mscale(scaling["factor"], scaling["mscale_all_dim"])
    ang = np.arange(L, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang) * gain, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * gain, jnp.float32)[None, :, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _swiglu(u, gate, up, down, r):
    import jax

    return r(jax.nn.silu(r(u) @ r(gate)) * (r(u) @ r(up))) @ r(down)


def _attention(p, pre, u, cfg, r):
    import jax
    import jax.numpy as jnp

    B, L, _ = u.shape
    H = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    scaling = cfg["rope_scaling"]
    assert scaling["type"] == "yarn"
    scale = yarn_mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2 \
        / math.sqrt(nope + rope)
    u = r(u)
    c_q = _rms_norm(u @ r(p[pre + "w_qa"]), p[pre + "q_norm.weight"], eps)
    q = (r(c_q) @ r(p[pre + "w_qb"])).reshape(B, L, H, nope + rope)
    kva = u @ r(p[pre + "w_kva"])
    c_kv = _rms_norm(kva[..., :rank], p[pre + "kv_norm.weight"], eps)
    kv = (r(c_kv) @ r(p[pre + "w_kvb"])).reshape(B, L, H, nope + dv)
    q = jnp.concatenate([q[..., :nope],
                         _rotary_pairs(q[..., nope:], theta, scaling)],
                        axis=-1)
    k_rope = _rotary_pairs(kva[..., None, rank:], theta, scaling)  # one head
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, L, H, rope))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((L, L), bool))

    def group(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) * scale
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", r(a), r(v))

    o = jnp.concatenate(
        [jax.checkpoint(group)(q[:, :, g:g + _HEAD_GROUP],
                               k[:, :, g:g + _HEAD_GROUP],
                               v[:, :, g:g + _HEAD_GROUP])
         for g in range(0, H, _HEAD_GROUP)], axis=2)
    return r(o.reshape(B, L, H * dv)) @ r(p[pre + "w_o"])


def _experts(p, pre, u, bias, cfg, index, r):
    """(held experts' part + shared expert [T, h], logits z, own index,
    gap, counts [E] as routed with the index used)."""
    import jax
    import jax.numpy as jnp

    k = cfg["num_experts_per_tok"]
    first, count = cfg["held_first"], cfg["n_routed_experts"]
    z = u @ p[pre + "router_w"]                              # [T, E]
    E = z.shape[-1]
    s = jax.nn.sigmoid(z)
    top, own_index = jax.lax.top_k(jax.lax.stop_gradient(s) + bias, k + 1)
    used = own_index[:, :k]
    if index is not None:
        used = jnp.where(index[0], index[1], used)
    index = used
    mask = jnp.sum(jax.nn.one_hot(index, E, dtype=jnp.float32), axis=1)
    g = s * mask
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    g = g[:, first:first + count]
    # every held expert on every token, then the mask times the weight
    gate = jnp.einsum("td,edf->tef", r(u), r(p[pre + "w_gate"]))
    up = jnp.einsum("td,edf->tef", r(u), r(p[pre + "w_up"]))
    act = r(jax.nn.silu(gate) * up) * g[:, :, None]
    y = jnp.einsum("tef,efd->td", act, r(p[pre + "w_down"]))
    y = y + _swiglu(u, p[pre + "shared.w_gate"], p[pre + "shared.w_up"],
                    p[pre + "shared.w_down"], r)
    return y, z, own_index[:, :k], top[:, k - 1] - top[:, k], \
        jnp.sum(mask, axis=0)


def sinkhorn(a, iters: int, eps: float):
    """SK(a) of [..., n, n]: exp, then ``iters`` times every column over
    (its sum + eps) and every row over (its sum + eps)."""
    import jax.numpy as jnp

    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)     # columns
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)     # rows
    return m


def mappings(p, pre, X, cfg):
    """(H_pre [B, L, n], H_post [B, L, n], H_res [B, L, n, n]) of the
    streams X [B, L, n, C] under the sublayer ``pre``'s Phi, b, alpha."""
    import jax
    import jax.numpy as jnp

    B, L, n, C = X.shape
    flat = X.reshape(B, L, n * C)
    xbar = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                           + cfg["rms_norm_eps"])
    z = xbar @ p[pre + "phi"]
    b, alpha = p[pre + "b"], p[pre + "alpha"]
    h_pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + b[n:2 * n])
    res = alpha[2] * z[..., 2 * n:].reshape(B, L, n, n) \
        + b[2 * n:].reshape(n, n)
    res = jnp.clip(res, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    return h_pre, h_post, sinkhorn(res, cfg["hc_sinkhorn_iters"],
                                   cfg["hc_eps"])


def _hyper_connected(p, pre, X, cfg, sublayer):
    """One sublayer over the streams: (X', what ``sublayer`` returned
    beside y, the largest |row sum - 1|, |column sum - 1| of H_res)."""
    import jax.numpy as jnp

    h_pre, h_post, h_res = mappings(p, pre, X, cfg)
    u = jnp.einsum("bln,blnc->blc", h_pre, X)
    y, *rest = sublayer(u)
    X = jnp.einsum("blij,bljc->blic", h_res, X) \
        + h_post[..., :, None] * y[:, :, None, :]
    err = jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, axis=-1) - 1.0)),
                      jnp.max(jnp.abs(jnp.sum(h_res, axis=-2) - 1.0)))
    return X, rest, err


def _block(p, pre, X, bias, cfg, index, r):
    import jax.numpy as jnp

    eps = cfg["rms_norm_eps"]
    B, L, n, h = X.shape
    X, _, err_a = _hyper_connected(
        p, pre + "hc_attn.", X, cfg, lambda u: (_attention(
            p, pre + "attn.", _rms_norm(u, p[pre + "norm1.weight"], eps),
            cfg, r),))

    def ffn(u):
        u = _rms_norm(u, p[pre + "norm2.weight"], eps)
        if bias is None:
            return (_swiglu(u, p[pre + "mlp.w_gate"], p[pre + "mlp.w_up"],
                            p[pre + "mlp.w_down"], r),)
        y, *route = _experts(p, pre + "moe.", u.reshape(B * L, h), bias, cfg,
                             index, r)
        return (y.reshape(B, L, h), *route)

    X, route, err_f = _hyper_connected(p, pre + "hc_ffn.", X, cfg, ffn)
    return X, (route or None), jnp.maximum(err_a, err_f)


def bias_names(cfg: Mapping[str, Any]):
    """The router-bias buffers, in the order of the expert layers."""
    return [f"blocks.{i}.moe.e_score_correction_bias"
            for i in range(cfg["first_k_dense_replace"],
                           cfg["num_hidden_layers"])]


def forward(p: Mapping[str, Any], biases, ids, labels,
            cfg: Mapping[str, Any], expert_index=None, given=True,
            operand_dtype=None):
    """(loss, (router logits [expert layers, T, E], own expert index
    [.., T, k], gap [.., T]: k-th less (k+1)-th ``s + b``, counts [.., E],
    biases after the step, the largest H_res error, logits [B, L, vocab])).
    ``expert_index`` is used where ``given`` (a traced flag, so that one
    compiled function serves both uses)."""
    import jax
    import jax.numpy as jnp

    def r(a):         # an operand as the matmul sees it
        return a if operand_dtype is None else a.astype(
            operand_dtype).astype(jnp.float32)

    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert cfg["num_nextn_predict_layers"] == 0
    n = cfg["hc_mult"]
    x = p["embed"][ids]
    X = jnp.broadcast_to(x[:, :, None, :], (*x.shape[:2], n, x.shape[-1]))
    routes, errs = [], []
    for i in range(cfg["num_hidden_layers"]):
        dense = i < cfg["first_k_dense_replace"]
        at = len(routes)
        index = None if expert_index is None or dense \
            else (given, expert_index[at])
        bias = None if dense else biases[at]
        X, route, err = jax.checkpoint(
            lambda p, X, bias, index, pre=f"blocks.{i}.": _block(
                p, pre, X, bias, cfg, index, r))(p, X, bias, index)
        errs.append(err)
        if route is not None:
            routes.append(route)
    trunk = _rms_norm(jnp.sum(X, axis=2), p["norm_f.weight"],
                      cfg["rms_norm_eps"])
    logits = r(trunk) @ r(p["head_w"])
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    loss = jnp.mean(-jnp.take_along_axis(logp, labels[..., None],
                                         axis=-1)[..., 0])
    z, index, gap, counts = (jnp.stack([rt[i] for rt in routes])
                             for i in range(4))
    after = [b + cfg["bias_update_rate"] * jnp.sign(jnp.mean(c) - c)
             for b, c in zip(biases, counts)]
    return loss, (z, index, gap, counts, after, jnp.max(jnp.stack(errs)),
                  logits)


_COMPILED: Dict[Any, Any] = {}
_SHAPE_KEYS = ("num_hidden_layers", "first_k_dense_replace",
               "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
               "v_head_dim", "kv_lora_rank", "num_experts_per_tok",
               "n_routed_experts", "held_first", "routed_scaling_factor",
               "rms_norm_eps", "rope_theta", "bias_update_rate", "hc_mult",
               "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
               "mhc_h_res_clamp_max")


def _key(cfg: Mapping[str, Any], operand_dtype):
    return tuple(cfg[k] for k in _SHAPE_KEYS) \
        + (tuple(sorted(cfg["rope_scaling"].items())), operand_dtype)


def _value_and_grad(cfg: Mapping[str, Any], operand_dtype=None):
    """One jitted function a configuration, whether or not the routing is
    given (``given`` is a traced flag): at full widths a compile is most
    of the reference's time."""
    import jax

    key = _key(cfg, operand_dtype)
    if key not in _COMPILED:
        def total(p, biases, ids, labels, expert_index, given):
            return forward(p, biases, ids, labels, cfg, expert_index, given,
                           operand_dtype)

        _COMPILED[key] = jax.jit(jax.value_and_grad(total, has_aux=True))
    return _COMPILED[key]


def _arguments(params, ids, labels, cfg, expert_index, buffers):
    """What the jitted function takes, and the buffers' names."""
    import jax.numpy as jnp

    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    E = cfg["router_width"]
    names = bias_names(cfg)
    biases = [jnp.asarray((buffers or {}).get(n, np.zeros(E)), jnp.float32)
              for n in names]
    given = expert_index is not None
    if not given:
        expert_index = np.zeros((len(names), np.size(ids),
                                 cfg["num_experts_per_tok"]), np.int32)
    return (p, biases, jnp.asarray(ids), jnp.asarray(labels),
            jnp.asarray(expert_index, jnp.int32), jnp.asarray(given)), names


def lowered(params: Mapping[str, Any], ids, labels, cfg: Mapping[str, Any],
            buffers: Optional[Mapping[str, Any]] = None):
    """The jitted function lowered for these arguments' shapes
    (``jax.stages.Lowered``), not compiled: at full widths the compile is a
    hundred seconds of host work and nothing of it needs this thread, so a
    caller with other programs to compile hands ``.compile()`` to a thread
    of its own and the result to ``adopt``."""
    import jax

    args, _ = _arguments(params, ids, labels, cfg, None, buffers)
    with jax.default_matmul_precision("highest"):
        return _value_and_grad(cfg).lower(*args)


def adopt(cfg: Mapping[str, Any], compiled) -> None:
    """``lowered(...).compile()``'s result in the jitted function's place:
    ``loss_and_grads`` at those shapes compiles nothing more (and at other
    shapes fails: the caller's process holds one size)."""
    _COMPILED[_key(cfg, None)] = compiled


def loss_and_grads(params: Mapping[str, Any], ids, labels,
                   cfg: Mapping[str, Any],
                   expert_index: Optional[Any] = None,
                   operand_dtype=None,
                   buffers: Optional[Mapping[str, Any]] = None
                   ) -> Dict[str, Any]:
    """``buffers`` holds the router biases under the system's names (zeros
    where absent). ``grads`` and ``logits`` stay where they were computed
    (jax arrays); ``compare`` reduces them there."""
    import jax

    args, names = _arguments(params, ids, labels, cfg, expert_index, buffers)
    biases, index, given = args[1], args[4], expert_index is not None
    with jax.default_matmul_precision("highest"):
        (loss, (z, own, gap, counts, after, err, logits)), grads = \
            _value_and_grad(cfg, operand_dtype)(*args)
    return {"loss": float(loss), "total": float(loss),
            "router_scores": 1.0 / (1.0 + np.exp(-np.asarray(z, np.float64))),
            "expert_index": np.asarray(index if given else own),
            "own_index": np.asarray(own),
            "bias": np.stack([np.asarray(b) for b in biases]),
            "gap": np.asarray(gap), "counts": np.asarray(counts),
            "bias_after": {n: np.asarray(b) for n, b in zip(names, after)},
            "hc_res_err": float(err), "logits": logits, "grads": grads}


def _harness(name: str):
    """``../harness/<name>.py`` by its path: this file is itself loaded by
    path, from places that have no ``harness`` to import."""
    spec = importlib.util.spec_from_file_location(
        "_bench_harness_" + name, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "harness", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _overlap(a: np.ndarray, b: np.ndarray):
    """(mean share of a token's experts in ``a`` that are in ``b`` too,
    [layers, T] whether the two sets are equal)."""
    both = (a[..., :, None] == b[..., None, :]).any(axis=-1)
    return float(np.mean(both)), both.all(axis=-1)


def compare_routing(got: Mapping[str, Any], ref: Mapping[str, Any],
                    mode: str) -> Dict[str, Any]:
    """The system's router (``router_scores``, ``expert_index``) against
    this reference's own choice (``own_index``: what it would choose on
    the hidden states it computed, whether or not it was GIVEN an index
    to use)."""
    tol = TOL[mode]
    out: Dict[str, Any] = {"mode": mode}
    overlap, same = _overlap(np.asarray(got["expert_index"]),
                             ref["own_index"])
    if mode == "f32":
        by_layer = np.max(np.abs(
            np.asarray(got["router_scores"], np.float64)
            - ref["router_scores"]), axis=(1, 2))
        out["score_abs"] = float(np.max(by_layer))
        out["score_abs_by_layer"] = [float(x) for x in by_layer]
        # near-ties the two resolved differently: past such a token the
        # two compute different functions (its later scores differ by
        # 5e-3: my chip run, PR 30), so scores, losses and gradients are
        # then compared with this reference GIVEN the system's index; the
        # system's experts there must still be a top-k of what this
        # reference ranks by, ``s + b`` (``harness/near_tie.py``). A
        # ``ref`` made by hand without ``bias`` is ranked by its scores
        # alone, and the result says so
        select = ref["router_scores"]
        if "bias" in ref:                   # [layers, E]
            select = select + ref["bias"][:, None, :]
        out["ranked_by"] = "s + b" if "bias" in ref else "s alone"
        out.update(_harness("near_tie").readings(
            select, ref["gap"], same, got["expert_index"], tol["gap"]))
        limits = {"score_abs": tol["score_abs"],
                  "topk_match_where_clear": 1.0,
                  "near_tie_excess": tol["gap"]}
        out["ok"] = bool(out["score_abs"] <= limits["score_abs"]
                         and out["topk_match_where_clear"] == 1.0
                         and out["near_tie_excess"]
                         <= limits["near_tie_excess"])
    else:
        out["topk_overlap"] = overlap
        out["topk_match"] = float(np.mean(same))
        limits = {"topk_overlap": tol["topk_overlap"]}
        out["ok"] = bool(overlap >= limits["topk_overlap"])
    out["tol"] = limits
    return out


def _bias_agrees(got: Mapping[str, Any], ref: Mapping[str, Any],
                 gap_tol: Optional[float]) -> Dict[str, Any]:
    """Biases after the step, equal bit for bit for every expert whose
    count is further from the mean than the layer has tokens within
    ``gap_tol`` of a tie (none when ``gap_tol`` is None: routing given)."""
    compared = wrong = 0
    for i, (name, want) in enumerate(ref["bias_after"].items()):
        c = ref["counts"][i]
        near = 0 if gap_tol is None else int(np.sum(ref["gap"][i] <= gap_tol))
        sure = np.abs(c - c.mean()) > near
        compared += int(sure.sum())
        wrong += int(np.sum(np.asarray(got["bias_after"][name])[sure]
                            != want[sure]))
    return {"experts_compared": compared, "experts_wrong": wrong,
            "tol": {"experts_wrong": 0}}


def leaf_table(got: Mapping[str, Any], ref: Mapping[str, Any]
               ) -> Dict[str, Any]:
    """{leaf: [largest |reference gradient|, largest |difference|,
    L2 of the reference gradient, L2 of the difference]}: what a limit
    is read from."""
    import jax.numpy as jnp

    out = {}
    for k, r in ref["grads"].items():
        d = jnp.asarray(got["grads"][k]) - r
        out[k] = [float(jnp.max(jnp.abs(r))), float(jnp.max(jnp.abs(d))),
                  float(jnp.sqrt(jnp.sum(r * r))),
                  float(jnp.sqrt(jnp.sum(d * d)))]
    return out


def adamw_first_step(p, g, lr, beta1, beta2, eps, weight_decay):
    """(parameter, second moment) after AdamW's first step from zero
    moments (Loshchilov & Hutter, arXiv:1711.05101, algorithm 2 with a
    constant schedule): m = (1 - beta1) g and v = (1 - beta2) g^2, each
    divided by its bias correction 1 - beta^1, and the decay decoupled
    from the gradient: p <- p - lr * (m_hat / (sqrt(v_hat) + eps)
    + weight_decay * p)."""
    import jax.numpy as jnp

    m = (1.0 - beta1) * g
    v = (1.0 - beta2) * g * g
    m_hat = m / (1.0 - beta1)
    v_hat = v / (1.0 - beta2)
    return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p), v


def compare_update(before: Mapping[str, Any], after: Mapping[str, Any],
                   m: Mapping[str, Any], v: Mapping[str, Any],
                   hyper: Mapping[str, float]) -> Dict[str, Any]:
    """The system's first AdamW step from zero moments: ``before`` ->
    ``after`` with first and second moments ``m``, ``v``. The gradient is
    read out of ``m`` (``m / (1 - beta1)``); ``after`` and ``v`` are held
    to ``adamw_first_step`` on it, leaf by leaf: the L2 norm of the
    difference — for the parameters, what of it is left beyond an ulp of
    each weight — over the L2 norm of the reference's update (of its
    second moment)."""
    import jax
    import jax.numpy as jnp

    tol = TOL["update"]

    @jax.jit
    def one(p0, p1, m, v):
        want, v_want = adamw_first_step(p0, m / (1.0 - hyper["beta1"]),
                                        **hyper)
        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
        ulp = float(np.finfo(np.float32).eps) * norm(p0)
        return (jnp.maximum(norm(p1 - want) - ulp, 0.0), norm(want - p0),
                norm(v - v_want), norm(v_want))

    worst = {"param_rel": (0.0, None), "moment_rel": (0.0, None)}
    moved = 0.0
    for k in before:
        dp, up, dv, vv = (float(x) for x in one(
            jnp.asarray(before[k], jnp.float32), after[k], m[k], v[k]))
        moved = max(moved, up)
        for key, err in (("param_rel", dp / up if up else float(dp > 0)),
                         ("moment_rel", dv / vv if vv else float(dv > 0))):
            if not err <= worst[key][0]:       # a NaN is the worst
                worst[key] = (err, k)
    out = {key: worst[key][0] for key in worst}
    out.update(worst_leaf={key: worst[key][1] for key in worst},
               leaves=len(before), largest_update_l2=moved, tol=tol,
               learning_rate=hyper["lr"])
    out["ok"] = bool(moved > 0.0 and all(out[key] <= tol[key]
                                         for key in tol))
    return out


def compare(got: Mapping[str, Any], ref: Mapping[str, Any],
            mode: str = "amp") -> Dict[str, Any]:
    """The losses ``got`` has (``loss``; ``total``, the same number: a
    train step returns that alone), the logits where it has them (largest
    absolute difference over the largest logit), every gradient leaf
    (largest absolute difference over the leaf's largest entry or
    ``GRADIENT_FLOOR[mode]``), the largest H_res error the step counted
    and the biases after the step against ``TOL[mode]``."""
    import jax.numpy as jnp

    tol = TOL[mode]
    losses = [k for k in ("loss", "total") if k in got]
    loss_rel = max((abs(got[k] - ref[k]) / abs(ref[k]) for k in losses),
                   default=float("inf"))
    # {leaf: [largest |gradient|, error over it]}, the worst first
    detail = sorted(((k, [top, err / max(top, GRADIENT_FLOOR[mode])])
                     for k, (top, err, _, _) in leaf_table(got, ref).items()
                     if top > 0.0), key=lambda kv: -kv[1][1])
    worst_leaf, (_, worst) = detail[0]
    out = {"mode": mode, **{k: [got[k], ref[k]] for k in losses},
           "loss_rel": loss_rel, "grad_leaf_rel": worst,
           "worst_leaf": worst_leaf, "leaves": len(ref["grads"]),
           "worst_leaves": dict(detail[:8]),
           "hc_abs": abs(got["hc_res_err"] - ref["hc_res_err"]),
           "hc_res_err": [got["hc_res_err"], ref["hc_res_err"]]}
    ok = (bool(losses) and all(np.isfinite(got[k]) for k in losses)
          and loss_rel <= tol["loss_rel"] and worst <= tol["grad_leaf_rel"]
          and out["hc_abs"] <= tol["hc_abs"])
    if "logits" in got:
        out["logit_rel"] = float(
            jnp.max(jnp.abs(jnp.asarray(got["logits"]) - ref["logits"]))
            / jnp.max(jnp.abs(ref["logits"])))
        ok = ok and out["logit_rel"] <= tol["logit_rel"]
    out["tol"] = tol
    if "bias_after" in got:
        out["bias"] = _bias_agrees(got, ref, tol.get("gap"))
        ok = ok and out["bias"]["experts_wrong"] == 0
    out["ok"] = bool(ok)
    return out
