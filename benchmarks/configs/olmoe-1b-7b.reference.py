"""Plain reference for ``olmoe-1b-7b``: decoder forward, next-token
cross-entropy, the two router losses and gradients — ``jax.numpy``,
float32, matmul precision ``highest``; every expert applied to every token
and masked by the top-k choice (no sort, no grouped matmul), einsum
attention over the full [L, L] score matrix with an explicit causal mask;
no kernel, no mixed precision, no trainer. Independent of ``paddle_tpu``.

The architecture, from the source's ``modeling_olmoe`` (arXiv:2409.02060):

    x = embed[ids]
    per layer:  h = x + Attn(RMSNorm_1(x));   y = h + MoE(RMSNorm_2(h))
    logits = RMSNorm_f(y) @ head_w
    RMSNorm(v) = v * rsqrt(mean(v^2) + eps) * g

``Attn(u)``: q = u Wq, k = u Wk, v = u Wv; q and k RMS-normalised over the
whole projection, then split into heads; rotary positions (rotate-half,
positions 0..L-1) on q and k; softmax(q k^T / sqrt(head_dim) + causal) v; Wo.
``MoE(u)``: z = u Wr, p = softmax(z); the ``num_experts_per_tok`` largest p
a token, weights those p as they are (``norm_topk_prob`` false);
out = sum_j p_j * W_down,j(silu(W_gate,j u) * W_up,j u). No bias anywhere.

Loss: mean next-token cross-entropy (``loss``) + ``router_aux_loss_coef`` *
sum_layers LB + ``router_z_loss_coef`` * sum_layers Z (``aux``), with
LB = E * sum_e f_e * P_e (f_e: assignments to expert e over the T tokens,
summing to k; P_e: mean of p_e over tokens) and Z = mean_t logsumexp(z_t)^2.
The gradients are those of ``loss + aux``.

``operand_dtype``, when given, rounds both operands of every matmul but the
router's to that dtype first (float32 accumulation): this reference "in
the nearest precision below" bf16 is ``float8_e4m3fn``, the reading that
the ``amp`` tolerances must refuse.

``expert_index`` [layers, T, k], when given, fixes which experts every
token uses (the weights are still this reference's own p at those
experts): that is how a step in lower precision, whose router flips
near-ties, is held to the same function.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Optional

import numpy as np

#: Tolerances, with their reasons.
#:
#: ``f32``: the system's step with ``amp`` off, einsum attention and matmul
#: precision ``highest`` computes the same float32 function by another
#: route (sorted rows and grouped matmuls against all-experts-masked);
#: only summation order differs. ``logits_abs`` is on the router logits
#: (values of order 1); the top-k sets must agree wherever this
#: reference's k-th and (k+1)-th probabilities differ by more than
#: ``gap``, below which either order is float32 noise.
#:
#: ``amp``: the step as measured — bf16 operands in every dense and grouped
#: matmul and in the flash kernel, float32 accumulation, float32 router.
#: Same bf16 reasoning and the same bounds as ``ernie-1.0-base.reference``
#: (flash kernel alone 7.7e-3 a leaf, PR 21; whole ERNIE step 1.8e-5 on the
#: loss and 1.4e-2 on the worst leaf, PR 23; the bounds leave 10x and 3x);
#: a step in fp8 (3-4 mantissa bits against bf16's 8) has ~16x the error
#: and fails both. The router's input has passed through bf16 attention, so
#: its logits carry ~0.4% error and the 8th/9th choice flips for some
#: tokens: ``topk_match`` is the least share of tokens whose top-k set
#: equals this reference's own (a wrong router reads near 0), and the loss
#: and gradients are compared with this reference given the system's
#: ``expert_index``.
TOL = {
    "f32": {"loss_rel": 3e-5, "grad_leaf_rel": 1e-4, "logits_abs": 1e-4,
            "gap": 1e-5},
    "amp": {"loss_rel": 2e-4, "grad_leaf_rel": 4e-2, "topk_match": 0.8},
}


def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary(x, theta):
    """x [B, L, H, D]; pair (i, i + D/2) rotated by pos * theta^(-2i/D)."""
    import jax.numpy as jnp

    L, D = x.shape[1], x.shape[-1]
    half = D // 2
    freq = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2.0 / D)
    ang = np.arange(L, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def forward(p: Mapping[str, Any], ids, labels, cfg: Mapping[str, Any],
            expert_index=None, given=True, operand_dtype=None):
    """(task loss, (aux loss, router logits [layers, T, E], expert index
    [layers, T, k], gap [layers, T]: k-th less (k+1)-th probability)).
    ``expert_index`` is used where ``given`` (a traced flag, so that one
    compiled function serves both uses)."""
    import jax
    import jax.numpy as jnp

    def r(a):         # an operand as the matmul sees it
        return a if operand_dtype is None else a.astype(
            operand_dtype).astype(jnp.float32)

    heads = cfg["num_attention_heads"]
    k = cfg["num_experts_per_tok"]
    eps = cfg["rms_norm_eps"]
    B, L = ids.shape
    x = p["embed"][ids]
    h = x.shape[-1]
    D = h // heads
    causal = jnp.tril(jnp.ones((L, L), bool))
    lb = z_loss = 0.0
    all_logits, all_index, all_gap = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        q_ = f"blocks.{i}."
        u = _rms_norm(x, p[q_ + "norm1.weight"], eps)
        u = r(u)
        q = _rms_norm(u @ r(p[q_ + "attn.wq"]), p[q_ + "attn.q_norm.weight"],
                      eps)
        kk = _rms_norm(u @ r(p[q_ + "attn.wk"]), p[q_ + "attn.k_norm.weight"],
                       eps)
        v = (u @ r(p[q_ + "attn.wv"])).reshape(B, L, heads, D)
        q = _rotary(q.reshape(B, L, heads, D), cfg["rope_theta"])
        kk = _rotary(kk.reshape(B, L, heads, D), cfg["rope_theta"])
        s = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(kk)) / math.sqrt(D)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", r(a), r(v)).reshape(B, L, h)
        x = x + r(o) @ r(p[q_ + "attn.wo"])

        u = _rms_norm(x, p[q_ + "norm2.weight"], eps).reshape(B * L, h)
        T = B * L
        z = u @ p[q_ + "moe.router_w"]                       # [T, E]
        E = z.shape[-1]
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        prob = jnp.exp(z - lse[:, None])
        top, own_index = jax.lax.top_k(jax.lax.stop_gradient(prob), k + 1)
        index = own_index[:, :k]
        if expert_index is not None:
            index = jnp.where(given, expert_index[i], index)
        mask = jnp.sum(jax.nn.one_hot(index, E, dtype=jnp.float32), axis=1)
        # every expert on every token, then the mask times the probability
        gate = jnp.einsum("td,edf->tef", r(u), r(p[q_ + "moe.w_gate"]))
        up = jnp.einsum("td,edf->tef", r(u), r(p[q_ + "moe.w_up"]))
        act = r(jax.nn.silu(gate) * up) * (mask * prob)[:, :, None]
        y = jnp.einsum("tef,efd->td", act, r(p[q_ + "moe.w_down"]))
        x = x + y.reshape(B, L, h)
        lb = lb + E * jnp.sum(jnp.sum(mask, axis=0) / T
                              * jnp.mean(prob, axis=0))
        z_loss = z_loss + jnp.mean(lse ** 2)
        all_logits.append(z)
        all_index.append(index)
        all_gap.append(top[:, k - 1] - top[:, k])
    x = _rms_norm(x, p["norm_f.weight"], eps)
    logits = r(x) @ r(p["head_w"])
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    aux = (cfg["router_aux_loss_coef"] * lb
           + cfg["router_z_loss_coef"] * z_loss)
    return -jnp.mean(picked), (aux, jnp.stack(all_logits),
                               jnp.stack(all_index), jnp.stack(all_gap))


_SHAPE_KEYS = ("num_hidden_layers", "num_attention_heads",
               "num_experts_per_tok", "rms_norm_eps", "rope_theta",
               "router_aux_loss_coef", "router_z_loss_coef")
_COMPILED: Dict[Any, Any] = {}


def _value_and_grad(cfg: Mapping[str, Any], operand_dtype=None):
    """One jitted function a configuration, whether or not the routing is
    given: at full widths a compile is most of the reference's time."""
    import jax

    key = tuple(cfg[k] for k in _SHAPE_KEYS) + (operand_dtype,)
    if key not in _COMPILED:
        def total(p, ids, labels, expert_index, given):
            loss, (aux, *rest) = forward(p, ids, labels, cfg, expert_index,
                                         given, operand_dtype)
            return loss + aux, (loss, aux, *rest)

        _COMPILED[key] = jax.jit(jax.value_and_grad(total, has_aux=True))
    return _COMPILED[key]


def loss_and_grads(params: Mapping[str, Any], ids, labels,
                   cfg: Mapping[str, Any],
                   expert_index: Optional[Any] = None,
                   operand_dtype=None) -> Dict[str, Any]:
    """``grads`` stay where they were computed (jax arrays: 2.3 GiB at
    full widths); ``compare`` reduces them there."""
    import jax
    import jax.numpy as jnp

    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    given = expert_index is not None
    if not given:
        expert_index = np.zeros((cfg["num_hidden_layers"], np.size(ids),
                                 cfg["num_experts_per_tok"]), np.int32)
    with jax.default_matmul_precision("highest"):
        (_, (loss, aux, logits, index, gap)), grads = _value_and_grad(
            cfg, operand_dtype)(
            p, jnp.asarray(ids), jnp.asarray(labels),
            jnp.asarray(expert_index, jnp.int32), jnp.asarray(given))
    return {"loss": float(loss), "aux": float(aux),
            "router_logits": np.asarray(logits),
            "expert_index": np.asarray(index), "gap": np.asarray(gap),
            "grads": grads}


def _same_set_share(a: np.ndarray, b: np.ndarray, where=None) -> float:
    """Share of (layer, token) pairs whose sets of experts are equal."""
    same = np.all(np.sort(a, axis=-1) == np.sort(b, axis=-1), axis=-1)
    if where is not None:
        same = same[where]
    return float(np.mean(same)) if same.size else 1.0


def compare_routing(got: Mapping[str, Any], ref: Mapping[str, Any],
                    mode: str) -> Dict[str, Any]:
    """The system's router (``router_logits``, ``expert_index``) against
    this reference's own choice (``ref`` computed WITHOUT
    ``expert_index``)."""
    tol = TOL[mode]
    out: Dict[str, Any] = {"mode": mode}
    if mode == "f32":
        out["logits_abs"] = float(np.max(np.abs(
            np.asarray(got["router_logits"], np.float64)
            - ref["router_logits"])))
        clear = ref["gap"] > tol["gap"]
        out["clear_tokens_share"] = float(np.mean(clear))
        out["topk_match_where_clear"] = _same_set_share(
            np.asarray(got["expert_index"]), ref["expert_index"], clear)
        out["ok"] = bool(out["logits_abs"] <= tol["logits_abs"]
                         and out["topk_match_where_clear"] == 1.0)
    else:
        out["topk_match"] = _same_set_share(np.asarray(got["expert_index"]),
                                            ref["expert_index"])
        out["ok"] = bool(out["topk_match"] >= tol["topk_match"])
    out["tol"] = tol
    return out


def compare(got: Mapping[str, Any], ref: Mapping[str, Any],
            mode: str = "amp") -> Dict[str, Any]:
    """Task loss and every gradient leaf (largest absolute difference over
    the leaf's largest entry) against ``TOL[mode]``."""
    import jax.numpy as jnp

    tol = TOL[mode]
    loss_rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    worst, worst_leaf = 0.0, None
    for k, r in ref["grads"].items():
        top = float(jnp.max(jnp.abs(r)))
        if top == 0.0:
            continue
        e = float(jnp.max(jnp.abs(jnp.asarray(got["grads"][k]) - r))) / top
        if e > worst:
            worst, worst_leaf = e, k
    ok = (np.isfinite(got["loss"]) and loss_rel <= tol["loss_rel"]
          and worst <= tol["grad_leaf_rel"])
    return {"ok": bool(ok), "mode": mode, "loss": [got["loss"], ref["loss"]],
            "loss_rel": loss_rel, "grad_leaf_rel": worst,
            "worst_leaf": worst_leaf, "leaves": len(ref["grads"]),
            "tol": tol}
