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
near-ties, is held to the same function. With ``within_gap`` it fixes
them only at the (layer, token)s whose own k-th and (k+1)-th
probabilities lie no further apart than that: how the float32 step, which
may take either expert of a tie, is held to the same function and to
this reference's own choice everywhere else.
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
#: route (sorted rows and grouped matmuls against all-experts-masked);
#: only summation order differs. ``logits_abs`` is on the router logits
#: (values of order 1); the top-k sets must agree wherever this
#: reference's k-th and (k+1)-th probabilities differ by more than
#: ``gap``, below which either order is float32 noise. At the other
#: tokens (``gap`` or nearer: not clear) loss and gradients are compared
#: with this reference taking the system's experts and its own at every
#: clear one (``within_gap``) — a token whose 8th and 9th probabilities
#: are EQUAL put every leaf 0.1-1.3% off in a correct step (PERF.md
#: section 6, PRs 41 and 43) — and the system may differ from this
#: reference only INSIDE the tie: ``near_tie_excess`` (``harness/
#: near_tie.py``: the largest probability outside the system's set less
#: the least inside it; the other expert of an exact tie reads 0.0, any
#: other expert the distance to it) has a limit of its own, set between
#: its two readings on the chip: sound steps read 0.0 in 76 runs of 79 and
#: 4.1e-8, 6.7e-8, 1.5e-7 in three (a 9th probability that near under the
#: 8th, taken); the control (``tests/near_tie_control.py``: at one
#: not-clear token the reference's 10th expert in place of its 8th, the
#: nearest wrong one there is) 9.3e-6, 3.6e-5, 2.1e-4 at three seeds
#: -> 2e-6, 13x over the largest sound reading and 4.6x under the
#: control's least (``gap`` itself, 1e-5, would let the 9.3e-6 through).
#: ``clear_tokens_share`` is a floor on the share of (layer, token)s that
#: are clear, so that the comparison cannot go empty: the cell reads
#: 0.9873-0.9983 over 22 trained states on the chip (PR 43;
#: 0.9919-0.9976 in the 8 on record before), 0.984-1.0 at the
#: rehearsal's 128 token-layers, a router whose logits are all equal 0.0
#: (PERF.md section 6, PR 43); 0.95 allows four times the share of
#: near-ties the worst run had.
#:
#: ``amp``: the step as measured — bf16 operands in every dense and grouped
#: matmul and in the flash kernel, float32 accumulation, float32 router.
#: Same bf16 reasoning as ``ernie-1.0-base.reference`` (flash kernel alone
#: 7.7e-3 a leaf, PR 21; whole ERNIE step 1.8e-5 on the loss and 1.4e-2 on
#: the worst leaf, PR 23), and its ``loss_rel``; a step in fp8 (3-4
#: mantissa bits against bf16's 8) has ~16x the error and fails both.
#: ``grad_leaf_rel`` (the widest entry of a leaf over the leaf's largest)
#: had ERNIE's 4e-2 until PR 43, and has a tail here that ERNIE's has
#: not, all of it ``embed``: over this cell's 97 sound runs on record the
#: median is 0.011, the four largest 0.025, 0.028, 0.035 and 0.084 (seed
#: 4300001014) — ONE rare token's row 8% (44% at seed 4300000812) off
#: while every other row agrees to 0.4-1.6%. That is bf16's rounding and
#: no kernel's fault: the program's bf16 step with EINSUM attention reads
#: the same row 8.2% (44.8%) off and the leaf 0.0843, and this reference
#: itself with its operands rounded to bf16 — no program, no kernel —
#: 7.8% (44.8%) and 0.0766, the three agreeing with one another to
#: 0.4-1.1% of the row (my chip run, PR 43's review round; PERF.md
#: section 6). So 4e-2 refused a sound step one run in a hundred, and
#: the limit lies between the sound steps' largest, 0.084, and this
#: reference in ``float8_e4m3fn``, 1.13 to 8.17 (five states, PRs 26 and
#: 43): 0.3, 3.6x over the one and 3.8x under the other. In front of it
#: ``grad_leaf_l2`` (PR 43), the norm of a leaf's difference over the
#: leaf's norm, which one entry cannot move: 0.0054-0.0179 over 16
#: trained states on the chip (``attn.wq`` every time), fp8 3.15, 4.91,
#: 6.31 -> 6e-2 (PERF.md section 6, PR 43). The router's input has passed
#: through bf16 attention, so its logits carry ~0.4% error and the 8th/9th
#: choice flips for some tokens: ``topk_match`` is the least share of
#: tokens whose top-k set equals this reference's own (a wrong router
#: reads near 0), and the loss and gradients are compared with this
#: reference given the system's ``expert_index``.
TOL = {
    "f32": {"loss_rel": 3e-5, "grad_leaf_rel": 1e-4, "logits_abs": 1e-4,
            "gap": 1e-5, "near_tie_excess": 2e-6,
            "clear_tokens_share": 0.95},
    "amp": {"loss_rel": 2e-4, "grad_leaf_rel": 3e-1, "grad_leaf_l2": 6e-2,
            "topk_match": 0.8},
}


def _harness(name: str):
    """``../harness/<name>.py`` by its path: this file is itself loaded by
    path, from places that have no ``harness`` to import."""
    spec = importlib.util.spec_from_file_location(
        "_bench_harness_" + name, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "harness", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
            expert_index=None, within=np.inf, operand_dtype=None):
    """(task loss, (aux loss, router logits [layers, T, E], probabilities
    [layers, T, E], expert index used [layers, T, k], own expert index
    [layers, T, k], gap [layers, T]: own k-th less (k+1)-th probability)).
    A token takes its row of ``expert_index`` where its ``gap <= within``
    (a traced number, so that one compiled function serves every use:
    infinity gives every token its row, a negative number none)."""
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
    routes = []
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
        gap = top[:, k - 1] - top[:, k]
        index = own_index[:, :k]
        if expert_index is not None:
            index = jnp.where((gap <= within)[:, None], expert_index[i],
                              index)
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
        routes.append((z, prob, index, own_index[:, :k], gap))
    x = _rms_norm(x, p["norm_f.weight"], eps)
    logits = r(x) @ r(p["head_w"])
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    aux = (cfg["router_aux_loss_coef"] * lb
           + cfg["router_z_loss_coef"] * z_loss)
    return -jnp.mean(picked), (aux, *(jnp.stack(part)
                                     for part in zip(*routes)))


_SHAPE_KEYS = ("num_hidden_layers", "num_attention_heads",
               "num_experts_per_tok", "rms_norm_eps", "rope_theta",
               "router_aux_loss_coef", "router_z_loss_coef")
_COMPILED: Dict[Any, Any] = {}


def _value_and_grad(cfg: Mapping[str, Any], operand_dtype=None):
    """One jitted function a configuration, wherever the routing is given:
    at full widths a compile is most of the reference's time."""
    import jax

    key = tuple(cfg[k] for k in _SHAPE_KEYS) + (operand_dtype,)
    if key not in _COMPILED:
        def total(p, ids, labels, expert_index, within):
            loss, (aux, *rest) = forward(p, ids, labels, cfg, expert_index,
                                         within, operand_dtype)
            return loss + aux, (loss, aux, *rest)

        _COMPILED[key] = jax.jit(jax.value_and_grad(total, has_aux=True))
    return _COMPILED[key]


def loss_and_grads(params: Mapping[str, Any], ids, labels,
                   cfg: Mapping[str, Any],
                   expert_index: Optional[Any] = None,
                   operand_dtype=None,
                   within_gap: Optional[float] = None) -> Dict[str, Any]:
    """``expert_index`` is used at every token, or with ``within_gap`` at
    those whose own ``gap`` is no larger. ``expert_index`` in the result is
    what was used, ``own_index`` this reference's own choice on the hidden
    states it computed. ``grads`` stay where they were computed (jax
    arrays: 2.3 GiB at full widths); ``compare`` reduces them there."""
    import jax
    import jax.numpy as jnp

    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    if expert_index is None:
        within = -1.0                   # no gap is negative: its own choice
        expert_index = np.zeros((cfg["num_hidden_layers"], np.size(ids),
                                 cfg["num_experts_per_tok"]), np.int32)
    else:
        within = np.inf if within_gap is None else within_gap
    with jax.default_matmul_precision("highest"):
        (_, (loss, aux, logits, probs, index, own, gap)), grads = \
            _value_and_grad(cfg, operand_dtype)(
                p, jnp.asarray(ids), jnp.asarray(labels),
                jnp.asarray(expert_index, jnp.int32),
                jnp.asarray(within, jnp.float32))
    return {"loss": float(loss), "aux": float(aux),
            "router_logits": np.asarray(logits),
            "router_probs": np.asarray(probs),
            "expert_index": np.asarray(index),
            "own_index": np.asarray(own), "gap": np.asarray(gap),
            "grads": grads}


def _same_set(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """[layers, T]: whether the two sets of experts are equal."""
    return np.all(np.sort(a, axis=-1) == np.sort(b, axis=-1), axis=-1)


def compare_routing(got: Mapping[str, Any], ref: Mapping[str, Any],
                    mode: str) -> Dict[str, Any]:
    """The system's router (``router_logits``, ``expert_index``) against
    this reference's own choice (``own_index``, ``router_probs``, ``gap``:
    what it chooses on the hidden states it computed, whatever index it
    was given to use). ``tol`` in the result holds the limit of every
    reading compared, under the reading's name."""
    tol = TOL[mode]
    out: Dict[str, Any] = {"mode": mode}
    index = np.asarray(got["expert_index"])
    same = _same_set(index, ref["own_index"])
    if mode == "f32":
        out["logits_abs"] = float(np.max(np.abs(
            np.asarray(got["router_logits"], np.float64)
            - ref["router_logits"])))
        # not clear and resolved differently: the system's experts must
        # still be a top-k of this reference's own probabilities
        out.update(_harness("near_tie").readings(
            ref["router_probs"], ref["gap"], same, index, tol["gap"]))
        limits = {"logits_abs": tol["logits_abs"],
                  "clear_tokens_share": tol["clear_tokens_share"],
                  "topk_match_where_clear": 1.0,
                  "near_tie_excess": tol["near_tie_excess"]}
        out["ok"] = bool(
            out["logits_abs"] <= limits["logits_abs"]
            and out["clear_tokens_share"] >= limits["clear_tokens_share"]
            and out["topk_match_where_clear"] == 1.0
            and out["near_tie_excess"] <= limits["near_tie_excess"])
    else:
        out["topk_match"] = float(np.mean(same))
        limits = {"topk_match": tol["topk_match"]}
        out["ok"] = bool(out["topk_match"] >= limits["topk_match"])
    out["tol"] = limits
    return out


def compare(got: Mapping[str, Any], ref: Mapping[str, Any],
            mode: str = "amp") -> Dict[str, Any]:
    """Task loss and every gradient leaf — ``grad_leaf_rel``: the largest
    absolute difference over the leaf's largest entry; ``grad_leaf_l2``:
    the norm of the difference over the leaf's norm — against
    ``TOL[mode]``, the worst leaf of each."""
    import jax.numpy as jnp

    tol = TOL[mode]
    loss_rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    worst = {"grad_leaf_rel": (0.0, None), "grad_leaf_l2": (0.0, None)}
    for k, r in ref["grads"].items():
        top = float(jnp.max(jnp.abs(r)))
        if top == 0.0:
            continue
        d = jnp.asarray(got["grads"][k]) - r
        for name, e in (
                ("grad_leaf_rel", float(jnp.max(jnp.abs(d))) / top),
                ("grad_leaf_l2", float(jnp.linalg.norm(d.ravel())
                                       / jnp.linalg.norm(r.ravel())))):
            if e > worst[name][0]:
                worst[name] = (e, k)
    out = {"mode": mode, "loss": [got["loss"], ref["loss"]],
           "loss_rel": loss_rel,
           **{name: e for name, (e, _) in worst.items()},
           "worst_leaf": worst["grad_leaf_rel"][1],
           "worst_leaf_l2": worst["grad_leaf_l2"][1],
           "leaves": len(ref["grads"]),
           "tol": {k: tol[k] for k in ("loss_rel", "grad_leaf_rel",
                                       "grad_leaf_l2") if k in tol}}
    out["ok"] = bool(np.isfinite(got["loss"])
                     and all(out[k] <= v for k, v in out["tol"].items()))
    return out
