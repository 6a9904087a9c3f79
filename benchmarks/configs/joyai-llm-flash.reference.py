"""Plain reference for ``joyai-llm-flash``: decoder forward, the two-part
loss, its gradients and the router-bias update — ``jax.numpy``, float32,
matmul precision ``highest``; einsum attention over the full [L, L] score
matrix with an explicit causal mask; the held experts applied to every
token and masked by the choice (no sort, no grouped matmul, no kernel, no
mixed precision, no trainer). Independent of ``paddle_tpu``.

The architecture: ``jdopensource/JoyAI-LLM-Flash`` (``model_type``
``joyai_llm_flash``), whose keys are DeepSeek-V3's (arXiv:2412.19437).
``N`` is RMSNorm (eps ``rms_norm_eps``), no bias anywhere:

    x = embed[ids]
    layer < first_k_dense_replace: h = x + MLA(N(x)); y = h + SwiGLU(N(h))
    other layers:                  h = x + MLA(N(x)); y = h + MoE(N(h))
    t = N_f(y_last);  logits = t @ head_w

``MLA(u)``: c_q = N(u W_qa); [q_nope | q_rope] = c_q W_qb a head;
[c_kv | k_rope] = u W_kva; [k_nope | v] = N(c_kv) W_kvb a head; rotary on
q_rope (each head) and on the one k_rope all heads share: adjacent pairs
(2i, 2i+1) turn by pos * theta^(-2i/64), positions 0..L-1;
softmax([q_nope | q_rope] . [k_nope | k_rope] / sqrt(192) + causal) v; W_o.
``MoE(u)``: z = u W_r; s = sigmoid(z); the ``num_experts_per_tok`` largest
of s + b (b: ``e_score_correction_bias``, a buffer, no gradient); g = s at
those experts, g / (sum g + 1e-20) * ``routed_scaling_factor``;
out = sum over the chosen experts IN THE HELD RANGE of g_i SwiGLU_i(u)
+ SwiGLU_shared(u). The held range ``(held_first, n_routed_experts)`` of
the ``router_width`` experts is an argument of the configuration: what the
absent experts would add is left out, here as in the system.
After the forward b <- b + ``bias_update_rate`` * sign(mean(c) - c), c the
assignment counts of this step over all ``router_width`` experts.
Prediction module: h'_i = [N_e(embed[ids[i+1]]) | N_h(t_i)] W_eh, one
block of the expert kind, logits'_i = N'(.) @ head_w, predicting token
i+2. Loss: CE(logits, labels) (``loss``) + ``mtp_loss_weight`` *
CE(logits'[:, :L-1], labels[:, 1:]) (``loss_mtp``); gradients of the sum.

Departures from the source, each also in the configuration's file:
- the source de-interleaves q_rope / k_rope (2i -> i, 2i+1 -> 32+i) and
  rotates halves; here adjacent pairs are rotated in place: one fixed
  permutation of the 64 channels of q and k alike, q.k unchanged;
- t, handed to the module, is the trunk's state AFTER N_f (this family's
  public implementations; the paper's figure leaves it open);
- the module's last position has no ids[i+1]: it is computed on
  embed[ids[i]] and masked in the loss (static shapes), so its token is
  in the module's expert counts;
- attention is computed a group of heads at a time and every block is
  recomputed in the backward pass (``jax.checkpoint``): memory, not
  arithmetic.

``operand_dtype``, when given, rounds both operands of every matmul but
the router's to that dtype first (float32 accumulation): this reference
"in the nearest precision below" bf16 is ``float8_e4m3fn``, the reading
that the ``amp`` tolerances must refuse.

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
#: route (held assignments sorted into a bounded buffer, grouped matmuls,
#: sums by token against every-held-expert-masked); only summation order
#: differs. ``score_abs`` is on the router's sigmoid scores (values in
#: 0.3..0.7); the top-k sets must agree wherever this reference's k-th and
#: (k+1)-th ``s + b`` differ by more than ``gap`` (3x ``score_abs``: below
#: it either order is float32 noise). Where the two resolve such a
#: near-tie differently (``near_ties_resolved_differently``) the token's
#: experts differ, which is no error of either: losses and gradients are
#: then compared with this reference GIVEN the system's index, as ``amp``
#: always is — and the system's k experts there must still be a top-k of
#: this reference's own ``s + b`` to within ``gap`` (``near_tie_excess``,
#: ``harness/near_tie.py``: the other expert of an exact tie reads 0.0,
#: one inside the tie ``gap`` at the most, any other the distance to it.
#: The limit is ``gap`` by that construction and NOT by two readings:
#: the cell's three runs with it read 0.0 with no near-tie resolved
#: differently, no control was run at its size; OLMoE's, between its
#: readings, sits a fifth under its ``gap``: PERF.md sections 6 and 7, PR
#: 43). (Where routing has collapsed, thousands of look-alike tokens sit
#: at one near-tie together: PERF.md section 6, PR 30.) The bias after
#: the step must be EQUAL for every expert whose count is further from
#: the mean than the layer has tokens inside ``gap`` (a flipped near-tie
#: moves a count by one; nothing else can).
#:
#: ``amp``: the step as measured — bf16 operands in every dense and grouped
#: matmul and in the flash kernels, float32 accumulation, float32 router,
#: norms, rotary, softmax statistics. The bf16 reasoning of
#: ``olmoe-1b-7b.reference`` with six blocks where that has one: PERF.md
#: section 6 (PR 30) has the readings the bounds sit between — the step as
#: measured below, this reference in ``float8_e4m3fn`` above. The router's
#: input has passed through bf16 attention, so the 8th / 9th of 256 scores
#: flip for many tokens: ``topk_overlap`` is the least mean share of a
#: token's k experts that are also this reference's own (a wrong router
#: reads k / 256 = 0.03), and loss and gradients are compared with this
#: reference GIVEN the system's ``expert_index``, where the bias after the
#: step must be equal outright.
#:
#: ``update``: the parameters and second moments the system's AdamW step
#: leaves, against ``adamw_first_step`` here on the SAME gradient (read out
#: of the system's first moment, and itself held to this reference by
#: ``amp``): the same float32 formula in another order of operations, so a
#: leaf differs by roundings of a weight against an update of ``lr``: an
#: ulp of each weight (1.2e-7 of it: 3e-4 of the update for a norm's
#: weights of 1.0 at lr 4e-4, 2e-6 for a matrix of 0.006) is allowed for,
#: and ``param_rel`` limits what is left. A skipped update reads 1.0, a
#: halved rate 0.5, a decay left out 0.1 on a norm's weights and
#: ``weight_decay * 0.006`` = 6e-4 on a matrix: the limit sits under that.
TOL = {
    "f32": {"loss_rel": 3e-5, "grad_leaf_rel": 1e-4, "score_abs": 1e-5,
            "gap": 3e-5},
    "amp": {"loss_rel": 3e-4, "grad_leaf_rel": 6e-2, "topk_overlap": 0.7},
    "update": {"param_rel": 1e-4, "moment_rel": 1e-5},
}

#: heads whose [L, L] scores are alive at once
_HEAD_GROUP = 8


def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary_pairs(x, theta):
    """x [B, L, H, D]; pair (2i, 2i+1) rotated by pos * theta^(-2i/D)."""
    import jax.numpy as jnp

    L, D = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(D // 2, dtype=np.float64) * 2.0 / D)
    ang = np.arange(L, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
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
    u = r(u)
    c_q = _rms_norm(u @ r(p[pre + "w_qa"]), p[pre + "q_norm.weight"], eps)
    q = (r(c_q) @ r(p[pre + "w_qb"])).reshape(B, L, H, nope + rope)
    kva = u @ r(p[pre + "w_kva"])
    c_kv = _rms_norm(kva[..., :rank], p[pre + "kv_norm.weight"], eps)
    kv = (r(c_kv) @ r(p[pre + "w_kvb"])).reshape(B, L, H, nope + dv)
    q = jnp.concatenate([q[..., :nope],
                         _rotary_pairs(q[..., nope:], theta)], axis=-1)
    k_rope = _rotary_pairs(kva[..., None, rank:], theta)     # one head
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_rope, (B, L, H, rope))], axis=-1)
    v = kv[..., nope:]
    causal = jnp.tril(jnp.ones((L, L), bool))

    def group(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) / math.sqrt(nope + rope)
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


def _block(p, pre, x, bias, cfg, index, r):
    eps = cfg["rms_norm_eps"]
    B, L, h = x.shape
    x = x + _attention(p, pre + "attn.",
                       _rms_norm(x, p[pre + "norm1.weight"], eps), cfg, r)
    u = _rms_norm(x, p[pre + "norm2.weight"], eps)
    if bias is None:
        return x + _swiglu(u, p[pre + "mlp.w_gate"], p[pre + "mlp.w_up"],
                           p[pre + "mlp.w_down"], r), None
    y, *route = _experts(p, pre + "moe.", u.reshape(B * L, h), bias, cfg,
                         index, r)
    return x + y.reshape(B, L, h), route


def bias_names(cfg: Mapping[str, Any]):
    """The router-bias buffers, in the order of the expert layers (the
    prediction module's last)."""
    return [f"blocks.{i}.moe.e_score_correction_bias"
            for i in range(cfg["first_k_dense_replace"],
                           cfg["num_hidden_layers"])] \
        + ["mtp.block.moe.e_score_correction_bias"]


def forward(p: Mapping[str, Any], biases, ids, labels,
            cfg: Mapping[str, Any], expert_index=None, given=True,
            operand_dtype=None):
    """(total loss, (main loss, module loss, router logits
    [expert layers, T, E], own expert index [.., T, k], gap [.., T]: k-th
    less (k+1)-th ``s + b``, counts [.., E], biases after the step)).
    ``expert_index`` is used where ``given`` (a traced flag, so that one
    compiled function serves both uses)."""
    import jax
    import jax.numpy as jnp

    def r(a):         # an operand as the matmul sees it
        return a if operand_dtype is None else a.astype(
            operand_dtype).astype(jnp.float32)

    assert cfg["n_group"] == 1 and cfg["topk_group"] == 1
    assert cfg["num_nextn_predict_layers"] == 1
    eps = cfg["rms_norm_eps"]
    x = p["embed"][ids]
    nxt = jnp.concatenate([x[:, 1:], x[:, -1:]], axis=1)
    routes = []

    def run(pre, x, dense):
        n = len(routes)
        index = None if expert_index is None else (given, expert_index[n])
        bias = None if dense else biases[n]
        x, route = jax.checkpoint(
            lambda p, x, bias, index: _block(p, pre, x, bias, cfg, index, r)
        )(p, x, bias, index)
        if route is not None:
            routes.append(route)
        return x

    for i in range(cfg["num_hidden_layers"]):
        x = run(f"blocks.{i}.", x, i < cfg["first_k_dense_replace"])
    trunk = _rms_norm(x, p["norm_f.weight"], eps)
    y = r(jnp.concatenate(
        [_rms_norm(nxt, p["mtp.norm_e.weight"], eps),
         _rms_norm(trunk, p["mtp.norm_h.weight"], eps)], axis=-1)
    ) @ r(p["mtp.w_eh"])
    y = _rms_norm(run("mtp.block.", y, False), p["mtp.norm_f.weight"], eps)

    def nll(hidden, labels):
        logits = r(hidden) @ r(p["head_w"])
        logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                    keepdims=True)
        return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]

    main = jnp.mean(nll(trunk, labels))
    mtp = jnp.mean(nll(y[:, :-1], labels[:, 1:]))
    z, index, gap, counts = (jnp.stack([rt[i] for rt in routes])
                             for i in range(4))
    after = [b + cfg["bias_update_rate"] * jnp.sign(jnp.mean(c) - c)
             for b, c in zip(biases, counts)]
    return main + cfg["mtp_loss_weight"] * mtp, (main, mtp, z, index, gap,
                                                 counts, after)


_COMPILED: Dict[Any, Any] = {}
_SHAPE_KEYS = ("num_hidden_layers", "first_k_dense_replace",
               "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
               "v_head_dim", "kv_lora_rank", "num_experts_per_tok",
               "n_routed_experts", "held_first", "routed_scaling_factor",
               "rms_norm_eps", "rope_theta", "mtp_loss_weight",
               "bias_update_rate")


def _value_and_grad(cfg: Mapping[str, Any], operand_dtype=None):
    """One jitted function a configuration, whether or not the routing is
    given (``given`` is a traced flag): at full widths a compile is most
    of the reference's time."""
    import jax

    key = tuple(cfg[k] for k in _SHAPE_KEYS) + (operand_dtype,)
    if key not in _COMPILED:
        def total(p, biases, ids, labels, expert_index, given):
            return forward(p, biases, ids, labels, cfg, expert_index, given,
                           operand_dtype)

        _COMPILED[key] = jax.jit(jax.value_and_grad(total, has_aux=True))
    return _COMPILED[key]


def loss_and_grads(params: Mapping[str, Any], ids, labels,
                   cfg: Mapping[str, Any],
                   expert_index: Optional[Any] = None,
                   operand_dtype=None,
                   buffers: Optional[Mapping[str, Any]] = None
                   ) -> Dict[str, Any]:
    """``buffers`` holds the router biases under the system's names (zeros
    where absent). ``grads`` stay where they were computed (jax arrays);
    ``compare`` reduces them there."""
    import jax
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
    index = jnp.asarray(expert_index, jnp.int32)
    with jax.default_matmul_precision("highest"):
        (total, (main, mtp, z, own, gap, counts, after)), grads = \
            _value_and_grad(cfg, operand_dtype)(
                p, biases, jnp.asarray(ids), jnp.asarray(labels), index,
                jnp.asarray(given))
    return {"loss": float(main), "loss_mtp": float(mtp),
            "total": float(total),
            "router_scores": 1.0 / (1.0 + np.exp(-np.asarray(z, np.float64))),
            "expert_index": np.asarray(index if given else own),
            "own_index": np.asarray(own),
            "bias": np.stack([np.asarray(b) for b in biases]),
            "gap": np.asarray(gap), "counts": np.asarray(counts),
            "bias_after": {n: np.asarray(b) for n, b in zip(names, after)},
            "grads": grads}


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
        # ``ref`` made by hand without ``bias`` (tests/test_joyai.py) is
        # ranked by its scores alone, and the result says so
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
    """The losses ``got`` has (``loss``, ``loss_mtp``, their weighted sum
    ``total``: a train step returns that alone), every gradient leaf
    (largest absolute difference over the leaf's largest entry) and the
    biases after the step against ``TOL[mode]``."""
    tol = TOL[mode]
    losses = [k for k in ("loss", "loss_mtp", "total") if k in got]
    loss_rel = max((abs(got[k] - ref[k]) / abs(ref[k]) for k in losses),
                   default=float("inf"))
    # {leaf: [largest |gradient|, error over it]}, the worst first
    detail = sorted(((k, [top, err / top]) for k, (top, err, _, _)
                     in leaf_table(got, ref).items() if top > 0.0),
                    key=lambda kv: -kv[1][1])
    worst_leaf, (_, worst) = detail[0]
    out = {"mode": mode, **{k: [got[k], ref[k]] for k in losses},
           "loss_rel": loss_rel, "grad_leaf_rel": worst,
           "worst_leaf": worst_leaf, "leaves": len(ref["grads"]), "tol": tol,
           "worst_leaves": dict(detail[:8])}
    ok = (bool(losses) and all(np.isfinite(got[k]) for k in losses)
          and loss_rel <= tol["loss_rel"] and worst <= tol["grad_leaf_rel"])
    if "bias_after" in got:
        out["bias"] = _bias_agrees(got, ref, tol.get("gap"))
        ok = ok and out["bias"]["experts_wrong"] == 0
    out["ok"] = bool(ok)
    return out
