"""Plain reference for ``lfm2-8b-a1b``: decoder forward, the loss, its
gradients and the router-bias update — ``jax.numpy``, float32, matmul
precision ``highest``; the convolution as an explicit sum over three
shifted copies; einsum attention over the full [L, L] score matrix with
repeated key-value heads and an explicit causal mask; the expert layer as
a loop over the held experts, each on every token, masked by the choice
(no sort, no buffer, no grouped matmul, no kernel, no mixed precision, no
trainer). Independent of ``paddle_tpu``. The loop is a ``lax.scan`` whose
body is rebuilt in the backward pass: written out eight times a layer, the
program took twice as long to compile (160 s against 71 for a described
v5e, 479 MiB of code against 156; CPU, PR 33) for the same arithmetic.

The architecture: ``LiquidAI/LFM2-8B-A1B`` (``model_type`` ``lfm2_moe``).
``x`` is the residual stream [B, L, 2048]; ``N`` is RMSNorm (eps
``norm_eps``) with a learned weight; no bias anywhere:

    x = embed[ids]
    block i:  h = x + Op_i(N_op(x));  y = h + FFN_i(N_ffn(h))
    logits = N_f(y_last) @ embed^T                       (tied head)

``Op_i`` by ``layer_types[first_layer + i]`` (``layer_kinds``: the
configuration runs ``num_hidden_layers`` consecutive published layers).
``conv``: [B^ | C^ | x~] = split_3(u W_in), W_in 2048 x 6144; z = B^ * x~;
c_t = sum_{j=0..2} w[:, j] * z_{t-2+j}, z zero before the sequence's first
position (depthwise, one ``conv_L_cache``-tap filter a channel, causal;
each row of the batch is one sequence, so no tap crosses a sequence);
Op(u) = (C^ * c) W_out. No activation: the two gates are the
non-linearity.
``full_attention``: q = u W_q (32 heads x 64), k = u W_k, v = u W_v (8
heads x 64); RMSNorm over the 64 of each head of q and of k (one learned
weight of 64 each); rotary theta ``rope_theta``, half-split convention
(channel i pairs with i + 32, both turn by pos * theta^(-2i/64)), positions
0..L-1; key-value head j serves query heads 4j..4j+3; causal softmax at
scale 1/8; W_o.
``FFN_i``: SwiGLU of width ``intermediate_size`` for i <
``num_dense_layers``; else z = u W_r; s = sigmoid(z) over all
``router_width`` experts; the ``num_experts_per_tok`` largest of s + b (b:
``expert_bias``, a buffer, no gradient: it moves the choice only); g = s at
those experts, g / (sum g + 1e-20) * ``routed_scaling_factor``; out = sum
over the chosen experts IN THE HELD RANGE of g_i W_2(silu(W_1 u) * W_3 u).
The held range ``(held_first, num_experts)`` of the ``router_width``
experts is an argument of the configuration: what the absent experts would
add is left out, here as in the system. No shared expert.
After the forward b <- b + ``bias_update_rate`` * sign(mean(c) - c), c the
assignment counts of this step over all ``router_width`` experts.
Loss: mean next-token cross-entropy over the (sliced) vocabulary.

Departures from the source, each also in the configuration's file:
- the head is the embedding transposed (the catalog row does not say; the
  published 8.3B total only adds up tied);
- the epsilon of the renormalisation (1e-20) and the bias rule and rate
  are not in the row: ``assumed``;
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

#: Tolerances, with their reasons. Each limit is set from this cell's own
#: two readings on the chip (PERF.md section 4 (c'''): twenty-four seeds of
#: the f32 comparison, fourteen of the step on one sequence repeated and
#: ten on a batch of four distinct ones, two of this reference in
#: ``float8_e4m3fn``; my chip runs, PR 33).
#:
#: ``f32`` (one sequence): the system's function with ``amp`` off, einsum
#: attention and matmul precision ``highest`` computes the same float32
#: function by another route (shifted slices of one padded product; held
#: assignments sorted into a bounded buffer, grouped matmuls, sums by
#: token); only summation order differs. ``score_abs`` is on the router's
#: sigmoid scores (values in 0.2..0.8; read <= 1.06e-6); the top-k sets must
#: agree wherever this reference's k-th and (k+1)-th ``s + b`` differ by
#: more than ``gap`` (3x ``score_abs``: below it either order is float32
#: noise). Where the two resolve such a near-tie differently the token's
#: experts differ, which is no error of either: loss and gradients are
#: then compared with this reference GIVEN the system's index, as ``amp``
#: always is — and the system's k experts there must still be a top-k of
#: this reference's own ``s + b`` to within ``gap`` (``near_tie_excess``,
#: ``harness/near_tie.py``: the other expert of an exact tie reads 0.0,
#: one inside the tie ``gap`` at the most, any other the distance to it.
#: The limit is ``gap`` by that construction and NOT by two readings:
#: the cell's three runs with it read 0.0 with no near-tie resolved
#: differently, no control was run at its size: PERF.md sections 6 and 7,
#: PR 43). The bias after the step must be EQUAL for every expert whose
#: count is further from the mean than the layer has tokens inside
#: ``gap``. ``loss_rel`` 3e-7 is three units in the last place of a float32
#: loss of 10: the f32 function read 0 or one (<= 9.5e-8) in all twenty-four
#: runs, the bf16 step reads 5.7e-7 at the least (to 2.9e-5), so a loss or
#: head in bf16 where the configuration says float32 is refused by this
#: limit alone, with 3x and 1.9x of room. ``grad_leaf_rel`` 1e-4: read
#: 1.7e-6 to 2.8e-6; the bf16 step reads 0.02 to 0.03.
#:
#: ``amp`` (a batch of four distinct sequences): the step as measured —
#: bf16 operands in every dense and grouped matmul and in the flash
#: kernels, float32 accumulation, float32 router, norms, gates and taps,
#: rotary, softmax statistics. ``grad_leaf_rel`` 6e-2 lies between its two
#: readings: the step 0.0198 to 0.0285, this reference in fp8 1.23 and 1.51
#: (2.1x above the first, 20x under the second): it is what refuses a
#: precision below bf16. ``loss_rel``: a mean over 16,384 tokens hardly
#: moves with the operands' precision — the step reads 2.7e-6 to 1.8e-5 on
#: the batch (5.7e-7 to 2.9e-5 on one sequence repeated), fp8 reads 1.04e-5
#: on the batch (1.93e-4 on one sequence): INSIDE the sound runs' range,
#: so no limit lies between the two and none set there would refuse fp8.
#: For such a number the limit is the accepted decoder cells' tighter one
#: (2e-4, ERNIE's and OLMoE's), 6.9x the first reading's largest; what it
#: refuses is a loss over other tokens than the batch's, not a precision
#: (half of the batch left out, planted in tests/test_lfm2.py at the
#: rehearsal's sizes, reads loss 5e-3 to 2e-2 and worst leaf 1.4 to 1.7).
#: ``topk_overlap`` is the least mean share of a token's k experts that
#: are also this reference's own (read 0.989 to 0.993; a wrong router
#: reads k / 32 = 0.125).
#:
#: ``update``: the parameters and second moments the system's AdamW step
#: leaves, against ``adamw_first_step`` here on the SAME gradient (read out
#: of the system's first moment, itself held to this reference by ``amp``):
#: one float32 formula in another order, so a leaf differs by roundings of
#: a weight against an update of ``lr``; an ulp of each weight is allowed
#: for and ``param_rel`` limits what is left (read 0.0; ``moment_rel`` read
#: <= 1.2e-7). A skipped update reads 1.0, a halved rate 0.5, a decay left
#: out 0.1 on a norm's weights and ``weight_decay * 0.02`` = 2e-3 on a
#: matrix: the limit sits under that.
TOL = {
    "f32": {"loss_rel": 3e-7, "grad_leaf_rel": 1e-4, "score_abs": 1e-5,
            "gap": 3e-5},
    "amp": {"loss_rel": 2e-4, "grad_leaf_rel": 6e-2, "topk_overlap": 0.7},
    "update": {"param_rel": 1e-4, "moment_rel": 1e-5},
}

#: heads whose [L, L] scores are alive at once
_HEAD_GROUP = 8


def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary_halves(x, theta):
    """x [B, L, H, D]; channel i and i + D/2 turn by pos * theta^(-2i/D)."""
    import jax.numpy as jnp

    L, D = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(D // 2, dtype=np.float64) * 2.0 / D)
    ang = np.arange(L, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _swiglu(u, gate, up, down, r):
    import jax

    return r(jax.nn.silu(r(u) @ r(gate)) * (r(u) @ r(up))) @ r(down)


def short_conv(b, c, x, w):
    """The mixing part alone, [B, L, C] each, ``w`` [C, K]: the sum over
    K shifted copies of z = b * x, written out, then the second gate."""
    import jax.numpy as jnp

    z = b * x
    L, K = z.shape[1], w.shape[1]
    out = jnp.zeros_like(z)
    for j in range(K):
        back = K - 1 - j                       # z_{t - back} meets tap j
        shifted = jnp.concatenate(
            [jnp.zeros_like(z[:, :back]), z[:, :L - back]], axis=1)
        out = out + w[:, j] * shifted
    return c * out


def _conv(p, pre, u, r):
    import jax.numpy as jnp

    b, c, x = jnp.split(r(u) @ r(p[pre + "w_in"]), 3, axis=-1)
    return r(short_conv(b, c, x, p[pre + "w_conv"])) @ r(p[pre + "w_out"])


def _attention(p, pre, u, cfg, r):
    import jax
    import jax.numpy as jnp

    B, L, h = u.shape
    H, G = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // H
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    u = r(u)
    q = (u @ r(p[pre + "wq"])).reshape(B, L, H, d)
    k = (u @ r(p[pre + "wk"])).reshape(B, L, G, d)
    v = (u @ r(p[pre + "wv"])).reshape(B, L, G, d)
    q = _rotary_halves(_rms_norm(q, p[pre + "q_norm.weight"], eps), theta)
    k = _rotary_halves(_rms_norm(k, p[pre + "k_norm.weight"], eps), theta)
    k = jnp.repeat(k, H // G, axis=2)     # head j serves queries 4j..4j+3
    v = jnp.repeat(v, H // G, axis=2)
    causal = jnp.tril(jnp.ones((L, L), bool))

    def group(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k)) / math.sqrt(d)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", r(a), r(v))

    o = jnp.concatenate(
        [jax.checkpoint(group)(q[:, :, g:g + _HEAD_GROUP],
                               k[:, :, g:g + _HEAD_GROUP],
                               v[:, :, g:g + _HEAD_GROUP])
         for g in range(0, H, _HEAD_GROUP)], axis=2)
    return r(o.reshape(B, L, H * d)) @ r(p[pre + "wo"])


def experts(p, pre, u, bias, cfg, index, r):
    """(held experts' part [T, h], logits z, own index, gap, counts [E] as
    routed with the index used). ``u`` [T, h]."""
    import jax
    import jax.numpy as jnp

    k = cfg["num_experts_per_tok"]
    first, count = cfg["held_first"], cfg["num_experts"]
    z = u @ p[pre + "router_w"]                              # [T, E]
    E = z.shape[-1]
    s = jax.nn.sigmoid(z)
    top, own_index = jax.lax.top_k(jax.lax.stop_gradient(s) + bias, k + 1)
    used = own_index[:, :k]
    if index is not None:
        used = jnp.where(index[0], index[1], used)
    mask = jnp.sum(jax.nn.one_hot(used, E, dtype=jnp.float32), axis=1)
    g = s * mask
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]

    def one_expert(y, bank):        # every held expert on every token
        gate, up, down, weight = bank
        return y + weight[:, None] * _swiglu(u, gate, up, down, r), None

    y, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(u),
        (p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"],
         g[:, first:first + count].T))
    return y, z, own_index[:, :k], top[:, k - 1] - top[:, k], \
        jnp.sum(mask, axis=0)


def _block(p, pre, x, kind, bias, cfg, index, r):
    eps = cfg["norm_eps"]
    B, L, h = x.shape
    u = _rms_norm(x, p[pre + "norm_op.weight"], eps)
    if kind == "conv":
        x = x + _conv(p, pre + "conv.", u, r)
    else:
        x = x + _attention(p, pre + "attn.", u, cfg, r)
    u = _rms_norm(x, p[pre + "norm_ffn.weight"], eps)
    if bias is None:
        return x + _swiglu(u, p[pre + "mlp.w_gate"], p[pre + "mlp.w_up"],
                           p[pre + "mlp.w_down"], r), None
    y, *route = experts(p, pre + "moe.", u.reshape(B * L, h), bias, cfg,
                        index, r)
    return x + y.reshape(B, L, h), route


def layer_kinds(cfg: Mapping[str, Any]):
    """The mixers of the layers this configuration runs: the
    ``num_hidden_layers`` entries of the published ``layer_types`` from
    ``first_layer`` on (0 where absent)."""
    first = cfg.get("first_layer", 0)
    kinds = list(cfg["layer_types"][first:first + cfg["num_hidden_layers"]])
    assert len(kinds) == cfg["num_hidden_layers"]
    assert set(kinds) <= {"conv", "full_attention"}
    return kinds


def bias_names(cfg: Mapping[str, Any]):
    """The router-bias buffers, in the order of the expert layers."""
    return [f"blocks.{i}.moe.expert_bias" for i in range(
        cfg["num_dense_layers"], cfg["num_hidden_layers"])]


def forward(p: Mapping[str, Any], biases, ids, labels,
            cfg: Mapping[str, Any], expert_index=None, given=True,
            operand_dtype=None):
    """(loss, (router logits [expert layers, T, E], own expert index
    [.., T, k], gap [.., T]: k-th less (k+1)-th ``s + b``, counts
    [.., E])). ``expert_index`` is used where ``given`` (a traced flag, so
    that one compiled function serves both uses)."""
    import jax
    import jax.numpy as jnp

    def r(a):         # an operand as the matmul sees it
        return a if operand_dtype is None else a.astype(
            operand_dtype).astype(jnp.float32)

    x = p["embed"][ids]
    routes = []
    for i, kind in enumerate(layer_kinds(cfg)):
        dense = i < cfg["num_dense_layers"]
        n = len(routes)
        index = None if expert_index is None or dense \
            else (given, expert_index[n])
        bias = None if dense else biases[n]
        pre = f"blocks.{i}."
        x, route = jax.checkpoint(
            lambda p, x, bias, index, pre=pre, kind=kind: _block(
                p, pre, x, kind, bias, cfg, index, r)
        )(p, x, bias, index)
        if route is not None:
            routes.append(route)
    hidden = _rms_norm(x, p["norm_f.weight"], cfg["norm_eps"])
    logits = r(hidden) @ r(p["embed"]).T
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                         axis=-1)[..., 0])
    return loss, tuple(jnp.stack([rt[i] for rt in routes])
                       for i in range(4))


def bias_after(biases, counts, cfg: Mapping[str, Any]):
    """The router biases after a step whose assignment counts over all
    ``router_width`` experts were ``counts`` [expert layers, E]."""
    import jax.numpy as jnp

    return [b + cfg["bias_update_rate"] * jnp.sign(jnp.mean(c) - c)
            for b, c in zip(biases, jnp.asarray(counts, jnp.float32))]


_COMPILED: Dict[Any, Any] = {}
_SHAPE_KEYS = ("num_hidden_layers", "num_dense_layers", "num_attention_heads",
               "num_key_value_heads", "num_experts_per_tok", "num_experts",
               "held_first", "routed_scaling_factor", "norm_eps",
               "rope_theta")


def _value_and_grad(cfg: Mapping[str, Any], operand_dtype=None):
    """One jitted function a configuration, whether or not the routing is
    given (``given`` is a traced flag): at full widths a compile is most
    of the reference's time."""
    import jax

    key = tuple(cfg[k] for k in _SHAPE_KEYS) \
        + (tuple(layer_kinds(cfg)), operand_dtype)
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
    """One step's loss, gradients, routing and biases on the batch ``ids``
    [B, L], computed a sequence at a time: the loss and the gradients are
    the means over the sequences (each is as long as every other), the
    counts their sums, and the biases move by the summed counts — what one
    step on the whole batch computes. ``expert_index`` and the routing
    returned are [expert layers, B * L, k], tokens in the batch's order.
    ``buffers`` holds the router biases under the system's names (zeros
    where absent). ``grads`` stay where they were computed (jax arrays);
    ``compare`` reduces them there."""
    import jax
    import jax.numpy as jnp

    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    ids, labels = np.atleast_2d(ids), np.atleast_2d(labels)
    n, L = ids.shape
    names = bias_names(cfg)
    biases = [jnp.asarray((buffers or {}).get(
        name, np.zeros(cfg["router_width"])), jnp.float32) for name in names]
    given = expert_index is not None
    if not given:
        expert_index = np.zeros((len(names), n * L,
                                 cfg["num_experts_per_tok"]), np.int32)
    index = np.asarray(expert_index, np.int32)
    fn = _value_and_grad(cfg, operand_dtype)
    add = jax.jit(lambda acc, g: jax.tree_util.tree_map(jnp.add, acc, g),
                  donate_argnums=(0,))
    loss, grads, routes = 0.0, None, []
    with jax.default_matmul_precision("highest"):
        for i in range(n):
            (one, route), g = fn(
                p, biases, jnp.asarray(ids[i:i + 1]),
                jnp.asarray(labels[i:i + 1]),
                jnp.asarray(index[:, i * L:(i + 1) * L]), jnp.asarray(given))
            loss += float(one) / n
            grads = g if grads is None else add(grads, g)
            routes.append(jax.device_get(route))
    if n > 1:
        grads = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda a: a / n, t), donate_argnums=(0,))(grads)
    z, own, gap = (np.concatenate([r[j] for r in routes], axis=1)
                   for j in range(3))
    counts = np.sum([r[3] for r in routes], axis=0)
    return {"loss": loss,
            "router_scores": 1.0 / (1.0 + np.exp(-z.astype(np.float64))),
            "expert_index": index if given else own,
            "own_index": own, "gap": gap, "counts": counts,
            "bias": np.stack([np.asarray(b) for b in biases]),
            "bias_after": {name: np.asarray(b) for name, b in zip(
                names, bias_after(biases, counts, cfg))},
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
        # two compute different functions, so losses and gradients are
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
    """{leaf: [largest |reference gradient|, largest |difference|]}: what
    a limit is read from."""
    import jax.numpy as jnp

    out = {}
    for k, r in ref["grads"].items():
        d = jnp.asarray(got["grads"][k]) - r
        out[k] = [float(jnp.max(jnp.abs(r))), float(jnp.max(jnp.abs(d)))]
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
    """The loss, every gradient leaf (largest absolute difference over the
    leaf's largest entry) and the biases after the step against
    ``TOL[mode]``."""
    tol = TOL[mode]
    loss_rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    # {leaf: [largest |gradient|, error over it]}, the worst first
    detail = sorted(((k, [top, err / top]) for k, (top, err)
                     in leaf_table(got, ref).items() if top > 0.0),
                    key=lambda kv: -kv[1][1])
    worst_leaf, (_, worst) = detail[0]
    out = {"mode": mode, "loss": [got["loss"], ref["loss"]],
           "loss_rel": loss_rel, "grad_leaf_rel": worst,
           "worst_leaf": worst_leaf, "leaves": len(ref["grads"]),
           "leaves_compared": len(detail), "tol": tol,
           "worst_leaves": dict(detail[:8])}
    ok = (np.isfinite(got["loss"]) and len(detail) == len(ref["grads"])
          and loss_rel <= tol["loss_rel"] and worst <= tol["grad_leaf_rel"])
    if "bias_after" in got:
        out["bias"] = _bias_agrees(got, ref, tol.get("gap"))
        ok = ok and out["bias"]["experts_wrong"] == 0
    out["ok"] = bool(ok)
    return out
