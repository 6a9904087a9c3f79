"""Plain reference for ``smallthinker-21b-a3b``: decoder forward, the loss,
its gradients and AdamW's first step — ``jax.numpy``, float32, matmul
precision ``highest``; attention as explicit scores under an explicit
mask, one key-value head's query heads and one block of queries at a
time (so that the [16384, 16384] scores of one head never exist whole);
the expert layer as a loop over the held experts, each on every token,
masked by the choice (no sort, no buffer, no grouped matmul, no kernel, no
mixed precision, no trainer). Independent of ``paddle_tpu``: written from
the equations below, not from ``models/smallthinker.py``.

The architecture: ``PowerInfer/SmallThinker-21BA3B-Instruct``
(arXiv:2507.20984). ``x`` is the residual stream [B, L, 2560] ENTERING
layer l; ``N`` is RMSNorm (eps ``rms_norm_eps``) with a learned weight; no
bias anywhere; every layer is an expert layer:

    x = embed[ids]
    layer l:  z = x W_r                        (2560 -> 64; the LAYER'S INPUT,
                                                before attention and its norm)
              h = x + W_o Attn_l(N_attn(x))
              y = h + sum_{e in top6(z), held} g_e W_down,e(relu(W_gate,e u)
                                                            * (W_up,e u)),
                                                u = N_ffn(h)
    logits = N_f(y_last) @ head                 (untied head)

``top6``: the ``moe_num_active_primary_experts`` largest of z (ties: the
lower expert); g = softmax over THOSE six logits
(``moe_primary_router_apply_softmax``, ``norm_topk_prob``). No bias, no
scale. The held range ``(held_first, moe_num_primary_experts)`` of the
``router_width`` experts is an argument of the configuration: what the
absent experts would add is left out, here as in the system.
``Attn_l``, l counted from ``first_layer`` in the published lists: 28
query / 4 key-value heads of 128 (key-value head j serves query heads
7j..7j+6), scale 1/sqrt(128), no QK-norm. ``sliding_window_layout[l]`` 1:
query i sees the keys j with i - ``sliding_window_size`` < j <= i; 0:
every j <= i. ``rope_layout[l]`` 1: rotary theta ``rope_theta`` on q and
k, half-split (channel c pairs with c + 64, both turn by pos *
theta^(-2c/128)), positions 0..L-1; 0: no positional encoding at all.
Loss: mean next-token cross-entropy over the (sliced) vocabulary, alone.

Departures from the source, each also in the configuration's file:
- the router's input (the un-normed stream entering the layer), the
  half-split rotary and the softmax over the chosen six are the public
  implementation's; the catalog row says only "router placed before
  attention": ``assumed``;
- attention is computed a block at a time and every layer is recomputed in
  the backward pass (``jax.checkpoint``): memory, not arithmetic.

``operand_dtype``, when given, rounds both operands of every matmul but
the router's to that dtype first (float32 accumulation): this reference
"in the nearest precision below" bf16 is ``float8_e4m3fn``, the reading
that the ``amp`` tolerances must refuse.

``expert_index`` [layers, T, k], when given, fixes which experts every
token uses (the weights are still this reference's own softmax over the
logits at those experts): that is how a step in lower precision, whose
router flips near-ties, is held to the same function.
"""

from __future__ import annotations

import importlib.util
import math
import os
from typing import Any, Dict, Mapping, Optional

import numpy as np

#: Tolerances, with their reasons. Each limit is set from this cell's own
#: two readings on the chip — the sound runs' largest over their seeds and
#: the nearest wrong program's (PERF.md section 4 (c''''): ten sound runs
#: at ten seeds, the five planted faults of ISSUE 44 through
#: ``benchmarks/tests/swa_fault_control.py``, this reference in
#: ``float8_e4m3fn`` through ``tools/reference_precision.py``; my chip
#: runs, PR 44).
#:
#: ``f32`` (one 16,384-token sequence): the system's function with ``amp``
#: off, einsum attention and matmul precision ``highest`` computes the same
#: float32 function by another route (the route from softmax over all 64,
#: renormalised; held assignments sorted into a bounded buffer, grouped
#: matmuls, sums by token; scores of seven heads and 2048 queries a block);
#: only summation order differs. ``logit_abs`` is on the routers' logits
#: (the stream's common part makes them as large as 0.5; read 2.4e-7 to
#: 3.9e-7; the router fed the post-attention stream reads 0.49, the other
#: wrong programs 0.04 to 0.12); the top-k sets must agree wherever this
#: reference's k-th and (k+1)-th logit differ by more than ``gap`` (3x
#: ``logit_abs``: below it either order is float32 noise). Where the two
#: resolve such a near-tie differently (0 to 1,551 of a run's 65,536
#: token-layers) the token's experts differ, which is no error of either:
#: loss and gradients are then compared with this reference GIVEN the
#: system's index, as ``amp`` always is — and the system's k experts there
#: must still be a top-k of this reference's own logits to within ``gap``
#: (``near_tie_excess``, ``harness/near_tie.py``: read <= 3.2e-7; the limit
#: is ``gap`` by that construction, not by two readings). ``loss_rel`` 3e-7
#: is three units in the last place of a float32 loss of 10: read 0 or ONE
#: unit (9.3e-8) in all ten runs, the bf16 step 1.6e-6 at the least.
#: The gradient has two limits because THIS MODEL'S GATE IS A RELU: its
#: derivative is a step, two float32 routes disagree on the sign of a gate
#: that is zero to seven digits, and each such flip moves a column of
#: ``w_gate`` by one token's whole contribution. So a leaf's widest entry
#: (``grad_leaf_rel``, over the leaf's largest) reads 2.4e-4 to 1.25e-3
#: here, every time on a ``w_gate`` (LFM2's SiLU: 3e-6), with a tail no
#: ten runs bound; the leaf's NORM (``grad_leaf_l2``: the L2 norm of the
#: difference over the leaf's) hardly sees a handful of columns and reads
#: 4.5e-5 to 1.3e-4. The four wrong programs read 0.60 to 1.89 there and
#: 0.67 to 2.34 at the widest entry; the nearest one, this same call under
#: ``amp`` (bf16 where the file says float32), 0.0207 and 0.0436 (and
#: 0.033 on the logits, 1.5e-5 on the loss).
#: ``grad_leaf_l2`` 1e-3 and ``grad_leaf_rel`` 1e-2 lie between.
#:
#: ``amp`` (the whole 16,384-token batch): the step as measured — bf16
#: operands in every dense and grouped matmul and in the flash kernels
#: (both masks), float32 accumulation, float32 router, norms, rotary and
#: softmax statistics. ``grad_leaf_l2`` read 0.0135 to 0.0199, this
#: reference in fp8 1.41 -> 6e-2 (3x over the one, 23x under the other):
#: what refuses a precision below bf16. ``grad_leaf_rel`` read 0.018 to
#: 0.038 (bf16 flips gates too), fp8 1.57 -> 0.15. ``loss_rel`` read
#: 1.6e-6 to 9.3e-5, fp8 4.9e-3 -> 2e-4, the accepted decoder cells'.
#: ``topk_overlap`` is the least mean share of a token's k experts that
#: are also this reference's own (read 0.9896 to 0.9941; a wrong router
#: reads k / 64 = 0.094).
#:
#: ``update``: the parameters and second moments the system's AdamW step
#: leaves, against ``adamw_first_step`` here on the SAME gradient (read out
#: of the system's first moment, itself held to this reference by ``amp``):
#: one float32 formula in another order; an ulp of each weight is allowed
#: for and ``param_rel`` limits what is left (read 0.0; ``moment_rel`` read
#: <= 1.2e-7). A skipped update reads 1.0, a halved rate 0.5, a decay left
#: out 0.1 on a norm's weights and ``weight_decay * 0.02`` = 2e-3 on a
#: matrix: the limit sits under that.
TOL = {
    "f32": {"loss_rel": 3e-7, "grad_leaf_l2": 1e-3, "grad_leaf_rel": 1e-2,
            "logit_abs": 2e-6, "gap": 6e-6},
    "amp": {"loss_rel": 2e-4, "grad_leaf_l2": 6e-2, "grad_leaf_rel": 0.15,
            "topk_overlap": 0.7},
    "update": {"param_rel": 1e-4, "moment_rel": 1e-5},
}

#: queries whose scores against every key are alive at once, a key-value
#: head's query heads at a time
_QUERY_BLOCK = 1024


def _rms_norm(x, g, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rotary_halves(x, theta):
    """x [B, L, H, D]; channel c and c + D/2 turn by pos * theta^(-2c/D)."""
    import jax.numpy as jnp

    L, D = x.shape[1], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(D // 2, dtype=np.float64) * 2.0 / D)
    ang = np.arange(L, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer_kinds(cfg: Mapping[str, Any]):
    """[(window or None, rotary or not)] of the layers this configuration
    runs: the ``num_hidden_layers`` entries of the two published lists
    from ``first_layer`` on (0 where absent)."""
    first = cfg.get("first_layer", 0)
    rows = slice(first, first + cfg["num_hidden_layers"])
    kinds = [(cfg["sliding_window_size"] if w else None, bool(t))
             for w, t in zip(cfg["sliding_window_layout"][rows],
                             cfg["rope_layout"][rows])]
    assert len(kinds) == cfg["num_hidden_layers"]
    return kinds


def attention(q, k, v, window, r=lambda a: a):
    """q [B, L, H, d], k and v [B, L, G, d] (NOT repeated): query i of
    head h sees the keys j <= i — under ``window`` those with i - window <
    j — of key-value head h // (H / G). The mask written out; a block of
    queries of one key-value head's query heads at a time."""
    import jax
    import jax.numpy as jnp

    B, L, H, d = q.shape
    G = k.shape[2]
    per = H // G
    bq = _QUERY_BLOCK if L % _QUERY_BLOCK == 0 else L
    j = jnp.arange(L)[None, :]

    def block(qb, first, kj, vj):      # qb [B, bq, per, d]; kj, vj [B, L, d]
        i = first + jnp.arange(bq)[:, None]
        seen = j <= i
        if window is not None:
            seen = seen & (i - window < j)
        s = jnp.einsum("bqhd,bkd->bhqk", r(qb), r(kj)) / math.sqrt(d)
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkd->bqhd", r(a), r(vj))

    heads = []
    for g in range(G):
        kj, vj = k[:, :, g], v[:, :, g]
        qg = q[:, :, g * per:(g + 1) * per].reshape(B, L // bq, bq, per, d)
        rows = jax.lax.map(          # one block of queries after another
            jax.checkpoint(lambda a: block(a[0], a[1], kj, vj)),
            (jnp.swapaxes(qg, 0, 1), jnp.arange(0, L, bq)))
        heads.append(jnp.swapaxes(rows, 0, 1).reshape(B, L, per, d))
    return jnp.concatenate(heads, axis=2)


def _attention(p, pre, u, kind, cfg, r):
    B, L, h = u.shape
    H, G, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    window, turns = kind
    u = r(u)
    q = (u @ r(p[pre + "wq"])).reshape(B, L, H, d)
    k = (u @ r(p[pre + "wk"])).reshape(B, L, G, d)
    v = (u @ r(p[pre + "wv"])).reshape(B, L, G, d)
    if turns:
        q = _rotary_halves(q, cfg["rope_theta"])
        k = _rotary_halves(k, cfg["rope_theta"])
    o = attention(q, k, v, window, r)
    return r(o.reshape(B, L, H * d)) @ r(p[pre + "wo"])


def route(z, k: int, index=None):
    """(weights [T, E]: the softmax over the chosen logits, 0 elsewhere;
    own index [T, k]; gap [T]: k-th less (k+1)-th logit; the 0/1 mask
    [T, E] of the index used). ``index`` = (given, [T, k])."""
    import jax
    import jax.numpy as jnp

    E = z.shape[-1]
    top, own = jax.lax.top_k(jax.lax.stop_gradient(z), k + 1)
    used = own[:, :k]
    if index is not None:
        used = jnp.where(index[0], index[1], used)
    mask = jnp.sum(jax.nn.one_hot(used, E, dtype=jnp.float32), axis=1)
    g = jax.nn.softmax(jnp.where(mask > 0, z, -jnp.inf), axis=-1)
    return g, own[:, :k], top[:, k - 1] - top[:, k], mask


def experts(p, pre, x_in, u, cfg, index, r):
    """(held experts' part [T, h], logits z, own index, gap, counts [E] as
    routed with the index used). ``x_in`` [T, h]: the layer's INPUT, what
    the router reads; ``u`` [T, h]: what the experts read."""
    import jax
    import jax.numpy as jnp

    k = cfg["moe_num_active_primary_experts"]
    first, count = cfg["held_first"], cfg["moe_num_primary_experts"]
    z = x_in @ p[pre + "router_w"]                            # [T, E]
    g, own, gap, mask = route(z, k, index)

    def one_expert(y, bank):        # every held expert on every token
        gate, up, down, weight = bank
        act = jax.nn.relu(r(u) @ r(gate)) * (r(u) @ r(up))
        return y + weight[:, None] * (r(act) @ r(down)), None

    y, _ = jax.lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(u),
        (p[pre + "w_gate"], p[pre + "w_up"], p[pre + "w_down"],
         g[:, first:first + count].T))
    return y, z, own, gap, jnp.sum(mask, axis=0)


def _layer(p, pre, x, kind, cfg, index, r):
    eps = cfg["rms_norm_eps"]
    B, L, h = x.shape
    x_in = x.reshape(B * L, h)
    x = x + _attention(p, pre + "attn.",
                       _rms_norm(x, p[pre + "norm_attn.weight"], eps),
                       kind, cfg, r)
    u = _rms_norm(x, p[pre + "norm_ffn.weight"], eps)
    y, *routed = experts(p, pre + "moe.", x_in, u.reshape(B * L, h), cfg,
                         index, r)
    return x + y.reshape(B, L, h), routed


def forward(p: Mapping[str, Any], ids, labels, cfg: Mapping[str, Any],
            expert_index=None, given=True, operand_dtype=None):
    """(loss, (router logits [layers, T, E], own expert index [.., T, k],
    gap [.., T]: k-th less (k+1)-th logit, counts [.., E])).
    ``expert_index`` is used where ``given`` (a traced flag, so that one
    compiled function serves both uses)."""
    import jax
    import jax.numpy as jnp

    assert not cfg["tie_word_embeddings"] and cfg["norm_topk_prob"] \
        and cfg["moe_primary_router_apply_softmax"] \
        and cfg["rope_scaling"] is None

    def r(a):         # an operand as the matmul sees it
        return a if operand_dtype is None else a.astype(
            operand_dtype).astype(jnp.float32)

    x = p["embed"][ids]
    routes = []
    for i, kind in enumerate(layer_kinds(cfg)):
        index = None if expert_index is None else (given, expert_index[i])
        pre = f"blocks.{i}."
        x, routed = jax.checkpoint(
            lambda p, x, index, pre=pre, kind=kind: _layer(
                p, pre, x, kind, cfg, index, r)
        )(p, x, index)
        routes.append(routed)
    hidden = _rms_norm(x, p["norm_f.weight"], cfg["rms_norm_eps"])
    logits = r(hidden) @ r(p["head"])
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[..., None],
                                         axis=-1)[..., 0])
    return loss, tuple(jnp.stack([rt[i] for rt in routes])
                       for i in range(4))


_COMPILED: Dict[Any, Any] = {}
_SHAPE_KEYS = ("num_hidden_layers", "num_attention_heads",
               "num_key_value_heads", "head_dim",
               "moe_num_active_primary_experts", "moe_num_primary_experts",
               "held_first", "rms_norm_eps", "rope_theta",
               "sliding_window_size")


def _value_and_grad(cfg: Mapping[str, Any], operand_dtype=None):
    """One jitted function a configuration, whether or not the routing is
    given (``given`` is a traced flag): at full widths a compile is most
    of the reference's time."""
    import jax

    key = tuple(cfg[k] for k in _SHAPE_KEYS) \
        + (tuple(layer_kinds(cfg)), operand_dtype)
    if key not in _COMPILED:
        def total(p, ids, labels, expert_index, given):
            return forward(p, ids, labels, cfg, expert_index, given,
                           operand_dtype)

        _COMPILED[key] = jax.jit(jax.value_and_grad(total, has_aux=True))
    return _COMPILED[key]


def loss_and_grads(params: Mapping[str, Any], ids, labels,
                   cfg: Mapping[str, Any],
                   expert_index: Optional[Any] = None,
                   operand_dtype=None) -> Dict[str, Any]:
    """One step's loss, gradients and routing on the batch ``ids`` [B, L],
    computed a sequence at a time: the loss and the gradients are the
    means over the sequences (each is as long as every other), the counts
    their sums — what one step on the whole batch computes.
    ``expert_index`` and the routing returned are [layers, B * L, k],
    tokens in the batch's order. ``grads`` stay where they were computed
    (jax arrays); ``compare`` reduces them there."""
    import jax
    import jax.numpy as jnp

    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    ids, labels = np.atleast_2d(ids), np.atleast_2d(labels)
    n, L = ids.shape
    layers = cfg["num_hidden_layers"]
    given = expert_index is not None
    if not given:
        expert_index = np.zeros(
            (layers, n * L, cfg["moe_num_active_primary_experts"]), np.int32)
    index = np.asarray(expert_index, np.int32)
    fn = _value_and_grad(cfg, operand_dtype)
    add = jax.jit(lambda acc, g: jax.tree_util.tree_map(jnp.add, acc, g),
                  donate_argnums=(0,))
    loss, grads, routes = 0.0, None, []
    with jax.default_matmul_precision("highest"):
        for i in range(n):
            (one, routed), g = fn(
                p, jnp.asarray(ids[i:i + 1]), jnp.asarray(labels[i:i + 1]),
                jnp.asarray(index[:, i * L:(i + 1) * L]), jnp.asarray(given))
            loss += float(one) / n
            grads = g if grads is None else add(grads, g)
            routes.append(jax.device_get(routed))
    if n > 1:
        grads = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda a: a / n, t), donate_argnums=(0,))(grads)
    z, own, gap = (np.concatenate([r[j] for r in routes], axis=1)
                   for j in range(3))
    return {"loss": loss, "router_logits": z.astype(np.float64),
            "expert_index": index if given else own,
            "own_index": own, "gap": gap,
            "counts": np.sum([r[3] for r in routes], axis=0),
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
    """The system's routers (``router_logits``, ``expert_index``) against
    this reference's own choice (``own_index``: what it would choose on
    the hidden states it computed, whether or not it was GIVEN an index
    to use)."""
    tol = TOL[mode]
    out: Dict[str, Any] = {"mode": mode}
    overlap, same = _overlap(np.asarray(got["expert_index"]),
                             ref["own_index"])
    if mode == "f32":
        by_layer = np.max(np.abs(
            np.asarray(got["router_logits"], np.float64)
            - ref["router_logits"]), axis=(1, 2))
        out["logit_abs"] = float(np.max(by_layer))
        out["logit_abs_by_layer"] = [float(x) for x in by_layer]
        # near-ties the two resolved differently: past such a token the
        # two compute different functions, so losses and gradients are
        # then compared with this reference GIVEN the system's index; the
        # system's experts there must still be a top-k of what this
        # reference ranks by, its logits (``harness/near_tie.py``)
        out.update(_harness("near_tie").readings(
            ref["router_logits"], ref["gap"], same, got["expert_index"],
            tol["gap"]))
        limits = {"logit_abs": tol["logit_abs"],
                  "topk_match_where_clear": 1.0,
                  "near_tie_excess": tol["gap"]}
        out["ok"] = bool(out["logit_abs"] <= limits["logit_abs"]
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


def leaf_table(got: Mapping[str, Any], ref: Mapping[str, Any]
               ) -> Dict[str, Any]:
    """{leaf: [largest |reference gradient|, largest |difference|, L2 norm
    of the reference gradient, L2 norm of the difference]}: what the
    limits are read from."""
    import jax.numpy as jnp

    norm = lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a))))
    out = {}
    for k, r in ref["grads"].items():
        d = jnp.asarray(got["grads"][k]) - r
        out[k] = [float(jnp.max(jnp.abs(r))), float(jnp.max(jnp.abs(d))),
                  norm(r), norm(d)]
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
    """The loss and every gradient leaf — the L2 norm of its difference
    over the leaf's norm (``grad_leaf_l2``), and its largest absolute
    difference over the leaf's largest entry (``grad_leaf_rel``) — against
    ``TOL[mode]``."""
    tol = TOL[mode]
    loss_rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    # {leaf: [largest |gradient|, widest error over it, error's norm over
    # the leaf's]}, the widest first
    detail = sorted(((k, [top, err / top, dn / rn]) for k, (top, err, rn, dn)
                     in leaf_table(got, ref).items() if top > 0.0),
                    key=lambda kv: -kv[1][1])
    worst_leaf, (_, worst, _) = detail[0]
    l2_leaf, (_, _, l2) = max(detail, key=lambda kv: kv[1][2])
    out = {"mode": mode, "loss": [got["loss"], ref["loss"]],
           "loss_rel": loss_rel, "grad_leaf_l2": l2, "grad_leaf_rel": worst,
           "worst_leaf": worst_leaf, "worst_leaf_l2": l2_leaf,
           "leaves": len(ref["grads"]), "leaves_compared": len(detail),
           "tol": tol, "worst_leaves": dict(detail[:8])}
    out["ok"] = bool(
        np.isfinite(got["loss"]) and len(detail) == len(ref["grads"])
        and loss_rel <= tol["loss_rel"] and l2 <= tol["grad_leaf_l2"]
        and worst <= tol["grad_leaf_rel"])
    return out
