"""Plain reference for ``evabyte-6.5b``: decoder forward, the eight heads'
loss, its gradients and AdamW's first step — ``jax.numpy``, float32, matmul
precision ``highest``; attention as explicit scores under an explicit
``[rows, L/16 + L]`` mask built from the words below, a block of queries at
a time (so that the [8192, 8704] scores of 32 heads never exist whole); no
kernel, no pair list, no mixed precision, no trainer. Independent of
``paddle_tpu``: written from the equations, not from ``models/evabyte.py``.

The architecture: ``EvaByte/EvaByte`` (6.5B, 2025-01; attention: EVA, Zheng
et al., ICLR 2023, arXiv:2302.04542). ``x`` is the residual stream [B, L,
4096]; ``N(x) = x / sqrt(mean(x^2) + rms_norm_eps) * (1 + w)``
(``norm_add_unit_offset``); no bias anywhere:

    x = embed[ids]
    layer:  h = x + W_o Attn(N_1(x))
            y = h + W_down(silu(W_gate u) * (W_up u)),        u = N_2(h)
    logits[r] = N_f(y_last) @ heads[:, 320 r : 320 (r + 1)]   r = 0..7

``Attn``: q, k, v = u W_q, u W_k, u W_v, 32 heads of 128; rotary theta
``rope_theta`` on q and k, half-split (channel c pairs with c + 64, both
turn by pos * theta^(-2c/128)), positions 0..L-1; s = 128^-1/2. Per head
with learned phi, mu (``adaptive_phi``, ``adaptive_mu_k``), for chunk m =
keys 16m .. 16m + 15:

    w_j  = softmax_{j in chunk m}(s * k_j . phi)
    k~_m = sum_j w_j k_j + mu            v~_m = sum_j w_j v_j

Query i in window n = floor(i / 2048) attends, under ONE softmax, to the
keys j with floor(j / 2048) = n and j <= i, and to the summaries m with
m < 128 n (the chunks of all earlier windows; none for the first window).
Head r at position t predicts byte t + 1 + r: with ``labels[t]`` = byte
t + 1, its target is ``labels[t + r]`` where t + r < L. The loss is the
mean over the eight heads of each head's mean cross-entropy over the
positions whose target exists.

Departures from the source, each also in the configuration's file: mu is
added after pooling, rotary is half-split, the heads are plain linear maps
and the loss their unweighted mean (``assumed``); attention is computed a
block of queries at a time and every layer is recomputed in the backward
pass (``jax.checkpoint``): memory, not arithmetic.

``operand_dtype``, when given, rounds both operands of every matmul to that
dtype first (float32 accumulation): this reference "in the nearest
precision below" bf16 is ``float8_e4m3fn``, the reading that the ``amp``
tolerances must refuse.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping

import numpy as np

#: Tolerances, with their reasons. Each limit is set from this cell's own
#: two readings on the chip — the sound runs' largest over their seeds and
#: states, and the nearest wrong program's (PERF.md section 4: the sound
#: runs, the four planted faults of ISSUE 46, the same program in bf16 and
#: this reference in ``float8_e4m3fn`` through
#: ``benchmarks/tests/eva_fault_control.py``; my chip runs, PR 46) — and
#: lies between them with room on both sides. One set of limits for both
#: states the check compares at (``adapters/causal_eva_lm.check_reference``):
#: the trainer's own parameters after the window — less the leaves under
#: ``GRADIENT_FLOOR`` — and the seed's initial ones, every leaf.
#:
#: ``f32`` (the 8192-byte check sequence): the system's function with
#: ``amp`` off and matmul precision ``highest`` — on the chip THROUGH THE
#: FLASH KERNELS, their operands float32 (``EvaByteConfig.attn_precision``),
#: so the pair list and the kernels' mask are what is compared — computes
#: the same float32 function by another route; only summation order
#: differs. ``loss_rel`` 3e-6 is three units in the last place of a float32
#: loss of 5: read 0 to 1.1e-7, the bf16 step 2.5e-5, seven heads
#: 1.2e-3. ``logit_rel`` is the largest difference of a logit over the
#: largest logit: read 6.5e-7 to 1.15e-6; the nearest wrong program, a bf16
#: pooling softmax, 1.56e-4 (a dropped mu 2.1e-3, a summary one window early
#: 0.30): 1.5e-5 lies 13x over the one and 10x under the other.
#: ``grad_leaf_l2`` (the L2 norm of a leaf's difference over the leaf's
#: norm) read 9.8e-6 to 1.4e-5 at the initial state and 2.2e-5 to 3.0e-5 at
#: the trained one, the bf16 pooling softmax 5.9e-3 (on ``adaptive_phi``;
#: 9.6e-3 at the trained state), the bf16 step 1.4e-2, seven heads 0.49, a
#: summary one window early 0.91, a dropped mu 1.0 (9.2e-3 trained): 3e-4,
#: 10x over and 20x under. ``grad_leaf_rel`` (a leaf's largest entry's over
#: the leaf's largest) read 4.6e-6 to 2.2e-5 (initial), 3.3e-5 to 4.8e-5
#: (trained), the bf16 pooling softmax 1.05e-2: 3e-4, 6x over, 35x under.
#:
#: ``amp`` (the same sequence, the step as measured): bf16 operands in every
#: dense matmul and in the flash kernels, float32 accumulation, float32
#: norms, rotary, pooling softmax, statistics, residual adds and loss.
#: ``grad_leaf_l2`` read 0.0130 to 0.0161 at the initial state, 0.011 to
#: 0.022 at the trained one in the step (ten seeds) and 0.031 there by the
#: same function outside the step (``eva_trained_witness.py``: a block's
#: attention norm, whose gradient sums what comes back through q and k —
#: rounding, there — with what comes through v), this reference in fp8 3.2
#: (54 at the trained state) -> 0.1: 3x over the one, 32x under the other
#: (6e-2, the accepted decoder cells', would leave the 0.031 under 2x; fresh
#: seeds read higher, so the more room is above the reading);
#: ``grad_leaf_rel`` 0.0130 to 0.0245 (initial), 0.007 to 0.027 (trained),
#: fp8 2.4 -> 0.15; ``logit_rel`` 0.0014 to 0.0109, fp8 1.10 -> 0.1 (at
#: the trained state fp8 reads 0.030 here and is refused by the leaves).
#: ``loss_rel`` read 0 to 1.19e-4
#: (a loss over ONE 8192-byte sequence: the widest of the readings over
#: seeds, so the accepted cells' 2e-4 would leave the largest 1.7x), fp8
#: 4.3e-3 -> 7e-4, 6x over and 6x under.
#:
#: ``update``: the parameters and second moments the system's AdamW step
#: leaves, against ``adamw_first_step`` here on the SAME gradient (read out
#: of the system's first moment, itself held to this reference by ``amp``):
#: one float32 formula in another order; an ulp of each weight is allowed
#: for and ``param_rel`` limits what is left (read 0 to 1.3e-7;
#: ``moment_rel`` 7.2e-8 to 9.9e-8). A skipped update reads 1.0, a halved
#: rate 0.5, a decay left out ``weight_decay * init_std`` = 1.3e-3 of a
#: matrix's update: the limit sits under that.
TOL = {
    "f32": {"loss_rel": 3e-6, "logit_rel": 1.5e-5, "grad_leaf_l2": 3e-4,
            "grad_leaf_rel": 3e-4},
    "amp": {"loss_rel": 7e-4, "logit_rel": 0.1, "grad_leaf_l2": 0.1,
            "grad_leaf_rel": 0.15},
    "update": {"param_rel": 1e-4, "moment_rel": 1e-5},
}

#: The trained state's gradient comparison leaves out a leaf whose
#: reference gradient is nought to rounding: its root-mean-square entry lies
#: under this. The traffic's ids are independent draws, so within the
#: window's ≈ 26 steps the model has learnt the byte frequencies and what
#: reaches q, k, φ and μ of the blocks after the first is the residue of
#: sums that cancel: 1e-10 to 3.7e-9 an entry (2e-6 to 1.4e-4 at the seed's
#: parameters), where bf16 operands leave ≤ 5e-10 an entry of rounding —
#: 0.09 to 0.51 of such a leaf by the system under ``amp`` AND 0.08 to 0.40
#: by this reference with its own operands rounded to bf16, no line of the
#: system in it; in float32 1.8e-5 to 1.1e-4 through the kernels, 1.3e-5 to
#: 6.4e-5 by the einsum form and 1.0e-5 to 5.3e-5 by this reference against
#: itself with its queries re-blocked (``tests/eva_trained_witness.py``,
#: seed 1234567891; my chip run, PR 46). The leaves with something to
#: learn start at 1.2e-7 an entry there (one, the first block's μ, at
#: 1.9e-8): the floor lies 8x over the one reading and 4x under the other,
#: and at it the rounding is 0.017 of a leaf. AdamW's own ε is 1e-8: the
#: step itself treats such a gradient as nought. The leaves left out are
#: counted and named (``leaves_floored``), and every leaf is compared at
#: the initial state.
GRADIENT_FLOOR = 3e-8

#: queries whose scores against every key and summary are alive at once
_QUERY_BLOCK = 512


def _rms_norm(x, w, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + w)


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


def seen(first, rows: int, L: int, window: int, chunk: int):
    """bool [rows, L / chunk + L]: what queries ``first .. first + rows - 1``
    see of [summaries | keys], from the words: summary m iff m < (window /
    chunk) * floor(i / window); key j iff the same aligned window and
    j <= i."""
    import jax.numpy as jnp

    i = first + jnp.arange(rows)[:, None]
    n = i // window
    m = jnp.arange(L // chunk)[None, :]
    j = jnp.arange(L)[None, :]
    return jnp.concatenate(
        [m < (window // chunk) * n, (j // window == n) & (j <= i)], axis=1)


def summaries(k, v, phi, mu, chunk: int):
    """(k~, v~) [B, L / chunk, H, d]: each chunk's keys and values pooled by
    the softmax of s * k . phi over the chunk; mu added to the pooled key."""
    import jax
    import jax.numpy as jnp

    B, L, H, d = k.shape
    kc = k.reshape(B, L // chunk, chunk, H, d)
    vc = v.reshape(B, L // chunk, chunk, H, d)
    w = jax.nn.softmax(
        jnp.einsum("bmchd,hd->bmch", kc, phi) / math.sqrt(d), axis=2)
    return (jnp.einsum("bmch,bmchd->bmhd", w, kc) + mu,
            jnp.einsum("bmch,bmchd->bmhd", w, vc))


def attention(q, k, v, phi, mu, window: int, chunk: int, r=lambda a: a):
    """q, k, v [B, L, H, d] -> [B, L, H, d]: the mask written out, one
    softmax over summaries and keys together, a block of queries at a
    time."""
    import jax
    import jax.numpy as jnp

    B, L, H, d = q.shape
    ks, vs = summaries(k, v, phi, mu, chunk)
    keys = jnp.concatenate([ks, k], axis=1)
    vals = jnp.concatenate([vs, v], axis=1)
    bq = _QUERY_BLOCK if L % _QUERY_BLOCK == 0 else L

    def block(qb, first):
        s = jnp.einsum("bqhd,bkhd->bhqk", r(qb), r(keys)) / math.sqrt(d)
        a = jax.nn.softmax(
            jnp.where(seen(first, bq, L, window, chunk)[None, None], s,
                      -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", r(a), r(vals))

    rows = jax.lax.map(          # one block of queries after another
        jax.checkpoint(lambda a: block(a[0], a[1])),
        (jnp.swapaxes(q.reshape(B, L // bq, bq, H, d), 0, 1),
         jnp.arange(0, L, bq)))
    return jnp.swapaxes(rows, 0, 1).reshape(B, L, H, d)


def _layer(p, pre, x, cfg, r):
    import jax

    eps, H = cfg["rms_norm_eps"], cfg["num_attention_heads"]
    B, L, h = x.shape
    d = h // H
    u = r(_rms_norm(x, p[pre + "norm_attn.weight"], eps))
    q = (u @ r(p[pre + "attn.wq"])).reshape(B, L, H, d)
    k = (u @ r(p[pre + "attn.wk"])).reshape(B, L, H, d)
    v = (u @ r(p[pre + "attn.wv"])).reshape(B, L, H, d)
    q = _rotary_halves(q, cfg["rope_theta"])
    k = _rotary_halves(k, cfg["rope_theta"])
    o = attention(q, k, v, p[pre + "attn.adaptive_phi"],
                  p[pre + "attn.adaptive_mu_k"], cfg["window_size"],
                  cfg["chunk_size"], r)
    x = x + r(o.reshape(B, L, h)) @ r(p[pre + "attn.wo"])
    u = r(_rms_norm(x, p[pre + "norm_ffn.weight"], eps))
    act = jax.nn.silu(u @ r(p[pre + "mlp.w_gate"])) \
        * (u @ r(p[pre + "mlp.w_up"]))
    return x + r(act) @ r(p[pre + "mlp.w_down"])


def head_targets(labels, heads: int):
    """[B, L, heads]: head r's target at t is ``labels[t + r]`` (byte
    t + 1 + r), -1 where the sequence has none."""
    import jax.numpy as jnp

    return jnp.stack(
        [jnp.pad(labels[:, r:], ((0, 0), (0, r)), constant_values=-1)
         for r in range(heads)], axis=-1)


def forward(p: Mapping[str, Any], ids, labels, cfg: Mapping[str, Any],
            operand_dtype=None):
    """(loss, logits [B, L, heads, vocab])."""
    import jax
    import jax.numpy as jnp

    assert not cfg["tie_word_embeddings"] and cfg["norm_add_unit_offset"] \
        and cfg["rope_scaling"] is None and not cfg["attention_bias"] \
        and cfg["num_key_value_heads"] == cfg["num_attention_heads"]

    def r(a):         # an operand as the matmul sees it
        return a if operand_dtype is None else a.astype(
            operand_dtype).astype(jnp.float32)

    x = p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        pre = f"blocks.{i}."
        x = jax.checkpoint(
            lambda p, x, pre=pre: _layer(p, pre, x, cfg, r))(p, x)
    hidden = _rms_norm(x, p["norm_f.weight"], cfg["rms_norm_eps"])
    P, V = cfg["num_pred_heads"], cfg["vocab_size"]
    logits = (r(hidden) @ r(p["heads"])).reshape(*ids.shape, P, V)
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    targets = head_targets(labels, P)
    there = targets >= 0
    picked = jnp.take_along_axis(
        logp, jnp.maximum(targets, 0)[..., None], axis=-1)[..., 0]
    per_head = -jnp.sum(jnp.where(there, picked, 0.0), axis=(0, 1)) \
        / jnp.sum(there, axis=(0, 1))
    return jnp.mean(per_head), logits


_COMPILED: Dict[Any, Any] = {}
_SHAPE_KEYS = ("num_hidden_layers", "num_attention_heads", "num_pred_heads",
               "vocab_size", "rms_norm_eps", "rope_theta", "window_size",
               "chunk_size")


def _value_and_grad(cfg: Mapping[str, Any], operand_dtype=None):
    """One jitted function a configuration: at full widths a compile is
    most of the reference's time."""
    import jax

    key = tuple(cfg[k] for k in _SHAPE_KEYS) + (operand_dtype,)
    if key not in _COMPILED:
        _COMPILED[key] = jax.jit(jax.value_and_grad(
            lambda p, ids, labels: forward(p, ids, labels, cfg,
                                           operand_dtype), has_aux=True))
    return _COMPILED[key]


def loss_and_grads(params: Mapping[str, Any], ids, labels,
                   cfg: Mapping[str, Any], operand_dtype=None
                   ) -> Dict[str, Any]:
    """One step's loss, logits and gradients on the batch ``ids`` [B, L].
    ``grads`` and ``logits`` stay where they were computed (jax arrays);
    ``compare`` reduces them there."""
    import jax
    import jax.numpy as jnp

    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    ids, labels = np.atleast_2d(ids), np.atleast_2d(labels)
    with jax.default_matmul_precision("highest"):
        (loss, logits), grads = _value_and_grad(cfg, operand_dtype)(
            p, jnp.asarray(ids), jnp.asarray(labels))
    return {"loss": float(loss), "logits": logits, "grads": grads}


def leaf_table(got: Mapping[str, Any], ref: Mapping[str, Any]
               ) -> Dict[str, Any]:
    """{leaf: [largest |reference gradient|, largest |difference|, L2 norm
    of the reference gradient, L2 norm of the difference, root-mean-square
    entry of the reference gradient]}: what the limits are read from."""
    import jax.numpy as jnp

    norm = lambda a: float(jnp.sqrt(jnp.sum(jnp.square(a))))
    out = {}
    for k, r in ref["grads"].items():
        d = jnp.asarray(got["grads"][k]) - r
        out[k] = [float(jnp.max(jnp.abs(r))), float(jnp.max(jnp.abs(d))),
                  norm(r), norm(d), norm(r) / math.sqrt(r.size)]
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
            mode: str = "amp", floored: bool = False) -> Dict[str, Any]:
    """The loss, the eight heads' logits (the largest difference over the
    largest logit) and every gradient leaf — the L2 norm of its difference
    over the leaf's norm (``grad_leaf_l2``), and its largest absolute
    difference over the leaf's largest entry (``grad_leaf_rel``) — against
    ``TOL[mode]``. ``floored`` (the trained state): the leaves whose
    reference gradient's root-mean-square entry lies under
    ``GRADIENT_FLOOR`` are left out, and named (``leaves_floored``)."""
    import jax.numpy as jnp

    tol = TOL[mode]
    floor = GRADIENT_FLOOR if floored else 0.0
    loss_rel = abs(got["loss"] - ref["loss"]) / abs(ref["loss"])
    logit_rel = float(
        jnp.max(jnp.abs(jnp.asarray(got["logits"], jnp.float32)
                        - ref["logits"])) / jnp.max(jnp.abs(ref["logits"])))
    # {leaf: [largest |gradient|, widest error over it, error's norm over
    # the leaf's]}, the widest first
    detail = sorted(((k, [top, err / top, dn / rn])
                     for k, (top, err, rn, dn, rms)
                     in leaf_table(got, ref).items()
                     if top > 0.0 and rms >= floor),
                    key=lambda kv: -kv[1][1])
    worst_leaf, (_, worst, _) = detail[0]
    l2_leaf, (_, _, l2) = max(detail, key=lambda kv: kv[1][2])
    out = {"mode": mode, "loss": [got["loss"], ref["loss"]],
           "loss_rel": loss_rel, "logit_rel": logit_rel,
           "grad_leaf_l2": l2, "grad_leaf_rel": worst,
           "worst_leaf": worst_leaf, "worst_leaf_l2": l2_leaf,
           "leaves": len(ref["grads"]), "leaves_compared": len(detail),
           "tol": tol, "worst_leaves": dict(detail[:8])}
    if floored:
        out["leaves_floored"] = sorted(set(ref["grads"]) - {
            k for k, _ in detail})
    out["ok"] = bool(
        np.isfinite(got["loss"])
        and len(detail) == len(ref["grads"]) - len(
            out.get("leaves_floored", ()))
        and loss_rel <= tol["loss_rel"] and logit_rel <= tol["logit_rel"]
        and l2 <= tol["grad_leaf_l2"] and worst <= tol["grad_leaf_rel"])
    return out
