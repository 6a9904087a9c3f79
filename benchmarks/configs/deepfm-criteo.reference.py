"""Plain reference for ``deepfm-criteo`` (as this repo's ``DeepFM`` runs
it; the configuration's ``departures`` say where that is not PaddleRec's):
DeepFM forward, log loss, gradients, plain SGD on the dense parameters and
the per-feature CTR AdaGrad rule on a table of rows — ``jax.numpy``,
float32, matmul precision ``highest``; no cache, no hash map, no packing,
no kernels, no sharding.

Follows PaddleRec ``models/rank/deepfm`` (first-order weight + second-order
FM over the slot embeddings + a ReLU tower over [embeddings, dense], plus a
linear term on the dense features as this repo's ``DeepFM`` has it) and the
shared-g2sum sparse AdaGrad of ``sparse_sgd_rule.cc``:

    scaled_g = g / push_show
    w       -= lr * scaled_g * sqrt(g0 / (g0 + g2sum));  clip to bounds
    g2sum   += mean_over_dims(scaled_g ** 2)

with show/click accumulation and lazy creation of the embedx block when
``(show - click) * nonclk_coeff + click * click_coeff >= embedx_threshold``
(a created block starts from zero state and takes this push's gradient:
the CPU accessor's order). Rows are a table keyed by feature: arrays
indexed by the position of the key in ``uniq``.

The dense optimizer of the comparison is SGD on purpose: its update is the
gradient times a constant, so the parameter difference measures the
gradients. (Adam's first step is lr*sign(g), which flips on rounding
wherever g is ~0 and would need a tolerance that hides real faults.)
"""

from __future__ import annotations

from typing import Any, Dict, Mapping

import numpy as np

#: Tolerances, with their reasons. Both sides run f32 with matmul precision
#: "highest", so what is left is summation order (a 221..400-wide dot, a
#: 4096-example mean, duplicate-key gradient sums) and transcendental
#: rounding. PR 21 measured, on the v5e, 2.9e-6 relative on the loss, 3e-8
#: absolute on the dense parameters and 1.5e-11 on the cache rows between
#: the chip and a CPU run of the same step. The bounds leave ~10x; a bf16
#: tower (8 mantissa bits) moves the loss by ~1e-3 and the parameters by
#: ~1e-5 and fails all three.
TOL = {"loss_rel": 3e-5, "params_abs": 1e-6, "rows_abs": 1e-6}

#: The step AS CONFIGURED AND MEASURED (``amp``: bf16 operands in every
#: ``linear`` with f32 accumulation; ``slab`` steps in one dispatch) against
#: this same f32 reference run step by step. The error of a parameter leaf
#: or a row column is the L2 norm of (system - reference) over the L2 norm
#: of the UPDATE the reference made to it, so it reads as a relative
#: gradient error, and a skipped update is 1.0 and a halved one 0.5
#: whatever the size of the weights. An f32 slab against this reference
#: reads 1e-7..9e-7 on every entry (CPU, PR 23), so all that is left is
#: precision. Measured with bf16, batch 4096, slab 8, six seeds (CPU, PR 23;
#: the chip's readings are in PERF.md section 6): loss 1.4e-4..3.8e-4; dense
#: leaves 0.0045..0.0049 (a mean over 4096 examples: the clean reading of the
#: tower's precision; fp8 operands would read ~16x); embed_w and its g2sum
#: 4.4e-4..8.4e-4 (no tower on that path: the logit's error alone);
#: embedx_w 0.070..0.075 and its g2sum 0.037..0.039 — most keys are seen
#: once, so their gradient comes through ONE example's ReLU masks, and
#: bf16 pre-activations flip 0.3 of the first layer's 400 units per example
#: (counted; more in the two layers behind it), each flip ~1/sqrt(200
#: active units) = 7% of that example's input gradient. The bounds leave 2x
#: on embedx, 2.4..4x elsewhere.
TOL_AMP = {"loss_rel": 1.5e-3, "params_upd_rel": 1.5e-2,
           "rows_upd_rel": {"embed_w": 2e-3, "embed_state": 2e-3,
                            "embedx_w": 0.15, "embedx_state": 0.08}}
#: small integers (counts and the created flag): equal or wrong
EXACT_COLS = ("show", "click", "has_embedx")


def _forward(params, emb, dense):
    import jax.numpy as jnp

    w1, v = emb[..., 0], emb[..., 1:]
    first = jnp.sum(w1, axis=-1)
    sum_v = jnp.sum(v, axis=1)
    second = 0.5 * jnp.sum(sum_v * sum_v - jnp.sum(v * v, axis=1), axis=-1)
    x = jnp.concatenate([v.reshape(v.shape[0], -1), dense], axis=-1)
    n = sum(1 for k in params if k.startswith("dnn.layers.")
            and k.endswith(".weight"))
    for i in range(n):
        x = x @ params[f"dnn.layers.{i}.weight"] + params[f"dnn.layers.{i}.bias"]
        if i + 1 < n:
            x = jnp.maximum(x, 0.0)
    lin = dense @ params["dense_lin.weight"] + params["dense_lin.bias"]
    return first + second + x[:, 0] + lin[:, 0]


def _loss(params, emb, dense, labels):
    import jax.numpy as jnp

    z = _forward(params, emb, dense)
    y = labels.astype(jnp.float32)
    # log loss on the logit: softplus(z) - y*z, written stably
    return jnp.mean(jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def _adagrad(w, g2sum, g, scale, lr, g0, bounds):
    import jax.numpy as jnp

    sg = g / scale
    w = jnp.clip(w - lr * sg * jnp.sqrt(g0 / (g0 + g2sum)), *bounds)
    return w, g2sum + jnp.mean(sg * sg, axis=1, keepdims=True)


def _one_step(params, rows, inv, dense, labels, hyper):
    import jax
    import jax.numpy as jnp

    B, S = inv.shape
    n = rows["show"].shape[0]
    emb = jnp.concatenate([rows["embed_w"], rows["embedx_w"]], axis=1)[inv]
    loss, (g_params, g_emb) = jax.value_and_grad(_loss, argnums=(0, 1))(
        params, emb, dense, labels)
    params = {k: v - hyper["lr_dense"] * g_params[k] for k, v in params.items()}

    flat = inv.reshape(-1)
    seg = lambda x: jax.ops.segment_sum(x, flat, num_segments=n)
    g = seg(g_emb.reshape(B * S, -1))
    dshow = seg(jnp.ones(B * S, jnp.float32))
    dclick = seg(jnp.repeat(labels.astype(jnp.float32), S))
    touched = (dshow > 0)[:, None]
    scale = jnp.maximum(dshow, 1e-10)[:, None]
    lr, g0, bounds = (hyper["lr_sparse"], hyper["initial_g2sum"],
                      hyper["weight_bounds"])
    show, click = rows["show"] + dshow, rows["click"] + dclick
    ew, es = _adagrad(rows["embed_w"], rows["embed_state"], g[:, :1], scale,
                      lr, g0, bounds)
    score = ((show - click) * hyper["nonclk_coeff"]
             + click * hyper["click_coeff"])
    had = rows["has_embedx"] > 0
    create = (~had) & (score >= hyper["embedx_threshold"]) & touched[:, 0]
    live = (had | create)[:, None]
    xs_base = jnp.where(create[:, None], 0.0, rows["embedx_state"])
    xw, xs = _adagrad(rows["embedx_w"], xs_base, g[:, 1:], scale, lr, g0,
                      bounds)
    keep = lambda new, old, m=touched: jnp.where(m, new, old)
    new_rows = {
        "show": show, "click": click,
        "embed_w": keep(ew, rows["embed_w"]),
        "embed_state": keep(es, rows["embed_state"]),
        "embedx_w": keep(xw, rows["embedx_w"], touched & live),
        "embedx_state": keep(xs, rows["embedx_state"], touched & live),
        "has_embedx": jnp.where(create, 1.0, rows["has_embedx"]),
    }
    return params, new_rows, loss


def steps(params: Mapping[str, np.ndarray], uniq: np.ndarray,
          rows: Mapping[str, np.ndarray], batches, hyper: Mapping[str, Any],
          table_rows: int) -> Dict[str, Any]:
    """One SGD step per entry of ``batches`` = [(keys [B, S], dense, labels),
    ...], in order, on one table. ``uniq`` [n] sorted unique keys, ``rows``
    the table before the first step (column -> [n, ...]). A batch given
    twice makes its second step run on rows that carry optimizer state and
    created embedx blocks."""
    import jax
    import jax.numpy as jnp

    # the table is padded to ``table_rows`` (>= n, fixed by the caller), so
    # that the program has the same shapes whatever the seed (the number of
    # distinct keys varies) and is compiled once; the padding rows are never
    # referenced
    n = len(uniq)
    assert n <= table_rows, (n, table_rows)
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    r = {}
    for k, v in rows.items():
        padded = np.zeros((table_rows,) + np.shape(v)[1:], np.float32)
        padded[:n] = v
        r[k] = jnp.asarray(padded)
    hyper = dict(hyper)
    step = jax.jit(lambda p, r, inv, x, y: _one_step(p, r, inv, x, y, hyper))
    losses = []
    with jax.default_matmul_precision("highest"):
        for keys, dense, labels in batches:
            inv = jnp.asarray(np.searchsorted(uniq, keys))
            p, r, loss = step(p, r, inv, jnp.asarray(dense, jnp.float32),
                              jnp.asarray(labels))
            losses.append(float(loss))
    return {"loss": losses,
            "params": {k: np.asarray(v) for k, v in p.items()},
            "rows": {k: np.asarray(v)[:n] for k, v in r.items()}}


def _loss_rel(got, ref) -> float:
    return max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))


def compare(got: Mapping[str, Any], ref: Mapping[str, Any]) -> Dict[str, Any]:
    """The f32 step: absolute differences (``TOL``)."""
    loss_rel = _loss_rel(got, ref)
    params_abs = max(float(np.max(np.abs(got["params"][k] - ref["params"][k])))
                     for k in ref["params"])
    rows_abs = max(float(np.max(np.abs(
        np.asarray(got["rows"][k], np.float64).reshape(ref["rows"][k].shape)
        - ref["rows"][k]))) for k in ref["rows"])
    ok = (loss_rel <= TOL["loss_rel"] and params_abs <= TOL["params_abs"]
          and rows_abs <= TOL["rows_abs"]
          and all(np.isfinite(x) for x in got["loss"]))
    return {"ok": bool(ok), "loss": [got["loss"], ref["loss"]],
            "loss_rel": loss_rel, "params_abs": params_abs,
            "rows_abs": rows_abs, "tol": TOL}


def compare_updates(got: Mapping[str, Any], ref: Mapping[str, Any],
                    before: Mapping[str, Any]) -> Dict[str, Any]:
    """The configured step: each leaf's and column's error over the largest
    update the reference made to it (``TOL_AMP``)."""

    def upd_rel(g, r, b):
        r = np.asarray(r, np.float64)
        g = np.asarray(g, np.float64).reshape(r.shape)
        moved = np.linalg.norm(r - np.asarray(b, np.float64).reshape(r.shape))
        return float(np.linalg.norm(g - r) / moved) if moved else float("inf")

    loss_rel = _loss_rel(got, ref)
    params = {k: upd_rel(got["params"][k], v, before["params"][k])
              for k, v in ref["params"].items()}
    rows = {k: upd_rel(got["rows"][k], v, before["rows"][k])
            for k, v in ref["rows"].items() if k not in EXACT_COLS}
    exact = all(np.array_equal(
        np.asarray(got["rows"][k]).reshape(ref["rows"][k].shape),
        ref["rows"][k]) for k in EXACT_COLS)
    ok = (loss_rel <= TOL_AMP["loss_rel"] and exact
          and max(params.values()) <= TOL_AMP["params_upd_rel"]
          and all(v <= TOL_AMP["rows_upd_rel"][k] for k, v in rows.items())
          and all(np.isfinite(x) for x in got["loss"]))
    return {"ok": bool(ok), "loss_first_last": [
                [got["loss"][0], got["loss"][-1]],
                [ref["loss"][0], ref["loss"][-1]]],
            "loss_rel": loss_rel, "counts_exact": bool(exact),
            "params_upd_rel": max(params.values()),
            "rows_upd_rel": rows, "tol": dict(TOL_AMP, counts_exact=True)}
