"""Pallas fused sparse-optimizer kernel (per-row CTR update, all rules).

The reference applies its sparse optimizer on-device inside the
hashtable update kernels (`/root/reference/paddle/fluid/framework/fleet/
heter_ps/optimizer.cuh.h:27-100` — update_lr/update_mf/update_value with
show/click coeffs, bounds, lazy mf creation), one GPU thread per row;
the CPU server supports the full rule family (sparse_sgd_rule.h:27-135:
naive / AdaGrad shared-g2sum / StdAdaGrad per-dim / Adam). The TPU
decomposition: random-access gather/scatter stays on XLA (the hardware's
bulk path — per-row DMA loops in Pallas serialize), and the PER-ROW
OPTIMIZER MATH between gather and scatter is one fused Pallas kernel:
every state column of a block of touched rows updates in a single VMEM
pass. All four reference rules are supported for both the embed (1-d)
and embedx (dim-d) blocks; the rule math lives in ``rule_update`` which
is shared verbatim by the kernel body and the jnp form
(``ps.embedding_cache.cache_push_sparse`` runs the jnp form unless
``CacheConfig.pallas_update=True`` asks for the kernel: on the v5e the
kernel measured slower, PR 25, its ``[n, 1]`` operands pad to 128 lanes;
bit-parity is tested in tests/test_sparse_optimizer.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.enforce import enforce

__all__ = ["ctr_sparse_rows", "rule_update", "rule_state_dim",
           "rule_init_state"]


def rule_state_dim(rule: str, dim: int) -> int:
    """Optimizer-state floats per feature (sparse_sgd_rule slot dims)."""
    return {"naive": 0, "adagrad": 1, "std_adagrad": dim,
            "adam": 2 * dim + 2}[rule]


def rule_init_state(rule: str, n: int, dim: int, *, beta1: float,
                    beta2: float):
    """Fresh-feature optimizer state (zeros; Adam's beta powers start at
    beta1/beta2 — sparse_sgd_rule.cc InitValueWork)."""
    if rule == "adam":
        # built by concatenation, not .at[].set: Mosaic has no scatter
        # lowering and this runs inside the Pallas rule kernel
        return jnp.concatenate(
            [jnp.zeros((n, 2 * dim), jnp.float32),
             jnp.full((n, 1), beta1, jnp.float32),
             jnp.full((n, 1), beta2, jnp.float32)], axis=1)
    return jnp.zeros((n, rule_state_dim(rule, dim)), jnp.float32)


def _m32(a, b):
    """f32 multiply with PINNED operand binding and IEEE rounding.

    The sparse rules must produce the same bits as the host engines
    (csrc builds with -ffp-contract=off; numpy never contracts) — the
    hot embedding tier round-trips rows between them. Two XLA behaviors
    break that on a plain ``a * b`` chain:

    - LLVM contracts a single-use `mul` feeding an `add`/`sub` into one
      FMA (no intermediate rounding);
    - the HLO algebraic simplifier re-associates scalar-constant mul
      chains (``lr*sg*ratio`` becomes ``sg*(lr*ratio)`` — the constant
      sinks onto the narrower broadcast operand).

    Every pure seal was tried and folded away (optimization_barrier,
    reduce_precision(8,23), bitcast pairs, min/max(±inf), +0.0); what
    holds is making the product MULTI-USE via ``t + 0*t``: LLVM only
    forms fmuladd from a single-use mul, XLA keeps ``0*x`` under strict
    inf/nan semantics, and the add consumer breaks the mul-chain pattern
    the re-associator matches on. Cost: one extra fused mul+add per
    element. Known edge: t=±inf becomes NaN here (0·inf) — already
    -diverged training only, and the nan/inf guard surfaces it anyway."""
    t = a * b
    return t + jnp.float32(0.0) * t


def rule_update(rule: str, w, state, g, scale, *, lr, initial_g2sum,
                wmin, wmax, beta1, beta2, eps):
    """One batched rule step on touched rows: (w [n,d], state [n,sd],
    g [n,d] merged grads, scale [n,1] push_show) -> (w', state').
    Mirrors this repo's host rules (ps/sgd_rule.py) exactly — which
    follow sparse_sgd_rule.cc (SURVEY Appendix A.2) except that Adam
    adds epsilon to the bias-corrected sqrt(v_hat) rather than the
    reference's raw sqrt(v) (an eps-placement difference only). Adam
    ignores the scale like the reference."""
    clip = lambda x: jnp.clip(x, wmin, wmax)
    lrf = jnp.float32(lr)
    if rule == "naive":
        return clip(w - _m32(lrf, g)), state
    if rule == "adagrad":  # one shared g2sum per feature
        sg = g / scale
        ratio = jnp.sqrt(initial_g2sum / (initial_g2sum + state))
        w2 = clip(w - _m32(_m32(lrf, sg), ratio))
        # g2sum accumulates in the native table's association (sequential
        # over dims, ONE divide at the end — sparse_table.h kRuleAdaGrad);
        # jnp.mean's tree reduce re-associates the f32 sum and breaks
        # bit-parity with the host/PS rows the hot tier must round-trip
        add = _m32(sg[:, 0], sg[:, 0])
        for i in range(1, g.shape[1]):
            add = add + _m32(sg[:, i], sg[:, i])
        return w2, state + (add / jnp.float32(g.shape[1]))[:, None]
    if rule == "std_adagrad":  # per-dim g2sum
        sg = g / scale
        ratio = jnp.sqrt(initial_g2sum / (initial_g2sum + state))
        return (clip(w - _m32(_m32(lrf, sg), ratio)), state + _m32(sg, sg))
    if rule == "adam":
        d = w.shape[1]
        m, v = state[:, :d], state[:, d:2 * d]
        b1p, b2p = state[:, 2 * d:2 * d + 1], state[:, 2 * d + 1:2 * d + 2]
        # (1 - beta) must round through f32 like the native rule's
        # `1.0f - cfg.beta1` — the python-double difference (1e-8 on
        # beta1=0.9) compounds into m/v and breaks row bit-parity
        b1f, b2f = jnp.float32(beta1), jnp.float32(beta2)
        one = jnp.float32(1.0)
        m2 = _m32(b1f, m) + _m32(one - b1f, g)
        v2 = _m32(b2f, v) + _m32(_m32(one - b2f, g), g)
        m_hat = m2 / (one - b1p)
        v_hat = v2 / (one - b2p)
        w2 = clip(w - _m32(lrf, m_hat) / (jnp.sqrt(v_hat) + eps))
        return w2, jnp.concatenate(
            [m2, v2, _m32(b1p, b1f), _m32(b2p, b2f)], axis=1)
    raise KeyError(f"unknown sparse sgd rule {rule!r}")


def fused_row_update(show, click, ew, estate, xw, xstate, has,
                     dshow, dclick, ge, gx,
                     *, embed_rule, embedx_rule, dim, lr, initial_g2sum,
                     wmin, wmax, beta1, beta2, eps, nonclk_coeff,
                     click_coeff, embedx_threshold, create_applies_grad):
    """The complete per-row CTR update on plain arrays (touched rows,
    pre-merged): show/click accumulation, embed rule step, lazy embedx
    creation, embedx rule step. ONE definition shared by the Pallas
    kernel body and the jnp fallback — divergence between the two paths
    is structurally impossible. Returns the seven updated columns.

    State arrays may carry one extra dummy column when the rule is
    stateless (the kernel's block specs need width >= 1); the rule
    ignores it and it round-trips unchanged."""
    upd = functools.partial(rule_update, lr=lr, initial_g2sum=initial_g2sum,
                            wmin=wmin, wmax=wmax, beta1=beta1, beta2=beta2,
                            eps=eps)
    # Mosaic lowers [n] -> [n,1] reshapes only for 32-bit types, so bool
    # masks broadcast to columns via f32 + compare, never via i1 reshape
    col = lambda m: m.astype(jnp.float32)[:, None] > 0.5

    show_new = show + dshow
    click_new = click + dclick
    scale = jnp.maximum(dshow, 1e-10)[:, None]

    es = rule_state_dim(embed_rule, 1)
    xs = rule_state_dim(embedx_rule, dim)
    ew_new, es_new = upd(embed_rule, ew, estate[:, :max(es, 1)], ge, scale)

    # lazy embedx creation on the show/click score: created rows start
    # from INIT state; create_applies_grad selects CPU (create + apply,
    # ctr_accessor.cc order) vs GPU (create only, optimizer.cuh.h:81-94)
    # the host computes this over totals too (pstpu::show_click_score);
    # both products sealed so the create-threshold compare sees the same
    # bits as the PS and creation fires on the same push
    score = (_m32(show_new - click_new, jnp.float32(nonclk_coeff))
             + _m32(click_new, jnp.float32(click_coeff)))
    had = has > 0
    create = jnp.logical_and(jnp.logical_not(had),
                             score >= embedx_threshold)
    apply_mask = jnp.logical_or(had, create) if create_applies_grad else had
    n = show.shape[0]
    if xs > 0:
        init = rule_init_state(embedx_rule, n, dim, beta1=beta1, beta2=beta2)
        st_base = jnp.where(col(create), init, xstate)
    else:
        st_base = xstate[:, :max(xs, 1)]
    xw_new, xs_new = upd(embedx_rule, xw, st_base, gx, scale)

    return (show_new, click_new, ew_new,
            es_new if es > 0 else estate,
            jnp.where(col(apply_mask), xw_new, xw),
            jnp.where(col(apply_mask), xs_new, st_base) if xs > 0 else xstate,
            jnp.where(create, 1.0, has))


def _kernel(show_ref, click_ref, ew_ref, es_ref, xw_ref, xs_ref, has_ref,
            dshow_ref, dclick_ref, ge_ref, gx_ref,
            o_show, o_click, o_ew, o_es, o_xw, o_xs, o_has,
            **fused_kwargs):
    outs = fused_row_update(
        show_ref[...], click_ref[...], ew_ref[...], es_ref[...],
        xw_ref[...], xs_ref[...], has_ref[...],
        dshow_ref[...], dclick_ref[...], ge_ref[...], gx_ref[...],
        **fused_kwargs)
    for ref, val in zip((o_show, o_click, o_ew, o_es, o_xw, o_xs, o_has),
                        outs):
        ref[...] = val


def ctr_sparse_rows(
    rows_state: Tuple[jax.Array, ...],  # show, click, ew, estate, xw, xstate, has
    dshow: jax.Array,   # [n] merged show deltas
    dclick: jax.Array,  # [n]
    g_embed: jax.Array,   # [n, 1] merged embed grads
    g_embedx: jax.Array,  # [n, dim]
    *,
    embed_rule: str, embedx_rule: str,
    lr: float, initial_g2sum: float, weight_bounds: Tuple[float, float],
    beta1: float, beta2: float, eps: float,
    nonclk_coeff: float, click_coeff: float, embedx_threshold: float,
    create_applies_grad: bool = True,
    block: int = 1024,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, ...]:
    """Fused per-row CTR update over gathered rows; returns the updated
    seven state columns in the same order. Rows are pre-merged uniques
    (the caller's segment-sum); padding rows are fine — the caller's
    scatter drops them. State columns may be zero-width (naive rule): a
    one-column dummy is threaded through the kernel and sliced away."""
    show, click, ew, estate, xw, xstate, has = rows_state
    n = show.shape[0]
    dim = xw.shape[1]
    es = rule_state_dim(embed_rule, 1)
    xs = rule_state_dim(embedx_rule, dim)
    # enforce (not assert): a mismatched cache/table state layout must
    # fail loudly even under python -O, not corrupt rows silently
    enforce(estate.shape[1] == es and xstate.shape[1] == xs,
            f"optimizer-state width mismatch: estate {estate.shape} vs "
            f"{es}, xstate {xstate.shape} vs {xs}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # zero-width state -> one dummy column through the kernel
    estate_k = estate if es > 0 else jnp.zeros((n, 1), jnp.float32)
    xstate_k = xstate if xs > 0 else jnp.zeros((n, 1), jnp.float32)
    wes, wxs = estate_k.shape[1], xstate_k.shape[1]
    bn = min(block, n)
    grid = (pl.cdiv(n, bn),)

    def spec1(): return pl.BlockSpec((bn,), lambda i: (i,))
    def spec2(d): return pl.BlockSpec((bn, d), lambda i: (i, 0))

    kern = functools.partial(
        _kernel, embed_rule=embed_rule, embedx_rule=embedx_rule, dim=dim,
        lr=lr, initial_g2sum=initial_g2sum,
        wmin=weight_bounds[0], wmax=weight_bounds[1],
        beta1=beta1, beta2=beta2, eps=eps,
        nonclk_coeff=nonclk_coeff, click_coeff=click_coeff,
        embedx_threshold=embedx_threshold,
        create_applies_grad=create_applies_grad)
    out_shapes = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                  for a in (show, click, ew, estate_k, xw, xstate_k, has)]
    out_specs = [spec1(), spec1(), spec2(1), spec2(wes), spec2(dim),
                 spec2(wxs), spec1()]
    in_specs = [spec1(), spec1(), spec2(1), spec2(wes), spec2(dim),
                spec2(wxs), spec1(), spec1(), spec1(), spec2(1), spec2(dim)]
    out = pl.pallas_call(
        kern,
        name="ctr_sparse_rows",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(show, click, ew, estate_k, xw, xstate_k, has, dshow, dclick,
      g_embed, g_embedx)
    o_show, o_click, o_ew, o_es, o_xw, o_xs, o_has = out
    if es == 0:
        o_es = estate
    if xs == 0:
        o_xs = xstate
    return o_show, o_click, o_ew, o_es, o_xw, o_xs, o_has
