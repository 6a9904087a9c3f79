"""Optimizers (``paddle.optimizer`` analogue).

Pure-functional update rules over parameter pytrees — the jit-friendly
replacement for the reference's per-op optimizer kernels
(phi/kernels/*/sgd_kernel, adam_kernel, …). Each optimizer exposes:

    opt.init(params)                       -> opt_state
    opt.update(grads, opt_state, params)   -> (new_params, new_opt_state)

Both are pure and traceable: the whole train step (fwd + bwd + update)
compiles to one XLA program. Eager paddle-style ``opt.step()`` does not
exist here — the Trainer/SpmdTrainer own the step loop and call
``update`` inside the compiled program.

Per-feature *sparse* optimizer rules (AdaGrad with shared g2sum, show/click
scaling — sparse_sgd_rule.cc semantics) live in ``paddle_tpu.ps.sgd_rule``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.enforce import InvalidArgumentError

__all__ = [
    "Optimizer",
    "SGD",
    "Momentum",
    "Adam",
    "AdamW",
    "Adagrad",
    "Adadelta",
    "Adamax",
    "RMSProp",
    "Lars",
    "Lamb",
    "MasterWeights",
    "decorate_o2",
    "ClipGradByGlobalNorm",
    "ClipGradByNorm",
    "ClipGradByValue",
    "lr",
]

PyTree = Any


def _tree_map(fn, *trees, **kwargs):
    return jax.tree_util.tree_map(fn, *trees, **kwargs)


def map_param_slots(slots: PyTree, params: PyTree, mirror_fn: Callable,
                    other_leaf_fn: Callable) -> PyTree:
    """Walk an optimizer's slot tree: apply ``mirror_fn`` to each maximal
    subtree whose pytree structure equals ``params``'s (Momentum's slots,
    each of Adam's m/v, …), recurse through container dicts, and map any
    remaining leaves with ``other_leaf_fn`` (scalar schedule state). The
    ONE place that encodes "slots mirror the params tree" — used by the
    hybrid trainer's ZeRO slot sharding and the auto-parallel Engine."""
    pstruct = jax.tree_util.tree_structure(params)

    def rec(sub):
        if sub is None:
            return None
        if jax.tree_util.tree_structure(sub) == pstruct:
            return mirror_fn(sub)
        if isinstance(sub, dict):
            return type(sub)((k, rec(v)) for k, v in sub.items())
        return jax.tree_util.tree_map(other_leaf_fn, sub)

    return rec(slots)


def global_norm(tree: PyTree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves))


class _GradClip:
    def __call__(self, grads: PyTree) -> PyTree:
        raise NotImplementedError


class ClipGradByGlobalNorm(_GradClip):
    """``paddle.nn.ClipGradByGlobalNorm``: scale all grads so the global
    L2 norm is at most ``clip_norm``."""

    def __init__(self, clip_norm: float) -> None:
        self.clip_norm = float(clip_norm)

    def __call__(self, grads: PyTree) -> PyTree:
        norm = global_norm(grads)
        scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(norm, 1e-12))
        return _tree_map(lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)


class ClipGradByNorm(_GradClip):
    def __init__(self, clip_norm: float) -> None:
        self.clip_norm = float(clip_norm)

    def __call__(self, grads: PyTree) -> PyTree:
        def clip_one(g):
            n = jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32))))
            scale = jnp.minimum(1.0, self.clip_norm / jnp.maximum(n, 1e-12))
            return (g * scale).astype(g.dtype)

        return _tree_map(clip_one, grads)


class ClipGradByValue(_GradClip):
    def __init__(self, max_value: float, min_value: Optional[float] = None) -> None:
        self.max_value = float(max_value)
        self.min_value = float(min_value) if min_value is not None else -self.max_value

    def __call__(self, grads: PyTree) -> PyTree:
        return _tree_map(lambda g: jnp.clip(g, self.min_value, self.max_value), grads)


class _LRSchedule:
    """Step→lr schedule; called inside the compiled step with a traced
    step counter so LR decay stays in-graph (the reference runs lr decay
    server-side via GlobalStepTable — here it's just math)."""

    def __call__(self, step: jax.Array) -> jax.Array:
        raise NotImplementedError


class _ConstantLR(_LRSchedule):
    def __init__(self, value: float) -> None:
        self.value = float(value)

    def __call__(self, step):
        return jnp.asarray(self.value, jnp.float32)


class _LambdaLR(_LRSchedule):
    def __init__(self, fn: Callable[[jax.Array], jax.Array]) -> None:
        self.fn = fn

    def __call__(self, step):
        return jnp.asarray(self.fn(step), jnp.float32)


class lr:
    """Namespace of LR schedules (``paddle.optimizer.lr`` analogue)."""

    @staticmethod
    def constant(value: float) -> _LRSchedule:
        return _ConstantLR(value)

    @staticmethod
    def exponential_decay(base_lr: float, gamma: float) -> _LRSchedule:
        return _LambdaLR(lambda step: base_lr * jnp.power(gamma, step.astype(jnp.float32)))

    @staticmethod
    def cosine_decay(base_lr: float, t_max: int, eta_min: float = 0.0) -> _LRSchedule:
        def fn(step):
            t = jnp.minimum(step.astype(jnp.float32), t_max)
            return eta_min + 0.5 * (base_lr - eta_min) * (1 + jnp.cos(jnp.pi * t / t_max))

        return _LambdaLR(fn)

    @staticmethod
    def warmup_linear(base_lr: float, warmup_steps: int, total_steps: int) -> _LRSchedule:
        def fn(step):
            s = step.astype(jnp.float32)
            warm = base_lr * s / jnp.maximum(warmup_steps, 1)
            decay = base_lr * jnp.maximum(0.0, (total_steps - s) / jnp.maximum(total_steps - warmup_steps, 1))
            return jnp.where(s < warmup_steps, warm, decay)

        return _LambdaLR(fn)

    @staticmethod
    def piecewise_decay(boundaries, values) -> _LRSchedule:
        """``paddle.optimizer.lr.PiecewiseDecay``: step-indexed constant
        segments."""
        bnd = jnp.asarray(list(boundaries), jnp.int32)
        val = jnp.asarray(list(values), jnp.float32)

        def fn(step):
            idx = jnp.sum((step >= bnd).astype(jnp.int32))
            return val[idx]

        return _LambdaLR(fn)

    @staticmethod
    def polynomial_decay(base_lr: float, decay_steps: int, end_lr: float = 0.0,
                         power: float = 1.0) -> _LRSchedule:
        def fn(step):
            t = jnp.minimum(step.astype(jnp.float32), decay_steps) / decay_steps
            return (base_lr - end_lr) * jnp.power(1.0 - t, power) + end_lr

        return _LambdaLR(fn)

    @staticmethod
    def noam_decay(d_model: int, warmup_steps: int, base_lr: float = 1.0) -> _LRSchedule:
        """``paddle.optimizer.lr.NoamDecay`` (transformer schedule)."""

        def fn(step):
            s = jnp.maximum(step.astype(jnp.float32), 1.0)
            return base_lr * d_model ** -0.5 * jnp.minimum(s ** -0.5, s * warmup_steps ** -1.5)

        return _LambdaLR(fn)

    @staticmethod
    def step_decay(base_lr: float, step_size: int, gamma: float = 0.1) -> _LRSchedule:
        def fn(step):
            return base_lr * jnp.power(gamma, (step // step_size).astype(jnp.float32))

        return _LambdaLR(fn)


def _as_schedule(learning_rate) -> _LRSchedule:
    if isinstance(learning_rate, _LRSchedule):
        return learning_rate
    return _ConstantLR(float(learning_rate))


class Optimizer:
    """Base: functional init/update plus an internal step counter."""

    def __init__(
        self,
        learning_rate=0.001,
        grad_clip: Optional[_GradClip] = None,
        weight_decay: float = 0.0,
    ) -> None:
        self.schedule = _as_schedule(learning_rate)
        self.grad_clip = grad_clip
        self.weight_decay = float(weight_decay)

    # -- functional core --------------------------------------------------

    def init(self, params: PyTree) -> Dict[str, Any]:
        return {"step": jnp.zeros((), jnp.int32), "slots": self._init_slots(params)}

    def update(
        self, grads: PyTree, opt_state: Dict[str, Any], params: PyTree
    ) -> Tuple[PyTree, Dict[str, Any]]:
        # the one scope every dense update runs under, whatever wraps
        # this optimizer (core/profiler.DEVICE_SCOPES)
        with jax.named_scope("pt.dense_opt"):
            if self.grad_clip is not None:
                grads = self.grad_clip(grads)
            step = opt_state["step"]
            lr_t = self.schedule(step)
            new_params, new_slots = self._apply(
                grads, opt_state["slots"], params, lr_t, step)
            return new_params, {"step": step + 1, "slots": new_slots}

    def _init_slots(self, params: PyTree) -> PyTree:
        raise NotImplementedError

    def _apply(self, grads, slots, params, lr_t, step):
        raise NotImplementedError

    # -- decoupled/coupled weight decay helper ----------------------------

    def _decay_grad(self, g, p):
        if self.weight_decay:
            return g + self.weight_decay * p
        return g


class SGD(Optimizer):
    def _init_slots(self, params):
        return None

    def _apply(self, grads, slots, params, lr_t, step):
        new_params = _tree_map(lambda p, g: p - lr_t * self._decay_grad(g, p), params, grads)
        return new_params, None


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, use_nesterov=False, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.momentum = float(momentum)
        self.use_nesterov = use_nesterov

    def _init_slots(self, params):
        return _tree_map(jnp.zeros_like, params)

    def _apply(self, grads, slots, params, lr_t, step):
        def upd(p, g, v):
            g = self._decay_grad(g, p)
            v_new = self.momentum * v + g
            if self.use_nesterov:
                return p - lr_t * (g + self.momentum * v_new), v_new
            return p - lr_t * v_new, v_new

        pairs = _tree_map(upd, params, grads, slots)
        new_params = _tree_map(lambda pair: pair[0], pairs, is_leaf=lambda x: isinstance(x, tuple))
        new_slots = _tree_map(lambda pair: pair[1], pairs, is_leaf=lambda x: isinstance(x, tuple))
        return new_params, new_slots


class Adam(Optimizer):
    def __init__(
        self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, **kw
    ) -> None:
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = float(beta1), float(beta2), float(epsilon)
        self.decoupled = False

    def _init_slots(self, params):
        return {
            "m": _tree_map(jnp.zeros_like, params),
            "v": _tree_map(jnp.zeros_like, params),
        }

    def _apply(self, grads, slots, params, lr_t, step):
        t = (step + 1).astype(jnp.float32)
        bc1 = 1 - jnp.power(self.beta1, t)
        bc2 = 1 - jnp.power(self.beta2, t)

        def upd(p, g, m, v):
            if self.decoupled:
                p = p * (1 - lr_t * self.weight_decay)
            else:
                g = self._decay_grad(g, p)
            m_new = self.beta1 * m + (1 - self.beta1) * g
            v_new = self.beta2 * v + (1 - self.beta2) * jnp.square(g)
            m_hat = m_new / bc1
            v_hat = v_new / bc2
            p_new = p - lr_t * m_hat / (jnp.sqrt(v_hat) + self.epsilon)
            return p_new, m_new, v_new

        triples = _tree_map(upd, params, grads, slots["m"], slots["v"])
        is_leaf = lambda x: isinstance(x, tuple)
        return (
            _tree_map(lambda tr: tr[0], triples, is_leaf=is_leaf),
            {
                "m": _tree_map(lambda tr: tr[1], triples, is_leaf=is_leaf),
                "v": _tree_map(lambda tr: tr[2], triples, is_leaf=is_leaf),
            },
        )


class AdamW(Adam):
    def __init__(self, learning_rate=0.001, weight_decay=0.01, **kw) -> None:
        super().__init__(learning_rate, weight_decay=weight_decay, **kw)
        self.decoupled = True


class Adagrad(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, initial_accumulator_value=0.0, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.epsilon = float(epsilon)
        self.initial_accumulator_value = float(initial_accumulator_value)

    def _init_slots(self, params):
        return _tree_map(lambda p: jnp.full_like(p, self.initial_accumulator_value), params)

    def _apply(self, grads, slots, params, lr_t, step):
        def upd(p, g, acc):
            g = self._decay_grad(g, p)
            acc_new = acc + jnp.square(g)
            return p - lr_t * g / (jnp.sqrt(acc_new) + self.epsilon), acc_new

        pairs = _tree_map(upd, params, grads, slots)
        is_leaf = lambda x: isinstance(x, tuple)
        return (
            _tree_map(lambda pr: pr[0], pairs, is_leaf=is_leaf),
            _tree_map(lambda pr: pr[1], pairs, is_leaf=is_leaf),
        )


class Adadelta(Optimizer):
    """``paddle.optimizer.Adadelta`` (phi adadelta_kernel semantics):
    accumulated squared grads + accumulated squared updates, rho decay."""

    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.rho, self.epsilon = float(rho), float(epsilon)

    def _init_slots(self, params):
        return {
            "avg_sq_grad": _tree_map(jnp.zeros_like, params),
            "avg_sq_update": _tree_map(jnp.zeros_like, params),
        }

    def _apply(self, grads, slots, params, lr_t, step):
        def upd(p, g, ag, au):
            g = self._decay_grad(g, p)
            ag_new = self.rho * ag + (1 - self.rho) * jnp.square(g)
            update = (jnp.sqrt(au + self.epsilon)
                      / jnp.sqrt(ag_new + self.epsilon)) * g
            au_new = self.rho * au + (1 - self.rho) * jnp.square(update)
            return p - lr_t * update, ag_new, au_new

        triples = _tree_map(upd, params, grads, slots["avg_sq_grad"],
                            slots["avg_sq_update"])
        is_leaf = lambda x: isinstance(x, tuple)
        return (
            _tree_map(lambda tr: tr[0], triples, is_leaf=is_leaf),
            {
                "avg_sq_grad": _tree_map(lambda tr: tr[1], triples, is_leaf=is_leaf),
                "avg_sq_update": _tree_map(lambda tr: tr[2], triples, is_leaf=is_leaf),
            },
        )


class Adamax(Optimizer):
    """``paddle.optimizer.Adamax`` (phi adamax_kernel semantics): Adam
    with an infinity-norm second moment."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.beta1, self.beta2, self.epsilon = float(beta1), float(beta2), float(epsilon)

    def _init_slots(self, params):
        return {
            "m": _tree_map(jnp.zeros_like, params),
            "u": _tree_map(jnp.zeros_like, params),
        }

    def _apply(self, grads, slots, params, lr_t, step):
        t = (step + 1).astype(jnp.float32)
        bc1 = 1 - jnp.power(self.beta1, t)

        def upd(p, g, m, u):
            g = self._decay_grad(g, p)
            m_new = self.beta1 * m + (1 - self.beta1) * g
            u_new = jnp.maximum(self.beta2 * u, jnp.abs(g))
            p_new = p - lr_t * (m_new / bc1) / (u_new + self.epsilon)
            return p_new, m_new, u_new

        triples = _tree_map(upd, params, grads, slots["m"], slots["u"])
        is_leaf = lambda x: isinstance(x, tuple)
        return (
            _tree_map(lambda tr: tr[0], triples, is_leaf=is_leaf),
            {
                "m": _tree_map(lambda tr: tr[1], triples, is_leaf=is_leaf),
                "u": _tree_map(lambda tr: tr[2], triples, is_leaf=is_leaf),
            },
        )


class RMSProp(Optimizer):
    """``paddle.optimizer.RMSProp`` (phi/kernels rmsprop_kernel semantics:
    centered=False, rho/epsilon/momentum)."""

    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-6, momentum=0.0, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.rho, self.epsilon, self.momentum = float(rho), float(epsilon), float(momentum)

    def _init_slots(self, params):
        return {
            "mean_sq": _tree_map(jnp.zeros_like, params),
            "mom": _tree_map(jnp.zeros_like, params),
        }

    def _apply(self, grads, slots, params, lr_t, step):
        def upd(p, g, ms, mom):
            g = self._decay_grad(g, p)
            ms_new = self.rho * ms + (1 - self.rho) * jnp.square(g)
            mom_new = self.momentum * mom + lr_t * g / jnp.sqrt(ms_new + self.epsilon)
            return p - mom_new, ms_new, mom_new

        triples = _tree_map(upd, params, grads, slots["mean_sq"], slots["mom"])
        is_leaf = lambda x: isinstance(x, tuple)
        return (
            _tree_map(lambda tr: tr[0], triples, is_leaf=is_leaf),
            {
                "mean_sq": _tree_map(lambda tr: tr[1], triples, is_leaf=is_leaf),
                "mom": _tree_map(lambda tr: tr[2], triples, is_leaf=is_leaf),
            },
        )


class Lars(Optimizer):
    """LARS momentum (reference operators/optimizers/lars_momentum_op.cc,
    fleet `lars` strategy): layer-wise trust ratio
    ``local_lr = lr * lars_coeff * ||p|| / (||g|| + wd * ||p|| + eps)``,
    then momentum on the locally-scaled gradient."""

    def __init__(self, learning_rate=0.001, momentum=0.9, lars_coeff=0.001,
                 lars_weight_decay=0.0005, epsilon=0.0, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.momentum = float(momentum)
        self.lars_coeff = float(lars_coeff)
        self.lars_weight_decay = float(lars_weight_decay)
        self.epsilon = float(epsilon)

    def _init_slots(self, params):
        return _tree_map(jnp.zeros_like, params)

    def _apply(self, grads, slots, params, lr_t, step):
        def upd(p, g, v):
            pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
            p_norm = jnp.sqrt(jnp.sum(jnp.square(pf)))
            g_norm = jnp.sqrt(jnp.sum(jnp.square(gf)))
            local_lr = jnp.where(
                (p_norm > 0) & (g_norm > 0),
                lr_t * self.lars_coeff * p_norm
                / (g_norm + self.lars_weight_decay * p_norm + self.epsilon),
                lr_t,
            )
            v_new = self.momentum * v + local_lr * (gf + self.lars_weight_decay * pf)
            return (pf - v_new).astype(p.dtype), v_new

        pairs = _tree_map(upd, params, grads, slots)
        is_leaf = lambda x: isinstance(x, tuple)
        return (
            _tree_map(lambda pr: pr[0], pairs, is_leaf=is_leaf),
            _tree_map(lambda pr: pr[1], pairs, is_leaf=is_leaf),
        )


class Lamb(Optimizer):
    """LAMB (reference operators/optimizers/lamb_op.cc, fleet `lamb`
    strategy): Adam moments + per-layer trust ratio ``||p|| / ||r||``
    where ``r = m_hat / (sqrt(v_hat)+eps) + wd * p``."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, **kw) -> None:
        super().__init__(learning_rate, **kw)
        self.lamb_weight_decay = float(lamb_weight_decay)
        self.beta1, self.beta2, self.epsilon = float(beta1), float(beta2), float(epsilon)

    def _init_slots(self, params):
        return {
            "m": _tree_map(jnp.zeros_like, params),
            "v": _tree_map(jnp.zeros_like, params),
        }

    def _apply(self, grads, slots, params, lr_t, step):
        t = (step + 1).astype(jnp.float32)
        bc1 = 1 - jnp.power(self.beta1, t)
        bc2 = 1 - jnp.power(self.beta2, t)

        def upd(p, g, m, v):
            pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
            m_new = self.beta1 * m + (1 - self.beta1) * gf
            v_new = self.beta2 * v + (1 - self.beta2) * jnp.square(gf)
            r = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + self.epsilon) \
                + self.lamb_weight_decay * pf
            p_norm = jnp.sqrt(jnp.sum(jnp.square(pf)))
            r_norm = jnp.sqrt(jnp.sum(jnp.square(r)))
            trust = jnp.where((p_norm > 0) & (r_norm > 0), p_norm / r_norm, 1.0)
            return (pf - lr_t * trust * r).astype(p.dtype), m_new, v_new

        triples = _tree_map(upd, params, grads, slots["m"], slots["v"])
        is_leaf = lambda x: isinstance(x, tuple)
        return (
            _tree_map(lambda tr: tr[0], triples, is_leaf=is_leaf),
            {
                "m": _tree_map(lambda tr: tr[1], triples, is_leaf=is_leaf),
                "v": _tree_map(lambda tr: tr[2], triples, is_leaf=is_leaf),
            },
        )


class MasterWeights:
    """O2 mixed-precision master-weight wrapper — the reference's
    ``paddle.amp.decorate(level='O2')`` + the ``multi_precision`` flag
    of its optimizer kernels (phi adam/momentum ``MasterParam``
    variants): the MODEL's parameters live in a low dtype (bf16 halves
    their HBM and feeds the MXU directly) while the optimizer update
    runs in f32 against a master copy carried in the wrapper's state.

    Functional drop-in for :class:`Optimizer`::

        opt = MasterWeights(Adam(1e-3))
        state  = opt.init(bf16_params)      # masters = f32(params)
        new_bf16, state = opt.update(grads, state, bf16_params)

    ``update`` upcasts the (possibly bf16) grads, steps the inner
    optimizer on the f32 masters, and returns the masters cast back to
    each param's storage dtype — the low-precision params never
    accumulate rounding across steps (they are pure projections of the
    master). Non-float params (int embedding tables etc.) pass through
    untouched.
    """

    def __init__(self, inner: Optimizer) -> None:
        if not isinstance(inner, Optimizer):
            raise InvalidArgumentError(
                f"MasterWeights wraps an Optimizer, got {type(inner).__name__}")
        if hasattr(inner, "scale_loss") or hasattr(inner, "inner"):
            # Meta-optimizer wrappers (AMPOptimizer, GradientMerge, …)
            # carry namespaced state ({'inner': ..., 'scaler': ...}) and
            # a scale_loss hook this wrapper neither reshapes nor
            # delegates — half-applying them would silently mis-scale
            # every update. Compose the other way around:
            # Meta(MasterWeights(plain_opt)).
            raise InvalidArgumentError(
                f"MasterWeights cannot wrap {type(inner).__name__}: wrap "
                "the PLAIN optimizer and put the meta-optimizer outside — "
                "e.g. AMPOptimizer(MasterWeights(Adam(...)))")
        self.inner = inner

    @staticmethod
    def _to_master(p):
        return p.astype(jnp.float32) if jnp.issubdtype(p.dtype, jnp.floating) else p

    def init(self, params: PyTree) -> Dict[str, Any]:
        master = _tree_map(self._to_master, params)
        inner_state = self.inner.init(master)
        return {"step": inner_state["step"],
                "slots": {"master": master, "inner": inner_state["slots"]}}

    def update(self, grads: PyTree, opt_state: Dict[str, Any],
               params: PyTree) -> Tuple[PyTree, Dict[str, Any]]:
        slots = opt_state["slots"]
        g32 = _tree_map(self._to_master, grads)
        inner_state = {"step": opt_state["step"], "slots": slots["inner"]}
        new_master, new_inner = self.inner.update(g32, inner_state,
                                                  slots["master"])
        new_params = _tree_map(
            lambda m, p: m.astype(p.dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else m,
            new_master, params)
        return new_params, {"step": new_inner["step"],
                            "slots": {"master": new_master,
                                      "inner": new_inner["slots"]}}


def decorate_o2(optimizer, params: PyTree):
    """O2 decoration (``paddle.amp.decorate(level='O2')``), shared by
    ``executor.Trainer(amp="O2")`` and ``hapi.Model.prepare``: ensure a
    :class:`MasterWeights` sits in the optimizer chain (inserted around
    the INNERMOST plain optimizer, so AMPOptimizer(Adam) becomes
    AMPOptimizer(MasterWeights(Adam)) and an already-decorated chain is
    left alone), initialize state with masters from the f32 ``params``,
    and return the bf16 storage params.

    Returns ``(optimizer, opt_state, bf16_params)``.
    """
    cur, holder = optimizer, None
    while cur is not None and not isinstance(cur, MasterWeights):
        nxt = getattr(cur, "inner", None)
        if nxt is None:
            break
        holder, cur = cur, nxt
    if not isinstance(cur, MasterWeights):
        wrapped = MasterWeights(cur)
        if holder is None:
            optimizer = wrapped
        else:
            holder.inner = wrapped
    opt_state = optimizer.init(params)  # masters from the f32 originals
    bf16 = type(params)(
        (k, v.astype(jnp.bfloat16)
         if jnp.issubdtype(v.dtype, jnp.floating) else v)
        for k, v in params.items())
    return optimizer, opt_state, bf16
