"""Functional ops (``paddle.nn.functional`` analogue).

Pure jnp/lax implementations; XLA fuses elementwise chains into surrounding
matmuls/convs, so these stay simple — no hand-written fusion. Hot sparse and
attention paths have Pallas kernels under ``paddle_tpu.ops.pallas``.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..core.enforce import InvalidArgumentError, enforce_eq
from ..core.profiler import RecordEvent
from .layer import next_rng_key

__all__ = [
    "relu",
    "gelu",
    "sigmoid",
    "tanh",
    "softmax",
    "log_softmax",
    "dropout",
    "linear",
    "lm_head",
    "conv2d",
    "max_pool2d",
    "avg_pool2d",
    "adaptive_avg_pool2d",
    "batch_norm",
    "layer_norm",
    "rms_norm",
    "embedding",
    "one_hot",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "binary_cross_entropy_with_logits",
    "mse_loss",
    "flatten",
]


def relu(x: jax.Array) -> jax.Array:
    return jnp.maximum(x, 0)


def gelu(x: jax.Array, approximate: bool = True) -> jax.Array:
    return jax.nn.gelu(x, approximate=approximate)


def sigmoid(x: jax.Array) -> jax.Array:
    return jax.nn.sigmoid(x)


def tanh(x: jax.Array) -> jax.Array:
    return jnp.tanh(x)


def softmax(x: jax.Array, axis: int = -1) -> jax.Array:
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x: jax.Array, axis: int = -1) -> jax.Array:
    return jax.nn.log_softmax(x, axis=axis)


def dropout(
    x: jax.Array,
    p: float = 0.5,
    training: bool = True,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    if not training or p <= 0.0:
        return x
    if p >= 1.0:
        return jnp.zeros_like(x)
    key = rng if rng is not None else next_rng_key()
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)


# ``linear``'s amp branch states its backward where both sides of the
# weight are at least this wide, and leaves a narrower weight's to the
# transpose of the forward. Stated, a layer pays a few passes over
# ``[tokens, in]`` and ``[tokens, out]`` (x16 and the narrow cotangent
# written out, ``dx`` finished in float32 before anything reads it) that
# the transposed form fuses away, and gains where XLA would otherwise
# rebuild an operand's producer inside the weight gradient's matmul (a
# gated FFN's product, a head's softmax gradient). On the chip the wide
# layers of a decoder come out ahead and its narrow projections behind
# (PERF.md section 6, PR 48)
_STATED_BACKWARD_MIN_WIDTH = 4096


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _amp_matmul(x: jax.Array, weight: jax.Array, dt) -> jax.Array:
    """``x @ W`` under amp: operands in ``dt``, float32 accumulation and
    result. The backward is stated like the forward (:func:`linear`)."""
    return _amp_matmul_fwd(x, weight, dt)[0]


def _amp_matmul_fwd(x, weight, dt):
    x16 = x.astype(dt)
    y = jnp.matmul(x16, weight.astype(dt), preferred_element_type=jnp.float32)
    return y, (x16, weight)


def _amp_matmul_bwd(dt, res, g):
    x16, weight = res
    # What each line is for was read off XLA:TPU's compiled steps and the
    # chip (PERF.md section 6, PR 48). Left to the transpose of the two
    # ``astype``s, the weight gradient's convolution rebuilds x16's and
    # g's float32 producers a tile and its result is rounded to ``dt`` and
    # back. x16 behind a barrier is a buffer that convolution READS; ONE
    # narrow cotangent feeds both matmuls.
    x16 = lax.optimization_barrier(x16)
    g16 = g.astype(dt)
    dx = jnp.matmul(g16, weight.astype(dt).T,
                    preferred_element_type=jnp.float32)
    dw = jnp.matmul(x16.reshape(-1, x16.shape[-1]).T,
                    g16.reshape(-1, g16.shape[-1]),
                    preferred_element_type=jnp.float32)
    # dx leaves through a barrier it shares with dW: the layer below
    # cannot start its backward before this weight gradient is done. Left
    # free, the scheduler parks the weight gradients at the END of the
    # step and keeps the bf16 operands they read until then (+1% to +3%
    # of a decoder cell's memory)
    dx = lax.optimization_barrier((dx, dw))[0]
    return dx, dw.astype(weight.dtype)


_amp_matmul.defvjp(_amp_matmul_fwd, _amp_matmul_bwd)


def _matmul(x: jax.Array, weight: jax.Array, stated: bool) -> jax.Array:
    """``x @ W`` as :func:`linear` and :func:`lm_head` multiply: under
    ``amp.auto_cast`` with a float32 ``x`` in the amp dtype (one
    ``pt.linear.amp`` span a traced call), else as given. The backward is
    stated where the caller says so and for a weight wide on both sides."""
    from .. import amp

    if not (amp.amp_enabled() and x.dtype == jnp.float32):
        return jnp.matmul(x, weight)
    dt = amp.amp_dtype()
    stated = stated or min(weight.shape) >= _STATED_BACKWARD_MIN_WIDTH
    with RecordEvent("pt.linear.amp", in_features=weight.shape[0],
                     out_features=weight.shape[-1],
                     bits=8 * jnp.dtype(dt).itemsize,
                     stated_backward=int(stated)):
        if stated:
            return _amp_matmul(x, weight, dt)
        return _amp_matmul_fwd(x, weight, dt)[0]


def linear(x: jax.Array, weight: jax.Array, bias: Optional[jax.Array] = None) -> jax.Array:
    """x @ W (+ b). Weight layout [in, out] (paddle convention).

    Under ``amp.auto_cast`` (checked at trace time, like the context's
    contract says) with a float32 ``x``, all THREE matmuls of the layer
    read operands in the amp dtype — bf16 feeds the MXU at full rate on
    TPU — and accumulate and leave in float32: the forward ``x16 @ w16``,
    and in the backward ``dx = g @ w16.T`` and ``dW = x16.T @ g``
    (parameters, bias math, both gradients and everything downstream stay
    float32). Where both sides of the weight are wide (4096 and up: the
    layers whose matmuls dominate a step) the backward is STATED, a
    ``jax.custom_vjp``: ``g`` is cast to the amp dtype ONCE for both
    matmuls, ``x16`` is the forward's own cast, a buffer the weight
    gradient reads, ``dW`` is never rounded, and ``dx`` is handed on only
    once ``dW`` is made, so a step's weight gradients are computed layer
    by layer and not at its end. Such a layer differentiates in reverse
    mode only: ``jax.jvp`` of it raises, where a narrower layer's, whose
    backward is the transpose of the forward (its float32 cotangent
    rounded by the MXU's default precision), does not. One
    ``pt.linear.amp`` host span a traced call (``profiler.host_spans()``:
    in, out, the operands' bits, ``stated_backward`` 1 | 0) records the
    branch and the choice, none on the step path. An ``x`` that is not
    float32, and amp off, multiply as given. A vocabulary head is
    :func:`lm_head`'s."""
    y = _matmul(x, weight, stated=False)
    if bias is not None:
        y = y + bias
    return y


def lm_head(x: jax.Array, weight: jax.Array) -> jax.Array:
    """Logits ``x @ W`` of a decoder's head, weight ``[hidden, vocab]``:
    :func:`linear` without a bias and with the backward stated whatever the
    weight's widths. A head's cotangent is the loss's softmax gradient, a
    float32 ``[tokens, vocab]`` expression that the transposed form
    rebuilds a tile of the weight gradient's matmul and the stated form
    casts once, into a buffer both backward matmuls read. The model says
    which matmul is its head, and whether the statement pays in its step:
    ``linear`` cannot see a layer's neighbours, and neither function the
    step it is part of (a head keeps ``linear`` where its cell read a
    loss; each such call site says why). Same span (``stated_backward``
    1), same reverse-mode-only rule; amp off and an ``x`` that is not
    float32 multiply as given."""
    return _matmul(x, weight, stated=True)


def _pair(v: Union[int, Sequence[int]]) -> Tuple[int, int]:
    if isinstance(v, int):
        return (v, v)
    return (int(v[0]), int(v[1]))


def conv2d(
    x: jax.Array,
    weight: jax.Array,
    bias: Optional[jax.Array] = None,
    stride: Union[int, Sequence[int]] = 1,
    padding: Union[int, str, Sequence[int]] = 0,
    dilation: Union[int, Sequence[int]] = 1,
    groups: int = 1,
) -> jax.Array:
    """NCHW conv with OIHW weights (paddle layout). XLA lowers this to the
    MXU; bf16 inputs hit the systolic array natively. Under
    ``amp.auto_cast`` (trace-time, same contract as :func:`linear`) the
    conv computes in the amp dtype with f32 accumulation."""
    from .. import amp

    strides = _pair(stride)
    dil = _pair(dilation)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        ph, pw = _pair(padding)
        pad = [(ph, ph), (pw, pw)]
    conv_kw = {}
    if amp.amp_enabled() and x.dtype == jnp.float32:
        dt = amp.amp_dtype()
        x, weight = x.astype(dt), weight.astype(dt)
        conv_kw["preferred_element_type"] = jnp.float32
    y = lax.conv_general_dilated(
        x,
        weight,
        window_strides=strides,
        padding=pad,
        rhs_dilation=dil,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=groups,
        **conv_kw,
    )
    if bias is not None:
        y = y + bias.reshape(1, -1, 1, 1)
    return y


def max_pool2d(
    x: jax.Array,
    kernel_size: Union[int, Sequence[int]],
    stride: Optional[Union[int, Sequence[int]]] = None,
    padding: Union[int, Sequence[int]] = 0,
) -> jax.Array:
    k = _pair(kernel_size)
    s = _pair(stride) if stride is not None else k
    ph, pw = _pair(padding)
    return lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        lax.max,
        window_dimensions=(1, 1, k[0], k[1]),
        window_strides=(1, 1, s[0], s[1]),
        padding=((0, 0), (0, 0), (ph, ph), (pw, pw)),
    )


def avg_pool2d(
    x: jax.Array,
    kernel_size: Union[int, Sequence[int]],
    stride: Optional[Union[int, Sequence[int]]] = None,
    padding: Union[int, Sequence[int]] = 0,
) -> jax.Array:
    k = _pair(kernel_size)
    s = _pair(stride) if stride is not None else k
    ph, pw = _pair(padding)
    summed = lax.reduce_window(
        x,
        jnp.array(0, x.dtype),
        lax.add,
        window_dimensions=(1, 1, k[0], k[1]),
        window_strides=(1, 1, s[0], s[1]),
        padding=((0, 0), (0, 0), (ph, ph), (pw, pw)),
    )
    if ph == 0 and pw == 0:
        return summed / (k[0] * k[1])
    ones = jnp.ones(x.shape[2:], x.dtype)[None, None]
    counts = lax.reduce_window(
        ones,
        jnp.array(0, x.dtype),
        lax.add,
        window_dimensions=(1, 1, k[0], k[1]),
        window_strides=(1, 1, s[0], s[1]),
        padding=((0, 0), (0, 0), (ph, ph), (pw, pw)),
    )
    return summed / counts


def adaptive_avg_pool2d(x: jax.Array, output_size: Union[int, Sequence[int]]) -> jax.Array:
    oh, ow = _pair(output_size)
    n, c, h, w = x.shape
    if h % oh == 0 and w % ow == 0:
        return x.reshape(n, c, oh, h // oh, ow, w // ow).mean(axis=(3, 5))
    raise InvalidArgumentError(
        f"adaptive_avg_pool2d needs divisible sizes on TPU (static shapes); got {(h, w)}→{(oh, ow)}"
    )


def batch_norm(
    x: jax.Array,
    running_mean: jax.Array,
    running_var: jax.Array,
    weight: jax.Array,
    bias: jax.Array,
    training: bool,
    momentum: float = 0.9,
    eps: float = 1e-5,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (y, new_running_mean, new_running_var). Channel axis = 1 for
    4-D (NCHW) input, last axis for 2-D."""
    if x.ndim == 4:
        axes = (0, 2, 3)
        shape = (1, -1, 1, 1)
    elif x.ndim == 3:  # (N, C, L)
        axes = (0, 2)
        shape = (1, -1, 1)
    elif x.ndim == 2:
        axes = (0,)
        shape = (1, -1)
    else:
        raise InvalidArgumentError(f"batch_norm: unsupported ndim {x.ndim}")
    if training:
        mean = jnp.mean(x, axis=axes)
        var = jnp.var(x, axis=axes)
        new_rm = momentum * running_mean + (1 - momentum) * mean
        new_rv = momentum * running_var + (1 - momentum) * var
    else:
        mean, var = running_mean, running_var
        new_rm, new_rv = running_mean, running_var
    inv = lax.rsqrt(var + eps)
    y = (x - mean.reshape(shape)) * (inv * weight).reshape(shape) + bias.reshape(shape)
    return y.astype(x.dtype), new_rm, new_rv


def rms_norm(x: jax.Array, weight: Optional[jax.Array] = None,
             eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return y if weight is None else y * weight


def layer_norm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    eps: float = 1e-5,
) -> jax.Array:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight
    if bias is not None:
        y = y + bias
    return y.astype(x.dtype)


def embedding(ids: jax.Array, table: jax.Array, padding_idx: Optional[int] = None) -> jax.Array:
    """Dense embedding lookup (``lookup_table_v2``). XLA lowers take() to an
    efficient dynamic-gather; the sparse/PS path lives in paddle_tpu.ps."""
    out = jnp.take(table, ids, axis=0)
    if padding_idx is not None:
        mask = (ids != padding_idx)[..., None]
        out = jnp.where(mask, out, 0.0)
    return out


def one_hot(ids: jax.Array, num_classes: int, dtype=jnp.float32) -> jax.Array:
    return jax.nn.one_hot(ids, num_classes, dtype=dtype)


def cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    soft_label: bool = False,
    reduction: str = "mean",
    ignore_index: int = -100,
) -> jax.Array:
    lp = jax.nn.log_softmax(logits, axis=-1)
    if soft_label:
        loss = -jnp.sum(labels * lp, axis=-1)
    else:
        labels = labels.reshape(logits.shape[:-1])
        picked = jnp.take_along_axis(lp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
        loss = -picked
        mask = labels != ignore_index
        loss = jnp.where(mask, loss, 0.0)
        if reduction == "mean":
            denom = jnp.maximum(jnp.sum(mask), 1)
            return jnp.sum(loss) / denom
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


softmax_with_cross_entropy = cross_entropy


def binary_cross_entropy_with_logits(
    logits: jax.Array, labels: jax.Array, reduction: str = "mean"
) -> jax.Array:
    labels = labels.astype(logits.dtype)
    loss = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def mse_loss(pred: jax.Array, target: jax.Array, reduction: str = "mean") -> jax.Array:
    loss = (pred - target.astype(pred.dtype)) ** 2
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def flatten(x: jax.Array, start_axis: int = 1) -> jax.Array:
    return x.reshape(x.shape[:start_axis] + (-1,))
