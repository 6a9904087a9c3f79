"""Standard layers (``paddle.nn`` surface).

Parameter layouts follow paddle conventions (Linear weight [in, out],
Conv2D weight OIHW) so reference model definitions port over verbatim.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import functional as F
from .layer import Layer, next_rng_key

__all__ = [
    "Linear",
    "Conv2D",
    "MaxPool2D",
    "AvgPool2D",
    "AdaptiveAvgPool2D",
    "BatchNorm2D",
    "BatchNorm1D",
    "LayerNorm",
    "Embedding",
    "Dropout",
    "ReLU",
    "GELU",
    "Sigmoid",
    "Tanh",
    "Softmax",
    "Flatten",
    "CrossEntropyLoss",
    "MSELoss",
    "BCEWithLogitsLoss",
]


class Linear(Layer):
    def __init__(self, in_features: int, out_features: int, bias_attr: bool = True) -> None:
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.create_parameter("weight", (in_features, out_features))
        if bias_attr:
            self.create_parameter("bias", (out_features,), init_value=np.zeros(out_features, np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        bias = self._parameters.get("bias")
        return F.linear(x, self.weight, bias)


class Conv2D(Layer):
    def __init__(
        self,
        in_channels: int,
        out_channels: int,
        kernel_size: Union[int, Sequence[int]],
        stride: Union[int, Sequence[int]] = 1,
        padding: Union[int, str, Sequence[int]] = 0,
        dilation: Union[int, Sequence[int]] = 1,
        groups: int = 1,
        bias_attr: bool = True,
    ) -> None:
        super().__init__()
        kh, kw = (kernel_size, kernel_size) if isinstance(kernel_size, int) else kernel_size
        self.stride, self.padding, self.dilation, self.groups = stride, padding, dilation, groups
        fan_in = in_channels // groups * kh * kw
        bound = 1.0 / np.sqrt(fan_in)
        self.create_parameter(
            "weight",
            (out_channels, in_channels // groups, kh, kw),
            initializer=lambda key, shape, dtype: jax.random.uniform(
                key, shape, dtype=dtype, minval=-bound, maxval=bound
            ),
        )
        if bias_attr:
            self.create_parameter("bias", (out_channels,), init_value=np.zeros(out_channels, np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        bias = self._parameters.get("bias")
        return F.conv2d(x, self.weight, bias, self.stride, self.padding, self.dilation, self.groups)


class MaxPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0) -> None:
        super().__init__()
        self.kernel_size, self.stride, self.padding = kernel_size, stride, padding

    def forward(self, x: jax.Array) -> jax.Array:
        return F.max_pool2d(x, self.kernel_size, self.stride, self.padding)


class AvgPool2D(Layer):
    def __init__(self, kernel_size, stride=None, padding=0) -> None:
        super().__init__()
        self.kernel_size, self.stride, self.padding = kernel_size, stride, padding

    def forward(self, x: jax.Array) -> jax.Array:
        return F.avg_pool2d(x, self.kernel_size, self.stride, self.padding)


class AdaptiveAvgPool2D(Layer):
    def __init__(self, output_size) -> None:
        super().__init__()
        self.output_size = output_size

    def forward(self, x: jax.Array) -> jax.Array:
        return F.adaptive_avg_pool2d(x, self.output_size)


class _BatchNormBase(Layer):
    def __init__(self, num_features: int, momentum: float = 0.9, epsilon: float = 1e-5) -> None:
        super().__init__()
        self.momentum, self.epsilon = momentum, epsilon
        self.create_parameter("weight", (num_features,), init_value=np.ones(num_features, np.float32))
        self.create_parameter("bias", (num_features,), init_value=np.zeros(num_features, np.float32))
        self.register_buffer("_mean", np.zeros(num_features, np.float32))
        self.register_buffer("_variance", np.ones(num_features, np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        y, new_mean, new_var = F.batch_norm(
            x, self._mean, self._variance, self.weight, self.bias,
            training=self.training, momentum=self.momentum, eps=self.epsilon,
        )
        if self.training:
            self._buffers["_mean"] = new_mean
            self._buffers["_variance"] = new_var
        return y


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm1D(_BatchNormBase):
    pass


class LayerNorm(Layer):
    def __init__(self, normalized_shape: Union[int, Sequence[int]], epsilon: float = 1e-5) -> None:
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = (normalized_shape,)
        self.epsilon = epsilon
        self.create_parameter("weight", tuple(normalized_shape), init_value=np.ones(normalized_shape, np.float32))
        self.create_parameter("bias", tuple(normalized_shape), init_value=np.zeros(normalized_shape, np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        return F.layer_norm(x, self.weight, self.bias, self.epsilon)


class RMSNorm(Layer):
    """``x * rsqrt(mean(x^2) + epsilon) * weight`` over the last axis, the
    statistics in float32 whatever ``x`` is. With ``unit_offset`` the scale
    is ``1 + weight`` and the weight starts at 0 (EvaByte's
    ``norm_add_unit_offset``): weight decay then pulls the scale to 1, not
    to 0."""

    def __init__(self, size: int, epsilon: float = 1e-5,
                 unit_offset: bool = False) -> None:
        super().__init__()
        self.epsilon = epsilon
        self.unit_offset = unit_offset
        start = np.zeros if unit_offset else np.ones
        self.create_parameter("weight", (size,),
                              init_value=start((size,), np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        if self.unit_offset:
            return F.rms_norm(x, 1.0 + self.weight, self.epsilon)
        return F.rms_norm(x, self.weight, self.epsilon)


class Embedding(Layer):
    def __init__(
        self,
        num_embeddings: int,
        embedding_dim: int,
        padding_idx: Optional[int] = None,
        sparse: bool = False,
    ) -> None:
        super().__init__()
        self.padding_idx = padding_idx
        self.sparse = sparse  # kept for API parity; PS tables handle true sparse
        scale = 1.0 / np.sqrt(embedding_dim)
        self.create_parameter(
            "weight",
            (num_embeddings, embedding_dim),
            initializer=lambda key, shape, dtype: jax.random.normal(key, shape, dtype) * scale,
        )

    def forward(self, ids: jax.Array) -> jax.Array:
        return F.embedding(ids, self.weight, self.padding_idx)


class Dropout(Layer):
    def __init__(self, p: float = 0.5) -> None:
        super().__init__()
        self.p = p

    def forward(self, x: jax.Array) -> jax.Array:
        return F.dropout(x, self.p, training=self.training)


class ReLU(Layer):
    def forward(self, x):
        return F.relu(x)


class GELU(Layer):
    def forward(self, x):
        return F.gelu(x)


class Sigmoid(Layer):
    def forward(self, x):
        return F.sigmoid(x)


class Tanh(Layer):
    def forward(self, x):
        return F.tanh(x)


class Softmax(Layer):
    def __init__(self, axis: int = -1) -> None:
        super().__init__()
        self.axis = axis

    def forward(self, x):
        return F.softmax(x, self.axis)


class Flatten(Layer):
    def __init__(self, start_axis: int = 1) -> None:
        super().__init__()
        self.start_axis = start_axis

    def forward(self, x):
        return F.flatten(x, self.start_axis)


class CrossEntropyLoss(Layer):
    def __init__(self, reduction: str = "mean", soft_label: bool = False, ignore_index: int = -100) -> None:
        super().__init__()
        self.reduction, self.soft_label, self.ignore_index = reduction, soft_label, ignore_index

    def forward(self, logits, labels):
        return F.cross_entropy(logits, labels, self.soft_label, self.reduction, self.ignore_index)


class MSELoss(Layer):
    def __init__(self, reduction: str = "mean") -> None:
        super().__init__()
        self.reduction = reduction

    def forward(self, pred, target):
        return F.mse_loss(pred, target, self.reduction)


class BCEWithLogitsLoss(Layer):
    def __init__(self, reduction: str = "mean") -> None:
        super().__init__()
        self.reduction = reduction

    def forward(self, logits, labels):
        return F.binary_cross_entropy_with_logits(logits, labels, self.reduction)
