"""Tensor (model) parallel layers.

Rebuild of the reference's dygraph TP layers
(``fleet/meta_parallel/parallel_layers/mp_layers.py:30-259`` —
VocabParallelEmbedding, ColumnParallelLinear, RowParallelLinear,
ParallelCrossEntropy) and their static-graph collective ops
(``c_embedding``, ``c_split``, ``c_concat``, ``_mp_allreduce``,
``c_softmax_with_cross_entropy``) as mesh-axis-explicit layers.

Each layer holds only its OWN shard of the weight (per-rank construction,
like the reference) and calls XLA collectives on the ``mp`` axis. They are
designed to run inside ``shard_map`` over the mesh — the step function is
SPMD, collectives ride ICI. When the mp axis has size 1 (or mesh_axis is
None) they degrade to the serial layer exactly.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..nn.layer import Layer, next_rng_key
from ..ops import collectives as coll

__all__ = [
    "VocabParallelEmbedding",
    "ColumnParallelLinear",
    "RowParallelLinear",
    "ParallelCrossEntropy",
]


def _axis_active(axis: Optional[str]) -> bool:
    if axis is None:
        return False
    try:
        lax.axis_size(axis)
        return True
    except NameError:
        return False


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dimension sharded over ``mp``
    (mp_layers.py:30 + c_embedding_op.cu semantics): each rank owns rows
    [rank*per, (rank+1)*per); out-of-range ids contribute zeros; partial
    results are summed with an mp all-reduce."""

    def __init__(self, num_embeddings: int, embedding_dim: int, mp_size: int = 1,
                 mp_rank: int = 0, mesh_axis: Optional[str] = "mp") -> None:
        super().__init__()
        # Megatron-style vocab padding: round the sharded vocab up to a
        # multiple of mp_size; padded rows exist but no real id reaches
        # them (ids < num_embeddings), so their init values are inert —
        # non-divisible vocabularies keep working
        mp = max(mp_size, 1)
        self.num_embeddings = num_embeddings
        self.padded_vocab = ((num_embeddings + mp - 1) // mp) * mp
        self.mesh_axis = mesh_axis if mp_size > 1 else None
        self.per_part = self.padded_vocab // mp
        self.mp_rank = mp_rank
        scale = 1.0 / np.sqrt(embedding_dim)
        # fold mp_rank into the init key so each rank's vocab shard gets a
        # distinct initialization (matching per-rank construction in the
        # reference; without this all shards would be identical copies)
        self.create_parameter(
            "weight",
            (self.per_part, embedding_dim),
            initializer=lambda key, shape, dtype: jax.random.normal(
                jax.random.fold_in(key, mp_rank), shape, dtype
            ) * scale,
        )

    def forward(self, ids: jax.Array) -> jax.Array:
        if not _axis_active(self.mesh_axis):
            return jnp.take(self.weight, ids, axis=0)
        rank = lax.axis_index(self.mesh_axis)
        start = rank * self.per_part
        local = ids - start
        # ids ≥ num_embeddings (incl. the padded tail rows) contribute
        # zeros on every rank — the documented c_embedding semantics
        in_range = ((local >= 0) & (local < self.per_part)
                    & (ids < self.num_embeddings))
        safe = jnp.clip(local, 0, self.per_part - 1)
        out = jnp.take(self.weight, safe, axis=0)
        out = jnp.where(in_range[..., None], out, 0.0)
        return lax.psum(out, self.mesh_axis)


class ColumnParallelLinear(Layer):
    """Linear with output features sharded (mp_layers.py:97). Input is
    replicated across mp; output is this rank's column block, optionally
    all-gathered (``gather_output``)."""

    def __init__(self, in_features: int, out_features: int, mp_size: int = 1,
                 gather_output: bool = True, has_bias: bool = True,
                 mesh_axis: Optional[str] = "mp") -> None:
        super().__init__()
        enforce_eq(out_features % max(mp_size, 1), 0, "out_features must divide mp size")
        self.mesh_axis = mesh_axis if mp_size > 1 else None
        self.gather_output = gather_output
        per = out_features // max(mp_size, 1)
        self.create_parameter("weight", (in_features, per))
        if has_bias:
            self.create_parameter("bias", (per,), init_value=np.zeros(per, np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        y = jnp.matmul(x, self.weight)
        bias = self._parameters.get("bias")
        if bias is not None:
            y = y + bias
        if self.gather_output and _axis_active(self.mesh_axis):
            y = lax.all_gather(y, self.mesh_axis, axis=y.ndim - 1, tiled=True)
        return y


class RowParallelLinear(Layer):
    """Linear with input features sharded (mp_layers.py:170). Input is
    either already split (``input_is_parallel``, the usual case after a
    ColumnParallelLinear) or split here; partial products are summed with
    an mp all-reduce; bias added once after the reduce."""

    def __init__(self, in_features: int, out_features: int, mp_size: int = 1,
                 input_is_parallel: bool = True, has_bias: bool = True,
                 mesh_axis: Optional[str] = "mp") -> None:
        super().__init__()
        enforce_eq(in_features % max(mp_size, 1), 0, "in_features must divide mp size")
        self.mesh_axis = mesh_axis if mp_size > 1 else None
        self.input_is_parallel = input_is_parallel
        per = in_features // max(mp_size, 1)
        self.create_parameter("weight", (per, out_features))
        if has_bias:
            self.create_parameter("bias", (out_features,), init_value=np.zeros(out_features, np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        active = _axis_active(self.mesh_axis)
        if active and not self.input_is_parallel:
            x = coll.split_axis(x, self.mesh_axis, dim=-1)
        y = jnp.matmul(x, self.weight)
        if active:
            y = lax.psum(y, self.mesh_axis)
        bias = self._parameters.get("bias")
        if bias is not None:
            y = y + bias
        return y


# pinned-VJP psum (moved to ops.collectives so hybrid.py's loss
# reduction shares the one definition); see its docstring
_psum_replicated = coll.psum_replicated


class ParallelCrossEntropy(Layer):
    """Cross entropy over vocab-sharded logits (mp_layers.py:249 +
    c_softmax_with_cross_entropy_op.cu): logits' last dim is the local
    vocab shard; max/sum/log-sum-exp and the picked-logit term reduce over
    mp without materializing the full vocab anywhere."""

    def __init__(self, mp_size: int = 1, mesh_axis: Optional[str] = "mp") -> None:
        super().__init__()
        self.mesh_axis = mesh_axis if mp_size > 1 else None

    def forward(self, logits: jax.Array, labels: jax.Array) -> jax.Array:
        if not _axis_active(self.mesh_axis):
            return nn.functional.cross_entropy(logits, labels, reduction="none")
        axis = self.mesh_axis
        per = logits.shape[-1]
        rank = lax.axis_index(axis)
        start = rank * per
        # stable log-sum-exp across shards
        # max is for numerical stability only — stop_gradient both for
        # correctness of the softmax grad and because pmax lacks a VJP
        local_max = lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        global_max = lax.pmax(local_max, axis)
        # the two reductions below are DIFFERENTIATED inside the
        # shard_map body — identity-VJP psum (coll.psum_replicated), so
        # the loss grad is right under either check_vma setting
        sumexp = jnp.sum(jnp.exp(logits - global_max), axis=-1, keepdims=True)
        lse = jnp.log(_psum_replicated(sumexp, axis)) + global_max  # [..., 1]
        # picked logit: only the owning shard contributes
        local_label = labels - start
        in_range = (local_label >= 0) & (local_label < per)
        safe = jnp.clip(local_label, 0, per - 1)
        picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        picked = jnp.where(in_range, picked, 0.0)
        picked = _psum_replicated(picked, axis)
        return lse[..., 0] - picked
