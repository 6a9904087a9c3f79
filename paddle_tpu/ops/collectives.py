"""Collective communication over mesh axes.

The TPU-native Communicator replacing the reference's three comm stacks
(SURVEY §2.4): NCCL collective ops (``paddle/fluid/operators/collective/``
— c_allreduce_{sum,max,min,prod}, c_allgather, c_broadcast,
c_reducescatter, alltoall, c_concat, c_split, partial_send/recv), the
eager ``ProcessGroup`` family (``distributed/collective/ProcessGroup.h``),
and the Gloo CPU path. All of them collapse into XLA collectives over
named mesh axes: a "ring_id"/"process group" is an axis name; the compiler
schedules the transfer over ICI inside the step program.

Two execution contexts:
- inside ``shard_map`` (explicit SPMD): these call ``lax.psum`` etc. on
  the bound axis — exact control, used by TP/PP/ring-attention internals;
- outside (GSPMD/pjit): prefer sharding annotations and let XLA insert
  collectives; these wrappers then raise a clear error if the axis is
  unbound rather than silently doing nothing.

The ``ProcessGroup`` class offers the reference's eager API shape
(all_reduce/broadcast/all_gather/…) for porting user code.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..core.enforce import InvalidArgumentError

__all__ = [
    "all_reduce",
    "all_gather",
    "reduce_scatter",
    "all_to_all",
    "ppermute",
    "broadcast",
    "reduce",
    "axis_index",
    "axis_size",
    "barrier",
    "split_axis",
    "psum_replicated",
    "spec_reduced_grads",
    "ProcessGroup",
    "ReduceOp",
]

AxisName = Union[str, Sequence[str]]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


def all_reduce(x: jax.Array, axis: AxisName, op: str = ReduceOp.SUM) -> jax.Array:
    """c_allreduce_{sum,max,min,prod} → lax.p{sum,max,min,prod}."""
    if op == ReduceOp.SUM:
        return lax.psum(x, axis)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axis)
    if op == ReduceOp.MIN:
        return lax.pmin(x, axis)
    if op == ReduceOp.PROD:
        # no pprod primitive; gather + prod handles zeros/negatives exactly
        return jnp.prod(lax.all_gather(x, axis), axis=0)
    if op == ReduceOp.AVG:
        return lax.pmean(x, axis)
    raise InvalidArgumentError(f"unknown reduce op {op!r}")


def all_gather(x: jax.Array, axis: AxisName, concat_axis: int = 0, tiled: bool = True) -> jax.Array:
    """c_allgather / c_concat: gather shards along ``concat_axis``."""
    return lax.all_gather(x, axis, axis=concat_axis, tiled=tiled)


def reduce_scatter(x: jax.Array, axis: AxisName, scatter_axis: int = 0) -> jax.Array:
    """c_reducescatter: sum across the axis, keep this rank's shard."""
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_axis, tiled=True)


def all_to_all(
    x: jax.Array,
    axis: AxisName,
    split_axis_: int = 0,
    concat_axis: int = 0,
) -> jax.Array:
    """alltoall op (MoE global_scatter/gather building block)."""
    return lax.all_to_all(x, axis, split_axis=split_axis_, concat_axis=concat_axis, tiled=True)


def ppermute(x: jax.Array, axis: str, perm: Sequence[tuple]) -> jax.Array:
    """partial_send/recv pairs → a single compiled permutation
    (PP p2p and ring-attention KV rotation both use this)."""
    return lax.ppermute(x, axis, perm)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _psum_identity_vjp(x, axes, varying):
    return lax.psum(x, axes)


def _psum_identity_bwd(axes, varying, _, ct):
    # the cotangent arrives typed like the (replicated) output; hand it
    # back typed like the input — varying over the axes the input varied
    return (lax.pcast(ct, varying, to="varying") if varying else ct,)


_psum_identity_vjp.defvjp(
    lambda x, axes, varying: (lax.psum(x, axes), None), _psum_identity_bwd)


def psum_replicated(x: jax.Array, axis_name: AxisName) -> jax.Array:
    """psum of a value whose DOWNSTREAM cotangent is replicated over
    ``axis_name`` (every shard computes the same loss from the summed
    result), differentiated INSIDE the shard_map body: the correct
    per-shard gradient is that cotangent unscaled, so the backward is
    pinned to the identity. Under ``check_vma=True`` that is also what
    jax derives for psum (varying → invariant transposes to a cast
    back to varying), and the pinned rule returns the cotangent with
    the input's varying-axes type; under ``check_vma=False`` jax
    transposes a plain psum into another psum, which would scale such
    gradients by the axis size. ``axis_name`` may be one axis or a
    tuple (the mp CE reductions, the pipeline's masked output psum and
    the hybrid loss reduction all route through here)."""
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    varying = tuple(a for a in axes if a in jax.typeof(x).vma)
    return _psum_identity_vjp(x, axes, varying)


def spec_reduced_grads(grads, specs, mesh_shape) -> jax.Array:
    """Explicit spec-driven gradient reduction for a ``check_vma=False``
    shard_map step where autodiff inserts NO
    cross-rank reductions (every differentiated psum pinned via
    :func:`psum_replicated`): each rank then holds only its own partial
    contribution, and the true gradient of a param is the psum over
    every mesh axis the param is NOT sharded on — batch/sequence shards
    and tensor-parallel partials sum to the full gradient, while
    disjoint contributions (pipeline-stage-owned aux params) are zero
    off their owning rank. Axes IN the param's spec hold that rank's
    own shard and are left alone. Shared by the hybrid trainer and the
    TP parity tests."""
    def reduce_one(g, spec):
        in_spec = {a for e in tuple(spec)
                   for a in (e if isinstance(e, tuple) else (e,)) if a}
        red = tuple(a for a in mesh_shape
                    if a not in in_spec and mesh_shape[a] > 1)
        return lax.psum(g, red) if red else g

    return jax.tree_util.tree_map(reduce_one, grads, specs)


def shift(x: jax.Array, axis: str, offset: int = 1) -> jax.Array:
    """Ring rotation by ``offset`` hops (helper over ppermute)."""
    n = lax.axis_size(axis)
    perm = [(i, (i + offset) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def broadcast(x: jax.Array, axis: str, root: int = 0) -> jax.Array:
    """c_broadcast: all ranks take root's value."""
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis)


def reduce(x: jax.Array, axis: str, root: int = 0, op: str = ReduceOp.SUM) -> jax.Array:
    """c_reduce: full value on root, zeros elsewhere (SPMD can't have
    rank-dependent shapes, so non-root ranks carry zeros)."""
    total = all_reduce(x, axis, op)
    idx = lax.axis_index(axis)
    return jnp.where(idx == root, total, jnp.zeros_like(total))


def axis_index(axis: str) -> jax.Array:
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    return lax.axis_size(axis)


def barrier(axis: str) -> None:
    """Inside a compiled program a barrier is implicit in any collective;
    provided for API parity (BarrierTable / gloo barrier)."""
    return None


def split_axis(x: jax.Array, axis: str, dim: int = -1) -> jax.Array:
    """c_split: each rank keeps its slice of ``dim`` (inverse of
    all_gather). Requires dim divisible by axis size."""
    n = lax.axis_size(axis)
    i = lax.axis_index(axis)
    if x.shape[dim] % n != 0:
        raise InvalidArgumentError(
            f"split_axis: dim {dim} (size {x.shape[dim]}) not divisible by axis {axis!r} size {n}"
        )
    size = x.shape[dim] // n
    return lax.dynamic_slice_in_dim(x, i * size, size, axis=dim)


class ProcessGroup:
    """Eager-API shape of the reference ProcessGroup (ProcessGroup.h:53),
    bound to a mesh axis. Methods are usable inside shard_map'd code;
    results are returned (no Task futures — XLA schedules async)."""

    def __init__(self, axis: str) -> None:
        self.axis = axis

    def all_reduce(self, x, op: str = ReduceOp.SUM):
        return all_reduce(x, self.axis, op)

    def all_gather(self, x, concat_axis: int = 0):
        return all_gather(x, self.axis, concat_axis)

    def reduce_scatter(self, x, scatter_axis: int = 0):
        return reduce_scatter(x, self.axis, scatter_axis)

    def all_to_all(self, x, split_axis_: int = 0, concat_axis: int = 0):
        return all_to_all(x, self.axis, split_axis_, concat_axis)

    def broadcast(self, x, root: int = 0):
        return broadcast(x, self.axis, root)

    def reduce(self, x, root: int = 0, op: str = ReduceOp.SUM):
        return reduce(x, self.axis, root, op)

    def rank(self):
        return axis_index(self.axis)

    def size(self):
        return axis_size(self.axis)

    def barrier(self):
        return barrier(self.axis)
