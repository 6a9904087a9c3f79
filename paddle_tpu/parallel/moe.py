"""Mixture-of-Experts with expert parallelism.

Rebuild of the reference MoE stack (SURVEY §2.5 MoE row):
``incubate/distributed/models/moe/moe_layer.py`` (MoELayer), its gates
(gate/gshard_gate.py top-2, switch_gate.py top-1, naive_gate.py) and the
``global_scatter``/``global_gather`` all-to-all-v collective ops
(operators/collective/global_scatter_op.*).

TPU-native inversion: variable-count all-to-all-v is hostile to XLA's
static shapes, so dispatch uses the GShard fixed-capacity formulation —
tokens are combined into dense ``[experts, capacity, d]`` buffers
(dropping overflow, like the reference's capacity in gshard_gate) and
exchanged with a single tiled ``all_to_all`` over the ``ep`` axis. Each
rank hosts ``num_experts / ep_size`` experts.

Beside it, the **dropless** formulation (OLMoE, ``models/olmoe.py``):
``dropless_moe`` routes every token to its top-k experts for any k, sorts
the T*k assignments by expert, runs the expert banks as grouped matmuls
over the run-time group sizes and brings the rows back — no capacity, no
``[T, E, C]`` tensor, nothing dropped. Its stages are separate functions
(``topk_route`` -> ``sort_by_expert`` -> ``dispatch_rows`` ->
``expert_ffn`` -> ``combine_rows``): an expert-parallel exchange belongs
between ``dispatch_rows`` and ``expert_ffn``.

And the **held** formulation (JoyAI-LLM-Flash / DeepSeek-V3,
``models/joyai.py``): ``held_moe`` is told which ``(first, count)`` of the
E experts live here, routes over all E by the sigmoid rule
(``sigmoid_route``), and computes the part of the result its own experts
give — what one rank of an expert-parallel layer computes between the
exchanges, with no exchange and nothing standing in for the absent ranks.
The held assignments are sorted first, and a row buffer of twice their
even-load number (``dispatch_ladder``) bounds what is gathered, multiplied
and summed; the gathers and token sums walk it ``_HELD_CHUNK`` rows at a
time as far as the last chunk that holds an assignment (half of it at even
loads). A step whose routing sends more than the buffer holds takes the
other branch of a ``lax.cond``: every held expert on every token, masked by
the choice (16 / 8 of the dropless layer's rows, no kernel, exact), so none
is dropped; each form counts what it computed, ``dropped`` is the held ones
less that. ``held = (0, E)`` runs the dropless formulation's own stages.
The router may sit elsewhere (SmallThinker, ``models/smallthinker.py``:
it reads the stream BEFORE attention): ``held_moe(route=...)`` takes the
route it is given — ``topk_route``'s softmax, renormalised over the chosen
— and dispatches other rows than those it was made from; the experts' gate
is an argument (``activation``: SiLU, or that model's ReLU).
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..nn.layer import Layer
from ..ops import collectives as coll
from ..ops.grouped_matmul import _GMM_TILE, expert_ffn, grouped_matmul

__all__ = ["top1_gate", "top2_gate", "MoELayer", "ExpertFFN",
           "topk_route", "sort_by_expert", "dispatch_rows", "combine_rows",
           "grouped_matmul", "expert_ffn", "dropless_moe",
           "sigmoid_route", "dispatch_ladder", "router_logits", "held_moe"]


def _one_hot(x, n):
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


def top1_gate(
    logits: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Switch-style top-1 gating (switch_gate.py semantics).

    Returns (dispatch [T,E,C] one-hot, combine [T,E,C] weights, aux_loss).
    """
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # [T]
    gate_p = jnp.max(probs, axis=-1)  # [T]
    mask = _one_hot(expert, E)  # [T, E]
    # position of each token within its expert's capacity buffer
    pos = jnp.cumsum(mask, axis=0) * mask - 1.0  # [T, E], -1 where unrouted
    pos_in_expert = jnp.sum(pos * mask, axis=-1)  # [T]
    keep = (pos_in_expert >= 0) & (pos_in_expert < capacity)
    pos_clamped = jnp.clip(pos_in_expert, 0, capacity - 1).astype(jnp.int32)
    dispatch = (
        mask * keep[:, None]
    )[:, :, None] * _one_hot(pos_clamped, capacity)[:, None, :]  # [T,E,C]
    combine = dispatch * gate_p[:, None, None]
    # load-balancing aux loss (switch: E * mean(frac_tokens * frac_prob))
    frac_tokens = jnp.mean(mask, axis=0)
    frac_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_prob)
    return dispatch, combine, aux


def top2_gate(
    logits: jax.Array, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """GShard top-2 gating (gshard_gate.py semantics): second expert
    weighted by renormalized prob; both subject to capacity."""
    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    e1 = jnp.argmax(probs, axis=-1)
    p1 = jnp.max(probs, axis=-1)
    probs2 = probs * (1.0 - _one_hot(e1, E))
    e2 = jnp.argmax(probs2, axis=-1)
    p2 = jnp.max(probs2, axis=-1)
    denom = jnp.maximum(p1 + p2, 1e-9)
    w1, w2 = p1 / denom, p2 / denom

    mask1 = _one_hot(e1, E)
    mask2 = _one_hot(e2, E)
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - 1.0
    # expert-1 tokens occupy the buffer first; expert-2 appends after
    used1 = jnp.sum(mask1, axis=0, keepdims=True)  # tokens per expert via e1
    pos2 = (jnp.cumsum(mask2, axis=0) - 1.0 + used1) * mask2

    def build(mask, pos, w):
        p = jnp.sum(pos * mask, axis=-1)
        keep = (jnp.sum(mask, axis=-1) > 0) & (p >= 0) & (p < capacity)
        pc = jnp.clip(p, 0, capacity - 1).astype(jnp.int32)
        d = (mask * keep[:, None])[:, :, None] * _one_hot(pc, capacity)[:, None, :]
        return d, d * w[:, None, None]

    d1, c1 = build(mask1, pos1, w1)
    d2, c2 = build(mask2, pos2, w2)
    dispatch = jnp.minimum(d1 + d2, 1.0)
    combine = c1 + c2
    frac_tokens = jnp.mean(mask1, axis=0)
    frac_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * frac_prob)
    return dispatch, combine, aux


class ExpertFFN(Layer):
    """Per-rank bank of local experts: [E_local, d, h] batched weights,
    applied with einsum so all local experts run as one MXU batch."""

    def __init__(self, num_local_experts: int, d_model: int, d_hidden: int) -> None:
        super().__init__()
        scale_in = 1.0 / np.sqrt(d_model)
        scale_out = 1.0 / np.sqrt(d_hidden)
        self.create_parameter(
            "w_in",
            (num_local_experts, d_model, d_hidden),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) * scale_in,
        )
        self.create_parameter(
            "w_out",
            (num_local_experts, d_hidden, d_model),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) * scale_out,
        )

    def forward(self, x: jax.Array) -> jax.Array:
        # x: [E_local, tokens, d]
        h = jnp.einsum("etd,edh->eth", x, self.w_in)
        h = jax.nn.gelu(h)
        return jnp.einsum("eth,ehd->etd", h, self.w_out)


class MoELayer(Layer):
    """Expert-parallel MoE (moe_layer.py MoELayer analogue).

    Run inside shard_map with the ``ep`` axis bound; each rank holds
    ``num_experts // ep_size`` experts and sees its local token shard.
    With ep inactive (single rank) it degrades to local dense dispatch.
    """

    def __init__(
        self,
        d_model: int,
        d_hidden: int,
        num_experts: int,
        ep_size: int = 1,
        gate: str = "gshard",
        capacity_factor: float = 1.25,
        mesh_axis: Optional[str] = "ep",
    ) -> None:
        super().__init__()
        enforce_eq(num_experts % max(ep_size, 1), 0, "experts must divide ep size")
        self.num_experts = num_experts
        self.ep_size = max(ep_size, 1)
        self.num_local = num_experts // self.ep_size
        self.capacity_factor = capacity_factor
        self.mesh_axis = mesh_axis if ep_size > 1 else None
        self.gate_fn = {"gshard": top2_gate, "switch": top1_gate, "naive": top1_gate}[gate]
        self.create_parameter(
            "gate_w",
            (d_model, num_experts),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) * 0.01,
        )
        self.experts = ExpertFFN(self.num_local, d_model, d_hidden)
        # aux (load-balance) loss travels through the buffers path so
        # functional_call captures it under jit (a plain attribute would
        # leak a tracer); ``executor.make_train_step`` adds every buffer
        # named ``aux_loss`` to the loss it differentiates
        self.register_buffer("aux_loss", jnp.zeros(()))

    def _capacity(self, tokens: int) -> int:
        top_k = 2 if self.gate_fn is top2_gate else 1
        return max(4, int(math.ceil(tokens * top_k * self.capacity_factor / self.num_experts)))

    def forward(self, x: jax.Array) -> jax.Array:
        # x: [tokens_local, d]
        T, D = x.shape
        C = self._capacity(T)
        logits = x @ self.gate_w
        dispatch, combine, aux = self.gate_fn(logits, C)
        self._buffers["aux_loss"] = aux  # captured by functional_call
        # dense dispatch: [E, C, D]
        expert_in = jnp.einsum("tec,td->ecd", dispatch, x)
        active = self.mesh_axis is not None
        if active:
            # [E, C, D] → exchange so each rank holds its local experts'
            # buffers from ALL ranks: [E_local, ep*C, D]
            expert_in = coll.all_to_all(expert_in, self.mesh_axis, split_axis_=0, concat_axis=1)
        expert_out = self.experts(expert_in)
        if active:
            expert_out = coll.all_to_all(expert_out, self.mesh_axis, split_axis_=1, concat_axis=0)
        # combine back: [T, D]
        return jnp.einsum("tec,ecd->td", combine, expert_out)


# ---------------------------------------------------------------------------
# Dropless top-k routing: sort by expert, grouped matmul, gather back.
# ---------------------------------------------------------------------------


def topk_route(logits: jax.Array, k: int,
               renormalise: bool = False) -> Dict[str, jax.Array]:
    """Softmax router with the k largest probabilities a token, for any k:
    as they are (OLMoE's ``norm_topk_prob`` false), or with ``renormalise``
    divided by their sum — the softmax over the chosen logits alone
    (SmallThinker's ``norm_topk_prob`` true). ``logits`` [T, E] float32.
    Returns ``index`` [T, k] int32 (ties: the lower expert), ``weight``
    [T, k], ``counts`` [E] int32 (assignments an expert), and the two
    router losses:
    ``lb = E * sum_e f_e * P_e`` with ``f_e`` = assignments to e over T (so
    ``sum_e f_e = k``) and ``P_e`` the mean probability of e, and
    ``z = mean_t logsumexp(logits_t)^2``.

    The choice is not differentiated; ``weight`` is read out of the
    probabilities through the one-hot of the choice, so its backward is a
    product, not a scatter."""
    T, E = logits.shape
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    _, index = lax.top_k(lax.stop_gradient(probs), k)
    hot = _one_hot(index, E)                                  # [T, k, E]
    weight = jnp.einsum("tke,te->tk", hot, probs)
    if renormalise:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    per_expert = jnp.sum(hot, axis=(0, 1))                    # exact < 2^24
    lb = E * jnp.sum(per_expert / T * jnp.mean(probs, axis=0))
    return {"index": index, "weight": weight,
            "counts": per_expert.astype(jnp.int32), "lb": lb,
            "z": jnp.mean(jnp.square(lse))}


def sort_by_expert(index: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One stable sort of the T*k expert ids. Assignment ``a = t*k + j`` is
    token t's j-th choice. Returns ``order`` [T*k] (sorted position ->
    assignment: rows of one expert contiguous, in token order) and
    ``inverse`` (assignment -> sorted position), itself a sort: a scatter
    of T*k scalars goes row by row on the TPU."""
    flat = index.reshape(-1)
    slots = jnp.arange(flat.shape[0], dtype=jnp.int32)
    _, order = lax.sort((flat, slots), num_keys=1, is_stable=True)
    _, inverse = lax.sort((order, slots), num_keys=1)
    return order, inverse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch_rows(x: jax.Array, order: jax.Array, inverse: jax.Array,
                  k: int) -> jax.Array:
    """Token rows ``x`` [T, d] gathered into expert order: row i of the
    result is the token of assignment ``order[i]``. Backward: the inverse
    gather and a sum over a token's k rows — never a scatter-add."""
    return jnp.take(x, order // k, axis=0)


def _dispatch_fwd(x, order, inverse, k):
    return dispatch_rows(x, order, inverse, k), inverse


def _dispatch_bwd(k, inverse, g):
    per_choice = jnp.take(g, inverse, axis=0)
    return (per_choice.reshape(-1, k, g.shape[-1]).sum(axis=1), None, None)


dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _unsort_rows(y: jax.Array, order: jax.Array,
                 inverse: jax.Array) -> jax.Array:
    return jnp.take(y, inverse, axis=0)


_unsort_rows.defvjp(
    lambda y, order, inverse: (_unsort_rows(y, order, inverse), order),
    lambda order, g: (jnp.take(g, order, axis=0), None, None))


def combine_rows(y: jax.Array, weight: jax.Array, order: jax.Array,
                 inverse: jax.Array) -> jax.Array:
    """Expert outputs ``y`` [T*k, d] (expert order) back to tokens: a
    gather through the inverse permutation (backward: the gather through
    ``order``), then the sum over a token's k rows weighted by ``weight``
    [T, k]. Float32 out."""
    T, k = weight.shape
    per_choice = _unsort_rows(y, order, inverse).reshape(T, k, y.shape[-1])
    return jnp.einsum("tk,tkd->td", weight, per_choice)


def dropless_moe(x: jax.Array, router_w: jax.Array, w_gate: jax.Array,
                 w_up: jax.Array, w_down: jax.Array,
                 k: int) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Top-k-of-E expert layer with every assignment computed. ``x``
    [T, d]; ``router_w`` [d, E]; banks [E, d, f], [E, d, f], [E, f, d].
    Returns (out [T, d] float32, the router's ``topk_route`` dict with its
    float32 ``logits`` [T, E] and ``dropped``: T*k less the assignments
    that reached an expert, 0 by construction).

    The router runs in float32 at the highest matmul precision whatever
    ``amp`` says: the choice of experts is a comparison of near-equal
    numbers."""
    T = x.shape[0]
    with jax.named_scope("pt.moe.route"):
        logits = router_logits(x, router_w)
        route = topk_route(logits, k)
        route["logits"] = logits
        route["dropped"] = T * k - jnp.sum(route["counts"])
    return _every_assignment(x, route, w_gate, w_up, w_down, k), route


def _every_assignment(x, route, w_gate, w_up, w_down, k,
                      activation=jax.nn.silu):
    """The routed sum with all T*k assignments computed: sorted by expert,
    gathered, three grouped matmuls, gathered back and summed a token."""
    from .. import amp

    with jax.named_scope("pt.moe.dispatch"):
        order, inverse = sort_by_expert(route["index"])
        if amp.amp_enabled() and x.dtype == jnp.float32:
            x = x.astype(amp.amp_dtype())      # half the bytes to permute
        rows = dispatch_rows(x, order, inverse, k)
    with jax.named_scope("pt.moe.experts"):
        y = expert_ffn(rows, w_gate, w_up, w_down, route["counts"],
                       activation)
    with jax.named_scope("pt.moe.combine"):
        return combine_rows(y, route["weight"], order, inverse)


# ---------------------------------------------------------------------------
# Held experts: route over all E, compute the part this chip's experts give.
# ---------------------------------------------------------------------------


def sigmoid_route(logits: jax.Array, bias: jax.Array, k: int,
                  scale: float) -> Dict[str, jax.Array]:
    """DeepSeek-V3's auxiliary-loss-free router (``scoring_func`` sigmoid,
    ``topk_method`` noaux_tc, one group). ``logits`` [T, E] float32;
    ``bias`` [E], a buffer: it moves the CHOICE — the k largest of
    ``sigmoid(logits) + bias``, ties to the lower expert — and never the
    weight. ``weight`` [T, k] = the chosen scores without the bias,
    divided by their sum + 1e-20 (``norm_topk_prob``), times ``scale``
    (``routed_scaling_factor``). Also ``index`` [T, k] int32, ``counts``
    [E] int32 (assignments an expert, over ALL experts). The choice is not differentiated; the weight is read through
    the choice's one-hot, so its backward is a product, not a scatter."""
    E = logits.shape[-1]
    score = jax.nn.sigmoid(logits)
    _, index = lax.top_k(lax.stop_gradient(score) + bias, k)
    hot = _one_hot(index, E)                                  # [T, k, E]
    picked = jnp.einsum("tke,te->tk", hot, score)
    weight = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return {"index": index, "weight": weight * scale,
            "counts": jnp.sum(hot, axis=(0, 1)).astype(jnp.int32)}


def dispatch_ladder(tokens: int, k: int, experts: int,
                    count: int) -> Tuple[int, ...]:
    """Rows a layer that holds ``count`` of ``experts`` computes, by the
    form that runs: the sorted buffer — twice the ``tokens * k * count /
    experts`` assignments that land here when loads are even, in whole row
    tiles of the grouped matmul — and, past it, ``tokens * count``: every
    held expert on every token. The whole layer (``count == experts``)
    computes its ``tokens * k`` assignments as ``dropless_moe`` does.

    The buffer's rows are its SHAPE, what a step may land here before it
    takes the other form. What a step walks of it is read off the step:
    the grouped matmul visits the row tiles of its run-time group sizes,
    the gathers and token sums the ``_HELD_CHUNK``-row chunks up to the
    last live row (``held_moe``'s ``rows_walked``)."""
    if count == experts:
        return (tokens * k,)
    tile = _GMM_TILE[2][0]
    rows = -(-2 * tokens * k * count // (experts * tile)) * tile
    return (min(rows, tokens * k), tokens * count)


#: rows a chunk of the bounded buffer's row movement: a whole multiple of
#: the grouped matmul's row tile (``_GMM_TILE[2][0]``). ``_rows_of_tokens``
#: and ``_sum_to_tokens`` walk the buffer a chunk at a time, as far as the
#: last chunk that holds an assignment. Chosen on the chip among 512, 1024
#: and 2048 (``tools/held_rows_bench.py``; PERF.md section 6, PR 35).
#: Inside the loops a buffer is [chunks, chunk, d] and a chunk is named by
#: its index in that untiled leading dimension: an update at a ROW offset,
#: which the compiler cannot see is aligned to the tiles, stays a copy of
#: its own (28 us a [1024, 2048] f32 chunk where the fused in-place write
#: takes 13; my chip runs, PR 35).
_HELD_CHUNK = 1024


def _chunk_rows(rows: int) -> int:
    """Rows a chunk of a ``rows``-row buffer: a buffer smaller than
    ``_HELD_CHUNK`` is one chunk; the last chunk may be part empty."""
    return min(_HELD_CHUNK, rows)


def _live_chunks(n_held: jax.Array, rows: int) -> jax.Array:
    """Chunks of a ``rows``-row buffer that hold one of its first
    ``n_held`` rows: the trip count of the row movement."""
    chunk = _chunk_rows(rows)
    return jnp.minimum(-(-n_held // chunk), -(-rows // chunk))


class _HeldPlan(NamedTuple):
    """Which token each row of the bounded buffer belongs to, and where a
    token's rows lie once the buffer is sorted by token. ``tok`` [R]: the
    row's token, T for a row past the held assignments; ``perm`` [R]: token
    order -> buffer row; ``tok_sorted`` [R]; ``start`` [T]: a token's first
    row in token order; ``has`` [T]: whether it has one; ``live``: the
    chunks that hold a live row (``_live_chunks``) — in buffer order and
    in token order alike the live rows are the first ``n_held``, so both
    walks stop there."""
    tok: jax.Array
    perm: jax.Array
    tok_sorted: jax.Array
    start: jax.Array
    has: jax.Array
    live: jax.Array


def _held_plan(order: jax.Array, held: jax.Array, n_held: jax.Array,
               rows: int, k: int) -> _HeldPlan:
    T = held.shape[0]
    slots = jnp.arange(rows, dtype=jnp.int32)
    tok = jnp.where(slots < n_held, order[:rows] // k, T)
    tok_sorted, perm = lax.sort((tok, slots), num_keys=1, is_stable=True)
    per_token = jnp.sum(held, axis=1, dtype=jnp.int32)
    start = jnp.cumsum(per_token) - per_token
    return _HeldPlan(tok, perm, tok_sorted, jnp.minimum(start, rows - 1),
                     per_token > 0, _live_chunks(n_held, rows))


# jitted by shape, dtype, k and scope: a model's expert layers are alike, so
# the walk is traced once a process — not once a layer, once more where the
# backward re-runs the form and twice in a set-up program that runs the
# forward pass (a second of Python on the chip's host, PERF.md section 6)
@functools.partial(jax.jit, static_argnames=("k", "scope", "chunk"))
def _sum_to_tokens(z: jax.Array, plan: _HeldPlan, k: int, scope: str,
                   chunk: int) -> jax.Array:
    """Rows ``z`` [R, d] summed by token, [T, d] float32, with gathers
    alone: the rows in token order (a token's at most k rows are then
    neighbours), log2(k) shifted adds that leave a token's sum in its first
    row, and one gather of T rows. A token has 0 to k rows here, so
    ``combine_rows``' gather of k rows a token would read T*k rows to find
    the R that exist (8 times as many at a sixteenth held), and a
    scatter-add of rows this wide goes row by row on the TPU (0.37 us a
    row, PERF.md section 5: ten times a gathered row).

    Walked ``chunk`` rows at a time (``_chunk_rows``) over ``plan.live``
    chunks: in token order the rows of no token (``plan.tok`` T) sort
    last, so what lies past the live chunks is never read — rows of ``z``
    there may hold anything, and the sums' buffer is not even filled. A
    chunk gathers the rows its tokens can reach past its end as well (a
    token straddling the edge still sums whole, in the same order of
    additions as over the whole buffer). The last gather reads a token's first row, or for a token
    with no row a row of one more chunk, of zeros, kept past the buffer (a
    select over [T, d] after the gather was a pass of its own; spread over
    the chunk, because a gather whose padding all names ONE row is the
    slower, PERF.md section 7). The loop's body carries ``scope`` (a
    jitted function is lowered once: what it names must not be the first
    caller's)."""
    R, d = z.shape
    T = plan.has.shape[0]
    chunks = -(-R // chunk)
    shifts = [1 << i for i in range(max(k - 1, 0).bit_length())]
    halo = -(-sum(shifts) // 8) * 8                # whole sublanes
    past = chunks * chunk + halo - R
    perm = jnp.concatenate([plan.perm, jnp.zeros((past,), jnp.int32)])
    tok = jnp.concatenate([plan.tok_sorted, jnp.full((past,), T, jnp.int32)])

    def sum_chunk(c, sums):
        with jax.named_scope(scope):
            t = lax.dynamic_slice(tok, (c * chunk,), (chunk + halo,))
            rows = lax.dynamic_slice(perm, (c * chunk,), (chunk + halo,))
            zs = jnp.take(z, rows, axis=0, mode="clip").astype(jnp.float32)
            for shift in shifts:
                same = jnp.concatenate(
                    [t[shift:] == t[:-shift], jnp.zeros((shift,), bool)])
                nxt = jnp.concatenate([zs[shift:], jnp.zeros_like(zs[:shift])])
                zs = zs + jnp.where(same[:, None], nxt, 0.0)
            return lax.dynamic_update_index_in_dim(sums, zs[:chunk], c, 0)

    sums = lax.dynamic_update_index_in_dim(
        lax.empty((chunks + 1, chunk, d), jnp.float32),
        jnp.zeros((chunk, d), jnp.float32), chunks, 0)
    sums = lax.fori_loop(0, plan.live, sum_chunk, sums)
    none = chunks * chunk + jnp.arange(T, dtype=jnp.int32) % chunk
    return jnp.take(sums.reshape(-1, d),
                    jnp.where(plan.has, plan.start, none), axis=0,
                    mode="clip")


@functools.partial(jax.jit, static_argnames=("scope", "chunk"))
def _rows_of_tokens(x: jax.Array, plan: _HeldPlan, scope: str,
                    chunk: int) -> jax.Array:
    """[R, d]: the token rows of the buffer, gathered ``chunk`` rows at a
    time over ``plan.live`` chunks; zero past the held ones — the chunks
    with no live row are written once, with zeros. The loops' bodies carry
    ``scope``."""
    T, d = x.shape
    R = plan.tok.shape[0]
    chunks = -(-R // chunk)
    toks = jnp.concatenate(
        [plan.tok, jnp.full((chunks * chunk - R,), T, jnp.int32)]).reshape(
            chunks, chunk)

    def zero_chunk(c, buf):
        with jax.named_scope(scope):
            return lax.dynamic_update_index_in_dim(
                buf, jnp.zeros((chunk, d), x.dtype), c, 0)

    def gather_chunk(c, buf):
        with jax.named_scope(scope):
            tok = lax.dynamic_index_in_dim(toks, c, 0, keepdims=False)
            live = tok < T
            # a dead row reads a row of its own, as a token with no row does
            own = (c * chunk + jnp.arange(chunk, dtype=jnp.int32)) % T
            rows = jnp.take(x, jnp.where(live, tok, own), axis=0,
                            mode="clip")
            return lax.dynamic_update_index_in_dim(
                buf, jnp.where(live[:, None], rows, 0), c, 0)

    buf = lax.fori_loop(plan.live, chunks, zero_chunk,
                        lax.empty((chunks, chunk, d), x.dtype))
    buf = lax.fori_loop(0, plan.live, gather_chunk, buf)
    return buf.reshape(-1, d)[:R]


def _chunk(plan: _HeldPlan) -> int:
    return _chunk_rows(plan.tok.shape[0])


# the two are each other's transpose: neither backward is a scatter-add
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gather_held(x, plan, k):
    return _rows_of_tokens(x, plan, "pt.moe.dispatch", _chunk(plan))


_gather_held.defvjp(
    lambda x, plan, k: (_rows_of_tokens(
        x, plan, "pt.moe.dispatch", _chunk(plan)), plan),
    lambda k, plan, g: (_sum_to_tokens(
        g, plan, k, "pt.moe.dispatch", _chunk(plan)).astype(g.dtype), None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _combine_held(z, plan, k):                   # z float32, as the sum is
    return _sum_to_tokens(z, plan, k, "pt.moe.combine", _chunk(plan))


_combine_held.defvjp(
    lambda z, plan, k: (_sum_to_tokens(
        z, plan, k, "pt.moe.combine", _chunk(plan)), plan),
    lambda k, plan, g: (_rows_of_tokens(
        g, plan, "pt.moe.combine", _chunk(plan)), None))


@jax.custom_vjp
def _weights_in_order(weight, order, inverse):
    """[R]: the weights of the buffer's assignments (``order`` [R], the
    head of the sort); backward: a gather through ``inverse`` [T*k]."""
    return jnp.take(weight.reshape(-1), order)


def _weights_in_order_bwd(res, g):
    shape, inverse = res
    got = jnp.take(g, jnp.minimum(inverse, g.shape[0] - 1))
    return (jnp.where(inverse < g.shape[0], got, 0.0).reshape(shape),
            None, None)


_weights_in_order.defvjp(
    lambda weight, order, inverse: (_weights_in_order(weight, order, inverse),
                                    (weight.shape, inverse)),
    _weights_in_order_bwd)


def _held_sorted(rows: int, k: int, activation, x, weight, w_gate, w_up,
                 w_down, order, inverse, held, group_sizes):
    """(the held experts' part of the layer's output through a buffer of
    ``rows`` rows, the assignments it computed: its live rows)."""
    from .. import amp

    n_held = jnp.sum(group_sizes)
    with jax.named_scope("pt.moe.dispatch"):
        plan = _held_plan(order, held, n_held, rows, k)
        if amp.amp_enabled() and x.dtype == jnp.float32:
            x = x.astype(amp.amp_dtype())      # half the bytes to permute
        buf = _gather_held(x, plan, k)
    with jax.named_scope("pt.moe.experts"):
        y = expert_ffn(buf, w_gate, w_up, w_down, group_sizes, activation)
    with jax.named_scope("pt.moe.combine"):
        live = plan.tok < held.shape[0]
        w = jnp.where(live, _weights_in_order(weight, order[:rows], inverse),
                      0.0)
        # the kernel writes nothing into rows past the last group
        y = jnp.where(live[:, None], y, 0.0) * w[:, None]
        return _combine_held(y, plan, k), jnp.sum(live, dtype=jnp.int32)


#: tokens a block of the every-expert form
_DENSE_BLOCK = 1024


def _held_dense(k, activation, x, weight, w_gate, w_up, w_down, local):
    """(the held experts' part with no buffer at all, the assignments it
    computed): every held expert's FFN on every token, times the token's
    weight for that expert (0 where it did not choose it) — ``local``
    [T, k] is the choice less ``first``; the count is of the choices its
    mask let through. A block of tokens at a time; operands as
    ``nn.functional.linear`` casts them under ``amp``."""
    from .. import amp

    T, d = x.shape
    count = w_gate.shape[0]
    dt = amp.amp_dtype() if amp.amp_enabled() else x.dtype
    banks = [w.astype(dt) for w in (w_gate, w_up, w_down)]
    mm = functools.partial(jnp.einsum, preferred_element_type=jnp.float32)

    @jax.checkpoint          # the backward rebuilds a block, keeps none
    def block(args):
        x, weight, local = args
        hot = _one_hot(local, count)
        g = jnp.einsum("tk,tke->te", weight, hot)
        u = x.astype(dt)
        act = activation(mm("td,edf->tef", u, banks[0])) \
            * mm("td,edf->tef", u, banks[1])
        return (mm("tef,efd->td", (act * g[..., None]).astype(dt), banks[2]),
                jnp.sum(hot).astype(jnp.int32))

    n = T // _DENSE_BLOCK if T % _DENSE_BLOCK == 0 else 1
    split = lambda a: a.reshape(n, T // n, *a.shape[1:])
    with jax.named_scope("pt.moe.experts"):
        out, computed = lax.map(block,
                                (split(x), split(weight), split(local)))
        return out.reshape(T, d), jnp.sum(computed)


def _held_forms(rows, k, activation, x, weight, banks, ints):
    """[the sorted buffer of ``rows`` rows, the every-expert form]."""
    order, inverse, held, group_sizes, local = ints
    return [lambda: _held_sorted(rows, k, activation, x, weight, *banks,
                                 order, inverse, held, group_sizes),
            lambda: _held_dense(k, activation, x, weight, *banks, local)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _held_experts(rows, k, activation, dense, x, weight, banks, ints):
    return lax.cond(dense, *reversed(_held_forms(rows, k, activation, x,
                                                 weight, banks, ints)))


def _held_experts_fwd(rows, k, activation, dense, x, weight, banks, ints):
    # nothing a form computes is kept: a ``cond`` under autodiff would keep
    # BOTH forms' residuals. The backward runs the chosen form again (the
    # held experts are a twentieth of the layer's required FLOPs) and
    # differentiates that.
    return (_held_experts(rows, k, activation, dense, x, weight, banks, ints),
            (dense, x, weight, banks, ints))


def _held_experts_bwd(rows, k, activation, res, g):
    dense, x, weight, banks, ints = res

    def back(form):
        def run(x, weight, banks, g):
            _, vjp = jax.vjp(lambda x, w, b: _held_forms(
                rows, k, activation, x, w, b, ints)[form]()[0],
                x, weight, banks)
            return vjp(g)
        return run

    dx, dw, dbanks = lax.cond(dense, back(1), back(0), x, weight, banks,
                              g[0])
    return None, dx, dw, dbanks, None


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def router_logits(x: jax.Array, router_w: jax.Array) -> jax.Array:
    """``x`` [T, d] times ``router_w`` [d, E] in float32 at the highest
    matmul precision whatever ``amp`` says: the choice of experts is a
    comparison of near-equal numbers."""
    return jnp.matmul(x.astype(jnp.float32), router_w,
                      precision=lax.Precision.HIGHEST)


def held_moe(x: jax.Array, router_w: Optional[jax.Array],
             bias: Optional[jax.Array], w_gate: jax.Array, w_up: jax.Array,
             w_down: jax.Array, k: int, held: Tuple[int, int],
             scale: float = 1.0, *,
             route: Optional[Dict[str, jax.Array]] = None,
             activation: Callable = jax.nn.silu
             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Top-k-of-E expert layer of which experts ``first .. first + count``
    (``held``) live here. ``x`` [T, d]; ``router_w`` [d, E]; ``bias`` [E];
    banks [count, d, f], [count, d, f], [count, f, d]: the absent experts'
    weights do not exist; ``activation`` gates an expert (SiLU unless told
    otherwise). Routes over all E (``sigmoid_route``, float32 at
    the highest precision whatever ``amp`` says) — or takes a ``route``
    made elsewhere, from another tensor than the rows dispatched here (a
    router that reads the stream before attention): the router's dict with
    ``logits`` [T, E], ``index`` and ``weight`` [T, k] and ``counts`` [E]
    over ALL experts (``topk_route``'s, ``sigmoid_route``'s); ``router_w``,
    ``bias`` and ``scale`` are then not read — and returns the sum over
    the chosen experts THAT ARE HELD — a partial result — and the router's
    dict with ``logits``, ``held_assignments`` (how many of the T*k landed
    here), ``rung`` (the rows of the form that ran, one of
    ``dispatch_ladder``: the sorted buffer where it holds them, else every
    held expert on every token), ``rows_walked`` (the rows the form's row
    movement passed over: the buffer's ``_HELD_CHUNK``-row chunks up to the
    last that holds an assignment — about half of ``rung`` at even loads,
    all of it where loads fill the buffer — else ``rung`` itself) and
    ``dropped``: the held assignments less those the form that ran computed
    (its live rows, or the choices its mask let through). ``held = (0, E)``
    is the whole layer: every assignment is live, and it runs
    ``dropless_moe``'s stages."""
    T = x.shape[0]
    first, count = held
    if route is None:
        with jax.named_scope("pt.moe.route"):
            logits = router_logits(x, router_w)
            route = sigmoid_route(logits, bias, k, scale)
            route["logits"] = logits
    else:
        route = dict(route)
        enforce_eq(route["index"].shape, (T, k),
                   "a route made elsewhere names k experts a row")
    E = route["logits"].shape[-1]
    enforce(0 <= first and count >= 1 and first + count <= E,
            f"held experts {held} outside 0..{E}")
    enforce_eq(w_gate.shape[0], count, "banks hold the held experts")
    rungs = dispatch_ladder(T, k, E, count)
    if count == E:
        out = _every_assignment(x, route, w_gate, w_up, w_down, k, activation)
        n_held = jnp.sum(route["counts"])
        rung = jnp.asarray(rungs[0], jnp.int32)
        route.update(held_assignments=n_held, dropped=T * k - n_held,
                     rung=rung, rows_walked=rung)
        return out, route
    with jax.named_scope("pt.moe.dispatch"):
        # held assignments first, by expert; the others, all alike, last
        local = route["index"] - first
        held_mask = (local >= 0) & (local < count)
        order, inverse = sort_by_expert(jnp.where(held_mask, local, count))
        group_sizes = route["counts"][first:first + count]
        n_held = jnp.sum(group_sizes)
        dense = n_held > rungs[0]
    out, computed = _held_experts(
        rungs[0], k, activation, dense, x, route["weight"],
        (w_gate, w_up, w_down),
        (order, inverse, held_mask, group_sizes, local))
    walked = jnp.minimum(
        _live_chunks(n_held, rungs[0]) * _chunk_rows(rungs[0]), rungs[0])
    route.update(held_assignments=n_held, dropped=n_held - computed,
                 rung=jnp.where(dense, rungs[1], rungs[0]).astype(jnp.int32),
                 rows_walked=jnp.where(dense, rungs[1], walked).astype(
                     jnp.int32))
    return out, route
