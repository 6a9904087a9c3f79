"""Multi-chip sharded embedding serving.

TPU-native rebuild of HeterComm's multi-GPU sharded KV serving
(`/root/reference/paddle/fluid/framework/fleet/heter_ps/heter_comm_inl.h`):
the reference routes each key to its owner GPU (`calc_shard_index`,
`split_input_to_shard` :441), walks values through p2p staging buffers
(`walk_to_dest` :207), and serves `pull_sparse` :479 / `push_sparse` :575
against per-GPU hash tables. Here the cache state is a jax array sharded
over a mesh axis (rows block-partitioned into HBM shards) and the routing
runs *inside* the compiled step over ICI.

Two routing strategies:

- **key-routed all-to-all** (``routed_cache_pull`` / ``routed_cache_push``
  — the default, the true split_input_to_shard analogue): each device
  dedups its batch slice locally (the merge_grad step,
  heter_comm_inl.h:388), partitions the unique row ids by owner shard
  into fixed-capacity buckets ``[K, cap]``, and ONE ``lax.all_to_all``
  ships each shard exactly the slice it owns (walk_to_dest :207 as a
  compiler-scheduled ICI collective). The owner serves / updates
  O(batch/K) rows and pull results ride a second all_to_all back. Per
  -chip FLOPs and HBM traffic are O(batch·dim/K·cap_factor) — independent
  of the shard count, matching pull :479 / push :575. XLA needs static
  shapes where brpc sends variable-length messages, so buckets carry a
  slack factor and an in-graph **overflow counter** reports any dropped
  entry loudly (no silent truncation; see ``check_route_overflow``).
- **gathered** (``sharded_cache_pull`` / ``sharded_cache_push``, the
  round-2 formulation, kept as the dense fallback and as the parity
  oracle): all_gather the ENTIRE global batch to every shard; each shard
  does the full batch's work. O(batch·K) per-chip — correct but does not
  scale with K.

Bit-for-bit parity with the single-device cache: routing is stable —
device-major bucket order preserves each row's occurrence order, so
per-row segment sums accumulate in the same order as the unsharded push,
and each row's AdaGrad math runs once on its owner shard with identical
inputs. Local pre-dedup (``pre_dedup=True``, the default — it is what
caps hot-key bucket load) pre-merges duplicates, which changes the f32
scatter-add sequence per row (~1-ulp differences); pass
``pre_dedup=False`` for strict bitwise parity with the single-device
push.

Host side, ``shard_spread_rows`` round-robins the dense row ids the
FeasignIndex allocates across the block partition so hot passes fill all
shards evenly (the `key % total_gpu` placement of calc_shard_index,
expressed as a row permutation instead of a hash).
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from .. import nn
from ..core.enforce import enforce, enforce_eq
from .embedding_cache import (CacheConfig, cache_pull, cache_push,
                              resolve_push_mode)

__all__ = [
    "routed_dedup",
    "sharded_cache_pull",
    "sharded_cache_push",
    "routed_cache_pull",
    "routed_cache_push",
    "route_bucket_capacity",
    "check_route_overflow",
    "select_routing",
    "shard_spread_rows",
    "shard_unspread_rows",
    "make_sharded_ctr_train_step",
    "make_sharded_ctr_train_step_from_keys",
]

Axis = Union[str, Tuple[str, ...]]


def _axis_size(axis: Axis) -> jax.Array:
    return lax.psum(1, axis)


# ---------------------------------------------------------------------------
# key-routed all-to-all serving (split_input_to_shard / walk_to_dest)
# ---------------------------------------------------------------------------


def route_bucket_capacity(m: int, K: int, cap_factor: float = 2.0) -> int:
    """Static per-destination bucket capacity for routing ``m`` local rows
    over ``K`` shards. Mean load is m/K; ``cap_factor`` is the slack over
    the mean (the reference's brpc messages are variable-length — XLA
    buckets are the static-shape equivalent, sized like an MoE capacity
    factor). +8 absolute slack keeps tiny batches safe; rounded up to the
    8-lane sublane for TPU layouts. With host-side `shard_spread_rows`
    round-robin placement and pre-dedup, per-bucket load is a tight
    binomial around m/K — factor 2 is ~100σ at production batch sizes."""
    cap = math.ceil(cap_factor * m / K) + 8
    cap = (cap + 7) // 8 * 8
    return min(m, cap)


def check_route_overflow(overflow) -> None:
    """Raise if a routed pull/push reported dropped entries (bucket
    capacity exceeded). Hosts should call this on the step's overflow
    output at whatever cadence they sync losses."""
    n = int(overflow)
    enforce(
        n == 0,
        f"sharded-cache routing overflow: {n} row(s) exceeded the "
        "per-shard bucket capacity and were dropped. Raise cap_factor on "
        "the sharded step (or check shard_spread_rows placement).")


def _route_to_buckets(owner, K: int, cap: int, payloads, fills,
                      presorted: bool = False):
    """Partition ``m`` local entries into per-destination buckets
    (split_input_to_shard, heter_comm_inl.h:441, with static shapes).

    owner: [m] int32 in [0, K]; K marks invalid entries (never routed).
    payloads/fills: arrays of leading dim m and their padding values.
    Returns (buckets [K, cap, ...] per payload, src [K, cap] int32 with
    m = padding, overflow count). Stable: entries keep their original
    relative order inside each bucket (device-major order downstream
    preserves per-row f32 accumulation order vs the unsharded push).
    ``presorted``: owner is already non-decreasing (true after
    jnp.unique — block ownership is monotone in row id), skipping the
    O(m log m) sort on the hot path."""
    m = owner.shape[0]
    with jax.named_scope("pt.route"):
        if presorted:
            order, so = jnp.arange(m), owner
        else:
            order = jnp.argsort(owner, stable=True)
            so = owner[order]
        start = jnp.searchsorted(so, jnp.arange(K + 1))  # bucket starts
        pos = jnp.arange(m) - start[so]  # rank within the destination bucket
        overflow = jnp.sum((so < K) & (pos >= cap)).astype(jnp.int32)
        buckets = []
        for p, fill in zip(payloads, fills):
            b = jnp.full((K, cap) + p.shape[1:], fill, p.dtype)
            # owner K / pos >= cap are out-of-bounds → mode="drop" discards
            buckets.append(b.at[so, pos].set(p[order], mode="drop"))
        src = jnp.full((K, cap), m, jnp.int32)
        src = src.at[so, pos].set(order.astype(jnp.int32), mode="drop")
    return buckets, src, overflow


def _canonical_rows(rows: jax.Array, sentinel: int) -> jax.Array:
    """int32 rows with negative miss markers mapped to the canonical
    out-of-range sentinel (keeps sorted-unique output owner-ordered)."""
    with jax.named_scope("pt.route"):
        rows = rows.astype(jnp.int32)
        return jnp.where(rows < 0, sentinel, rows)


def routed_dedup(rows: jax.Array, sentinel: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """The local merge (CopyKeys/merge_grad dedup half) shared by
    routed pull and push: sorted-unique rows (padded with ``sentinel``)
    + inverse positions. Compute ONCE per step when pull and push see
    the same batch rows — the sort is the routing's main local cost.
    Canonicalizes internally (idempotent): negative miss markers become
    the sentinel so the sorted-unique output stays owner-ordered."""
    rows = _canonical_rows(rows, sentinel)
    m = rows.shape[0]
    with jax.named_scope("pt.route"):
        uniq, inv = jnp.unique(rows, size=m, fill_value=sentinel,
                               return_inverse=True)
        return uniq, inv.reshape(-1)


def _owner_of(rows, shard_rows: int, K: int):
    """Owner shard of each global row id; K for sentinel/out-of-range."""
    with jax.named_scope("pt.route"):
        valid = (rows >= 0) & (rows < shard_rows * K)
        return jnp.where(valid, rows // shard_rows, K).astype(jnp.int32)


def routed_cache_pull(
    state: Dict[str, jax.Array],
    rows: jax.Array,  # [m] global row ids for this device's batch slice
    axis: Axis,
    cap_factor: float = 2.0,
    pre_dedup: bool = True,
    dedup: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Inside shard_map: key-routed pull — this device's [m] global rows
    → ([m, 1+dim] values, overflow count). The HeterComm pull_sparse
    chain (heter_comm_inl.h:479): local merge (dedup), split to shard,
    all_to_all request, owner gathers O(m/K) rows, all_to_all reply,
    scatter back to batch order. Sentinel rows (no owner) pull zeros.
    ``dedup``: a precomputed ``(uniq, inv)`` pair (from
    :func:`routed_dedup`) so a step doing pull AND push on the same rows
    sorts once, not twice."""
    K = int(_axis_size(axis))
    shard_rows = state["embed_w"].shape[0]
    m = rows.shape[0]
    my_start = lax.axis_index(axis) * shard_rows
    rows = _canonical_rows(rows, shard_rows * K)
    enforce(dedup is None or pre_dedup,
            "dedup= requires pre_dedup=True (raw routing ignores it)")
    if pre_dedup:
        lookup, inv = dedup if dedup is not None else routed_dedup(
            rows, shard_rows * K)
    else:
        lookup = rows
    cap = route_bucket_capacity(m, K, cap_factor)
    (breq,), src, overflow = _route_to_buckets(
        _owner_of(lookup, shard_rows, K), K, cap, [lookup], [0],
        presorted=pre_dedup)
    # the collectives carry no pt.* scope: the trace finds them by name
    req = lax.all_to_all(breq, axis, 0, 0)  # [K, cap] rows I serve
    with jax.named_scope("pt.pull"):        # the owner-side gather
        loc = jnp.clip(req.reshape(-1) - my_start, 0, shard_rows - 1)
        vals = cache_pull(state, loc).reshape(K, cap, -1)
    back = lax.all_to_all(vals, axis, 0, 0)  # [K, cap, D] my requests
    with jax.named_scope("pt.route"):       # un-bucket to batch order
        D = back.shape[-1]
        uvals = jnp.zeros((m + 1, D), back.dtype)
        uvals = uvals.at[src.reshape(-1)].set(back.reshape(K * cap, D))[:m]
        out = uvals[inv] if pre_dedup else uvals
    return out, lax.psum(overflow, axis)


def routed_cache_push(
    state: Dict[str, jax.Array],
    rows: jax.Array,   # [m] global row ids for this device's batch slice
    grads: jax.Array,  # [m, 1+dim]
    shows: jax.Array,  # [m]
    clicks: jax.Array,  # [m]
    cfg: CacheConfig,
    axis: Axis,
    cap_factor: float = 2.0,
    pre_dedup: bool = True,
    dedup: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Inside shard_map: key-routed push (heter_comm_inl.h:575): local
    merge_grad (segment-sum duplicates), split to shard, ONE all_to_all
    pair ships each owner only its rows+grads, owner runs the batch
    -scaled `cache_push` over O(m·cap_factor) rows — per-chip update work
    independent of the shard count. Returns (new_state, overflow).
    ``dedup``: precomputed ``(uniq, inv)`` (see :func:`routed_dedup`)."""
    K = int(_axis_size(axis))
    shard_rows = state["embed_w"].shape[0]
    C_total = shard_rows * K
    m = rows.shape[0]
    my_start = lax.axis_index(axis) * shard_rows
    rows = _canonical_rows(rows, C_total)
    enforce(dedup is None or pre_dedup,
            "dedup= requires pre_dedup=True (raw routing ignores it)")
    with jax.named_scope("pt.route"):
        payload = jnp.concatenate(
            [grads, shows[:, None], clicks[:, None]], axis=1)
    if pre_dedup:
        # merge_grad: per-device partial sums, one wire entry per row
        uniq, inv = dedup if dedup is not None else routed_dedup(
            rows, C_total)
        with jax.named_scope("pt.route"):
            payload = jax.ops.segment_sum(payload, inv, num_segments=m)
        rows = uniq
    cap = route_bucket_capacity(m, K, cap_factor)
    (brow, bpay), _, overflow = _route_to_buckets(
        _owner_of(rows, shard_rows, K), K, cap,
        [rows, payload], [C_total, 0.0], presorted=pre_dedup)
    rrow = lax.all_to_all(brow, axis, 0, 0).reshape(-1)
    rpay = lax.all_to_all(bpay, axis, 0, 0).reshape(K * cap, -1)
    with jax.named_scope("pt.route"):       # wire rows → my local rows
        loc = rrow - my_start
        own = (loc >= 0) & (loc < shard_rows)
        loc = jnp.where(own, loc, shard_rows)  # sentinel → dropped in push
        g, dshow, dclick = rpay[:, :-2], rpay[:, -2], rpay[:, -1]
    new_state = cache_push(state, loc, g, dshow, dclick, cfg)
    return new_state, lax.psum(overflow, axis)


def sharded_cache_pull(state: Dict[str, jax.Array], rows: jax.Array,
                       axis: Axis) -> jax.Array:
    """Inside shard_map: pull [m, 1+dim] values for this device's batch
    slice ``rows`` (global row ids, [m]) from the row-sharded cache.

    HeterComm pull_sparse (heter_comm_inl.h:479) analogue: gather-where-
    owned + psum_scatter replaces split_input_to_shard + p2p walk.
    """
    shard_rows = state["embed_w"].shape[0]  # local block size
    my_start = lax.axis_index(axis) * shard_rows
    rows_all = lax.all_gather(rows, axis, tiled=True)  # [m*K], global order
    with jax.named_scope("pt.pull"):
        loc = rows_all - my_start
        own = (loc >= 0) & (loc < shard_rows)
        vals = cache_pull(state, jnp.clip(loc, 0, shard_rows - 1))
        vals = jnp.where(own[:, None], vals, 0.0)
    # each row has exactly one owner → sum assembles, scatter returns my slice
    return lax.psum_scatter(vals, axis, scatter_dimension=0, tiled=True)


def sharded_cache_push(
    state: Dict[str, jax.Array],
    rows: jax.Array,   # [m] global row ids for this device's batch slice
    grads: jax.Array,  # [m, 1+dim]
    shows: jax.Array,  # [m]
    clicks: jax.Array,  # [m]
    cfg: CacheConfig,
    axis: Axis,
) -> Dict[str, jax.Array]:
    """Inside shard_map: push the batch's gradients into the row-sharded
    cache (HeterComm push_sparse, heter_comm_inl.h:575). Each shard runs
    the batch-scaled merge+AdaGrad (`cache_push`) on the full gathered
    batch with non-owned rows mapped to the dropped sentinel."""
    shard_rows = state["embed_w"].shape[0]
    my_start = lax.axis_index(axis) * shard_rows
    rows_all = lax.all_gather(rows, axis, tiled=True)
    grads_all = lax.all_gather(grads, axis, tiled=True)
    shows_all = lax.all_gather(shows, axis, tiled=True)
    clicks_all = lax.all_gather(clicks, axis, tiled=True)
    with jax.named_scope("pt.route"):
        loc = rows_all - my_start
        own = (loc >= 0) & (loc < shard_rows)
        loc = jnp.where(own, loc, shard_rows)  # sentinel → dropped in push
    return cache_push(state, loc, grads_all, shows_all, clicks_all, cfg)


def shard_spread_rows(rows: np.ndarray, capacity: int, n_shards: int) -> np.ndarray:
    """Host-side: permute dense row ids (0,1,2,…) round-robin across the
    block partition so shard s owns rows {r : r % n_shards == s} at block
    offset r // n_shards (calc_shard_index's `key % total_gpu` placement
    as a permutation). Requires capacity % n_shards == 0."""
    block = capacity // n_shards
    return (rows % n_shards) * block + rows // n_shards


def shard_unspread_rows(rows: np.ndarray, capacity: int, n_shards: int) -> np.ndarray:
    """Inverse of shard_spread_rows."""
    block = capacity // n_shards
    return (rows % block) * n_shards + rows // block


def select_routing(m_local: int, shard_rows: int, K: int,
                   push_mode: str) -> Tuple[str, str]:
    """Trace-time routing auto-selection (the decision rule VERDICT r3 #2
    asked for): given the LOCAL per-device row count ``m_local`` (batch
    slice × slots), the per-shard capacity ``shard_rows`` (= C/K), the
    shard count ``K`` and the cache's ``push_mode``, return
    ``(pull_routing, push_routing)`` — each "alltoall" or "allgather".

    The rule is calibrated from the measured 8-combo grid
    (``tools/routed_grid.py`` → ROUTED_GRID.json, CPU mesh; re-run on
    hardware when the chip allows):

    - **Never mix sides.** The routing sort (``routed_dedup``) is paid
      once and SHARED by routed pull and routed push, and the gathered
      formulations share nothing with it — so "a2a pull + ag push" pays
      BOTH the sort and the full-batch all_gather, and was the worst or
      near-worst combo in every measured K=8 cell (e.g. sparse
      1024×1M×8: mixed 79.7 ms vs 44.9 routed / 82.4 gathered). This
      rules out the otherwise-plausible "route the pull, gather the
      push" composition for dense mode.
    - **K ≥ 4 → ("alltoall", "alltoall").** Per-shard serving work and
      wire volume are O(batch/K); measured best or within 5% of best in
      every K=8 cell, both push modes, and its cost is FLAT in K
      (ROUTED_SCALING growth 0.89-0.91× from 2→8 shards) where gathered
      grows toward O(batch·K).
    - **K < 4 → ("allgather", "allgather").** At tiny shard counts the
      gather multiplier barely bites and skipping the dedup sort wins:
      measured best in 7 of 8 K=2 cells. The exception regime —
      dense push with a table much larger than the batch — is a tie:
      the O(C/K) full-table update dominates BOTH routings there
      (all four combos within ~6%), so the choice is immaterial.

    ``m_local`` and ``shard_rows`` key the push formulation:
    ``push_mode="auto"`` is resolved by
    :func:`embedding_cache.resolve_push_mode` for a shard of
    ``shard_rows`` rows receiving the routed push's ``K`` buckets of
    ``route_bucket_capacity(m_local, K)`` slots — the shapes the
    owner-side ``cache_push`` sees at the default ``cap_factor`` (on
    the v5e, PR 25: PERF.md §5's crossover table). Inputs are static at
    trace time, so the selection specializes per compiled shape, like
    every other XLA shape decision.

    **KNOWN RISK — CPU provenance.** Every number behind this rule was
    measured on the 8-device virtual CPU mesh (ROUTED_GRID.json records
    ``"platform": "cpu"``); no on-chip timing of the ROUTINGS exists
    (ROADMAP S6/D5; the push formulation they carry was measured on the
    v5e in PR 25).
    CPU relative costs do NOT transfer to the chip, so the K≥4
    threshold and especially the "never mix sides" conclusion may
    invert on ICI, where all_gather bandwidth and the dedup sort have
    completely different relative prices. Re-key this rule on a
    measured TPU regime before trusting ``routing="auto"`` for
    performance work; correctness is unaffected (all combos are exact,
    and chip_smoke.py's four-chip leg runs the K=4 choice on the chip).
    """
    push_mode = resolve_push_mode(
        push_mode, shard_rows, K * route_bucket_capacity(m_local, K))
    enforce(push_mode in ("dense", "sparse"),
            f"push_mode must be 'dense' or 'sparse', got {push_mode!r}")
    # multi-PROCESS meshes in DENSE mode route at every K: the
    # cross-process sweep (ROUTED_MULTIHOST_DENSE.json) measured
    # routed/gathered 0.92x at K=2, 0.82x at K=4, 0.60x at K=8 — the
    # gathered formulation's full-batch volume loses once a process
    # boundary is in the path. Sparse mode does NOT flip at K=2: its
    # routed path pays the dedup sort, and the sparse sweep
    # (ROUTED_MULTIHOST_SPARSE.json) measured 1.28x at K=2 (routing
    # WORSE) vs 0.75x at K=4 / 0.55x at K=8 — so sparse keeps the K>=4
    # threshold everywhere. Measure, don't extrapolate: the first
    # version of this branch assumed the dense K=2 flip carried over.
    import jax

    if jax.process_count() > 1 and push_mode == "dense":
        return "alltoall", "alltoall"
    if K < 4:
        return "allgather", "allgather"
    return "alltoall", "alltoall"


def _resolve_routing(routing, m_local: int, shard_rows: int, K: int,
                     push_mode: str) -> Tuple[str, str]:
    """Normalize the ``routing`` knob: "auto" → :func:`select_routing`,
    a single mode → both sides, a (pull, push) pair → itself."""
    if routing == "auto":
        return select_routing(m_local, shard_rows, K, push_mode)
    if isinstance(routing, str):
        return routing, routing
    pull, push = routing
    return pull, push


def _check_routing_arg(routing) -> None:
    ok = routing in ("alltoall", "allgather", "auto") or (
        isinstance(routing, tuple) and len(routing) == 2
        and all(r in ("alltoall", "allgather") for r in routing))
    enforce(ok, "routing must be 'alltoall', 'allgather', 'auto' or a "
            f"(pull, push) pair of the former two, got {routing!r}")


def make_sharded_ctr_train_step(
    model,
    optimizer,
    cache_cfg: CacheConfig,
    mesh: Mesh,
    axis: str = "ps",
    donate: bool = True,
    routing="auto",
    cap_factor: float = 2.0,
    pre_dedup: bool = True,
) -> Callable:
    """Multi-chip GPUPS step: the CTR step of models/ctr.py with the
    batch data-parallel over ``axis`` and the embedding cache row-sharded
    over the same devices — pull/push become in-graph all-to-all traffic
    (PSGPUWorker::TrainFiles + HeterComm serving, compiled).

    step(params, opt_state, cache_state, rows, dense_x, labels)
      → (params, opt_state, cache_state, loss, overflow)

    ``rows`` are GLOBAL spread row ids ([B, S], from
    ``HbmEmbeddingCache.lookup`` of a mesh-sharded cache); params/opt
    replicated, grads averaged over ``axis`` (the Reducer/allreduce role).
    ``routing``: "alltoall" (key-routed, O(batch/K) per shard — the
    split_input_to_shard path), "allgather" (dense fallback, O(batch·K)
    per shard), a ``(pull, push)`` pair to mix, or "auto" (the default —
    :func:`select_routing` picks per side from the measured decision
    rule at trace time). ``overflow`` is 0 unless a routed bucket dropped
    entries (check with :func:`check_route_overflow`; always 0 for
    allgather).
    """
    _check_routing_arg(routing)
    K = mesh.shape[axis]

    def inner(params, opt_state, cache_state, rows, dense_x, labels):
        flat = rows.reshape(-1)
        return _sharded_step_body(model, optimizer, cache_cfg, axis, K,
                                  params, opt_state, cache_state, flat,
                                  rows.shape[0], rows.shape[1], dense_x,
                                  labels, routing, cap_factor, pre_dedup)

    shmapped = shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P(axis), P(), P()),
        check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=(0, 1, 2) if donate else ())


def _sharded_step_body(model, optimizer, cache_cfg, axis, K, params,
                       opt_state, cache_state, flat_rows, B, S, dense_x,
                       labels, routing="auto", cap_factor=2.0,
                       pre_dedup=True):
    """Per-rank body of the multi-chip CTR step: sharded pull, local
    fwd/bwd, grad pmean (Reducer role), sharded push. ``flat_rows`` are
    GLOBAL spread row ids for this rank's batch slice; sentinel rows
    (≥ global capacity) pull zeros and drop their pushes. ``routing``
    resolves per side (pull, push) — see :func:`select_routing`."""
    shard_rows = cache_state["embed_w"].shape[0]
    pull_r, push_r = _resolve_routing(routing, flat_rows.shape[0],
                                      shard_rows, K, cache_cfg.push_mode)
    dedup = None
    if pre_dedup and "alltoall" in (pull_r, push_r):
        # pull and push see the SAME batch rows — sort once, use twice
        C_total = shard_rows * K
        flat_rows = _canonical_rows(flat_rows, C_total)
        dedup = routed_dedup(flat_rows, C_total)
    if pull_r == "alltoall":
        emb, ov_pull = routed_cache_pull(cache_state, flat_rows, axis,
                                         cap_factor, pre_dedup, dedup=dedup)
    else:
        emb = sharded_cache_pull(cache_state, flat_rows, axis)
        ov_pull = jnp.int32(0)
    emb = emb.reshape(B, S, -1)

    def loss_fn(params, emb):
        out, _ = nn.functional_call(model, params, emb, dense_x,
                                    training=True)
        loss = nn.functional.binary_cross_entropy_with_logits(
            out, labels.astype(jnp.float32))
        return loss, out

    with jax.named_scope("pt.tower"):  # forward and backward of the model
        (loss, _), (grads, emb_grad) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(params, emb)
        # local-mean → global-mean: scale emb grads by 1/K (exact for
        # power-of-two K) so push matches the unsharded step
        emb_grad = emb_grad / K
    # ... and pmean the dense grads (collectives: no pt.* scope)
    grads = jax.tree.map(lambda g: lax.pmean(g, axis), grads)
    loss = lax.pmean(loss, axis)

    new_params, new_opt = optimizer.update(grads, opt_state, params)
    shows = jnp.ones((B * S,), jnp.float32)
    clicks = jnp.repeat(labels.astype(jnp.float32), S)
    if push_r == "alltoall":
        new_cache, ov_push = routed_cache_push(
            cache_state, flat_rows, emb_grad.reshape(B * S, -1), shows,
            clicks, cache_cfg, axis, cap_factor, pre_dedup, dedup=dedup)
    else:
        new_cache = sharded_cache_push(cache_state, flat_rows,
                                       emb_grad.reshape(B * S, -1), shows,
                                       clicks, cache_cfg, axis)
        ov_push = jnp.int32(0)
    return new_params, new_opt, new_cache, loss, ov_pull + ov_push


def make_sharded_ctr_train_step_from_keys(
    model,
    optimizer,
    cache_cfg: CacheConfig,
    mesh: Mesh,
    slot_ids,
    axis: str = "ps",
    donate: bool = True,
    routing="auto",
    cap_factor: float = 2.0,
    pre_dedup: bool = True,
) -> Callable:
    """Multi-chip GPUPS step with IN-GRAPH key lookup: each device probes
    its local batch slice's slot-tagged keys against the replicated
    per-pass cuckoo map (ps/device_hash.py — the HeterComm CopyKeys +
    HashTable::get front half) and serves pull/push from the row-sharded
    cache over ``axis``. The complete compiled analogue of
    PSGPUWorker::TrainFiles on a multi-chip mesh.

    step(params, opt_state, cache_state, map_state, keys_lo, dense_x,
         labels) → (params, opt_state, cache_state, loss, overflow)
    """
    from .device_hash import device_hash_lookup

    _check_routing_arg(routing)
    K = mesh.shape[axis]
    slot_hi = jnp.asarray(np.asarray(slot_ids, np.uint32))[None, :]

    def inner(params, opt_state, cache_state, map_state, keys_lo, dense_x,
              labels):
        B, S = keys_lo.shape  # local slice
        hi = jnp.broadcast_to(slot_hi, (B, S)).reshape(-1)
        rows = device_hash_lookup(map_state, hi, keys_lo.reshape(-1))
        C_total = cache_state["embed_w"].shape[0] * K  # global capacity
        with jax.named_scope("pt.probe"):
            rows = jnp.where(rows >= 0, rows, C_total)  # sentinel: no owner
        return _sharded_step_body(model, optimizer, cache_cfg, axis, K,
                                  params, opt_state, cache_state, rows, B, S,
                                  dense_x, labels, routing, cap_factor,
                                  pre_dedup)

    shmapped = shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P(axis), P(), P()),
        check_vma=False,
    )
    return jax.jit(shmapped, donate_argnums=(0, 1, 2) if donate else ())
