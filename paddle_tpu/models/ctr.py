"""CTR model family: DeepFM and Wide&Deep over the sparse PS path.

Reference ladder rungs 3-4 (/root/repo/BASELINE.json): "DeepFM on Criteo
(PaddleRec, Fleet the_one_ps parameter-server mode)" and "Wide&Deep
trillion-feature CTR (HeterPS / GPUPS sparse embedding path)". The
reference runs these as static programs whose ``distributed_lookup_table``
/ ``pull_gpups_sparse`` ops call the PS; here the whole step — embedding
pull (gather), dense fwd/bwd, dense update, and the per-feature CTR
AdaGrad push (scatter) — is ONE jitted XLA program over the HBM cache
state (ps/embedding_cache.py), reproducing the GPUPS pass model
(ps_gpu_wrapper.cc:759 build_task / :825 PullSparse / :893 PushSparseGrad)
with the compiler scheduling what HeterComm hand-routed.

Semantics kept for parity: show=1 per example-slot, click=label
(FleetWrapper::PushSparseFromTensorAsync fills show/click this way,
ps/wrapper/fleet.cc), first-order weight = embed_w, second-order/deep
embedding = embedx_w.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..nn.layer import Layer
from ..ps.device_hash import device_hash_lookup
from ..amp import step_ctx
from ..ps.embedding_cache import CacheConfig, cache_pull, cache_push

__all__ = ["CtrConfig", "DeepFM", "WideDeep", "DCN", "XDeepFM",
           "export_ctr_inference", "serving_pull",
           "make_ctr_train_step",
           "make_ctr_train_step_from_keys", "make_ctr_pooled_train_step",
           "make_ctr_train_step_packed", "make_ctr_train_step_slab",
           "pack_ctr_batch", "make_random_packs"]


@dataclasses.dataclass
class CtrConfig:
    num_sparse_slots: int = 26       # Criteo categorical slots
    num_dense: int = 13              # Criteo continuous features
    embedx_dim: int = 8
    dnn_hidden: Tuple[int, ...] = (400, 400, 400)


class _DNN(Layer):
    """Relu MLP tower; ``out_dim=1`` (the default) squeezes to a logit
    — the ONE tower definition the whole model family shares."""

    def __init__(self, in_dim: int, hidden: Tuple[int, ...],
                 out_dim: int = 1) -> None:
        super().__init__()
        dims = (in_dim,) + tuple(hidden) + (out_dim,)
        self.out_dim = out_dim
        self.layers = nn.LayerList(
            [nn.Linear(dims[i], dims[i + 1]) for i in range(len(dims) - 1)]
        )

    def forward(self, x: jax.Array) -> jax.Array:
        for i, lin in enumerate(self.layers):
            x = lin(x)
            if i + 1 < len(self.layers):
                x = nn.functional.relu(x)
        return x[..., 0] if self.out_dim == 1 else x


class DeepFM(Layer):
    """FM (first + second order over slot embeddings) + DNN tower
    (PaddleRec models/rank/deepfm semantics).

    forward(emb, dense_x): ``emb`` is the pulled [B, S, 1+dim] block
    (embed_w ++ embedx_w per slot) — the embedding table itself lives in
    the PS cache, not in this layer."""

    def __init__(self, cfg: CtrConfig) -> None:
        super().__init__()
        self.cfg = cfg
        self.dense_lin = nn.Linear(cfg.num_dense, 1)
        self.dnn = _DNN(cfg.num_sparse_slots * cfg.embedx_dim + cfg.num_dense,
                        cfg.dnn_hidden)

    def forward(self, emb: jax.Array, dense_x: jax.Array) -> jax.Array:
        cfg = self.cfg
        w1 = emb[..., 0]                      # [B, S] first-order weights
        v = emb[..., 1:]                      # [B, S, dim]
        first = jnp.sum(w1, axis=-1)
        sum_v = jnp.sum(v, axis=1)            # [B, dim]
        sum_sq = jnp.sum(v * v, axis=1)
        second = 0.5 * jnp.sum(sum_v * sum_v - sum_sq, axis=-1)
        deep_in = jnp.concatenate(
            [v.reshape(v.shape[0], cfg.num_sparse_slots * cfg.embedx_dim),
             dense_x], axis=-1)
        deep = self.dnn(deep_in)
        return first + second + deep + self.dense_lin(dense_x)[..., 0]


class WideDeep(Layer):
    """Wide (first-order sparse + dense linear) & Deep (DNN over
    embeddings) — PaddleRec models/rank/wide_deep semantics, the HeterPS
    trillion-feature workload."""

    def __init__(self, cfg: CtrConfig) -> None:
        super().__init__()
        self.cfg = cfg
        self.wide = nn.Linear(cfg.num_dense, 1)
        self.dnn = _DNN(cfg.num_sparse_slots * cfg.embedx_dim + cfg.num_dense,
                        cfg.dnn_hidden)

    def forward(self, emb: jax.Array, dense_x: jax.Array) -> jax.Array:
        cfg = self.cfg
        wide = jnp.sum(emb[..., 0], axis=-1) + self.wide(dense_x)[..., 0]
        v = emb[..., 1:]
        deep_in = jnp.concatenate(
            [v.reshape(v.shape[0], cfg.num_sparse_slots * cfg.embedx_dim),
             dense_x], axis=-1)
        return wide + self.dnn(deep_in)


class DCN(Layer):
    """Deep & Cross Network (PaddleRec models/rank/dcn semantics): an
    explicit feature-cross tower ``x_{l+1} = x0 * (w_l · x_l) + b_l +
    x_l`` alongside the DNN, combined linearly. Same (emb, dense)
    interface as DeepFM — the embedding table lives in the PS cache."""

    def __init__(self, cfg: CtrConfig, num_cross: int = 3) -> None:
        super().__init__()
        self.cfg = cfg
        d = cfg.num_sparse_slots * cfg.embedx_dim + cfg.num_dense
        self.num_cross = num_cross
        self.cross = nn.LayerList(
            [nn.Linear(d, 1) for _ in range(num_cross)])
        self.dnn = _DNN(d, cfg.dnn_hidden)
        self.combine = nn.Linear(d + 1, 1)

    def forward(self, emb: jax.Array, dense_x: jax.Array) -> jax.Array:
        cfg = self.cfg
        v = emb[..., 1:]
        x0 = jnp.concatenate(
            [v.reshape(v.shape[0], cfg.num_sparse_slots * cfg.embedx_dim),
             dense_x], axis=-1)
        x = x0
        for lin in self.cross:
            # x0 * (w·x) + b + x  (bias lives in the Linear)
            x = x0 * lin(x) + x
        deep = self.dnn(x0)
        out = self.combine(jnp.concatenate([x, deep[:, None]], axis=-1))
        return out[..., 0] + jnp.sum(emb[..., 0], axis=-1)


class XDeepFM(Layer):
    """xDeepFM (PaddleRec models/rank/xdeepfm): Compressed Interaction
    Network over the slot embeddings (vector-wise explicit crosses of
    bounded order) + DNN + first-order terms."""

    def __init__(self, cfg: CtrConfig,
                 cin_layers: Tuple[int, ...] = (16, 16)) -> None:
        super().__init__()
        self.cfg = cfg
        self.cin_sizes = tuple(cin_layers)
        S = cfg.num_sparse_slots
        prev = S
        self.cin = nn.LayerList([])
        for h in self.cin_sizes:
            # one 1x1 conv per CIN layer ≡ Linear over the S*prev
            # pairwise-product channels, applied per embedding dim
            self.cin.append(nn.Linear(S * prev, h, bias_attr=False))
            prev = h
        self.cin_out = nn.Linear(sum(self.cin_sizes), 1)
        self.dnn = _DNN(S * cfg.embedx_dim + cfg.num_dense, cfg.dnn_hidden)
        self.dense_lin = nn.Linear(cfg.num_dense, 1)

    def forward(self, emb: jax.Array, dense_x: jax.Array) -> jax.Array:
        cfg = self.cfg
        S, D = cfg.num_sparse_slots, cfg.embedx_dim
        v = emb[..., 1:]                       # [B, S, D]
        x0 = v
        xk = v
        pooled = []
        for lin in self.cin:
            # pairwise products [B, S, Hk, D] → linear over (S·Hk) per dim
            z = (x0[:, :, None, :] * xk[:, None, :, :]).reshape(
                v.shape[0], -1, D)             # [B, S*Hk, D]
            xk = lin(z.transpose(0, 2, 1)).transpose(0, 2, 1)  # [B, H, D]
            pooled.append(jnp.sum(xk, axis=-1))  # sum-pool over dim
        cin = self.cin_out(jnp.concatenate(pooled, axis=-1))[..., 0]
        deep_in = jnp.concatenate(
            [v.reshape(v.shape[0], S * D), dense_x], axis=-1)
        return (cin + self.dnn(deep_in) + self.dense_lin(dense_x)[..., 0]
                + jnp.sum(emb[..., 0], axis=-1))


def make_ctr_train_step(
    model: Layer,
    optimizer,
    cache_cfg: CacheConfig,
    donate: bool = True,
) -> Callable:
    """Build the jitted GPUPS-style step:

    step(params, opt_state, cache_state, rows, dense_x, labels)
      → (params, opt_state, cache_state, loss)

    ``rows``: [B, S] cache-row ids from ``HbmEmbeddingCache.lookup``.
    Embedding pull, dense fwd/bwd+update, and the CTR AdaGrad sparse push
    (show=1, click=label) compile into one XLA program; cache/opt/param
    buffers are donated so HBM is updated in place.
    """

    def step(params, opt_state, cache_state, rows, dense_x, labels,
             weights=None):
        B, S = rows.shape
        return _ctr_step_body(model, optimizer, cache_cfg, params, opt_state,
                              cache_state, rows.reshape(-1), B, S, dense_x,
                              labels, weights)

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())


def _weighted_mean(per: jax.Array, weights) -> jax.Array:
    """Mean of per-example losses under the optional [B] 0/1 tail-batch
    padding mask — THE reduction every CTR-family objective shares."""
    if weights is None:
        return jnp.mean(per)
    w = weights.astype(jnp.float32)
    return jnp.sum(per * w) / jnp.maximum(jnp.sum(w), 1.0)


def _make_loss_fn(model, dense_x, labels, weights):
    """Weighted BCE over the model's logits; ``weights`` ([B] 0/1,
    optional) is the tail-batch padding mask — padded examples
    contribute neither loss nor pushes."""

    def loss_fn(params, emb):
        out, _ = nn.functional_call(model, params, emb, dense_x,
                                    training=True)
        per = nn.functional.binary_cross_entropy_with_logits(
            out, labels.astype(jnp.float32), reduction="none")
        return _weighted_mean(per, weights), out

    return loss_fn


def _push_stats(labels, weights, n_cols, real=None):
    """Per-position (show, click) for the sparse push: show=1 per real
    example-position, click=label (FleetWrapper::PushSparseFromTensorAsync
    semantics); ``real`` ([B*n_cols] 0/1, optional) masks padding
    positions of multi-valued slots."""
    if weights is None:
        shows = jnp.ones((labels.shape[0] * n_cols,), jnp.float32)
    else:
        shows = jnp.repeat(weights.astype(jnp.float32), n_cols)
    if real is not None:
        shows = shows * real
    clicks = jnp.repeat(labels.astype(jnp.float32), n_cols) * shows
    return shows, clicks


def _ctr_step_body(model, optimizer, cache_cfg, params, opt_state,
                   cache_state, flat_rows, B, S, dense_x, labels,
                   weights=None, loss_builder=None, with_real=False):
    # hosts may ship dense/labels in narrow wire dtypes (f16 / int8);
    # compute is f32.
    # ``loss_builder`` (default: single-task weighted BCE) lets model
    # families with their own objective (multitask, attention) reuse
    # this body — masked pull, tail weights, push stats — without
    # copying it. ``with_real``: derive the [B, S] real-position mask
    # from the sentinel and hand it to the builder (attention models
    # consume it; push stats mask padding positions with it).
    dense_x = dense_x.astype(jnp.float32)
    labels = labels.astype(jnp.int32)
    emb = cache_pull(cache_state, flat_rows).reshape(B, S, -1)
    builder = loss_builder or _make_loss_fn
    real = None
    if with_real:
        C = cache_state["embed_w"].shape[0]
        real = (flat_rows < C).astype(jnp.float32).reshape(B, S)
        built = builder(model, dense_x, labels, weights, real)
    else:
        built = builder(model, dense_x, labels, weights)
    with jax.named_scope("pt.tower"):  # forward and backward of the model
        (loss, _), (grads, emb_grad) = jax.value_and_grad(
            built, argnums=(0, 1), has_aux=True)(params, emb)

    new_params, new_opt = optimizer.update(grads, opt_state, params)
    # the click task is column 0 when labels carry multiple tasks
    click_labels = labels if labels.ndim == 1 else labels[:, 0]
    shows, clicks = _push_stats(click_labels, weights, S,
                                real=None if real is None
                                else real.reshape(-1))
    new_cache = cache_push(cache_state, flat_rows,
                           emb_grad.reshape(B * S, -1), shows, clicks,
                           cache_cfg)
    return new_params, new_opt, new_cache, loss


def make_ctr_pooled_train_step(
    model: Layer,
    optimizer,
    cache_cfg: CacheConfig,
    slot_of_column,
    donate: bool = True,
    amp: bool = False,
) -> Callable:
    """GPUPS step for MULTI-VALUED sparse slots: each slot carries up to
    max_len feasigns per example and their embeddings SUM-POOL into the
    slot representation (the reference's
    ``FleetWrapper::PullSparseToTensorSync`` accumulates multiple
    feasigns into one output tensor slice, ps/wrapper/fleet.cc:110; push
    hands the slot gradient to every contributing feasign with show=1
    each — PushSparseFromTensorAsync :169).

    ``slot_of_column``: static [T] int array mapping each padded key
    column to its slot (T = sum of per-slot max_lens, S slots).
    ``rows``: [B, T] cache rows from ``HbmEmbeddingCache.lookup``;
    PADDING positions must hold the capacity sentinel C — they pull
    zeros (identity for the sum-pool) and their pushes are dropped.

    step(params, opt_state, cache_state, rows, dense_x, labels)
      → (params, opt_state, cache_state, loss)
    """
    seg = jnp.asarray(np.asarray(slot_of_column, np.int32))
    S = int(np.asarray(slot_of_column).max()) + 1

    def step(params, opt_state, cache_state, rows, dense_x, labels,
             weights=None):
      with step_ctx(amp):
        # same narrow-wire contract as _ctr_step_body: f16/int8 inputs
        # up-cast here, compute is f32
        dense_x = dense_x.astype(jnp.float32)
        labels = labels.astype(jnp.int32)
        B, T = rows.shape
        C = cache_state["embed_w"].shape[0]
        flat = rows.reshape(-1)
        emb_pos = cache_pull(cache_state, flat).reshape(B, T, -1)
        # sum-pool columns into slots: [B, T, 1+dim] → [B, S, 1+dim]
        pooled = jax.ops.segment_sum(
            jnp.swapaxes(emb_pos, 0, 1), seg, num_segments=S)
        pooled = jnp.swapaxes(pooled, 0, 1)

        (loss, _), (grads, pooled_grad) = jax.value_and_grad(
            _make_loss_fn(model, dense_x, labels, weights),
            argnums=(0, 1), has_aux=True)(params, pooled)
        new_params, new_opt = optimizer.update(grads, opt_state, params)

        # sum-pool ⇒ each contributing position receives the slot grad
        pos_grad = pooled_grad[:, seg, :].reshape(B * T, -1)
        real = (flat < C).astype(jnp.float32)
        shows, clicks = _push_stats(labels, weights, T, real=real)
        new_cache = cache_push(cache_state, flat, pos_grad, shows, clicks,
                               cache_cfg)
        return new_params, new_opt, new_cache, loss

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())


def pack_ctr_batch(lo32: np.ndarray, dense: np.ndarray,
                   labels: np.ndarray,
                   weights: Optional[np.ndarray] = None) -> np.ndarray:
    """Host side: one contiguous uint8 buffer per step —
    [lo32 u32 | dense f16 | labels i8 | weights u8?] — so the H2D path
    pays ONE transfer + dispatch instead of three or four (per-transfer
    overhead on this host: not measured). ``weights`` (0/1 tail-padding mask) is optional; the
    unpacking step must be built with the matching ``with_weights``.
    Shapes are checked: a transposed array would repack to the same
    byte count and silently scramble examples."""
    B = labels.shape[0]
    enforce(lo32.ndim == 2 and lo32.shape[0] == B,
            f"lo32 must be [B={B}, S], got {lo32.shape}")
    enforce(dense.ndim == 2 and dense.shape[0] == B,
            f"dense must be [B={B}, D], got {dense.shape}")
    # f16 wire: fine for normalized CTR features (Criteo's are
    # log-transformed); an unnormalized column overflowing f16 must fail
    # HERE, loudly, not as a silent inf/NaN pass downstream
    with np.errstate(over="ignore"):  # overflow handled by the enforce
        dense16 = np.ascontiguousarray(dense, np.float16)
    enforce(bool(np.isfinite(dense16).all())
            or not bool(np.isfinite(np.asarray(dense)).all()),
            "dense features overflow the f16 wire format (|x| > 65504); "
            "normalize them or widen the wire")
    # single host copy: byte views concatenated once, no bytes objects
    parts = [
        np.ascontiguousarray(lo32, np.uint32).view(np.uint8).ravel(),
        dense16.view(np.uint8).ravel(),
        np.ascontiguousarray(labels, np.int8).view(np.uint8).ravel(),
    ]
    if weights is not None:
        enforce(weights.shape == (B,), f"weights must be [B={B}]")
        w = np.asarray(weights)
        # the u8 wire column carries the 0/1 tail-padding MASK only —
        # fractional importance weights would silently floor to 0
        enforce(bool(((w == 0) | (w == 1)).all()),
                "packed weights must be a 0/1 padding mask")
        parts.append(np.ascontiguousarray(w, np.uint8).ravel())
    return np.concatenate(parts)


def make_random_packs(rng, pool: np.ndarray, batch: int, num_dense: int,
                      n: int, p_click: float = 0.3) -> list:
    """``n`` random packed wire buffers drawn from a slot-tagged key pool
    [rows, S] — the ONE place bench/smoke/tests get the random-batch
    recipe, so a wire-format change can't drift between them."""
    packs = []
    for _ in range(n):
        idx = rng.integers(0, len(pool), size=batch)
        lo32 = (pool[idx] & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        dense = rng.normal(size=(batch, num_dense)).astype(np.float16)
        labels = (rng.random(batch) < p_click).astype(np.int8)
        packs.append(pack_ctr_batch(lo32, dense, labels))
    return packs


def _packed_layout(B: int, S: int, D: int, with_weights: bool):
    o_dense = B * S * 4
    o_label = o_dense + B * D * 2
    o_weight = o_label + B
    total = o_weight + (B if with_weights else 0)
    return o_dense, o_label, o_weight, total


def _unpack_ctr(packed, B, S, D, o_dense, o_label, o_weight, with_weights):
    """In-graph bitcast of ONE packed wire buffer back into
    (lo32, dense, labels, weights) — static offsets."""
    from jax import lax

    with jax.named_scope("pt.unpack"):
        lo = lax.bitcast_convert_type(
            packed[:o_dense].reshape(B * S, 4), jnp.uint32)
        dense_x = lax.bitcast_convert_type(
            packed[o_dense:o_label].reshape(B, D, 2), jnp.float16)
        labels = lax.bitcast_convert_type(packed[o_label:o_weight], jnp.int8)
        weights = (packed[o_weight:].astype(jnp.float32)
                   if with_weights else None)
        return lo, dense_x, labels, weights


def make_ctr_train_step_packed(
    model: Layer,
    optimizer,
    cache_cfg: CacheConfig,
    slot_ids,
    batch_size: int,
    num_dense: int,
    with_weights: bool = False,
    donate: bool = True,
    amp: bool = False,
) -> Callable:
    """The from-keys GPUPS step over a SINGLE packed wire buffer
    (``pack_ctr_batch``): the step bitcasts the buffer back into
    lo32/dense/labels in-graph (static offsets — B, S, D are trace-time
    constants) and continues exactly like make_ctr_train_step_from_keys.

    step(params, opt_state, cache_state, map_state, packed_u8)
      → (params, opt_state, cache_state, loss)
    """
    slot_hi = jnp.asarray(np.asarray(slot_ids, np.uint32))
    B, S, D = int(batch_size), int(slot_hi.shape[0]), int(num_dense)
    o_dense, o_label, o_weight, total = _packed_layout(B, S, D, with_weights)

    def step(params, opt_state, cache_state, map_state, packed):
        enforce_eq(packed.shape[0], total, "packed batch size")
        with step_ctx(amp):
            lo, dense_x, labels, weights = _unpack_ctr(
                packed, B, S, D, o_dense, o_label, o_weight, with_weights)
            hi = jnp.broadcast_to(slot_hi[None, :], (B, S)).reshape(-1)
            rows = _lookup_rows(cache_state, map_state, hi, lo)
            return _ctr_step_body(model, optimizer, cache_cfg, params,
                                  opt_state, cache_state, rows, B, S,
                                  dense_x, labels, weights)

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())


def make_ctr_train_step_slab(
    model: Layer,
    optimizer,
    cache_cfg: CacheConfig,
    slot_ids,
    batch_size: int,
    num_dense: int,
    slab: int,
    with_weights: bool = False,
    donate: bool = True,
    amp: bool = False,
) -> Callable:
    """``slab`` packed train steps per DISPATCH: a ``lax.scan`` over a
    device-resident [slab, total] stack of packed wire buffers runs the
    whole per-batch pipeline (unpack → probe → pull → fwd/bwd → update →
    push) ``slab`` times inside one XLA program — per-dispatch host
    overhead amortizes by 1/slab, and the slab uploads as ONE
    transfer. The wire
    format and per-step math are byte-identical to the packed step
    (bitwise-parity tested), so the host pipeline just stacks ``slab``
    ``pack_ctr_batch`` rows.

    step(params, opt_state, cache_state, map_state, packed_slab[slab,·])
      → (params, opt_state, cache_state, losses [slab])
    """
    from jax import lax

    slot_hi = jnp.asarray(np.asarray(slot_ids, np.uint32))
    B, S, D = int(batch_size), int(slot_hi.shape[0]), int(num_dense)
    o_dense, o_label, o_weight, total = _packed_layout(B, S, D, with_weights)
    slab = int(slab)
    enforce(slab >= 1, "slab >= 1")

    def step(params, opt_state, cache_state, map_state, packed_slab):
        enforce_eq(tuple(packed_slab.shape), (slab, total),
                   "packed slab shape")
        hi = jnp.broadcast_to(slot_hi[None, :], (B, S)).reshape(-1)

        def one(carry, packed):
            params, opt_state, cache_state = carry
            lo, dense_x, labels, weights = _unpack_ctr(
                packed, B, S, D, o_dense, o_label, o_weight, with_weights)
            rows = _lookup_rows(cache_state, map_state, hi, lo)
            params, opt_state, cache_state, loss = _ctr_step_body(
                model, optimizer, cache_cfg, params, opt_state,
                cache_state, rows, B, S, dense_x, labels, weights)
            return (params, opt_state, cache_state), loss

        with step_ctx(amp):
            (params, opt_state, cache_state), losses = lax.scan(
                one, (params, opt_state, cache_state), packed_slab)
        return params, opt_state, cache_state, losses

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())


def _lookup_rows(cache_state, map_state, hi, lo):
    """In-graph key→row probe with the missing-key sentinel contract:
    keys outside the pass working set map to capacity C (zero pull,
    dropped push) — ONE definition for the packed and from-keys steps."""
    rows = device_hash_lookup(map_state, hi, lo)
    C = cache_state["embed_w"].shape[0]
    with jax.named_scope("pt.probe"):
        return jnp.where(rows >= 0, rows, C)


def make_ctr_train_step_from_keys(
    model: Layer,
    optimizer,
    cache_cfg: CacheConfig,
    slot_ids=None,
    donate: bool = True,
    amp: bool = False,
) -> Callable:
    """GPUPS step with IN-GRAPH key lookup — the architecture the
    reference uses on GPU (PSGPUWorker: CopyKeys then device
    ``HashTable::get``, heter_ps/hashtable_inl.h): the host ships only the
    low-32 halves of the slot-tagged feasigns; the key→row probe
    (ps/device_hash.py over the pass's cuckoo table), embedding pull,
    fwd/bwd, dense update, and CTR AdaGrad push all compile into ONE XLA
    program. ``slot_ids`` are the static per-column high halves
    (key = slot_id << 32 | lo32 — the slot-tagged layout of
    FleetWrapper::PullSparseToTensorSync inputs).

    step(params, opt_state, cache_state, map_state, keys_lo, dense_x,
         labels) → (params, opt_state, cache_state, loss)

    Keys missing from the pass working set map to the capacity sentinel:
    pushes for them are dropped; pulls return zeros (pass protocol
    guarantees batch ⊆ pass keys, matching the build/serve contract).

    ``slot_ids=None`` selects the wide-key variant for feasigns whose
    high halves are NOT the column slot: the step then takes
    ``(keys_hi, keys_lo)`` instead of ``keys_lo`` (double the wire
    bytes — prefer slot-tagged keys where the layout allows).
    """
    slot_hi = (jnp.asarray(np.asarray(slot_ids, np.uint32))[None, :]
               if slot_ids is not None else None)

    def _finish(params, opt_state, cache_state, hi, lo, B, S, dense_x,
                labels, map_state, weights):
        with step_ctx(amp):
            rows = _lookup_rows(cache_state, map_state, hi, lo)
            return _ctr_step_body(model, optimizer, cache_cfg, params,
                                  opt_state, cache_state, rows, B, S,
                                  dense_x, labels, weights)

    if slot_ids is not None:
        def step(params, opt_state, cache_state, map_state, keys_lo,
                 dense_x, labels, weights=None):
            B, S = keys_lo.shape
            hi = jnp.broadcast_to(slot_hi, (B, S)).reshape(-1)
            return _finish(params, opt_state, cache_state, hi,
                           keys_lo.reshape(-1), B, S, dense_x, labels,
                           map_state, weights)
    else:
        def step(params, opt_state, cache_state, map_state, keys_hi,
                 keys_lo, dense_x, labels, weights=None):
            B, S = keys_lo.shape
            return _finish(params, opt_state, cache_state,
                           keys_hi.reshape(-1), keys_lo.reshape(-1), B, S,
                           dense_x, labels, map_state, weights)

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())


def serving_pull(tables, map_state, slot_hi_d, lo32, with_real=False):
    """THE serving-side probe→pull ([B, S] lo32 keys → [B, S, 1+dim]
    embeddings) — shared by every serving export so serving and
    training cannot diverge on sentinel masking or row layout: the
    probe is device_hash_lookup and the gather is the training
    cache_pull (rows ≥ C zero-fill). ``with_real`` also returns the
    [B, S] 0/1 real-position mask (attention models consume it — the
    training steps' with_real contract)."""
    B, S = lo32.shape
    C = tables["embed_w"].shape[0]
    hi = jnp.broadcast_to(slot_hi_d[None, :], (B, S)).reshape(-1)
    rows = device_hash_lookup(map_state, hi,
                              lo32.reshape(-1).astype(jnp.uint32))
    rows = jnp.where(rows >= 0, rows, C)
    emb = cache_pull(tables, rows).reshape(B, S, -1)
    if with_real:
        return emb, (rows < C).astype(jnp.float32).reshape(B, S)
    return emb


def export_ctr_inference(dirname: str, model: Layer, cache, slot_ids,
                         num_dense: int, freeze: bool = False,
                         with_real: bool = False, params=None,
                         refresh_only: bool = False) -> None:
    """``fleet.save_inference_model`` for the CTR serving path: export
    probe → pull → forward → sigmoid as one portable program
    (io/inference.py StableHLO export). The exported parameters are the
    dense model params plus the PRUNED serving tables — embed_w /
    embedx_w only; optimizer state, show/click and lifecycle stats are
    training-only and dropped, the reference's persistables pruning
    (save_inference_model prunes the program to feed→fetch and keeps
    only referenced persistables) — plus the pass's key→row map.

    Serving input: (lo32 [B, S] uint32, dense [B, D] float32) → pctr
    [B] float32 (or a tuple of per-task probabilities for multitask
    models — sigmoid applies per output leaf). Missing keys probe to
    the sentinel and contribute zero embeddings, the serving-side
    contract for out-of-pass features. ``with_real=True`` feeds the
    model the [B, S] real-position mask as its second argument (the
    attention family's with_real step contract — DIN).

    ``refresh_only=True``: overwrite just the serving VALUES (model
    params + tables + key map) of an existing unfrozen export — the
    online-learning refresh, skipping the program re-trace/re-serialize
    (the dominant export cost). Shapes must match the original export
    (same capacity/dims — true between refreshes of one serving job)."""
    from ..io.inference import refresh_inference_params, save_inference_model

    enforce(cache.state is not None, "begin_pass first")
    enforce(cache.device_map is not None,
            "export_ctr_inference needs device_map=True on the cache "
            "(the serving program probes the pass's key map in-graph)")
    slot_hi = np.asarray(slot_ids, np.uint32)
    S, D = int(slot_hi.shape[0]), int(num_dense)
    # ``params``: trained param dict override — trainers whose jitted
    # steps DONATE their buffers hold the live params themselves; the
    # Layer's own arrays may be stale/deleted there
    serving = {
        "model": {"params": dict(params if params is not None
                                 else model.named_parameters()),
                  "buffers": {}},
        "tables": {"embed_w": cache.state["embed_w"],
                   "embedx_w": cache.state["embedx_w"]},
        "map": cache.device_map.state,
    }
    if refresh_only:
        enforce(not freeze, "refresh_only applies to unfrozen exports")
        refresh_inference_params(dirname, serving)
        return
    slot_hi_d = jnp.asarray(slot_hi)

    def serve_fn(params, lo32, dense_x):
        # the Layer is a trace-time closure, not exported data
        if with_real:
            emb, real = serving_pull(params["tables"], params["map"],
                                     slot_hi_d, lo32, with_real=True)
            args = (emb, real, dense_x.astype(jnp.float32))
        else:
            emb = serving_pull(params["tables"], params["map"], slot_hi_d,
                               lo32)
            args = (emb, dense_x.astype(jnp.float32))
        out, _ = nn.functional_call(model, params["model"], *args,
                                    training=False)
        # the model's OWN logits→probability mapping when it defines one
        # (ESMM.predict returns (pCTR, pCTCVR = pCTR·pCVR) — the exact
        # quantity offline eval scored; serving must not diverge from
        # it); plain sigmoid per leaf otherwise
        predict = getattr(type(model), "predict", None)
        if predict is not None:
            return predict(out)
        return jax.tree_util.tree_map(jax.nn.sigmoid, out)

    # batch-polymorphic export: serving batch size is a deploy-time choice
    (b,) = jax.export.symbolic_shape("b")
    example = (jax.ShapeDtypeStruct((b, S), jnp.uint32),
               jax.ShapeDtypeStruct((b, D), jnp.float32))
    save_inference_model(dirname, serve_fn, serving, example, freeze=freeze)
