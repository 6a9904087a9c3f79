"""Dataset-driven sparse training: the ``train_from_dataset`` role.

The reference drives CTR training with `exe.train_from_dataset(program,
dataset)` → `Executor::RunFromDataset` (executor.cc:157) →
`PSGPUTrainer`/`MultiTrainer` whose per-device workers loop
`device_reader->Next()` and run pull→fwd/bwd→push (ps_gpu_worker.cc:121,
hogwild_worker.cc:212). Here the trainer drives an ``InMemoryDataset``
through the GPUPS pass lifecycle against the HBM cache:

    pass_feasigns → cache.begin_pass (dedup + build + upload + cuckoo map)
    per batch     → ONE jitted step (in-graph key lookup, pull, fwd/bwd,
                    dense update, CTR AdaGrad push), fed through the
                    async device prefetcher
    end of pass   → cache.end_pass flush back to the host table

Slot-tagged keys: feasign = slot_id << 32 | id (the framework's slot
layout — FleetWrapper::PullSparseToTensorSync tags by tensor position).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.enforce import enforce
from ..core.flags import flag
from ..core.nan_inf import check_numerics
from ..core.profiler import RecordEvent
from ..obs import flightrec as _flightrec
from ..obs import registry as _obs_registry
from ..data.prefetcher import DevicePrefetcher
from .embedding_cache import CacheConfig, HbmEmbeddingCache
from .table import MemorySparseTable

__all__ = ["CtrPassTrainer", "CtrStreamTrainer"]


def _slot_tagged_keys(batch, sparse_slots) -> np.ndarray:
    """[B, S] slot-tagged feasigns (slot_id << 32 | lo32) from a dataset
    batch's sparse columns — THE key-layout definition both trainers
    share."""
    cols = []
    for si, s in enumerate(sparse_slots):
        v = batch[s][0][:, 0].astype(np.uint64)
        cols.append((v & np.uint64(0xFFFFFFFF))
                    + (np.uint64(si) << np.uint64(32)))
    return np.stack(cols, axis=1)


def _dense_and_labels(batch, dense_slots, label_slot, n_rows: int):
    dense = (np.concatenate([batch[s][0] for s in dense_slots], axis=1)
             .astype(np.float32)
             if dense_slots else np.zeros((n_rows, 0), np.float32))
    labels = batch[label_slot][0][:, 0].astype(np.int32)
    return dense, labels


_PAD_LO32 = np.uint32(0xFFFFFFFF)  # padding key (missing from any pass →
#                                    sentinel row: pulls zeros, push drops)


def _pad_tail(lo32, dense, labels, target_b: int):
    """Pad a short tail batch up to ``target_b`` (the reference pads the
    final mini-batch to a fixed shape instead of recompiling; weights
    mask the padding out of loss/pushes)."""
    b = lo32.shape[0]
    weights = np.ones(target_b, np.float32)
    if b == target_b:
        return lo32, dense, labels, weights
    pad = target_b - b
    weights[b:] = 0.0
    lo32 = np.concatenate(
        [lo32, np.full((pad, lo32.shape[1]), _PAD_LO32, np.uint32)])
    dense = np.concatenate(
        [dense, np.zeros((pad, dense.shape[1]), np.float32)])
    labels = np.concatenate([labels, np.zeros(pad, np.int32)])
    return lo32, dense, labels, weights


@dataclasses.dataclass
class _PassStats:
    steps: int = 0
    samples: int = 0
    loss_sum: float = 0.0

    @property
    def mean_loss(self) -> float:
        return self.loss_sum / max(self.steps, 1)


class CtrPassTrainer:
    """PSGPUTrainer analogue over (model, table, cache).

    ``sparse_slots``/``dense_slots``/``label_slot`` name the dataset's
    slots; sparse slots contribute one feasign per record (CTR layout),
    dense slots concatenate into the float feature vector.
    """

    def __init__(
        self,
        model,
        optimizer,
        table: MemorySparseTable,
        cache_config: CacheConfig,
        sparse_slots: Sequence[str],
        dense_slots: Sequence[str],
        label_slot: str,
        prefetch_depth: int = 3,
        slab: int = 1,
        amp: bool = False,
    ) -> None:
        self.model = model
        self.optimizer = optimizer
        self.table = table
        self.cache = HbmEmbeddingCache(table, cache_config, device_map=True)
        self.sparse_slots = list(sparse_slots)
        self.dense_slots = list(dense_slots)
        self.label_slot = label_slot
        self.prefetch_depth = prefetch_depth
        #: train steps per dispatch (lax.scan over a packed stack —
        #: bitwise-identical to sequential steps, amortizes the
        #: per-dispatch host cost; tail batches run single steps)
        self.slab = int(slab)
        #: bf16 contractions in the dense tower (f32 accumulation and
        #: state) — precision is a property of the compiled steps
        self.amp = bool(amp)

        self.params = {"params": dict(model.named_parameters()), "buffers": {}}
        self.opt_state = optimizer.init(self.params)
        # one compiled step per (batch size, slab) — packed wire offsets
        # bake B in; train_from_dataset reuses across passes
        self._packed_steps: Dict[Tuple[int, int], Any] = {}

    def _packed_step(self, batch_size: int, slab: int = 1):
        from ..models.ctr import (make_ctr_train_step_packed,
                                  make_ctr_train_step_slab)

        step = self._packed_steps.get((batch_size, slab))
        if step is None:
            kw = dict(slot_ids=np.arange(len(self.sparse_slots)),
                      batch_size=batch_size,
                      num_dense=len(self.dense_slots), with_weights=True,
                      amp=self.amp)
            if slab > 1:
                step = make_ctr_train_step_slab(
                    self.model, self.optimizer, self.cache.config,
                    slab=slab, **kw)
            else:
                step = make_ctr_train_step_packed(
                    self.model, self.optimizer, self.cache.config, **kw)
            self._packed_steps[(batch_size, slab)] = step
        return step

    # -- batch packing (MiniBatchGpuPack role) ---------------------------

    def _pack(self, batch: Dict[str, Tuple[np.ndarray, np.ndarray]]):
        """Dataset batch (CSR-ish padded columns) → (lo32, dense, label).
        One feasign per sparse slot (CTR); ids are slot-tagged so only
        the low halves go to the device."""
        tagged = _slot_tagged_keys(batch, self.sparse_slots)
        lo32 = (tagged & np.uint64(0xFFFFFFFF)).astype(np.uint32)
        dense, labels = _dense_and_labels(batch, self.dense_slots,
                                          self.label_slot, lo32.shape[0])
        return lo32, dense, labels

    def _tagged_pass_keys(self, dataset) -> np.ndarray:
        """All slot-tagged feasigns of the pass (the PreBuildTask dedup
        input, ps_gpu_wrapper.cc:92): one walk over the host columns."""
        out = [_slot_tagged_keys(b, self.sparse_slots).reshape(-1)
               for b in dataset.batch_iter(8192, drop_last=False)]
        return np.concatenate(out) if out else np.zeros(0, np.uint64)

    # -- checkpoint / resume (fleet.save_persistables role) --------------

    def save(self, dirname: str, mode: int = 0) -> None:
        """Persist the full training state: sparse table shards (accessor
        save format + mode filter, fleet.save_persistables →
        FleetWrapper::SaveModel) and the dense params/opt snapshot.
        Call at a pass boundary (cache flushed)."""
        import os

        from ..io.checkpoint import save_checkpoint

        enforce(self.cache.state is None,
                "save at a pass boundary (after end_pass)")
        os.makedirs(dirname, exist_ok=True)
        self.table.save(os.path.join(dirname, "sparse"), mode=mode)
        save_checkpoint(os.path.join(dirname, "dense"),
                        self.params, self.opt_state)

    def load(self, dirname: str) -> None:
        """Restore table + dense state saved by :meth:`save`."""
        import os

        from ..io.checkpoint import load_checkpoint

        self.table.load(os.path.join(dirname, "sparse"))
        snap = load_checkpoint(os.path.join(dirname, "dense"))
        self.params = snap["model"]
        self.opt_state = snap["opt"]

    def _infer_fn(self):
        """The ONE inference definition shared by evaluate() and the
        serving export: (params, pulled emb, dense) → CTR probability."""
        import jax.nn as jnn

        from .. import nn

        model = self.model

        def infer(params, emb, dense_x):
            out, _ = nn.functional_call(model, params, emb, dense_x,
                                        training=False)
            return jnn.sigmoid(out)

        return infer

    def save_inference_model(self, dirname: str, fused: bool = False,
                             keys: Optional[np.ndarray] = None) -> None:
        """Export the serving artifact, two deploy shapes:

        - default (``fused=False``): the DENSE graph only
          (fleet.save_inference_model on a PS program — the reference
          prunes ``distributed_lookup_table`` into the serving split):
          the artifact takes (pulled embeddings [B,S,1+dim], dense
          [B,D]) and returns CTR probabilities; pair with
          ``table.pull_sparse`` (or a serving PS client) at inference
          time.
        - ``fused=True``: the WHOLE serving program — in-graph key
          probe + table pull + forward + sigmoid (models/ctr.py
          export_ctr_inference) with this trainer's trained params and
          persistables-pruned tables. Needs an active pass: pass
          ``keys`` (the serving key universe — a fresh pass is built
          from the host table) or call before end_pass.
        """
        if fused:
            from ..models.ctr import export_ctr_inference

            if keys is not None:
                self.cache.begin_pass(np.ascontiguousarray(keys, np.uint64))
            enforce(self.cache.state is not None,
                    "no active pass to export: pass `keys` (the serving "
                    "key universe) or call before end_pass")
            export_ctr_inference(dirname, self.model, self.cache,
                                 slot_ids=np.arange(len(self.sparse_slots)),
                                 num_dense=len(self.dense_slots),
                                 params=self.params["params"])
            return
        from ..io.inference import save_inference_model as _save

        serve = self._infer_fn()
        S = len(self.sparse_slots)
        dim = self.cache.config.embedx_dim
        # batch-polymorphic export: serving batch size is a symbolic dim
        (b,) = jax.export.symbolic_shape("b")
        emb = jax.ShapeDtypeStruct((b, S, 1 + dim), jnp.float32)
        dense = jax.ShapeDtypeStruct((b, len(self.dense_slots)), jnp.float32)
        _save(dirname, serve, self.params, (emb, dense))

    # -- evaluation (worker AUC metric role, metrics_py.cc) --------------

    def evaluate(self, dataset, batch_size: int = 1024,
                 user_slot: Optional[str] = None):
        """AUC over ``dataset`` against the HOST table state (pull
        create=False — unseen features contribute zeros), the reference's
        in-training metric pass. Returns {"auc": float,
        "auc_buckets": [2, B] ndarray} — multi-worker callers sum the
        buckets across workers via ``fleet.util.all_reduce`` and recompute
        (metrics/auc.auc_from_buckets), the GlooWrapper reduce pattern.

        ``user_slot`` names a sparse slot carrying the user/group id; when
        given, the result also includes ``wuauc`` (user-weighted AUC, the
        CTR-serving ranking metric — metrics.h WuaucCalculator)."""
        from ..metrics.auc import AUC
        from ..metrics.basic import WuAUC

        if user_slot is not None:
            enforce(user_slot in self.sparse_slots,
                    f"user_slot {user_slot!r} must be a sparse slot "
                    f"(have {self.sparse_slots})")
        if not hasattr(self, "_infer"):
            self._infer = jax.jit(self._infer_fn())

        S = len(self.sparse_slots)
        dim = self.cache.config.embedx_dim
        metric = AUC()
        wu = WuAUC() if user_slot is not None else None
        for batch in dataset.batch_iter(batch_size, drop_last=False):
            lo32, dense, labels = self._pack(batch)
            keys = (lo32.astype(np.uint64)
                    + (np.arange(S, dtype=np.uint64) << np.uint64(32))).reshape(-1)
            pulled = self.table.pull_sparse(keys, create=False)
            # trailing 1+dim columns = embed_w ++ embedx for BOTH accessor
            # layouts (CTR prefixes show/click; Sparse doesn't)
            emb = pulled[:, -(1 + dim):].reshape(-1, S, 1 + dim)
            probs = np.asarray(self._infer(self.params, jnp.asarray(emb),
                                           jnp.asarray(dense)))
            metric.update(probs, labels)
            if wu is not None:
                uids = batch[user_slot][0][:, 0].astype(np.int64)
                wu.update(uids, probs, labels)
        out = {"auc": float(metric.accumulate()),
               "auc_buckets": metric._buckets.copy()}
        if wu is not None:
            # raw (uid, pred, label) records: the mergeable state — a
            # multi-worker wuauc needs the records gathered (the
            # reference groups by uid after a global shuffle), unlike
            # AUC whose buckets just sum
            st = wu.state  # concatenate the records once
            out["wuauc"] = float(wu.accumulate(st))
            out["wuauc_state"] = st
        return out

    # -- the RunFromDataset loop (see class docstring) --------------------

    def train_from_dataset(self, dataset, batch_size: int = 512,
                           drop_last: bool = True) -> Dict[str, float]:
        """One pass over ``dataset``: begin_pass → steps → end_pass.
        Returns {'loss': mean step loss, 'steps', 'samples',
        'samples_per_sec'}."""
        return self._run_pass(dataset, None, batch_size, drop_last)

    def train_passes(self, datasets: Iterable, batch_size: int = 512,
                     drop_last: bool = True) -> list:
        """Multi-day stream: train each dataset as one pass, OVERLAPPING
        the next pass's host build (dedup + row assignment + cuckoo —
        cache.prepare_pass) with the current pass's training, the
        reference's pre_build_thread pattern (ps_gpu_wrapper.cc:733).
        Table reads/uploads still happen at the pass boundary, so
        results are identical to sequential train_from_dataset calls."""
        from concurrent.futures import ThreadPoolExecutor

        _END = object()

        it = iter(datasets)
        try:
            current = next(it)
        except StopIteration:
            return []
        prepared = self._prepare(current)
        results = []
        with ThreadPoolExecutor(max_workers=1) as pool:
            while True:
                # the background task also PULLS the next dataset: a lazy
                # day-loading generator overlaps its IO with training too
                def _bg():
                    try:
                        ds = next(it)
                    except StopIteration:
                        return _END
                    return ds, self._prepare(ds)

                fut = pool.submit(_bg)
                try:
                    results.append(self._run_pass(current, prepared,
                                                  batch_size, drop_last))
                except BaseException:
                    # never leave a prepare thread running past an
                    # exception (it holds native calls mid-flight) — but
                    # keep the TRAINING failure primary: a secondary
                    # prepare error must not mask this traceback
                    try:
                        fut.result()
                    except Exception:
                        pass
                    raise
                nxt = fut.result()
                if nxt is _END:
                    return results
                current, prepared = nxt

    def _prepare(self, dataset) -> dict:
        with RecordEvent("ctr_pass_prepare"):
            keys = self._tagged_pass_keys(dataset)
            enforce(len(keys) > 0, "dataset has no sparse feasigns")
            return self.cache.prepare_pass(keys)

    def _run_pass(self, dataset, prepared: Optional[dict],
                  batch_size: int, drop_last: bool) -> Dict[str, float]:
        import time

        with RecordEvent("ctr_pass_build"):  # PreBuildTask..BuildGPUTask
            if prepared is None:
                prepared = self._prepare(dataset)
            self.cache.activate_pass(prepared)
        map_state = self.cache.device_map.state

        from ..models.ctr import pack_ctr_batch

        step = self._packed_step(batch_size)
        slab = max(1, self.slab)
        slab_step = (self._packed_step(batch_size, slab) if slab > 1
                     else None)

        def host_batches():
            for batch in dataset.batch_iter(batch_size, drop_last=drop_last):
                lo32, dense, labels = self._pack(batch)
                n_real = lo32.shape[0]  # pre-pad count (host-side)
                # fixed step shape: pad the tail batch instead of
                # recompiling (weights mask loss + pushes); ONE packed
                # buffer per step (lo32 | f16 dense | i8 labels | u8
                # weights) — a single H2D transfer
                lo32, dense, labels, weights = _pad_tail(
                    lo32, dense, labels, batch_size)
                yield pack_ctr_batch(lo32, dense, labels,
                                     weights=weights), n_real

        def host_groups():
            # group `slab` packed buffers per dispatch; the tail of the
            # pass (fewer than slab) falls back to single steps
            buf, reals = [], []
            for packed, n_real in host_batches():
                buf.append(packed)
                reals.append(n_real)
                if len(buf) == slab:
                    yield np.stack(buf), sum(reals), True
                    buf, reals = [], []
            for packed, n_real in zip(buf, reals):
                yield packed, n_real, False

        def to_device(item):
            packed, n_real, is_slab = item
            return jnp.asarray(packed), n_real, is_slab

        stats = _PassStats()
        t0 = time.perf_counter()
        pf = DevicePrefetcher(host_groups() if slab > 1 else (
                                  (p, n, False) for p, n in host_batches()),
                              depth=self.prefetch_depth,
                              transform=to_device)
        losses = []  # device scalars — ONE host sync at pass end
        try:
            for packed, n_real, is_slab in pf:
                with RecordEvent("ctr_train_step"):
                    if is_slab:
                        self.params, self.opt_state, self.cache.state, ls = \
                            slab_step(self.params, self.opt_state,
                                      self.cache.state, map_state, packed)
                        losses.append(jnp.sum(ls))
                        stats.steps += slab
                    else:
                        self.params, self.opt_state, self.cache.state, loss = \
                            step(self.params, self.opt_state,
                                 self.cache.state, map_state, packed)
                        losses.append(loss)
                        stats.steps += 1
                stats.samples += n_real  # host count — no device sync
        finally:
            pf.close()
        if losses:
            stats.loss_sum = float(jnp.sum(jnp.stack(losses)))
            # flag-gated numeric guard (FLAGS_check_nan_inf role,
            # operator.cc:1252): one pass-end check over the synced sum.
            # On divergence, DISCARD the pass (the host table keeps its
            # last-good state and stays checkpointable) and re-raise.
            if flag("check_nan_inf"):
                try:
                    check_numerics(
                        {"pass_loss_sum": jnp.asarray(stats.loss_sum)},
                        "CtrPassTrainer pass")
                except Exception:
                    self.cache.discard_pass()
                    raise
        dt = time.perf_counter() - t0
        self.cache.end_pass()
        return {
            "loss": stats.mean_loss,
            "steps": float(stats.steps),
            "samples": float(stats.samples),
            "samples_per_sec": stats.samples / max(dt, 1e-9),
        }


class CtrStreamTrainer:
    """the_one_ps CPU-table worker loop (streaming, no pass build).

    The reference's non-GPUPS CTR path: `HogwildWorker::TrainFiles`
    (hogwild_worker.cc:212) pulls from the host MemorySparseTable per
    batch (`distributed_lookup_table` → PullSparseToTensorSync), runs the
    dense fwd/bwd, and pushes gradients — synchronously or through the
    async Communicator queue (communicator.cc:554 MainThread merge+send).
    Works with streaming datasets (QueueDataset) since no pass-wide key
    scan is needed; the HBM-cache pass path (CtrPassTrainer) is the
    higher-throughput choice when the working set fits.

    With a ``communicator``, BOTH pulls and pushes route through its
    PSClient under ``table_id`` (pushes async via the queue) — the table
    may be remote; ``table`` is then unused and may be None. Without
    one, ``table`` is the local host table accessed synchronously.
    """

    def __init__(
        self,
        model,
        optimizer,
        table: Optional[MemorySparseTable],
        sparse_slots: Sequence[str],
        dense_slots: Sequence[str],
        label_slot: str,
        communicator=None,   # route via its PSClient (pushes async)
        table_id: int = 0,
        embedx_dim: Optional[int] = None,
        pull_ahead: Optional[int] = None,
        hot_tier=None,       # HotEmbeddingTier | HotTierConfig | None
        placement=None,      # distributed.placement.PlacementManager
    ) -> None:
        from .. import nn
        from .communicator import SyncCommunicator

        enforce(table is not None or communicator is not None,
                "need a local table or a communicator-wrapped client")
        self.model = model
        self.table = table
        self.sparse_slots = list(sparse_slots)
        self.dense_slots = list(dense_slots)
        self.label_slot = label_slot
        self.communicator = communicator
        self.table_id = table_id
        #: sparse pull prefetch depth — batch N+k's pull issues (via
        #: communicator.pull_sparse_async) while batch N computes,
        #: hiding PS round-trip latency behind the step. Defaults to
        #: FLAGS_communicator_pull_ahead for Async/HalfAsync
        #: communicators; forced 0 for Sync mode and local tables, whose
        #: contract is exact pull-after-push ordering per batch.
        if communicator is None or isinstance(communicator, SyncCommunicator):
            self.pull_ahead = 0
        elif pull_ahead is None:
            self.pull_ahead = max(0, int(flag("communicator_pull_ahead")))
        else:
            self.pull_ahead = max(0, int(pull_ahead))
        #: measured auto-placement (distributed/placement.py): per-batch
        #: poll() may swap this table PS↔collective at an epoch fence —
        #: prefetched pulls would straddle the swap plane, so placement
        #: forces exact per-batch ordering (pull_ahead 0), and the hot
        #: tier owns its own residency story (mutually exclusive)
        self.placement = placement
        if placement is not None:
            enforce(hot_tier is None,
                    "placement and hot_tier are mutually exclusive — "
                    "the tier already owns this table's residency")
            self.pull_ahead = 0
        if embedx_dim is not None:
            self._dim = int(embedx_dim)
        else:
            enforce(table is not None,
                    "pass embedx_dim when no local table is given")
            self._dim = table.accessor.config.embedx_dim
        self._pull_width = 1 + self._dim

        self.params = {"params": dict(model.named_parameters()), "buffers": {}}
        self.opt_state = optimizer.init(self.params)
        opt = optimizer

        def loss_fn(params, emb, dense_x, labels):
            out, _ = nn.functional_call(model, params, emb, dense_x,
                                        training=True)
            loss = nn.functional.binary_cross_entropy_with_logits(
                out, labels.astype(jnp.float32))
            return loss, out

        @jax.jit
        def step(params, opt_state, emb, dense_x, labels):
            (loss, _), (grads, emb_grad) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(params, emb, dense_x,
                                                       labels)
            new_params, new_opt = opt.update(grads, opt_state, params)
            return new_params, new_opt, loss, emb_grad

        self._step = step
        #: completed-batch cursor of the LAST (or current)
        #: train_from_dataset run — the stream position a job
        #: checkpoint records and a restarted job resumes from
        self.batches_done = 0
        # obs: per-step wall time as a job-wide histogram — the curve
        # the step-time SLO rule (obs/slo.py) burns against. Bound here
        # (cold path); observed once per step (lock-cheap)
        self._h_step = _obs_registry.REGISTRY.histogram(
            "trainer_step_time_s", max_series=256, table=str(table_id))

        #: persistent HBM hot-embedding tier (ps/hot_tier.py): warm ids
        #: resolve/pull/push INSIDE the compiled step — a warm
        #: steady-state batch performs ZERO PS RPCs; misses backfill
        #: from the PS (prefetched on the communicator's pull workers
        #: when pull-ahead is on) and evictions write dirty rows back
        self.hot_tier = None
        self._hot_step = None
        if hot_tier is not None:
            from .hot_tier import (HotEmbeddingTier, HotTierConfig,
                                   make_hot_ctr_train_step,
                                   make_sharded_hot_train_step)

            if isinstance(hot_tier, HotTierConfig):
                cold = table
                if cold is None:
                    cli = communicator.client
                    if hasattr(cli, "_sparse"):  # LocalPsClient
                        cold = cli._sparse(table_id)
                    else:  # RpcPsClient — full-row view over the wire
                        from .rpc import RemoteSparseTable

                        cold = RemoteSparseTable(
                            cli, table_id, cli.sparse_config(table_id))
                hot_tier = HotEmbeddingTier(cold, hot_tier)
            self.hot_tier = hot_tier
            enforce(self.hot_tier.cache_config.embedx_dim == self._dim,
                    "hot tier embedx_dim must match the trainer's")
            slot_ids = np.arange(len(self.sparse_slots))
            tc = self.hot_tier.config
            pb = self.hot_tier.device_map.probe_buckets
            bks = self.hot_tier.device_map.banks
            if tc.mesh is not None:
                self._hot_step = make_sharded_hot_train_step(
                    model, optimizer, self.hot_tier.cache_config, tc.mesh,
                    slot_ids=slot_ids, axis=tc.axis, routing=tc.routing,
                    cap_factor=tc.cap_factor, probe_buckets=pb, banks=bks)
            else:
                self._hot_step = make_hot_ctr_train_step(
                    model, optimizer, self.hot_tier.cache_config,
                    slot_ids=slot_ids, probe_buckets=pb, banks=bks)

    # -- job checkpoint surface (io/job_checkpoint.py) --------------------

    def train_state(self) -> Dict[str, Any]:
        """The dense tier of a job snapshot: params + optimizer state
        (save_train_state schema; no rng — the stream step is
        deterministic given the pulled rows)."""
        return {"state": self.params, "opt": self.opt_state}

    # -- live-reshard surface (ps/reshard.py) -----------------------------

    def on_reshard(self) -> None:
        """Trainer-side reshard participation, called from the TRAINING
        thread at a batch boundary (tests/demos; a production loop
        wires it to the controller's journal or an operator signal).
        Strictly optional — the data plane self-corrects either way
        (misrouted ops bounce and replay) — but it tightens the window:
        the communicator quiesces (no queued push straddles the
        cutover), the hot tier flushes dirty residents WITHOUT dropping
        the resident set (HotEmbeddingTier.on_reshard — warm hit rate
        survives the topology flip), and the client re-resolves the
        routing table proactively instead of paying one bounced op."""
        if self.communicator is not None:
            self.communicator.quiesce()
        if self.hot_tier is not None:
            self.hot_tier.on_reshard()
        if self.communicator is not None:
            refresh = getattr(self.communicator.client, "refresh_routing",
                              None)
            if refresh is not None:
                refresh()
        if self.placement is not None:
            # the reshard's pre-cutover hook already fenced the manager;
            # this batch boundary is the first safe point after it —
            # apply any armed swap now instead of waiting a batch
            self.placement.poll(self)

    def restore_train_state(self, dense: Dict[str, Any]) -> None:
        """Inverse of :meth:`train_state` — accepts the dict
        ``load_train_state``/``RestoredJob.dense`` returns."""
        self.params = dense["state"]
        self.opt_state = dense["opt"]
        if self.placement is not None:
            # the PS was (or is about to be) rebuilt from the
            # checkpoint — a collective-plane residence is stale
            # relative to it; fall back to the PS plane and let the
            # policy re-densify from fresh density samples
            self.placement.reset_to_ps()
        if self.hot_tier is not None:
            # the cold table was (or is about to be) rebuilt from the
            # checkpoint — the resident set is stale relative to it;
            # restart cold and refill on miss (resume-exact: rows
            # round-trip the PS bit-for-bit)
            self.hot_tier.drop()

    def train_from_dataset(self, dataset, batch_size: int = 512,
                           drop_last: bool = True,
                           start_batch: "int | Dict[str, Any]" = 0,
                           checkpoint=None, checkpoint_every: int = 0
                           ) -> Dict[str, float]:
        """See :meth:`_train_from_dataset` — this wrapper only adds the
        flight-recorder hook: an exception that escapes the stream loop
        (a failover that out-ran every replay, a poisoned batch, NaN
        guard) notifies ``trainer_exception`` so the postmortem bundle
        with the last steps' telemetry is dumped BEFORE the stack
        unwinds past anyone who could still read it."""
        try:
            return self._train_from_dataset(
                dataset, batch_size=batch_size, drop_last=drop_last,
                start_batch=start_batch, checkpoint=checkpoint,
                checkpoint_every=checkpoint_every)
        except BaseException as e:
            _flightrec.notify("trainer_exception",
                              error=f"{type(e).__name__}: {e}",
                              batches_done=self.batches_done)
            raise

    def _train_from_dataset(self, dataset, batch_size: int = 512,
                            drop_last: bool = True,
                            start_batch: "int | Dict[str, Any]" = 0,
                            checkpoint=None, checkpoint_every: int = 0
                            ) -> Dict[str, float]:
        """``start_batch`` re-enters the stream at a saved cursor —
        pass ``RestoredJob.cursor`` itself (the dict form validates
        that ``batch_size`` matches the one the cursor was recorded
        under; a batch offset at a different size is a WRONG record
        offset) or a raw batch index; ``checkpoint`` (a
        JobCheckpointManager this trainer's table(s)
        are registered with) snapshots the whole job every
        ``checkpoint_every`` completed batches: the communicator is
        quiesced first (no queued push or in-flight prefetch pull
        straddles the cut), then the manager gates PS mutations and
        captures tables + dense state + this cursor as one cut. The
        resume-exact contract (restart bit-identical to an oracle)
        holds in sync mode (pull_ahead 0); async modes resume within
        their usual staleness envelope."""
        import inspect
        import time
        from collections import deque

        if isinstance(start_batch, dict):
            # the saved cursor: its batch offset counts batches OF THE
            # RECORDED SIZE — resuming at a different batch_size would
            # silently re-enter the stream at the wrong record offset
            # (or re-train records), exactly the silent-wrong-position
            # class the checkpoint checksums exist to rule out
            saved_bs = start_batch.get("batch_size")
            enforce(saved_bs is None or int(saved_bs) == int(batch_size),
                    f"cursor was recorded at batch_size={saved_bs}; "
                    f"resuming at batch_size={batch_size} re-enters the "
                    "stream at the wrong record offset — resume with "
                    "the saved batch_size")
            start_batch = int(start_batch.get("batch", 0))
        S = len(self.sparse_slots)
        slot_ids = np.tile(np.arange(S, dtype=np.int32), batch_size)
        # streaming QueueDataset.batch_iter has no drop_last; older
        # dataset shims may predate the start_batch cursor
        params = inspect.signature(dataset.batch_iter).parameters
        kw = {k: v for k, v in (("drop_last", drop_last),
                                ("start_batch", start_batch))
              if k in params}
        enforce(start_batch == 0 or "start_batch" in params,
                f"{type(dataset).__name__}.batch_iter has no start_batch "
                "cursor — cannot resume mid-stream")
        stats = _PassStats()
        depth = self.pull_ahead
        self.batches_done = int(start_batch)

        if self.hot_tier is not None:
            return self._train_hot(dataset, batch_size, kw, stats, depth,
                                   checkpoint, checkpoint_every)

        def _prep(batch):
            keys = _slot_tagged_keys(batch, self.sparse_slots)
            flat = keys.reshape(-1)
            dense, labels = _dense_and_labels(batch, self.dense_slots,
                                              self.label_slot, keys.shape[0])
            # pull-ahead: kick batch N+depth's pull NOW so it overlaps
            # the compiled steps in front of it (double-buffered at 1)
            fut = (self.communicator.pull_sparse_async(
                       self.table_id, flat, create=True,
                       slots=slot_ids[:len(flat)])
                   if depth > 0 else None)
            return keys, flat, dense, labels, fut

        def _run(item):
            # RecordEvent = trace ROOT while obs tracing is on: one
            # sampled stream step becomes one cross-process trace whose
            # pull/push child spans flow-link to the PS shards' spans
            t_step = time.perf_counter()
            with RecordEvent("ctr_stream_step"):
                keys, flat, dense, labels, fut = item
                # measured-placement hook: a swap armed by the policy
                # (and fenced by a reshard epoch) executes HERE, at the
                # batch boundary — never mid-push
                lt = None
                if self.placement is not None:
                    self.placement.poll(self)
                    lt = self.placement.local_table
                if lt is not None:  # collective-plane local residence
                    pulled = lt.pull_sparse(
                        flat, slots=slot_ids[:len(flat)], create=True)
                elif fut is not None:
                    pulled = fut.result()
                elif self.communicator is not None:  # same client as pushes
                    pulled = self.communicator.client.pull_sparse(
                        self.table_id, flat, create=True,
                        slots=slot_ids[:len(flat)])
                else:
                    pulled = self.table.pull_sparse(
                        flat, slots=slot_ids[:len(flat)], create=True)
                emb = pulled[:, -self._pull_width:].reshape(
                    keys.shape[0], S, self._pull_width)
                self.params, self.opt_state, loss, emb_grad = self._step(
                    self.params, self.opt_state, jnp.asarray(emb),
                    jnp.asarray(dense), jnp.asarray(labels))
                g = np.asarray(emb_grad).reshape(-1, self._pull_width)
                push = np.empty((len(flat), 4 + self._dim), np.float32)
                push[:, 0] = slot_ids[:len(flat)]
                push[:, 1] = 1.0                        # show
                push[:, 2] = np.repeat(labels, S)       # click
                push[:, 3:] = g
                if lt is not None:
                    lt.push_sparse(flat, push)
                    # local pushes never cross the wire counters — feed
                    # the placement window directly so sparsify-back
                    # still has a live signal
                    self.placement.observe_push(push)
                elif self.communicator is not None:
                    self.communicator.send_sparse(self.table_id, flat, push)
                else:
                    self.table.push_sparse(flat, push)
                stats.steps += 1
                stats.samples += int(labels.shape[0])
                stats.loss_sum += float(loss)
                self.batches_done += 1
                self._h_step.observe(time.perf_counter() - t_step)
                self._maybe_checkpoint(checkpoint, checkpoint_every,
                                       batch_size)

        t0 = time.perf_counter()
        window: deque = deque()  # batches with an issued (or due) pull
        try:
            for batch in dataset.batch_iter(batch_size, **kw):
                window.append(_prep(batch))
                if len(window) > depth:
                    _run(window.popleft())
            while window:
                _run(window.popleft())
        finally:
            # an exception mid-pass must not leave prefetched pulls in
            # flight (their worker would race the caller's recovery)
            if depth > 0:
                self.communicator._drain_pulls()
        dt = time.perf_counter() - t0
        if self.communicator is not None:
            # drains sends AND prefetch pulls, and RAISES any failure the
            # background push thread hit mid-pass (a PS shard death that
            # out-ran failover must fail the pass loudly, not lose
            # whatever gradients were queued behind the dead connection)
            self.communicator.barrier()
        return {
            "loss": stats.mean_loss,
            "steps": float(stats.steps),
            "samples": float(stats.samples),
            "samples_per_sec": stats.samples / max(dt, 1e-9),
        }

    def _train_hot(self, dataset, batch_size: int, kw: Dict[str, Any],
                   stats: "_PassStats", depth: int, checkpoint,
                   checkpoint_every: int) -> Dict[str, float]:
        """The hot-tier loop: residency is ensured host-side per batch
        (warm batch → pure mirror lookups, ZERO PS RPCs), then ONE
        compiled step does map probe → pull → fwd/bwd → dense update →
        CTR push entirely in HBM. Misses backfill full rows from the
        cold store — prefetched on the communicator's pull workers when
        pull-ahead is on — and evictions write dirty rows back, so the
        PS sees exactly the end_pass-style flush traffic, never
        per-batch pulls/pushes."""
        import time
        from collections import deque

        tier = self.hot_tier
        sharded = tier.config.mesh is not None
        overflow = None  # device scalar accumulator (sharded routing)
        # deferred loss sync: the hot step is fully in-graph, so keeping
        # the loss as a DEVICE scalar lets the dispatch return while the
        # chip still computes — the next batch's host work (key tagging,
        # ensure() mirror lookups, H2D) overlaps the step in front of it
        # (the CtrPassTrainer losses-list pattern). The pass-end
        # conversion runs the SAME per-step float() accumulation, so the
        # reported mean loss is bit-identical to the per-step sync.
        losses: list = []

        from ..data.prefetcher import DevicePrefetcher

        # batch PACKING (dataset column slicing, key tagging, H2D
        # staging) is pure read-only work — it runs on the prefetcher
        # thread and overlaps the compiled steps, exactly the
        # CtrPassTrainer feed pattern. Tier mutations (prefetch issue,
        # ensure) STAY on the training thread: the host mirror is not
        # thread-safe and the creation-order determinism contract
        # depends on the single consumer.
        def _packed_batches():
            for batch in dataset.batch_iter(batch_size, **kw):
                keys = _slot_tagged_keys(batch, self.sparse_slots)
                flat = keys.reshape(-1)
                dense, labels = _dense_and_labels(
                    batch, self.dense_slots, self.label_slot, keys.shape[0])
                lo32 = (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)
                yield (flat, jnp.asarray(lo32), jnp.asarray(dense),
                       jnp.asarray(labels), int(labels.shape[0]))

        # graftlint: hot-path
        def _prep(item):
            if depth > 0:
                # issue the COLD fetch for batch N+depth's misses now —
                # warm batches fetch nothing, so this is free in steady
                # state and hides the PS round-trip when residency moves
                tier.prefetch(item[0], self.communicator)
            return item

        # graftlint: hot-path
        def _run(item):
            t_step = time.perf_counter()
            with RecordEvent("ctr_hot_step"):
                _run_body(*item)
            self._h_step.observe(time.perf_counter() - t_step)

        # graftlint: hot-path
        def _run_body(flat, lo32, dense, labels, n_real):
            nonlocal overflow
            tier.ensure(flat)
            map_state = tier.device_map.device_state()
            out = self._hot_step(self.params, self.opt_state, tier.state,
                                 map_state, lo32, dense, labels)
            self.params, self.opt_state, tier.state, loss = out[:4]
            if sharded:
                ov = out[4]
                overflow = ov if overflow is None else overflow + ov
            losses.append(loss)  # device scalar — no sync here
            if len(losses) >= 4096:
                # bounded retention: steps this old finished long ago,
                # so draining the prefix costs no overlap (same
                # per-item float() order as the pass-end drain)
                for l in losses:
                    stats.loss_sum += float(l)
                losses.clear()
            stats.steps += 1
            stats.samples += n_real
            self.batches_done += 1
            self._maybe_checkpoint(checkpoint, checkpoint_every, batch_size)

        t0 = time.perf_counter()
        window: deque = deque()
        pf = DevicePrefetcher(_packed_batches(), depth=max(depth, 2))
        try:
            for item in pf:
                window.append(_prep(item))
                if len(window) > depth:
                    _run(window.popleft())
            while window:
                _run(window.popleft())
        finally:
            pf.close()
            if depth > 0 and self.communicator is not None:
                self.communicator._drain_pulls()
        # ONE host sync for the whole pass (per-item float() keeps the
        # accumulation association identical to a per-step sync)
        for l in losses:
            stats.loss_sum += float(l)
        if overflow is not None:
            from .sharded_cache import check_route_overflow

            check_route_overflow(overflow)
        dt = time.perf_counter() - t0
        if self.communicator is not None:
            self.communicator.barrier()
        return {
            "loss": stats.mean_loss,
            "steps": float(stats.steps),
            "samples": float(stats.samples),
            "samples_per_sec": stats.samples / max(dt, 1e-9),
            # the observability satellite: hit-rate/churn/occupancy ride
            # the result dict so benches and chaos gates assert on
            # counters, not timing alone
            "hot_tier": tier.stats(),
        }

    def _maybe_checkpoint(self, checkpoint, every: int,
                          batch_size: int) -> None:
        if checkpoint is None or every <= 0 or \
                self.batches_done % every != 0:
            return
        if self.communicator is not None:
            # local quiesce, NOT barrier(): sync mode's barrier is a
            # cross-trainer rendezvous the others aren't at
            self.communicator.quiesce()
        if self.placement is not None:
            # collective-plane residents write back (without leaving
            # the plane) so the captured PS table is complete — same
            # contract as the hot tier's flush-dirty-then-snapshot
            self.placement.flush()
        if self.hot_tier is not None:
            # flush-dirty-then-snapshot: every resident row's training
            # lands in the cold table BEFORE the manager gates mutations
            # and digests the cut — the captured checkpoint is complete
            # without knowing the tier exists
            self.hot_tier.flush()
        checkpoint.save(step=self.batches_done,
                        cursor={"batch": self.batches_done,
                                "batch_size": int(batch_size)},
                        dense=self.train_state())
