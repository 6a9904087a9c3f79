"""The system under test for CTR pass training: the pass lifecycle of
``CtrPassTrainer`` (``begin_pass`` -> slab steps fed by the device
prefetcher -> ``end_pass``) on one chip, and the key-routed sharded step on
a mesh. The only file that touches the program for these cells.

Program surface held on to (all public, in their modules' ``__all__``):
``paddle_tpu.seed``, ``optimizer.Adam/SGD``,
``models.ctr.{CtrConfig, DeepFM, make_ctr_train_step_slab,
make_ctr_train_step_packed, pack_ctr_batch}``,
``ps.embedding_cache.{CacheConfig, HbmEmbeddingCache}``
(``begin_pass``, ``lookup``, ``end_pass``, ``discard_pass``, ``.state``,
``.device_map.state``), ``ps.table.{MemorySparseTable, TableConfig}``
(``export_full``), ``ps.accessor.AccessorConfig``,
``ps.sharded_cache.make_sharded_ctr_train_step_from_keys``,
``core.mesh.make_mesh``, ``data.prefetcher.DevicePrefetcher``.

The one-chip loop is ``CtrPassTrainer._run_pass``'s own: slab steps built
``with_weights=True`` at the trainer's ``amp`` and ``slab``, fed by a
``DevicePrefetcher`` of the trainer's depth. The trainer itself is not
driven because it runs a dataset to its end and always flushes; a window
needs neither. The sharded path has no trainer, no slab and no ``amp``
option in the program, so the mesh cell dispatches the program's jitted
step as it is: one step per dispatch, f32.
"""

from __future__ import annotations

import itertools
import time
import types
from typing import Any, Dict, List

import numpy as np

#: dispatches the trainers keep ready on the device
#: (``CtrPassTrainer(prefetch_depth=3)``)
PREFETCH_DEPTH = 3
#: rows of device state read back and compared in every run
SAMPLE_ROWS = 4096

STATE_COLS = ("show", "click", "embed_w", "embed_state", "embedx_w",
              "embedx_state", "has_embedx")


def _model(cfg):
    from paddle_tpu.models.ctr import CtrConfig, DeepFM

    return DeepFM(CtrConfig(
        num_sparse_slots=cfg["num_sparse_slots"],
        num_dense=cfg["dense_input_dim"],
        embedx_dim=cfg["sparse_feature_dim"] - 1,
        dnn_hidden=tuple(cfg["fc_sizes"])))


def _table(cfg, seed):
    from paddle_tpu.ps.accessor import AccessorConfig
    from paddle_tpu.ps.table import MemorySparseTable, TableConfig

    return MemorySparseTable(TableConfig(
        shard_num=16, seed=seed, accessor_config=AccessorConfig(
            embedx_dim=cfg["sparse_feature_dim"] - 1)))


def _cache_cfg(cfg, capacity):
    from paddle_tpu.ps.embedding_cache import CacheConfig

    return CacheConfig(capacity=capacity,
                       embedx_dim=cfg["sparse_feature_dim"] - 1,
                       embedx_threshold=cfg["embedx_threshold"])


class CtrPassSystem:
    unit = "samples"

    def __init__(self, cell, seed: int, devices: List[Any], sizes, gen,
                 spans: Dict[str, float]) -> None:
        import jax
        import jax.numpy as jnp

        import paddle_tpu as pt
        from paddle_tpu import optimizer
        from paddle_tpu.models.ctr import (make_ctr_train_step_slab,
                                           pack_ctr_batch)
        from paddle_tpu.ps.embedding_cache import HbmEmbeddingCache

        cfg = self.cfg = cell.config
        self.seed, self.spans = seed, spans
        K = len(devices)
        S, D = cfg["num_sparse_slots"], cfg["dense_input_dim"]
        B = self.batch = sizes["batch_per_chip"] * K
        self.sharded = cell.traffic.get("mesh") is not None
        self.slab = 1 if self.sharded else sizes["slab"]
        self.steps_per_dispatch = self.slab
        self.units_per_dispatch = B * self.slab
        capacity = sizes["rows_per_chip"] * K
        self.table_rows = sizes["rows_per_chip"]
        pool_per_slot = int(cell.traffic["pass_keys"]) // S

        t = time.perf_counter()
        n_disp = sizes["host_dispatches"]
        data = gen.generate(cell.traffic, seed, slots=S, dense=D,
                            pool_per_slot=pool_per_slot,
                            batches=n_disp * self.slab, batch=B)
        self.data = data
        spans["data_s"] = time.perf_counter() - t

        pt.seed(seed)
        self.table = _table(cfg, seed)
        self.cache_cfg = _cache_cfg(cfg, capacity)
        self.model = _model(cfg)
        self.opt = optimizer.Adam(learning_rate=cfg["learning_rate"])
        self.params = {"params": dict(self.model.named_parameters()),
                       "buffers": {}}
        self.opt_state = self.opt.init(self.params)
        slot_ids = np.arange(S)
        if self.sharded:
            from jax.sharding import NamedSharding, PartitionSpec

            from paddle_tpu.core import mesh as mesh_mod
            from paddle_tpu.ps.sharded_cache import \
                make_sharded_ctr_train_step_from_keys

            (axis, k), = cell.traffic["mesh"].items()
            assert k == K, (k, K)
            self.mesh = mesh_mod.make_mesh({axis: K}, devices=devices)
            self.mesh_kw = dict(mesh=self.mesh, axis=axis)
            self.cache = HbmEmbeddingCache(self.table, self.cache_cfg,
                                           device_map=True, **self.mesh_kw)
            self.step = make_sharded_ctr_train_step_from_keys(
                self.model, self.opt, self.cache_cfg, slot_ids=slot_ids,
                **self.mesh_kw)
            rows = NamedSharding(self.mesh, PartitionSpec(axis))
            self._to_device = lambda item: tuple(
                jax.device_put(a, rows) for a in item)
            self.host_items = [
                (data["lo32"][i], data["dense"][i],
                 data["labels"][i].astype(np.int32)) for i in range(n_disp)]
        else:
            self.cache = HbmEmbeddingCache(self.table, self.cache_cfg,
                                           device_map=True)
            self.step = make_ctr_train_step_slab(
                self.model, self.opt, self.cache_cfg, slot_ids=slot_ids,
                batch_size=B, num_dense=D, slab=self.slab, with_weights=True,
                amp=cfg["amp"])
            self._to_device = jnp.asarray
            ones = np.ones(B, np.uint8)
            t = time.perf_counter()
            self.host_items = [np.stack([
                pack_ctr_batch(data["lo32"][j], data["dense"][j],
                               data["labels"][j], weights=ones)
                for j in range(i * self.slab, (i + 1) * self.slab)])
                for i in range(n_disp)]
            spans["pack_s"] = time.perf_counter() - t

        # the pass build, closed by block_until_ready on the state
        t = time.perf_counter()
        self.pass_keys = self.cache.begin_pass(data["pool"])
        jax.block_until_ready((self.cache.state, self.cache.device_map.state))
        spans["pass_build_s"] = time.perf_counter() - t
        self.cache_state = self.cache.state
        self.cache.state = None       # the step donates it; we thread it
        self.map_state = self.cache.device_map.state
        self.dispatched = np.zeros(n_disp, np.int64)
        self._next = 0
        self._sample_before_flush = None

    # -- the loop's three calls ------------------------------------------

    def feeder(self):
        from paddle_tpu.data.prefetcher import DevicePrefetcher

        return DevicePrefetcher(itertools.cycle(self.host_items),
                                depth=PREFETCH_DEPTH,
                                transform=self._to_device)

    def dispatch(self, item):
        self.dispatched[self._next % len(self.host_items)] += 1
        self._next += 1
        if self.sharded:
            (self.params, self.opt_state, self.cache_state, loss,
             overflow) = self.step(self.params, self.opt_state,
                                   self.cache_state, self.map_state, *item)
            return loss, overflow
        self.params, self.opt_state, self.cache_state, losses = self.step(
            self.params, self.opt_state, self.cache_state, self.map_state,
            item)
        return (losses,)

    def outcomes(self, handles):
        """(steps whose loss is not finite or that dropped keys, mean loss
        of each dispatch) — one fetch of the window's small outputs."""
        import jax

        failed, means = 0, []
        for h in jax.device_get(handles):
            loss = np.atleast_1d(np.asarray(h[0]))
            bad = ~np.isfinite(loss)
            if self.sharded and int(h[1]) != 0:
                bad[:] = True
            failed += int(bad.sum())
            means.append(float(loss.mean()))
        return failed, means

    def compiled_text(self) -> str:
        """Optimised HLO of the dispatched step (found in the compile cache,
        so this costs a load, not a compile)."""
        item = self._to_device(self.host_items[0])
        args = (self.params, self.opt_state, self.cache_state, self.map_state)
        args += tuple(item) if self.sharded else (item,)
        return self.step.lower(*args).compile().as_text()

    # -- correctness, outside the window ---------------------------------

    def _sample_keys(self) -> np.ndarray:
        """Seeded sample of pass keys, always ``SAMPLE_ROWS`` of them (a key
        may repeat; a fixed count keeps the read-back programs' shapes, and
        so their cache entries, the same for every seed): half from the
        traffic (hot, touched), half from the whole pool (mostly untouched)."""
        rng = np.random.default_rng(self.seed + 1)
        S = self.cfg["num_sparse_slots"]
        lo = self.data["lo32"].reshape(-1, S)
        pick = rng.integers(0, lo.shape[0], SAMPLE_ROWS // 2)
        col = rng.integers(0, S, SAMPLE_ROWS // 2)
        hot = lo[pick, col].astype(np.uint64) + (col.astype(np.uint64)
                                                 << np.uint64(32))
        cold = rng.choice(self.data["pool"], SAMPLE_ROWS // 2)
        return np.concatenate([hot, cold])

    def _device_rows(self, keys: np.ndarray) -> Dict[str, np.ndarray]:
        import jax
        import jax.numpy as jnp

        self.cache.state = self.cache_state
        rows = jnp.asarray(self.cache.lookup(keys))
        return {c: np.asarray(jax.device_get(self.cache_state[c][rows]))
                for c in STATE_COLS}

    def check_state(self) -> Dict[str, Any]:
        """Rows read back from the device state after the window. Show and
        click counts are exact sums of small integers, so a sampled row must
        hold EXACTLY the occurrences of its key in the batches dispatched
        (every step of warm-up and window ran, on the right rows, once);
        a key no batch held must still have its untouched embedding."""
        keys = self._sample_keys()
        dev = self._device_rows(keys)
        self._sample_before_flush = (keys, dev)
        S = self.cfg["num_sparse_slots"]
        slab, n_disp = self.slab, len(self.host_items)
        slot_hi = np.arange(S, dtype=np.uint64) << np.uint64(32)
        tagged = (self.data["lo32"].reshape(n_disp, -1, S).astype(np.uint64)
                  + slot_hi)                                 # per dispatch
        lab = self.data["labels"].reshape(n_disp, -1).astype(bool)

        def count(sorted_keys):
            return (np.searchsorted(sorted_keys, keys, side="right")
                    - np.searchsorted(sorted_keys, keys, side="left"))

        show = np.zeros(len(keys))
        click = np.zeros(len(keys))
        for d in np.flatnonzero(self.dispatched):
            show += self.dispatched[d] * count(np.sort(tagged[d], axis=None))
            click += self.dispatched[d] * count(
                np.sort(tagged[d][lab[d]], axis=None))
        ok_show = np.array_equal(dev["show"], show.astype(np.float32))
        ok_click = np.array_equal(dev["click"], click.astype(np.float32))
        untouched = show == 0
        finite = all(np.isfinite(v).all() for v in dev.values())
        return {"ok": bool(ok_show and ok_click and finite
                           and (show > 0).any()),
                "sampled": int(len(keys)), "touched": int((show > 0).sum()),
                "untouched": int(untouched.sum()),
                "show_exact": bool(ok_show), "click_exact": bool(ok_click),
                "steps_counted": int(self.dispatched.sum() * slab)}

    def check_reference(self, reference) -> Dict[str, Any]:
        """The system's step against the plain reference at the published
        widths, on a cache just big enough for the batches: (1) two f32
        steps on the seed's first batch (the sharded step on the mesh cell),
        to f32 tolerances; (2) where the cell runs ``amp`` or a slab, ONE
        dispatch of the step as configured and measured — same builder,
        ``amp`` and ``slab`` — on the first dispatch's batches, against the
        reference run step by step, to a bf16 tolerance. The dense
        optimizer of both is SGD (see the reference: Adam's first steps are
        lr*sign(g), which no tolerance can hold)."""
        import jax

        import paddle_tpu as pt
        from paddle_tpu import optimizer
        from paddle_tpu.models.ctr import (make_ctr_train_step_packed,
                                           make_ctr_train_step_slab,
                                           pack_ctr_batch)
        from paddle_tpu.ps.embedding_cache import HbmEmbeddingCache

        cfg = self.cfg
        S, D = cfg["num_sparse_slots"], cfg["dense_input_dim"]
        B = self.batch
        slot_hi = (np.arange(S, dtype=np.uint64) << np.uint64(32))[None, :]
        lr = 0.1
        opt = optimizer.SGD(learning_rate=lr)
        kw = self.mesh_kw if self.sharded else {}
        took, t = {}, time.perf_counter()

        def lap(name):
            nonlocal t
            took[name] = round(time.perf_counter() - t, 3)
            t = time.perf_counter()

        def fresh(n_batches):
            """A cache holding the first ``n_batches`` batches' keys, a
            model from the seed, and the rows before any step."""
            keys = self.data["lo32"][:n_batches].astype(np.uint64) + slot_hi
            cache_cfg = _cache_cfg(cfg, 1 << int(np.ceil(np.log2(
                keys.size * 2))))
            cache = HbmEmbeddingCache(_table(cfg, self.seed), cache_cfg,
                                      device_map=True, **kw)
            cache.begin_pass(keys.reshape(-1))
            pt.seed(self.seed + 2)
            model = _model(cfg)
            params = {"params": dict(model.named_parameters()), "buffers": {}}
            uniq = np.unique(keys)
            rows = cache.lookup(uniq)
            before = {c: np.asarray(jax.device_get(cache.state[c]))[rows]
                      for c in STATE_COLS}
            sgd = cache_cfg.sgd
            hyper = {"lr_dense": lr, "lr_sparse": sgd.learning_rate,
                     "initial_g2sum": sgd.initial_g2sum,
                     "weight_bounds": tuple(sgd.weight_bounds),
                     "nonclk_coeff": cache_cfg.nonclk_coeff,
                     "click_coeff": cache_cfg.click_coeff,
                     "embedx_threshold": cache_cfg.embedx_threshold}
            return types.SimpleNamespace(
                keys=keys, cache_cfg=cache_cfg, cache=cache, model=model,
                params=params, uniq=uniq, rows=rows, before=before,
                hyper=hyper)

        def after(state, rows):
            return {c: np.asarray(jax.device_get(state[c]))[rows]
                    for c in STATE_COLS}

        def np_params(params):
            return {k: np.asarray(v) for k, v in params["params"].items()}

        # (1) two f32 steps on one batch: the second runs on rows that have
        # optimizer state and created embedx blocks, which fresh rows have not
        f = fresh(1)
        lo32, dense, labels = (self.data[k][0] for k in
                               ("lo32", "dense", "labels"))
        if self.sharded:
            from paddle_tpu.ps.sharded_cache import \
                make_sharded_ctr_train_step_from_keys

            step = make_sharded_ctr_train_step_from_keys(
                f.model, opt, f.cache_cfg, slot_ids=np.arange(S), donate=False,
                **kw)
            item = self._to_device((lo32, dense, labels.astype(np.int32)))
        else:
            step = make_ctr_train_step_packed(
                f.model, opt, f.cache_cfg, slot_ids=np.arange(S), batch_size=B,
                num_dense=D, with_weights=True, donate=False, amp=False)
            # the packed wire carries dense as f16: the reference gets the
            # values the step really sees
            item = (jax.numpy.asarray(pack_ctr_batch(
                lo32, dense, labels, weights=np.ones(B, np.uint8))),)
            dense = dense.astype(np.float16).astype(np.float32)
        lap("build")
        with jax.default_matmul_precision("highest"):
            out = step(f.params, opt.init(f.params), f.cache.state,
                       f.cache.device_map.state, *item)
            out2 = step(out[0], out[1], out[2], f.cache.device_map.state,
                        *item)
        overflow = int(out[4]) + int(out2[4]) if self.sharded else 0
        got = {"loss": [float(out[3]), float(out2[3])],
               "params": np_params(out2[0]), "rows": after(out2[2], f.rows)}
        f.cache.discard_pass()
        lap("system_steps")
        batch = (f.keys[0], dense, labels)
        ref = reference.steps(np_params(f.params), f.uniq, f.before,
                              [batch, batch], f.hyper,
                              table_rows=f.keys.size)
        verdict = reference.compare(got, ref)
        lap("reference_steps")
        verdict["overflow"] = overflow
        verdict["tol"] = dict(verdict["tol"], overflow=0)
        verdict["ok"] = bool(verdict["ok"] and overflow == 0)

        # (2) the step as the window dispatches it
        if not self.sharded and (cfg["amp"] or self.slab > 1):
            n = self.slab
            f = fresh(n)
            step = make_ctr_train_step_slab(
                f.model, opt, f.cache_cfg, slot_ids=np.arange(S), batch_size=B,
                num_dense=D, slab=n, with_weights=True, donate=False,
                amp=cfg["amp"])
            lap("slab_build")
            out = step(f.params, opt.init(f.params), f.cache.state,
                       f.cache.device_map.state,
                       self._to_device(self.host_items[0]))
            got = {"loss": [float(x) for x in np.asarray(out[3])],
                   "params": np_params(out[0]),
                   "rows": after(out[2], f.rows)}
            f.cache.discard_pass()
            lap("slab_system")
            ref = reference.steps(
                np_params(f.params), f.uniq, f.before,
                [(f.keys[j], self.data["dense"][j].astype(np.float16)
                  .astype(np.float32), self.data["labels"][j])
                 for j in range(n)], f.hyper, table_rows=f.keys.size)
            slab = reference.compare_updates(
                got, ref, {"params": np_params(f.params), "rows": f.before})
            lap("slab_reference")
            verdict["configured_step"] = slab
            verdict["ok"] = bool(verdict["ok"] and slab["ok"])
        verdict["took"] = took
        return verdict

    # -- the end of the pass ---------------------------------------------

    def finish(self, flush: bool) -> Dict[str, Any]:
        """``end_pass`` (traced runs only: 44 s on four chips, ledger PR 22)
        and the flushed host rows against the device's, bit for bit."""
        import jax

        self.cache.state = self.cache_state
        if not flush:
            self.cache.discard_pass()
            return {"ok": True, "flushed": False}
        keys, dev = self._sample_before_flush
        t = time.perf_counter()
        self.cache.end_pass()
        self.spans["pass_flush_s"] = time.perf_counter() - t
        full, found = self.table.export_full(keys)
        acc = self.table.accessor
        es, xd = acc.embed_rule.state_dim, acc.config.embedx_dim
        xs = acc.embedx_rule.state_dim
        has = dev["has_embedx"] > 0
        same = (found.all()
                and np.array_equal(full[:, 3], dev["show"])
                and np.array_equal(full[:, 4], dev["click"])
                and np.array_equal(full[:, 5], dev["embed_w"][:, 0])
                and np.array_equal(full[:, 6:6 + es], dev["embed_state"])
                and np.array_equal(full[has, 7 + es:7 + es + xd],
                                   dev["embedx_w"][has])
                and np.array_equal(
                    full[has, 7 + es + xd:7 + es + xd + xs],
                    dev["embedx_state"][has]))
        return {"ok": bool(same), "flushed": True, "rows": int(len(keys))}


def build(cell, seed: int, devices: List[Any], rehearse: bool, gen,
          spans: Dict[str, float]) -> CtrPassSystem:
    return CtrPassSystem(cell, seed, devices, cell.sizes, gen, spans)
