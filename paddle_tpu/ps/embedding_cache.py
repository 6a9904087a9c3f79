"""HBM-resident sparse embedding cache.

TPU-native rebuild of the HeterPS/GPUPS layer (SURVEY §2.3): the
reference keeps a per-GPU ``HashTable`` of hot features built per pass
(``PSGPUWrapper`` PreBuildTask→BuildPull→BuildGPUTask, then
PullSparse/PushSparseGrad during the pass, EndPass→dump_to_cpu). Here:

- the **feasign→cache-row map is built on host** once a pass: the
  native FeasignIndex serves host ``lookup`` and the flush, and with
  ``device_map=True`` a static cuckoo map (ps/device_hash.py) serves the
  in-graph probe (hash tables that GROW are hostile to XLA's static
  shapes — the reference's own build/serve split validates this design);
- **what a row number is**: with ``device_map=True`` and the map's slot
  table fitting the cache (``nbuckets·4 ≤ capacity``: the cache at most
  half full) a key's row IS its slot in the key map — the probe
  computes it, no ``row`` array exists, and the pass's keys are kept in
  row order so the build and the flush walk memory forwards (implicit
  rows; span count ``implicit_rows``). Otherwise it is the index's
  dense number, dealt round-robin over the shards, and the map stores
  it (explicit rows). The form follows from the sizes alone, and a
  pass's result does not depend on it;
- the **working set lives in HBM as dense row arrays** (values + per-row
  optimizer state), donated through the jitted train step so pull
  (gather), push (scatter) and the per-feature AdaGrad update
  (optimizer.cuh.h math = sparse_sgd_rule AdaGrad) all fuse into the
  step's XLA program — no host round-trip per batch;
- multi-chip: rows shard over the mesh; the batch's row ids are global,
  XLA turns the gather/scatter into all-to-all traffic over ICI (the
  HeterComm walk_to_dest p2p analogue, compiler-scheduled).

Value layout per cache row (mirrors heter_ps/feature_value.h semantics,
SoA):  show, click, embed_w[1], embed_state[es], embedx_w[dim],
embedx_state[xs], has_embedx — where es/xs are the optimizer-state
widths of the configured sparse SGD rules (shared-g2sum AdaGrad: 1;
StdAdaGrad: dim; Adam: 2·dim+2; naive: 0).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.enforce import enforce, enforce_le
from ..core.profiler import RecordEvent
from ..ops.sparse_optimizer import fused_row_update
from .native import FeasignIndex
from .sgd_rule import SGDRuleConfig
from .table import MemorySparseTable

__all__ = ["CacheConfig", "HbmEmbeddingCache", "cache_pull", "cache_push",
           "cache_push_dense", "cache_push_sparse", "merge_sparse_grads",
           "resolve_push_mode"]


#: table rows per pushed slot below which ``auto`` takes the full-table
#: sweep (``cache_push_dense``) on a TPU. ONE number, from the crossover
#: table of PERF.md section 5 (v5e, PR 25, ``tools/push_crossover.py``):
#: with no row named twice, the touched rows' worst case, the sweep still
#: wins at 79 rows a slot (19.8 against 23.8 ms) and loses at 157 (59.3
#: against 32.7); with the benchmark's Zipf repeats the touched rows win
#: at every shape measured, 10 to 630 rows a slot.
SWEEP_MAX_ROWS_PER_SLOT = 128

#: the state's columns, in the order ``fused_row_update`` takes and
#: returns them
_COLUMNS = ("show", "click", "embed_w", "embed_state", "embedx_w",
            "embedx_state", "has_embedx")

#: slots the touched-rows push walks at a time (see cache_push_sparse;
#: v5e, PR 25, 2^26 rows x 106,496 slots, ms a push: 19.45 at 4096,
#: 19.5 at 8192, 22.1 at 16384, 31.5 with the batch in one piece)
PUSH_CHUNK = 8192


def resolve_push_mode(mode: str, capacity: int, rows: int) -> str:
    """Resolve ``CacheConfig.push_mode`` for a push of ``rows`` slots
    into a table of ``capacity`` rows (both static at trace time, so the
    answer specializes per compiled shape). "dense" and "sparse" mean
    themselves. "auto" off TPU is "sparse" (the formulation the host
    tables are bit-identical to); on TPU it is the sweep ("dense") iff
    ``capacity < SWEEP_MAX_ROWS_PER_SLOT * rows``, else the touched rows
    ("sparse"). The single source of truth — ``cache_push`` and
    ``sharded_cache.select_routing`` both use it."""
    if mode != "auto":
        return mode
    if jax.default_backend() != "tpu":
        return "sparse"
    return "dense" if capacity < SWEEP_MAX_ROWS_PER_SLOT * rows else "sparse"


@dataclasses.dataclass
class CacheConfig:
    capacity: int = 1 << 20
    embedx_dim: int = 8
    sgd: SGDRuleConfig = dataclasses.field(default_factory=SGDRuleConfig)
    nonclk_coeff: float = 0.1
    click_coeff: float = 1.0
    embedx_threshold: float = 10.0  # lazy mf creation score threshold
    #: per-feature rules (sparse_sgd_rule registry names); must match the
    #: host table's accessor so flush-back state round-trips
    embed_rule: str = "adagrad"
    embedx_rule: str = "adagrad"
    #: lazy-embedx creation semantics. The reference's CPU accessor
    #: creates the mf block then applies this push's gradient
    #: (ctr_accessor.cc Update order); its GPU optimizer creates WITHOUT
    #: applying (optimizer.cuh.h:81-94 inits and returns). True = CPU
    #: order (default — bit-parity with the host tables); False = GPU.
    create_applies_grad: bool = True
    #: push formulation. "sparse": the touched rows, the reference's
    #: merge_grad shape — one sort, one segment-sum, gather the rows the
    #: batch named, rule, scatter back; cost follows the batch. "dense":
    #: the sweep — one duplicate-safe 2-D scatter-add of
    #: [grads|show|click] into a [C+1, 4+dim] accumulator, then the SAME
    #: fused_row_update math over the whole table under a touched-row
    #: mask; no sort, no row gather/scatter, cost O(capacity·width).
    #: "auto": sparse off TPU (keeps CPU-path tests bit-identical to the
    #: reference formulation); on TPU whichever the shapes favour
    #: (:func:`resolve_push_mode`). Measured on the v5e in PR 25
    #: (PERF.md section 5, tools/push_crossover.py): at 2^26 rows and a
    #: 4096 x 26 batch the sweep takes 85.4 ms a push, the touched rows
    #: 19.5 (28.0 when no row repeats); the sweep costs about 1.07 ns a
    #: table row plus 0.113 us a slot whatever the rows, the touched
    #: rows cost by the distinct rows named.
    push_mode: str = "auto"


def cache_pull(state: Dict[str, jax.Array], rows: jax.Array) -> jax.Array:
    """In-graph pull: [n, 1+dim] = embed_w ++ embedx_w for given rows.
    (PullSparse / CopyForPull analogue — one fused gather.)

    SENTINEL-SAFE: rows ≥ capacity (missing key / padding) pull ZEROS.
    Without the mask, a sentinel row would read the clamped last row's
    values under jit — another feature's embedding — and NaN-fill in
    eager mode; both are silent corruption."""
    C = state["embed_w"].shape[0]
    with jax.named_scope("pt.pull"):
        safe = jnp.minimum(rows, C - 1)
        # gather each column block THEN concat the [n, ·] results — never
        # concat the [C, ·] table first (XLA may materialize the 72 MB
        # temp every step at bench scale)
        pulled = jnp.concatenate(
            [jnp.take(state["embed_w"], safe, axis=0),
             jnp.take(state["embedx_w"], safe, axis=0)], axis=1)
        return jnp.where((rows < C)[:, None], pulled, 0.0)


def cache_push(
    state: Dict[str, jax.Array],
    rows: jax.Array,  # [n] cache rows (may repeat)
    grads: jax.Array,  # [n, 1+dim] embed_g ++ embedx_g
    shows: jax.Array,  # [n]
    clicks: jax.Array,  # [n]
    cfg: CacheConfig,
) -> Dict[str, jax.Array]:
    """In-graph push (PushSparseGrad / merge_grad analogue). Dispatches
    on ``cfg.push_mode`` and the two shapes (:func:`resolve_push_mode`);
    both formulations apply the same ``fused_row_update`` math to the
    same per-row summed deltas, so they agree up to f32 re-association
    of duplicate-row sums. The choice is static per compiled shape and
    is recorded where it is made: one ``pt.push.select`` host span a
    trace (``profiler.host_spans()``), none on the step path."""
    C, n = state["embed_w"].shape[0], rows.shape[0]
    mode = resolve_push_mode(cfg.push_mode, C, n)
    enforce(mode in ("dense", "sparse"),
            f"unknown push_mode {cfg.push_mode!r}")
    with RecordEvent("pt.push.select", capacity=C, rows=n,
                     sweep=1 if mode == "dense" else 0):
        push = cache_push_dense if mode == "dense" else cache_push_sparse
        return push(state, rows, grads, shows, clicks, cfg)


def cache_push_dense(
    state: Dict[str, jax.Array],
    rows: jax.Array,
    grads: jax.Array,
    shows: jax.Array,
    clicks: jax.Array,
    cfg: CacheConfig,
) -> Dict[str, jax.Array]:
    """TPU-first push: ONE duplicate-safe 2-D scatter-add merges the
    batch ([grads | show | click] rows into a [C+1, 3+dim] accumulator —
    the sentinel row C collects and drops padding/missing keys), then
    the per-row optimizer math runs VECTORIZED over the full table and a
    touched mask (summed show > 0) selects which rows keep their update.

    Rationale: the reference's merge_grad (cub sort + reduce,
    heter_comm_inl.h:388) exists because GPUs update rows one-thread-
    per-row; the TPU shape of "merge then update touched rows" is
    "scatter-add then masked dense update" — cost O(capacity), against
    the touched-rows shape's sort + row gather/scatter at O(batch).
    Measured on the v5e (PR 25, PERF.md section 5): about 1.07 ns a
    table row (five sweeps of the rule and the mask, zeroing and
    reading the accumulator) plus 0.113 us a slot (the scatter-add) —
    85.4 ms a push at 2^26 rows, 12.2 at 2^21 — so it is what ``auto``
    takes only where the table is small against the batch
    (:func:`resolve_push_mode`). "Touched"
    means PRESENT IN THE BATCH (an occurrence count rides the
    accumulator), exactly the sparse path's `uniq` membership — so a
    row whose occurrences all carry show=0 still gets the rule applied
    at zero delta (Adam decays m/v there, like the sparse path and the
    host table), and rows absent from the batch are bit-untouched.
    """
    C = state["embed_w"].shape[0]
    sgd = cfg.sgd
    dim = cfg.embedx_dim
    with jax.named_scope("pt.push.accumulate"):
        ones = jnp.ones((rows.shape[0], 1), jnp.float32)
        upd = jnp.concatenate(
            [grads.astype(jnp.float32), shows[:, None], clicks[:, None],
             ones], axis=1)  # [n, 4+dim]: grads | show | click | occurrences
        acc = jnp.zeros((C + 1, upd.shape[1]), jnp.float32)
        acc = acc.at[rows].add(upd)[:C]
        ge, gx = acc[:, :1], acc[:, 1:1 + dim]
        dshow, dclick = acc[:, 1 + dim], acc[:, 2 + dim]
        touched = acc[:, 3 + dim] > 0

    with jax.named_scope("pt.push.update"):
        outs = fused_row_update(
            state["show"], state["click"], state["embed_w"],
            state["embed_state"], state["embedx_w"], state["embedx_state"],
            state["has_embedx"], dshow, dclick, ge, gx,
            embed_rule=cfg.embed_rule, embedx_rule=cfg.embedx_rule,
            dim=dim, lr=sgd.learning_rate, initial_g2sum=sgd.initial_g2sum,
            wmin=sgd.weight_bounds[0], wmax=sgd.weight_bounds[1],
            beta1=sgd.beta1, beta2=sgd.beta2, eps=sgd.ada_epsilon,
            nonclk_coeff=cfg.nonclk_coeff, click_coeff=cfg.click_coeff,
            embedx_threshold=cfg.embedx_threshold,
            create_applies_grad=cfg.create_applies_grad)
        tcol = touched[:, None]
        return {k: jnp.where(touched if new.ndim == 1 else tcol, new,
                             state[k])
                for k, new in zip(_COLUMNS, outs)}


def merge_sparse_grads(rows: jax.Array, grads: jax.Array, shows: jax.Array,
                       clicks: jax.Array, capacity: int):
    """merge_grad: in-batch dedup (the cub sort+reduce step,
    heter_comm_inl.h:388): ONE stable sort of the rows, run starts by
    comparing neighbours, ONE segment-sum of the packed
    [grads | show | click] payload over the sorted run ids. ``uniq`` is
    the (padded) ascending set of distinct rows; padding slots get the
    sentinel ``capacity`` and are dropped at scatter time. The sort is
    stable and a run's occurrences are summed in batch order, the f32
    association of ``segment_sum`` over ``jnp.unique``'s inverse: the
    f32 merge association is part of the bit-parity contract with the
    host tables."""
    n = rows.shape[0]
    with jax.named_scope("pt.push.accumulate"):
        rows = jnp.where(rows < 0, capacity, rows)  # a miss marker drops too
        srows, order = lax.sort((rows, jnp.arange(n, dtype=jnp.int32)),
                                num_keys=1, is_stable=True)
        first = jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), srows[1:] != srows[:-1]])
        run = jnp.cumsum(first.astype(jnp.int32)) - 1  # sorted, in [0, n)
        packed = jnp.concatenate(
            [grads.astype(jnp.float32), shows[:, None], clicks[:, None]],
            axis=1)  # [n, 1+dim+2]
        summed = jax.ops.segment_sum(packed[order], run, num_segments=n,
                                     indices_are_sorted=True)
        # every occurrence of a run writes the same row id
        uniq = jnp.full((n,), capacity, rows.dtype).at[run].set(
            srows, indices_are_sorted=True)
    return uniq, summed[:, -2], summed[:, -1], summed[:, :-2]


def cache_push_sparse(
    state: Dict[str, jax.Array],
    rows: jax.Array,  # [n] cache rows (may repeat)
    grads: jax.Array,  # [n, 1+dim] embed_g ++ embedx_g
    shows: jax.Array,  # [n]
    clicks: jax.Array,  # [n]
    cfg: CacheConfig,
) -> Dict[str, jax.Array]:
    """The touched-rows push, the reference's merge_grad shape: dedup
    duplicate rows inside the batch (the cub sort+reduce merge_grad
    step, heter_comm_inl.h:388, is :func:`merge_sparse_grads`), gather
    the rows the batch named, apply the per-feature CTR rule
    (optimizer.cuh.h:35-70 / sparse_sgd_rule) and scatter only those
    rows back. Per-step HBM traffic is O(batch·dim), independent of
    cache capacity: what hosts and CPUs run, and what ``auto`` takes on
    a TPU where the table dwarfs the batch (v5e, PR 25: 19.5 ms a push
    at 2^26 rows x 106,496 slots half of them repeats, against the
    sweep's 85.4; PERF.md section 5 has the split by operation)."""
    n = rows.shape[0]
    C = state["embed_w"].shape[0]
    sgd = cfg.sgd
    enforce_le(C + n, np.iinfo(np.int32).max,
               "capacity + batch rows must fit an int32 row id")

    uniq, show_sum, click_sum, g = merge_sparse_grads(rows, grads, shows,
                                                      clicks, C)

    def rule(gathered, dshow, dclick, g):
        return fused_row_update(
            *gathered, dshow, dclick, g[:, :1], g[:, 1:],
            embed_rule=cfg.embed_rule, embedx_rule=cfg.embedx_rule,
            dim=cfg.embedx_dim, lr=sgd.learning_rate,
            initial_g2sum=sgd.initial_g2sum, wmin=sgd.weight_bounds[0],
            wmax=sgd.weight_bounds[1], beta1=sgd.beta1, beta2=sgd.beta2,
            eps=sgd.ada_epsilon, nonclk_coeff=cfg.nonclk_coeff,
            click_coeff=cfg.click_coeff,
            embedx_threshold=cfg.embedx_threshold,
            create_applies_grad=cfg.create_applies_grad)

    def gather(col, uniq):
        # ascending like uniq; padding reads the last row, and its
        # update is dropped by ``scatter``
        return col.at[jnp.minimum(uniq, C - 1)].get(indices_are_sorted=True)

    def scatter(col, uniq, lo, new):
        # uniq is ascending with its padding (everything >= C) last:
        # giving each padding slot its own out-of-range id makes the
        # indices sorted AND unique, which spares XLA:TPU a sort of the
        # indices per scatter; mode="drop" discards them
        own = (C + lo + jnp.arange(uniq.shape[0])).astype(uniq.dtype)
        srows = jnp.where(uniq < C, uniq, own)
        return col.at[srows].set(new, mode="drop", indices_are_sorted=True,
                                 unique_indices=True)

    with jax.named_scope("pt.push.update"):
        # The distinct rows sit first in ``uniq``, so only the chunks of
        # ``step`` slots that hold one are walked: gathers and rule
        # follow the rows the batch NAMED, not the slots it was padded
        # to. What XLA:TPU makes of the scatters decides where each goes
        # (v5e, PERF.md section 5, PR 25): into a [C, w > 1] column it
        # writes row by row, so that scatter is walked with the chunks;
        # into a [C] or [C, 1] column it makes one pass over the whole
        # column whatever the number of updates, so those are scattered
        # once, after the walk, from the [n, .] buffers the walk fills.
        step = min(PUSH_CHUNK, n)
        pad = -n % step
        uniq = jnp.pad(uniq, (0, pad), constant_values=C)
        show_sum, click_sum = (jnp.pad(a, (0, pad))
                               for a in (show_sum, click_sum))
        g = jnp.pad(g, ((0, pad), (0, 0)))
        chunks = (jnp.sum(uniq < C) + step - 1) // step
        walked = tuple(k for k in _COLUMNS
                       if state[k].ndim == 2 and state[k].shape[1] > 1)
        after = tuple(k for k in _COLUMNS if k not in walked)

        def chunk(i, carry):
            tables, rows_out = carry
            lo = i * step
            cut = lambda a: lax.dynamic_slice_in_dim(a, lo, step)
            u = cut(uniq)
            new_rows = dict(zip(_COLUMNS, rule(
                tuple(gather(tables.get(k, state[k]), u) for k in _COLUMNS),
                cut(show_sum), cut(click_sum), cut(g))))
            tables = {k: scatter(tables[k], u, lo, new_rows[k])
                      for k in walked}
            rows_out = {k: lax.dynamic_update_slice_in_dim(
                rows_out[k], new_rows[k], lo, 0) for k in after}
            return tables, rows_out

        tables, rows_out = lax.fori_loop(
            0, chunks, chunk,
            ({k: state[k] for k in walked},
             {k: jnp.zeros(uniq.shape + state[k].shape[1:], state[k].dtype)
              for k in after}))
        return dict(tables, **{k: scatter(state[k], uniq, 0, rows_out[k])
                               for k in after})


class HbmEmbeddingCache:
    """Pass-scoped device working set over a host MemorySparseTable.

    Usage (the PSGPUWrapper pass lifecycle):
        cache.begin_pass(all_keys_of_pass)      # dedup + build + upload
        rows = cache.lookup(batch_keys)          # host index → row ids
        ... jitted step uses cache_pull/cache_push on cache.state ...
        cache.end_pass()                         # flush back to host table
    """

    def __init__(
        self,
        table: MemorySparseTable,
        config: Optional[CacheConfig] = None,
        sharding=None,
        mesh=None,
        axis: str = "ps",
        device_map: bool = False,
    ) -> None:
        self.table = table
        acc_cfg = table.accessor.config
        self.config = config or CacheConfig(
            embedx_dim=acc_cfg.embedx_dim,
            embed_rule=acc_cfg.embed_sgd_rule,
            embedx_rule=acc_cfg.embedx_sgd_rule,
            sgd=acc_cfg.sgd,
            nonclk_coeff=acc_cfg.nonclk_coeff,
            click_coeff=acc_cfg.click_coeff,
            embedx_threshold=acc_cfg.embedx_threshold,
        )
        enforce(
            self.config.embedx_dim == acc_cfg.embedx_dim,
            "cache embedx_dim must match table",
        )
        # flush-back writes optimizer state into the table's columns —
        # the rules (and so the state layouts) must agree
        enforce(
            self.config.embed_rule == acc_cfg.embed_sgd_rule
            and self.config.embedx_rule == acc_cfg.embedx_sgd_rule,
            f"cache rules ({self.config.embed_rule}/{self.config.embedx_rule})"
            f" must match table accessor ({acc_cfg.embed_sgd_rule}/"
            f"{acc_cfg.embedx_sgd_rule})",
        )
        # ... and so must the hyperparameters the DEVICE math uses —
        # a cache training Adam with different betas than the host rule
        # would silently corrupt the flushed-back optimizer state.
        # (initial_range is host-init-only; the lifecycle coeffs
        # nonclk/click/embedx_threshold stay free cache knobs.)
        for f in ("learning_rate", "initial_g2sum", "weight_bounds",
                  "beta1", "beta2", "ada_epsilon"):
            enforce(
                getattr(self.config.sgd, f) == getattr(acc_cfg.sgd, f),
                f"cache sgd.{f} ({getattr(self.config.sgd, f)}) must match "
                f"table accessor sgd.{f} ({getattr(acc_cfg.sgd, f)})",
            )
        self._sharding = sharding
        self._n_shards = 1
        if mesh is not None:
            # row-shard the working set over `axis` (HeterComm-style
            # multi-chip serving, ps/sharded_cache.py); lookup() then
            # returns GLOBAL spread row ids for sharded_cache_pull/push
            from jax.sharding import NamedSharding, PartitionSpec

            self._sharding = NamedSharding(mesh, PartitionSpec(axis))
            self._n_shards = int(mesh.shape[axis])
            enforce(
                self.config.capacity % self._n_shards == 0,
                "cache capacity must divide evenly over the shard axis",
            )
        self._index: Optional[FeasignIndex] = None
        self.state: Optional[Dict[str, jax.Array]] = None
        self._pass_keys: Optional[np.ndarray] = None
        #: implicit rows only: the row of each of ``_pass_keys``, ascending
        #: (the index's dense number of a key is its position there);
        #: None = rows are the dense numbers, spread over the shards
        self._pass_rows: Optional[np.ndarray] = None
        self._device_map_enabled = device_map
        #: per-pass in-HBM key→row map (ps/device_hash.py; the reference's
        #: GPU HashTable) — set by begin_pass when device_map=True
        self.device_map = None

    def _spread(self, rows: np.ndarray) -> np.ndarray:
        """Dense index rows → shard-balanced block-partition positions."""
        if self._n_shards == 1:
            return rows
        from .sharded_cache import shard_spread_rows

        return shard_spread_rows(rows, self.config.capacity, self._n_shards)

    # -- pass lifecycle ---------------------------------------------------

    def prepare_pass(self, keys: np.ndarray) -> dict:
        """The HOST-ONLY half of begin_pass (the reference's
        pre_build_thread work, ps_gpu_wrapper.cc:733: dedup + row
        assignment + cuckoo build): touches neither the table nor device
        state, so it can run in a background thread while the PREVIOUS
        pass trains. Activate with :meth:`activate_pass` after the
        previous end_pass — table values are only read then, so the
        overlap changes nothing numerically."""
        with RecordEvent("pt.pass.prepare", keys=len(keys)) as ev:
            prepared = self._prepare(keys)
            ev["unique_keys"] = len(prepared["uniq"])
        return prepared

    def _prepare(self, keys: np.ndarray) -> dict:
        cfg = self.config
        from .native import dedup_u64

        with RecordEvent("pt.pass.dedup"):
            uniq = dedup_u64(keys)  # parallel PreBuildTask-style dedup
        enforce_le(len(uniq), cfg.capacity,
                   "pass working set exceeds cache capacity")
        # With a device map the build comes first and DECIDES the rows
        # wherever its slot table fits the cache: a key's row is its slot
        # (DeviceKeyMap.build_host_implicit), the pass's keys go on in
        # row order, and the index's dense numbers (0..n-1, the order of
        # insertion) name positions in ``rows``. Otherwise rows are the
        # dense numbers spread over the shards, and the map stores them.
        map_host = None
        implicit = False
        if self._device_map_enabled:
            from .device_hash import DeviceKeyMap

            implicit = DeviceKeyMap.rows_can_be_slots(
                len(uniq), cfg.capacity, self._n_shards)
        if implicit:
            with RecordEvent("pt.pass.map_build"):
                map_host, uniq, rows = DeviceKeyMap.build_host_implicit(
                    uniq, cfg.capacity, self._n_shards)
        with RecordEvent("pt.pass.index"):
            index = FeasignIndex(len(uniq) * 2)
            dense, _ = index.lookup_or_insert(uniq)
            if not implicit:
                rows = self._spread(dense)
        if self._device_map_enabled and not implicit:
            with RecordEvent("pt.pass.map_build"):
                map_host = DeviceKeyMap.build_host(uniq, rows)
        return {"uniq": uniq, "index": index, "rows": rows,
                "map_host": map_host, "implicit_rows": implicit}

    def begin_pass(self, keys: np.ndarray) -> int:
        """PreBuildTask + BuildPull + BuildGPUTask: dedup the pass's keys,
        pull current values from the host table, upload the working set.
        One ``pt.pass.begin`` span over the phases of both halves."""
        with RecordEvent("pt.pass.begin", keys=len(keys),
                         capacity=self.config.capacity,
                         shards=self._n_shards) as ev:
            prepared = self._prepare(keys)
            ev["unique_keys"] = len(prepared["uniq"])
            ev["implicit_rows"] = int(prepared["implicit_rows"])
            return self._activate(prepared)

    def activate_pass(self, prepared: dict) -> int:
        """The device half of begin_pass: export current table values
        for the prepared key set (insert-on-miss) and upload the working
        set + key map. Returns once the upload has landed."""
        with RecordEvent("pt.pass.activate",
                         unique_keys=len(prepared["uniq"]),
                         capacity=self.config.capacity,
                         shards=self._n_shards,
                         implicit_rows=int(prepared["implicit_rows"])):
            return self._activate(prepared)

    def _activate(self, prepared: dict) -> int:
        cfg = self.config
        uniq, rows = prepared["uniq"], prepared["rows"]
        self._index = prepared["index"]
        self._pass_keys = uniq
        self._pass_rows = rows if prepared["implicit_rows"] else None

        # ONE shard traversal creates missing features and exports full
        # rows (values + optimizer state) — round 1 walked the table
        # twice here (pull_sparse then export_full over the same keys)
        acc = self.table.accessor
        es = acc.embed_rule.state_dim
        xs = acc.embedx_rule.state_dim
        xd = acc.config.embedx_dim
        with RecordEvent("pt.pass.export") as ev:
            values, _ = self.table.export_full(uniq, create=True)
            ev["bytes"] = values.nbytes
        dim = cfg.embedx_dim
        with RecordEvent("pt.pass.layout") as ev:
            host = {
                "show": np.zeros(cfg.capacity, np.float32),
                "click": np.zeros(cfg.capacity, np.float32),
                "embed_w": np.zeros((cfg.capacity, 1), np.float32),
                "embed_state": np.zeros((cfg.capacity, es), np.float32),
                "embedx_w": np.zeros((cfg.capacity, dim), np.float32),
                "embedx_state": np.zeros((cfg.capacity, xs), np.float32),
                "has_embedx": np.zeros(cfg.capacity, np.float32),
            }
            # full layout: slot, unseen_days, delta_score, show, click,
            # embed_w, embed_state[es], has_embedx, embedx_w[xd],
            # embedx_state
            host["show"][rows] = values[:, 3]
            host["click"][rows] = values[:, 4]
            host["embed_w"][rows, 0] = values[:, 5]
            host["embed_state"][rows] = values[:, 6 : 6 + es]
            host["has_embedx"][rows] = values[:, 6 + es]
            host["embedx_w"][rows] = values[:, 7 + es: 7 + es + xd]
            host["embedx_state"][rows] = \
                values[:, 7 + es + xd : 7 + es + xd + xs]
            ev["bytes"] = sum(v.nbytes for v in host.values())
            del values  # the exported copy goes before the upload starts

        with RecordEvent("pt.pass.upload") as ev:
            if self._device_map_enabled:
                from .device_hash import DeviceKeyMap

                map_sharding = None
                if self._n_shards > 1:  # __init__ set _sharding with the mesh
                    # replicate the key→row map across the serving mesh
                    # (the probe runs per device on its local batch slice)
                    from jax.sharding import NamedSharding, PartitionSpec

                    map_sharding = NamedSharding(self._sharding.mesh,
                                                 PartitionSpec())
                self.device_map = DeviceKeyMap(
                    sharding=map_sharding, host_built=prepared["map_host"])

            if self._sharding is not None:
                self.state = {k: jax.device_put(jnp.asarray(v), self._sharding)
                              for k, v in host.items()}
            else:
                self.state = {k: jnp.asarray(v) for k, v in host.items()}
            # the span ends when the bytes have landed, not when the
            # copies were enqueued; every caller needs the state next
            uploaded = (self.state, self.device_map.state
                        if self.device_map is not None else None)
            jax.block_until_ready(uploaded)
            ev["bytes"] = sum(a.nbytes for a in jax.tree.leaves(uploaded))
            del host    # ... and the host columns once they have landed
        return len(uniq)

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """Batch keys → cache rows (host-side; feed into the jitted step)."""
        enforce(self._index is not None, "begin_pass first")
        rows = self._index.lookup(np.ascontiguousarray(keys, np.uint64))
        enforce(bool((rows >= 0).all()), "batch contains keys outside the pass working set")
        return self._pass_rows[rows] if self._pass_rows is not None \
            else self._spread(rows)

    def end_pass(self) -> None:
        """EndPass / dump_to_cpu: write the working set back into the host
        table (values + optimizer state, direct overwrite)."""
        if self._index is None or self.state is None:
            return
        with RecordEvent("pt.pass.end", keys=len(self._pass_keys)):
            self._flush()
        self._index = None
        self.state = None
        self._pass_keys = None
        self._pass_rows = None
        self.device_map = None

    def _flush(self) -> None:
        with RecordEvent("pt.pass.fetch") as ev:
            host = {k: np.asarray(v)
                    for k, v in jax.device_get(self.state).items()}
            ev["bytes"] = sum(v.nbytes for v in host.values())
        keys = self._pass_keys
        with RecordEvent("pt.pass.flush_index"):
            # implicit rows: the pass's keys are kept beside their rows
            rows = self._pass_rows if self._pass_rows is not None \
                else self._spread(self._index.lookup(keys))
        acc = self.table.accessor
        es = acc.embed_rule.state_dim
        xs = acc.embedx_rule.state_dim
        xd = acc.config.embedx_dim
        # NB: like the reference's PSGPUWrapper::EndPass, flush-back runs
        # at a pass boundary with trainers quiesced — the export/modify/
        # import below is not atomic against concurrent push_sparse on
        # the same keys. All pass keys were created in begin_pass, so
        # every row must still exist (a mid-pass shrink would violate
        # the pass protocol; fail loudly rather than write stale rows).
        with RecordEvent("pt.pass.flush_export"):
            old, found = self.table.export_full(keys)
        enforce(bool(found.all()),
                "end_pass: pass keys missing from host table (table was "
                "shrunk or mutated mid-pass)")
        with RecordEvent("pt.pass.merge"):
            new = old.copy()
            # lifecycle stats: cache-trained features were seen this pass
            # — zero unseen_days and fold the show/click growth into
            # delta_score (else daily shrink would age out hot features
            # and delta saves would drop them)
            cfg = acc.config
            d_show = host["show"][rows] - old[:, 3]
            d_click = host["click"][rows] - old[:, 4]
            new[:, 2] = (old[:, 2] + (d_show - d_click) * cfg.nonclk_coeff
                         + d_click * cfg.click_coeff)
            new[:, 1] = 0.0
            new[:, 3] = host["show"][rows]
            new[:, 4] = host["click"][rows]
            new[:, 5] = host["embed_w"][rows, 0]
            new[:, 6 : 6 + es] = host["embed_state"][rows]
            has = host["has_embedx"][rows] > 0
            keep_old = old[:, 6 + es] != 0.0
            new[:, 6 + es] = (has | keep_old).astype(np.float32)
            new[has, 7 + es : 7 + es + xd] = host["embedx_w"][rows[has]]
            new[has, 7 + es + xd : 7 + es + xd + xs] = \
                host["embedx_state"][rows[has]]
            del host, old   # GBs each: released where they stop being used
        with RecordEvent("pt.pass.import"):
            self.table.import_full(keys, new)
            del new

    def discard_pass(self) -> None:
        """Drop the working set WITHOUT flushing back (diverged/aborted
        pass): the host table keeps its last-good state and the HBM
        arrays are released; a new begin_pass starts clean."""
        self._index = None
        self.state = None
        self._pass_keys = None
        self._pass_rows = None
        self.device_map = None
