"""Persistent HBM-resident sharded hot-embedding tier.

The GPUPS HBM hash-table as a first-class TPU citizen (PAPER.md's north
star; ROADMAP item 1): where :class:`~paddle_tpu.ps.embedding_cache.
HbmEmbeddingCache` builds a working set per PASS and flushes it at the
pass boundary, this tier lives on the device for the WHOLE training run:

- **residency** — a :class:`~paddle_tpu.ps.device_hash.DynamicDeviceKeyMap`
  (insert/evict-capable open-addressing map, probed in-graph) plus the
  same seven row-state columns the pass cache uses, optionally
  row-sharded over a GSPMD mesh axis (``shard_spread_rows`` placement,
  ``all_to_all``-routed pull/push via ps/sharded_cache.py);
- **warm path** — batch keys resolve to rows INSIDE the compiled step
  (two bucket-row gathers), pull is an in-graph gather, the CTR rule
  update an in-graph scatter: a warm step performs ZERO PS RPCs and the
  hot ids never leave HBM;
- **miss path** — cold ids backfill from the C++ PS through the full-row
  save exporter (``export_full(create=True)`` — values AND optimizer
  state, binary-exact), optionally prefetched on the communicator's
  pull workers (PR 2's ``pull_sparse_async`` machinery) so the fetch
  overlaps the compiled steps in front of it;
- **eviction** — LFU/LRU victims write their dirty rows back to the PS
  with the exact ``end_pass`` flush-back semantics (export-modify-import
  — delta_score fold, unseen reset, lazy-embedx splice), demoting the
  RPC/SSD tiers to cold/capacity storage;
- **checkpointing** — ``flush()`` writes every dirty row back so a
  JobCheckpointManager cut taken right after is complete
  (flush-dirty-then-snapshot; the cut's content digests then pin the
  restore). A restarted job starts the tier cold and refills on miss —
  resume-exact, because every row round-trips the PS bit-for-bit.

Bit-parity contract: the device rule math (ops/sparse_optimizer.py) is
pinned bit-identical to the host engines on the fp32 path (sealed
products + ``-ffp-contract=off`` in csrc — see ``_m32``), so training
with the tier enabled reproduces the RPC-only trainer's pulled rows and
dense params EXACTLY, through eviction churn and checkpoint/restore
(tests/test_hot_tier.py pins all three). Known non-goal: ``delta_score``
folds per flush (the established end_pass association), not per push.

Concurrency note (py_locks lint contract): this module is deliberately
LOCK-FREE — the tier is single-threaded per host (the trainer's step
loop owns it; miss-path prefetch hands results back through the
communicator's own synchronized buffers), so it carries no mutexes and
no `# LOCK` annotations. Adding a thread here means adding locks AND
the pass-7 decls that govern them; do not share a tier across threads.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.enforce import enforce
from ..obs.registry import CounterGroup
from .device_hash import DynamicDeviceKeyMap, dynamic_map_lookup
from .embedding_cache import CacheConfig, cache_pull, cache_push

__all__ = ["HotTierConfig", "HotEmbeddingTier", "make_hot_ctr_train_step",
           "make_sharded_hot_train_step"]


@dataclasses.dataclass
class HotTierConfig:
    """Knobs of the persistent hot tier (the row-update math itself —
    rules, hyperparameters — always comes from the cold table's accessor;
    anything else would corrupt the flush-back)."""

    #: resident rows (HBM budget = capacity × row width × 4 bytes)
    capacity: int = 1 << 18
    #: eviction policy: "lfu" (fewest ensure() appearances) or "lru"
    #: (oldest last appearance); ties break by row id — deterministic
    policy: str = "lfu"
    #: extra victims evicted per shortfall (amortizes writeback RPCs;
    #: 0 = evict exactly the shortfall). PER BANK on a banked tier:
    #: each short bank evicts its own shortfall + evict_batch extras
    #: (bank-local churn has bank-local hysteresis), so a batch short
    #: in every bank writes back up to banks × evict_batch extras
    evict_batch: int = 0
    #: GSPMD mesh + axis: row-shard the tier state over the mesh (the
    #: per-chip-sharded serving layout; None = single-chip)
    mesh: Any = None
    axis: str = "ps"
    #: sharded-step routing knob (ps/sharded_cache.py select_routing)
    routing: Any = "auto"
    cap_factor: float = 2.0
    #: miss semantics: True (training) creates missing rows in the cold
    #: store (export_full(create=True) — the pass-build contract); False
    #: (read-only serving, paddle_tpu/serving) fetches WITHOUT creating —
    #: out-of-population keys admit as zero rows (the serving contract),
    #: and a read-only cold store (serving replica) accepts the fetch
    create_on_miss: bool = True
    #: in-graph push formulation (embedding_cache.resolve_push_mode):
    #: "dense" sweeps the whole capacity through the rule (cost ∝
    #: capacity), "sparse" sorts/dedups the batch and touches its rows
    #: (cost ∝ distinct keys); "auto" is sparse off TPU and picks from
    #: capacity against batch slots on TPU (v5e crossover, PR 25:
    #: PERF.md section 5 — a 2^21-row tier under a 4096 x 26 batch is on
    #: the sweep's side). A persistent tier sized tight can prefer
    #: "dense" even off-TPU: its capacity-stream can undercut the
    #: sparse mode's per-key sort at large batches.
    push_mode: str = "auto"
    #: NUMA-style bucket/row banks (ps/device_hash.py): keys hash to a
    #: bank with a FIXED seed; a bank's rows live in one contiguous HBM
    #: block that never crosses a mesh-shard boundary, so the sharded
    #: step's all_to_all ships every id straight to the host that owns
    #: it. None = one bank per mesh shard (sharded) or 1 (single-chip);
    #: must be a power of two and a multiple of the shard count.
    banks: Optional[int] = None
    #: multi-tenant HBM-slot caps (ps/tenancy.py; docs/OPERATIONS.md
    #: §20): tenant id → max resident rows the tenant may hold across
    #: the whole tier. ENFORCED at admission — a tenant pushing past its
    #: cap evicts ITS OWN least-valuable rows to make room, never a
    #: neighbor's; capacity-pressure eviction below stays tenant-blind
    #: (a shared cache is still a cache for whoever is under cap). Caps
    #: may oversubscribe capacity. None = single-tenant tier, unchanged.
    tenant_slots: Optional[Dict[int, int]] = None
    #: vectorized keys → tenant ids (np.uint64 array in, int array
    #: out). None = the tenancy key-namespacing default: the tenant id
    #: rides the key's top byte (ps/tenancy.py namespace_keys).
    tenant_of_key: Optional[Callable[[np.ndarray], np.ndarray]] = None


_TIER_SEQ = iter(range(1, 1 << 30))  # per-process tier tag allocator


def _tenant_of_key_default(keys: np.ndarray) -> np.ndarray:
    """Tenant id from the key's top byte — the ps/tenancy.py
    namespace_keys layout shared tiers use."""
    return (np.asarray(keys, np.uint64) >> np.uint64(56)).astype(np.int64)


def _pow2_pad(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


@jax.jit
def _gather_rows(state: Dict[str, jax.Array], rows: jax.Array):
    """Device→host staging gather (writeback path): padded row ids are
    clamped to 0 and dropped host-side."""
    C = state["embed_w"].shape[0]
    safe = jnp.minimum(rows, C - 1)
    return {k: jnp.take(v, safe, axis=0) for k, v in state.items()}


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_rows(state: Dict[str, jax.Array], rows: jax.Array,
                  cols: Dict[str, jax.Array]):
    """Upload fetched rows into the tier state (miss fill, in place —
    the state is donated): padded row ids carry the out-of-range
    sentinel and drop."""
    return {k: state[k].at[rows].set(cols[k], mode="drop")
            for k in state}


class HotEmbeddingTier:
    """See the module docstring. ``table`` is the COLD store — anything
    with the Table full-row surface (``export_full``/``import_full`` +
    an ``accessor``): a local MemorySparseTable/SsdSparseTable, or a
    RemoteSparseTable view over an RpcPsClient (the C++ PS)."""

    def __init__(self, table, config: Optional[HotTierConfig] = None,
                 cache_config: Optional[CacheConfig] = None) -> None:
        for attr in ("export_full", "import_full", "accessor"):
            enforce(hasattr(table, attr),
                    f"cold store lacks .{attr} — not a full-row Table")
        self.table = table
        self.config = config or HotTierConfig()
        enforce(self.config.policy in ("lfu", "lru"),
                f"unknown eviction policy {self.config.policy!r}")
        acc = table.accessor.config
        # the device math is the accessor's math — same derivation (and
        # the same reasoning) as HbmEmbeddingCache
        self.cache_config = cache_config or CacheConfig(
            capacity=self.config.capacity, embedx_dim=acc.embedx_dim,
            embed_rule=acc.embed_sgd_rule, embedx_rule=acc.embedx_sgd_rule,
            sgd=acc.sgd, nonclk_coeff=acc.nonclk_coeff,
            click_coeff=acc.click_coeff,
            embedx_threshold=acc.embedx_threshold,
            push_mode=self.config.push_mode)
        enforce(self.cache_config.capacity == self.config.capacity,
                "cache_config.capacity must equal HotTierConfig.capacity")

        C = self.config.capacity
        self._n_shards = 1
        self._sharding = None
        self._map_sharding = None
        if self.config.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            mesh, axis = self.config.mesh, self.config.axis
            self._n_shards = int(mesh.shape[axis])
            enforce(C % self._n_shards == 0,
                    "hot-tier capacity must divide evenly over the mesh axis")
            self._sharding = NamedSharding(mesh, PartitionSpec(axis))
            # the key→row map replicates (each device probes its local
            # batch slice; rows are GLOBAL spread ids the routed pull
            # exchanges over ICI)
            self._map_sharding = NamedSharding(mesh, PartitionSpec())

        # bank layout: default one bank per mesh shard so a key's row
        # block IS its owner shard's HBM (bank blocks must tile shard
        # blocks — banks % shards == 0 keeps them nested)
        self._banks = (self.config.banks if self.config.banks is not None
                       else max(self._n_shards, 1))
        enforce(self._banks >= 1
                and (self._banks & (self._banks - 1)) == 0,
                f"banks must be a power of two, got {self._banks}")
        enforce(C % self._banks == 0,
                "hot-tier capacity must divide evenly over the banks")
        enforce(self._banks % self._n_shards == 0,
                f"banks ({self._banks}) must be a multiple of the mesh "
                f"shard count ({self._n_shards})")

        ec = table.accessor
        self._es = ec.embed_rule.state_dim
        self._xs = ec.embedx_rule.state_dim
        self._xd = ec.config.embedx_dim

        # multi-tenant slot caps (tenancy): row → owning tenant, kept in
        # the control plane so cap enforcement never touches the device
        self._tenant_slots = (dict(self.config.tenant_slots)
                              if self.config.tenant_slots else None)
        self._tenant_of = (self.config.tenant_of_key
                           or _tenant_of_key_default)

        # host control plane (membership/policy/dirtiness — row values
        # live in HBM, never here)
        self._keys = np.zeros(C, np.uint64)
        self._row_tenant = np.zeros(C, np.int64)
        self._valid = np.zeros(C, bool)
        self._dirty = np.zeros(C, bool)
        self._freq = np.zeros(C, np.int64)
        self._tick = np.zeros(C, np.int64)
        self._clock = 0
        self._prefetched: Dict[int, Any] = {}   # id(batch keys) → future
        # prefetch→ensure single-scan: prefetch's host-mirror probe is
        # cached (keyed by the keys ARRAY OBJECT — the reference held
        # here keeps its id unique) and ensure() reuses it when the map
        # hasn't mutated since (version match), halving the warm path's
        # per-batch mirror scans
        self._probe_cache: Dict[int, Tuple[Any, np.ndarray, int]] = {}
        self._reset_resident_set()
        # registry-backed counters (obs/registry.py CounterGroup): the
        # dict-shaped increments below are unchanged, but every count
        # also lands in the job-wide ``hot_tier_events`` family labeled
        # by a per-process tier tag — ``stats()`` stays the exact local
        # accessor PR 6 tests and benches read
        self.counters = CounterGroup(
            "hot_tier_events",
            ("hits", "misses", "evictions", "writebacks", "cold_fetches",
             "flushes", "reshards", "tenant_cap_evictions"),
            max_series=1024, tier=str(next(_TIER_SEQ)))

    def _reset_resident_set(self) -> None:
        """Fresh map/state/control-plane — cold construction AND the
        post-restore drop() share this so the two can never
        desynchronize (same bank layout, same fill order)."""
        C = self.config.capacity
        self.device_map = DynamicDeviceKeyMap(C, sharding=self._map_sharding,
                                              banks=self._banks)
        self.state = self._fresh_state()
        self._valid[:] = False
        self._dirty[:] = False
        self._freq[:] = 0
        self._tick[:] = 0
        self._keys[:] = 0
        self._row_tenant[:] = 0
        # per-bank free row lists: bank b owns the contiguous block
        # [b·C/banks, (b+1)·C/banks) — the bucketized bank layout. Keys
        # hash uniformly over banks (DynamicDeviceKeyMap.bank_of), so
        # residency fills every bank (and therefore every mesh shard —
        # bank blocks tile shard blocks) evenly, replacing the old
        # round-robin spread with a placement the in-graph routing can
        # derive from the key alone.
        Cb = C // self._banks
        self._free = [list(range(b * Cb, (b + 1) * Cb))[::-1]
                      for b in range(self._banks)]
        self._row_bank = np.arange(C) // Cb  # row id → owning bank
        self._prefetched.clear()
        self._probe_cache.clear()

    # -- state ------------------------------------------------------------

    def _fresh_state(self) -> Dict[str, jax.Array]:
        C = self.config.capacity
        host = {
            "show": np.zeros(C, np.float32),
            "click": np.zeros(C, np.float32),
            "embed_w": np.zeros((C, 1), np.float32),
            "embed_state": np.zeros((C, self._es), np.float32),
            "embedx_w": np.zeros((C, self._xd), np.float32),
            "embedx_state": np.zeros((C, self._xs), np.float32),
            "has_embedx": np.zeros(C, np.float32),
        }
        if self._sharding is not None:
            return {k: jax.device_put(v, self._sharding)
                    for k, v in host.items()}
        return {k: jnp.asarray(v) for k, v in host.items()}

    def _full_to_cols(self, values: np.ndarray) -> Dict[str, np.ndarray]:
        """Full save-layout rows → the seven state columns (the
        activate_pass translation, one shared definition here)."""
        es, xs, xd = self._es, self._xs, self._xd
        return {
            "show": values[:, 3].copy(),
            "click": values[:, 4].copy(),
            "embed_w": values[:, 5:6].copy(),
            "embed_state": values[:, 6:6 + es].copy(),
            "has_embedx": values[:, 6 + es].copy(),
            "embedx_w": values[:, 7 + es:7 + es + xd].copy(),
            "embedx_state": values[:, 7 + es + xd:7 + es + xd + xs].copy(),
        }

    # -- miss prefetch (cold path overlap) --------------------------------

    def prefetch(self, keys: np.ndarray, communicator=None) -> None:
        """Issue the cold fetch for ``keys``'s non-resident ids NOW (on
        the communicator's pull workers — PR 2's prefetch machinery — or
        inline when none) so a later :meth:`ensure` for the same batch
        finds the rows already in flight. Fetch only — no tier mutation,
        so it can run ahead of the training step. Creation-order
        determinism holds only without overlapping prefetches (the sync
        trainer does not prefetch; async modes accept the same staleness
        envelope as their pull-ahead)."""
        keys = np.ascontiguousarray(keys, np.uint64)
        rows = self.device_map.lookup_host(keys)
        if len(self._probe_cache) > 64:   # unconsumed callers — bound it
            self._probe_cache.clear()
        self._probe_cache[id(keys)] = (keys, rows,
                                       self.device_map.version)
        missing, slots = self._missing_of(keys, rows=rows)
        if len(missing) == 0:
            return
        fetch = (lambda m=missing, s=slots:
                 (m, self.table.export_full(
                     m, create=self.config.create_on_miss, slots=s)))
        if communicator is not None:
            fut = communicator.fetch_async(fetch)
        else:
            class _Done:  # inline "future"
                def __init__(self, v):
                    self._v = v

                def result(self):
                    return self._v
            fut = _Done(fetch())
        self.counters["cold_fetches"] += 1
        self._prefetched[self._batch_token(keys)] = fut

    @staticmethod
    def _batch_token(keys: np.ndarray) -> int:
        # content token so ensure() matches the prefetch issued for the
        # same batch (cheap: first/last/len fingerprint)
        if len(keys) == 0:
            return 0
        return hash((len(keys), int(keys[0]), int(keys[-1]),
                     int(keys[len(keys) // 2])))

    def _missing_of(self, keys: np.ndarray, rows: Optional[np.ndarray] = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """First-occurrence-order unique non-resident keys + their slot
        ids (key>>32). Order matters: the PS creates missing rows in
        request order, and the RPC-only oracle's pull creates the same
        new keys in the same order — same per-shard rng draws."""
        if rows is None:
            rows = self.device_map.lookup_host(keys)
        miss = keys[rows < 0]
        if len(miss) == 0:
            return miss, miss
        _, first = np.unique(miss, return_index=True)
        missing = miss[np.sort(first)]
        return missing, (missing >> np.uint64(32)).astype(np.int32)

    # -- the resident-set contract ----------------------------------------

    # graftlint: hot-path
    def ensure(self, keys: np.ndarray, mark_dirty: bool = True
               ) -> np.ndarray:
        """Make every key resident; return its spread row ids ([n] i32).

        Misses fetch full rows from the cold store (consuming a matching
        :meth:`prefetch` if one is in flight), evicting victims first
        when the free list runs short. ``mark_dirty`` records that the
        following step PUSHES these rows (the CTR step always does;
        pull-only callers pass False so eviction can skip the
        writeback)."""
        keys = np.ascontiguousarray(keys, np.uint64)
        self._clock += 1
        cached = self._probe_cache.pop(id(keys), None)
        if cached is not None and cached[0] is keys \
                and cached[2] == self.device_map.version:
            rows = cached[1]   # prefetch's scan, map unchanged since
        else:
            rows = self.device_map.lookup_host(keys)
        n_hit = int((rows >= 0).sum())
        self.counters["hits"] += n_hit
        self.counters["misses"] += len(keys) - n_hit

        fut = self._prefetched.pop(self._batch_token(keys), None)
        if (rows < 0).any():
            if fut is not None:
                missing, (values, _) = fut.result()
                # the resident set may have moved since the prefetch was
                # issued; only still-missing keys take the fetched rows
                still = self.device_map.lookup_host(missing) < 0
                self._admit(missing[still], values[still], keys)
                rows = self.device_map.lookup_host(keys)
            if (rows < 0).any():
                # no prefetch, or evictions since prep widened the miss
                # set past what it fetched — the sync cold path covers
                # the remainder
                missing, slots = self._missing_of(keys)
                values, _ = self.table.export_full(
                    missing, create=self.config.create_on_miss, slots=slots)
                self.counters["cold_fetches"] += 1
                self._admit(missing, values, keys)
                rows = self.device_map.lookup_host(keys)
        enforce(bool((rows >= 0).all()), "hot tier ensure() left misses")
        if mark_dirty:
            self._dirty[rows] = True
        self._freq[rows] += 1
        self._tick[rows] = self._clock
        return rows

    # graftlint: cold-path — miss admission IS the RPC-bound cold path
    def _admit(self, missing: np.ndarray, values: np.ndarray,
               batch_keys: np.ndarray) -> None:
        if len(missing) == 0:
            return
        # tenant slot caps come FIRST: an over-cap tenant frees its own
        # rows before the bank-shortfall pass sees the free lists, so
        # capacity pressure from a capped tenant can never force the
        # tenant-blind eviction below onto a neighbor's working set
        if self._tenant_slots:
            self._enforce_tenant_caps(missing, batch_keys)
        # per-bank shortfall: each key admits into ITS bank's row block
        bk = self.device_map.bank_of(missing)
        counts = np.bincount(bk, minlength=self._banks)
        needs = counts - np.asarray([len(f) for f in self._free])
        if (needs > 0).any():
            self._evict(np.maximum(needs, 0), batch_keys)
        new_rows = np.asarray([self._free[b].pop() for b in bk], np.int64)
        if self._tenant_slots:
            self._row_tenant[new_rows] = self._tenant_of(missing)
        cols = self._full_to_cols(values)
        k = _pow2_pad(len(missing))
        pad_rows = np.full(k, self.config.capacity, np.int64)
        pad_rows[:len(missing)] = new_rows
        padded = {}
        for name, v in cols.items():
            pv = np.zeros((k,) + v.shape[1:], np.float32)
            pv[:len(missing)] = v
            padded[name] = jnp.asarray(pv)
        self.state = _scatter_rows(self.state, jnp.asarray(pad_rows), padded)
        self.device_map.insert(missing, new_rows.astype(np.int32))
        self._keys[new_rows] = missing
        self._valid[new_rows] = True
        self._dirty[new_rows] = False
        self._freq[new_rows] = 0
        self._tick[new_rows] = self._clock

    def _evict(self, needs: np.ndarray, batch_keys: np.ndarray) -> None:
        """Deterministic victim selection + dirty writeback. ``needs``
        is the PER-BANK shortfall — victims come from the short bank's
        own row block (a key can only admit into its bank, so evicting
        elsewhere would not free a usable slot)."""
        protect = np.zeros(self.config.capacity, bool)
        r = self.device_map.lookup_host(batch_keys)
        protect[r[r >= 0]] = True
        evictable = self._valid & ~protect
        victims_all = []
        for b in np.flatnonzero(needs > 0):
            need = int(needs[b])
            cand = np.flatnonzero(evictable & (self._row_bank == b))
            count = min(need + int(self.config.evict_batch), len(cand))
            enforce(count >= need,
                    f"hot tier bank {b} smaller than one batch's working "
                    "set — raise HotTierConfig.capacity (per-bank budget "
                    "is capacity/banks)")
            if self.config.policy == "lfu":
                order = np.lexsort((cand, self._tick[cand], self._freq[cand]))
            else:  # lru
                order = np.lexsort((cand, self._freq[cand], self._tick[cand]))
            victims_all.append(cand[order[:count]])
        victims = np.concatenate(victims_all) if victims_all else \
            np.zeros(0, np.int64)
        self._evict_rows(victims)
        self.counters["evictions"] += len(victims)

    def _evict_rows(self, victims: np.ndarray) -> None:
        """Shared eviction mechanics: dirty writeback, map removal,
        control-plane invalidation, rows returned to their banks'
        free lists. Callers count their own eviction flavor."""
        if len(victims) == 0:
            return
        self.writeback(victims[self._dirty[victims]])
        self.device_map.remove(self._keys[victims])
        self._valid[victims] = False
        self._dirty[victims] = False
        for v in victims:
            self._free[self._row_bank[v]].append(int(v))

    def _enforce_tenant_caps(self, missing: np.ndarray,
                             batch_keys: np.ndarray) -> None:
        """Per-tenant HBM-slot quota (tenancy): for each capped tenant
        whose resident + incoming rows would exceed its cap, evict the
        OVERAGE from that tenant's own rows (policy order, batch keys
        protected) — the freed slots return to their banks, so the
        bank-shortfall pass that follows sees them. A tenant whose cap
        is smaller than one batch's working set is a config error."""
        t_in = self._tenant_of(missing)
        protect = np.zeros(self.config.capacity, bool)
        r = self.device_map.lookup_host(batch_keys)
        protect[r[r >= 0]] = True
        for t, cap in self._tenant_slots.items():
            incoming = int((t_in == t).sum())
            if incoming == 0:
                continue
            enforce(incoming <= cap,
                    f"hot tier tenant {t}: one batch admits {incoming} "
                    f"rows but tenant_slots caps it at {cap} — raise the "
                    "cap (it must cover a batch's working set)")
            resident = self._valid & (self._row_tenant == t)
            over = int(resident.sum()) + incoming - cap
            if over <= 0:
                continue
            cand = np.flatnonzero(resident & ~protect)
            enforce(len(cand) >= over,
                    f"hot tier tenant {t}: cap {cap} cannot fit the "
                    "current batch even after evicting every unprotected "
                    f"resident row ({len(cand)} evictable, need {over})")
            if self.config.policy == "lfu":
                order = np.lexsort((cand, self._tick[cand],
                                    self._freq[cand]))
            else:  # lru
                order = np.lexsort((cand, self._freq[cand],
                                    self._tick[cand]))
            victims = cand[order[:over]]
            self._evict_rows(victims)
            self.counters["tenant_cap_evictions"] += len(victims)

    def tenant_residency(self) -> Dict[int, int]:
        """Resident row count per tenant (control-plane read): the
        hot-tier leg of the tenancy billing meter."""
        rows = self._row_tenant[self._valid]
        out: Dict[int, int] = {}
        for t in np.unique(rows):
            out[int(t)] = int((rows == t).sum())
        return out

    # -- flush-back (EndPass semantics, incremental) ----------------------

    # graftlint: cold-path — eviction/flush writeback owns its D2H gather
    def writeback(self, rows: np.ndarray) -> int:
        """Write these resident rows back into the cold store — the
        end_pass export-modify-import: stat totals overwrite, delta_score
        folds the growth, unseen_days zeroes, lazily-created embedx
        splices over the old block. Resident rows receive no PS pushes
        (the tier IS their write path), so the exported 'old' row is the
        at-admit baseline."""
        rows = np.asarray(rows, np.int64)
        if len(rows) == 0:
            return 0
        keys = self._keys[rows]
        k = _pow2_pad(len(rows))
        pad = np.full(k, self.config.capacity - 1, np.int64)
        pad[:len(rows)] = rows
        dev = _gather_rows(self.state, jnp.asarray(pad))
        host = {kk: np.asarray(v)[:len(rows)] for kk, v in dev.items()}
        old, found = self.table.export_full(keys)
        enforce(bool(found.all()),
                "hot-tier writeback: resident key missing from the cold "
                "store (table shrunk mid-run? the tier is its only writer)")
        es, xs, xd = self._es, self._xs, self._xd
        acc = self.table.accessor.config
        new = old.copy()
        d_show = host["show"] - old[:, 3]
        d_click = host["click"] - old[:, 4]
        new[:, 2] = old[:, 2] + (d_show - d_click) * acc.nonclk_coeff \
            + d_click * acc.click_coeff
        new[:, 1] = 0.0
        new[:, 3] = host["show"]
        new[:, 4] = host["click"]
        new[:, 5] = host["embed_w"][:, 0]
        new[:, 6:6 + es] = host["embed_state"]
        has = host["has_embedx"] > 0
        keep_old = old[:, 6 + es] != 0.0
        new[:, 6 + es] = (has | keep_old).astype(np.float32)
        new[has, 7 + es:7 + es + xd] = host["embedx_w"][has]
        new[has, 7 + es + xd:7 + es + xd + xs] = host["embedx_state"][has]
        self.table.import_full(keys, new)
        self.counters["writebacks"] += len(rows)
        return len(rows)

    def flush(self) -> int:
        """Write every dirty row back (rows stay resident, now clean) —
        the flush-dirty-then-snapshot half of a job-checkpoint cut: run
        this BEFORE JobCheckpointManager.save() gates mutations, and the
        captured table (and its pinned digest) contains the tier's
        training."""
        rows = np.flatnonzero(self._valid & self._dirty)
        n = self.writeback(rows)
        self._dirty[rows] = False
        self.counters["flushes"] += 1
        return n

    def drop(self) -> None:
        """Forget the whole resident set WITHOUT writeback (restore
        path: the cold store was just rebuilt from a checkpoint — the
        tier refills on miss)."""
        self._reset_resident_set()

    def on_reshard(self, plan=None) -> int:
        """Live-reshard hook (ps/reshard.py ``on_pre_cutover`` /
        CtrStreamTrainer.on_reshard): flush dirty resident rows and
        KEEP the resident set — the opposite of :meth:`drop`.

        Residency is keyed by feasign, not by PS shard, so a topology
        flip moves nothing in HBM: rows whose key class migrated simply
        have a different cold home, and the tier's writebacks/misses
        reach it through the client's re-resolved routing. The flush
        matters for FRESHNESS, not correctness — a dirty resident row's
        training lands in the cold store BEFORE the migration drains,
        so the moved copy (and any serving replica subscribed to the
        new shard) carries it instead of waiting for the row's next
        eviction. Call from the TRAINING thread (a batch boundary), the
        same contract as :meth:`flush`. Returns rows flushed."""
        n = self.flush()
        self.counters["reshards"] += 1
        return n

    def invalidate(self, keys: np.ndarray) -> int:
        """Forget just these keys' resident rows so the next ensure()
        re-fetches them from the cold store — the serving plane's
        bounded-staleness refresh (a row older than the freshness budget
        is dropped, not served). Dirty rows write back first (a training
        tier calling this loses nothing); read-only serving tiers
        (``mark_dirty=False`` readers) never have dirty rows, so the
        common path is a pure map/control-plane edit — no device I/O.
        Returns the number of rows dropped."""
        keys = np.ascontiguousarray(keys, np.uint64)
        rows = self.device_map.lookup_host(keys)
        rows = np.unique(rows[rows >= 0])
        if len(rows) == 0:
            return 0
        self.writeback(rows[self._dirty[rows]])
        self.device_map.remove(self._keys[rows])
        self._valid[rows] = False
        self._dirty[rows] = False
        for r in rows:
            self._free[self._row_bank[r]].append(int(r))
        return len(rows)

    def resident_keys(self) -> np.ndarray:
        """[occupancy] u64 — every key currently resident, in row
        order. The warm-handoff manifest (serving/fleet): a joining
        serving replica bulk-ensures a PEER's resident set instead of
        discovering it one cold miss at a time. A control-plane read
        (host arrays only — no device I/O).

        Concurrency: the tier is single-threaded by design (its owner
        thread mutates ``_keys``/``_valid``); this read is the ONE
        sanctioned cross-thread peek, and it is a BEST-EFFORT snapshot
        — the mask is copied before the key gather, so a row evicted
        or admitted mid-read yields at worst a stale or missing key in
        the manifest. Both are harmless to the consumer: a stale key
        bulk-admits one unused row on the joiner, a missed key is one
        ordinary cold miss later. Do not use this for anything that
        needs an exact set — quiesce the owner first."""
        valid = self._valid.copy()
        return self._keys[valid].copy()

    # -- observability ----------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Counters the bench and chaos gates assert on (satellite):
        hit-rate, churn, and occupancy — not timing alone."""
        total = self.counters["hits"] + self.counters["misses"]
        tenants = ({"tenants": self.tenant_residency()}
                   if self._tenant_slots else {})
        return {
            **self.counters,
            **tenants,
            "hit_rate": self.counters["hits"] / total if total else 0.0,
            "occupancy": int(self._valid.sum()),
            "capacity": self.config.capacity,
            "dirty": int((self._valid & self._dirty).sum()),
            "map_rebuilds": self.device_map.rebuilds,
            "shards": self._n_shards,
            "banks": self._banks,
        }


# ---------------------------------------------------------------------------
# compiled steps
# ---------------------------------------------------------------------------


def _stream_loss_fn(model, dense_x, labels):
    """EXACTLY CtrStreamTrainer's objective (plain mean BCE) — the
    RPC-only oracle and the hot-tier step must trace the same dense
    graph for the bit-parity contract to extend to the dense params."""

    def loss_fn(params, emb):
        out, _ = nn.functional_call(model, params, emb, dense_x,
                                    training=True)
        loss = nn.functional.binary_cross_entropy_with_logits(
            out, labels.astype(jnp.float32))
        return loss, out

    return loss_fn


def make_hot_ctr_train_step(model, optimizer, cache_cfg: CacheConfig,
                            slot_ids: Sequence[int], donate: bool = True,
                            probe_buckets: int = 2, banks: int = 1):
    """Single-chip hot-tier step: in-graph map probe → in-graph pull →
    fwd/bwd → dense update → in-graph CTR push. A warm batch never
    touches the host beyond shipping the lo32 key halves.
    ``probe_buckets`` and ``banks`` MUST be the map's own layout (the
    trainer passes ``tier.device_map.probe_buckets``/``.banks``): a
    narrower in-graph probe than the host mirror's would silently miss
    host-resident keys.

    step(params, opt_state, tier_state, map_state, keys_lo [B,S] u32,
         dense_x, labels) → (params, opt_state, tier_state, loss)
    """
    slot_hi = jnp.asarray(np.asarray(slot_ids, np.uint32))[None, :]

    def step(params, opt_state, tier_state, map_state, keys_lo, dense_x,
             labels):
        B, S = keys_lo.shape
        hi = jnp.broadcast_to(slot_hi, (B, S)).reshape(-1)
        C = tier_state["embed_w"].shape[0]
        rows = dynamic_map_lookup(map_state, hi, keys_lo.reshape(-1),
                                  probe_buckets, banks)
        # ensure() guarantees residency; sentinel-map anyway (a miss
        # pulls zeros and drops its push instead of corrupting C-1)
        rows = jnp.where(rows >= 0, rows, C)
        emb = cache_pull(tier_state, rows).reshape(B, S, -1)
        loss_fn = _stream_loss_fn(model, dense_x, labels)
        (loss, _), (grads, emb_grad) = jax.value_and_grad(
            loss_fn, argnums=(0, 1), has_aux=True)(params, emb)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        shows = jnp.ones((B * S,), jnp.float32)
        clicks = jnp.repeat(labels.astype(jnp.float32), S)
        new_tier = cache_push(tier_state, rows,
                              emb_grad.reshape(B * S, -1), shows, clicks,
                              cache_cfg)
        return new_params, new_opt, new_tier, loss

    # donate ONLY the tier state (the HBM-scale buffer): params/opt are
    # handed BY REFERENCE to the job-checkpoint background writer
    # (trainer._maybe_checkpoint → save(dense=train_state())) — donating
    # them would delete the very arrays the writer snapshots
    return jax.jit(step, donate_argnums=(2,) if donate else ())


def make_sharded_hot_train_step(model, optimizer, cache_cfg: CacheConfig,
                                mesh, slot_ids: Sequence[int],
                                axis: str = "ps", donate: bool = True,
                                routing="auto", cap_factor: float = 2.0,
                                pre_dedup: bool = True,
                                probe_buckets: int = 2, banks: int = 1):
    """Multi-host hot-tier step: each device probes its LOCAL batch
    slice against the replicated dynamic map, then the id/vector
    exchange rides the keyed tier's ``all_to_all`` routing
    (ps/sharded_cache.py routed pull/push) and the OWNER shard applies
    ``cache_push`` on its local bank block. With the banked map (``banks`` a
    multiple of the shard count) a key's row lives in its hash-bank's
    block, which never crosses a shard boundary — the exchange ships
    each id straight to the HBM bank that holds it, and each host's
    residency/eviction/writeback is a self-contained bank set.

    step(params, opt_state, tier_state, map_state, keys_lo, dense_x,
         labels) → (params, opt_state, tier_state, loss, overflow)
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from .sharded_cache import _check_routing_arg, _sharded_step_body

    _check_routing_arg(routing)
    K = mesh.shape[axis]
    slot_hi = jnp.asarray(np.asarray(slot_ids, np.uint32))[None, :]

    def inner(params, opt_state, tier_state, map_state, keys_lo, dense_x,
              labels):
        B, S = keys_lo.shape  # local slice
        hi = jnp.broadcast_to(slot_hi, (B, S)).reshape(-1)
        rows = dynamic_map_lookup(map_state, hi, keys_lo.reshape(-1),
                                  probe_buckets, banks)
        C_total = tier_state["embed_w"].shape[0] * K  # global capacity
        rows = jnp.where(rows >= 0, rows, C_total)  # sentinel: no owner
        return _sharded_step_body(model, optimizer, cache_cfg, axis, K,
                                  params, opt_state, tier_state, rows, B, S,
                                  dense_x, labels, routing, cap_factor,
                                  pre_dedup)

    shmapped = shard_map(
        inner, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(), P(), P(axis), P(), P()),
        check_vma=False,
    )
    # tier-state-only donation — see make_hot_ctr_train_step
    return jax.jit(shmapped, donate_argnums=(2,) if donate else ())
