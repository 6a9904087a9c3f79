"""Device-resident feasign→row hash table (in-graph lookup).

The reference keeps its per-pass hashtable ON the accelerator and looks
batch keys up inside the train loop (GPU ``HashTable::get`` kernels,
`/root/reference/paddle/fluid/framework/fleet/heter_ps/hashtable_inl.h`,
backed by the vendored cuDF concurrent map) — the host never touches
per-batch keys. Round-1's design looked keys up on host (native
FeasignIndex) per batch, which on a 1-core host costs ~4ms per 100k-key
batch and caps the whole pipeline; this module restores the reference's
architecture on TPU.

The table is a static bucketized cuckoo hash (2 hash functions × 4-slot
buckets, load ≤ ~0.5) BUILT on host once per pass (csrc/cuckoo.cc — the
HeterComm build_ps bulk-insert analogue) and probed in-graph with two
fixed bucket probes + compares: branch-free, bounded, fuses into the
train step. Keys are uint64 split into (hi, lo) uint32 halves — TPUs
have no native 64-bit int path, and x64 mode stays off. The map is
``key`` u32[nbuckets, 8], a bucket's four hi halves then its four lo
halves in ONE row (the chip pays a gather of ≤ 8 columns by the index,
not by the byte: PERF.md §5), in one of two forms, told apart by the
map's own contents (``"row" in state``):

- **implicit rows** (a pass of ``HbmEmbeddingCache`` whose slot table
  fits the cache, ``nbuckets·4 ≤ capacity``): a key's row IS the slot
  the build put it in — slot ``s`` of bucket ``b`` is row
  ``(b mod K)·shard_rows + (b div K)·4 + s`` over ``K`` shards (one
  chip: ``b·4 + s``), so the probe computes it from the compare it
  makes anyway: ONE row gather a hash, two a step, and no ``row``
  array exists on the host after the build or on the device at all
  (the reference's HBM table holds the values in the hash table
  itself: no second indirection either). ``K`` and ``shard_rows`` ride
  in the state beside ``seed``. Empty slots hold a FILLER key that
  provably does not hash to their bucket (:func:`_filler_keys`), so no
  key value is reserved and a lookup of key 0 or of a filler reads −1
  unless the pass holds it;
- **explicit rows** (caller-chosen rows, ``DeviceKeyMap(keys, rows)``;
  or a cache more than half full): a second array ``row``
  i32[nbuckets, 4] holds each slot's row, −1 in empty slots — two row
  gathers a hash, four a step.

The 32-bit mixer must match ``mix32`` in csrc/cuckoo.cc bit-for-bit.
"""

from __future__ import annotations

import itertools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.enforce import enforce
from .native import cuckoo_build, cuckoo_placement, native_available

__all__ = ["DeviceKeyMap", "DynamicDeviceKeyMap", "device_hash_lookup",
           "dynamic_map_lookup", "dynamic_probe_buckets", "split_keys"]

_SLOTS = 4
_SEED2_XOR = np.uint32(0x7FEB352D)


def _mix32(hi: jax.Array, lo: jax.Array, seed) -> jax.Array:
    """jnp mirror of csrc/cuckoo.cc mix32 (uint32 wrap-around math)."""
    h = jnp.uint32(seed) ^ hi.astype(jnp.uint32)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h ^ lo.astype(jnp.uint32)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def split_keys(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side uint64 → (hi, lo) uint32 halves (vectorized, ~free)."""
    keys = np.ascontiguousarray(keys, np.uint64)
    return ((keys >> np.uint64(32)).astype(np.uint32),
            (keys & np.uint64(0xFFFFFFFF)).astype(np.uint32))


def device_hash_lookup(table: Dict[str, jax.Array], keys_hi: jax.Array,
                       keys_lo: jax.Array) -> jax.Array:
    """In-graph probe: [n] int32 rows (−1 = missing) for (hi, lo) keys.

    Per hash one bucket-ROW gather of ``key`` ([n, 8]: the bucket's four
    hi halves, then its four lo) — whole buckets, the same efficient
    row-gather pattern as the embedding pull. (1-D scalar gathers lower
    to a pathological path on TPU; never probe slot-wise.) A map with
    implicit rows (no ``row`` in ``table``) needs nothing more: the row
    is the matching slot's own position. A map with explicit rows
    gathers the bucket's row of ``row`` ([n, 4]) too.
    """
    with jax.named_scope("pt.probe"):
        mask = jnp.uint32(table["key"].shape[0] - 1)  # nbuckets (power of 2)
        seed = table["seed"]  # scalar uint32 (device array, donated w/ state)
        hi = keys_hi.astype(jnp.uint32)
        lo = keys_lo.astype(jnp.uint32)
        found = jnp.full(hi.shape, -1, jnp.int32)
        for which in (0, 1):
            s = seed if which == 0 else seed ^ _SEED2_XOR
            b = (_mix32(hi, lo, s) & mask).astype(jnp.int32)
            bk = jnp.take(table["key"], b, axis=0)   # [n, 8]: hi×4 | lo×4
            match = ((bk[:, :_SLOTS] == hi[:, None])
                     & (bk[:, _SLOTS:] == lo[:, None]))
            if "row" in table:
                br = jnp.take(table["row"], b, axis=0)   # [n, 4]
                hit = jnp.max(jnp.where(match & (br >= 0), br, -1), axis=1)
            else:
                # empty slots hold a filler no probe of this bucket can
                # carry, so a match is a key of the pass
                slot = jnp.max(jnp.where(
                    match, jnp.arange(_SLOTS, dtype=jnp.int32), -1), axis=1)
                shift = table["shard_shift"]  # log2 K: shifts and masks only
                hit = jnp.where(
                    slot >= 0,
                    (b & ((1 << shift) - 1)) * table["shard_rows"]
                    + (b >> shift) * _SLOTS + slot, -1)
            found = jnp.where(hit >= 0, hit, found)
        return found


def _filler_keys(nb: int, seed: int):
    """What the empty slots of an implicit-row map hold: key 0 (the zero
    words the build leaves) in every bucket but the two that key 0
    itself hashes to, and there the least key whose own two buckets are
    neither of them. Returns that key and key 0's buckets. A probe of
    key ``X`` reads only X's two buckets, and neither holds X as a
    filler: so a filler never matches, whatever the key."""
    def buckets(k: int):
        hi, lo = np.uint32(k >> 32), np.uint32(k & 0xFFFFFFFF)
        return {int(_mix32_np(hi, lo, s) & np.uint32(nb - 1))
                for s in (seed, seed ^ int(_SEED2_XOR))}

    of_zero = buckets(0)
    other = next(k for k in itertools.count(1) if not buckets(k) & of_zero)
    return other, sorted(of_zero)


class DeviceKeyMap:
    """Per-pass static key→row map living in HBM.

    Built on host (cuckoo.cc) once a pass; ``state`` is a dict of device
    arrays a jitted step closes over (or threads through, for donation):
    ``key`` and ``seed``, then either ``row`` (explicit rows: the caller
    chose them) or ``shard_shift`` and ``shard_rows`` (implicit rows:
    the build chose them, a key's row is its slot). See the module
    docstring; :func:`device_hash_lookup` reads which from the dict.
    """

    @staticmethod
    def buckets_for(n: int) -> int:
        """Buckets of a map of ``n`` keys: the least power of two (≥ 64)
        with ``nb·4 ≥ 2·n``, load ≤ 0.5."""
        nb = 64
        while nb * _SLOTS < 2 * max(n, 1):
            nb <<= 1
        return nb

    @staticmethod
    def _build(keys: np.ndarray, rows: np.ndarray):
        if not native_available():
            raise RuntimeError(
                "DeviceKeyMap needs the native library (csrc/cuckoo.cc); "
                "use host-side HbmEmbeddingCache.lookup instead")
        n = len(keys)
        enforce(n == len(rows), "keys/rows length mismatch")
        nb = DeviceKeyMap.buckets_for(n)
        last_err: Optional[Exception] = None
        for seed in (0x1234ABCD, 0x9E3779B9, 0xDEADBEEF, 0x2545F491):
            try:
                key, row = cuckoo_build(keys, rows, nb, seed)
                return key, row, seed, nb
            except RuntimeError as e:  # placement failure: retry new seed
                last_err = e
        raise RuntimeError(f"cuckoo build failed for {n} keys: {last_err}")

    @staticmethod
    def build_host(keys: np.ndarray, rows: np.ndarray):
        """Host-only cuckoo build (the pre_build_thread half) of a map
        with EXPLICIT rows: returns the host arrays to upload later.
        Touches no device state, so it can run in a background thread
        while the previous pass trains."""
        key, row, seed, nb = DeviceKeyMap._build(keys, rows)
        return {"key": key, "row": row, "seed": np.uint32(seed), "nb": nb}

    @staticmethod
    def rows_can_be_slots(n: int, capacity: int, shards: int = 1) -> bool:
        """Whether a map of ``n`` keys can name the rows of a cache of
        ``capacity`` rows over ``shards`` by its own slots: the slot
        table fits the cache (it is at most half full) and the shards
        are a power of two that deals the buckets out evenly."""
        nb, K = DeviceKeyMap.buckets_for(n), int(shards)
        return (nb * _SLOTS <= capacity and K & (K - 1) == 0 and K <= nb
                and capacity % K == 0)

    @staticmethod
    def build_host_implicit(keys: np.ndarray, capacity: int, shards: int = 1):
        """Host-only build of a map with IMPLICIT rows over a cache of
        ``capacity`` rows block-partitioned over ``shards``
        (:meth:`rows_can_be_slots` must hold): returns ``(built, keys,
        rows)`` — the host arrays to upload (no ``row``), and the pass's
        keys in the order of their rows ``rows``, ASCENDING, so that
        everything the pass build and the flush index by row walks
        memory forwards.

        Slot ``s`` of bucket ``b`` is row ``(b mod K)·(capacity÷K) +
        (b div K)·4 + s``: the bucket's LOW bits pick the shard, a
        uniform hash of the key, so the shards stay balanced whatever
        the ratio of capacity to slots, and a shard has at least as many
        rows as the slots dealt to it."""
        n, K = len(keys), int(shards)
        enforce(DeviceKeyMap.rows_can_be_slots(n, capacity, K),
                f"a map of {n} keys cannot name the rows of {capacity} "
                f"over {K} shards by its slots")
        # rows = arange(n): the row array the build returns IS the
        # placement, slot → position in ``keys``; it stays on the host
        key, placed, seed, nb = DeviceKeyMap._build(
            keys, np.arange(n, dtype=np.int32))
        keys, rows = cuckoo_placement(key, placed, n, K, capacity // K)
        filler, of_zero = _filler_keys(nb, seed)
        for b in of_zero:        # every other bucket's zero words ARE key 0
            empty = placed[b] < 0
            key[b, :_SLOTS][empty] = filler >> 32
            key[b, _SLOTS:][empty] = filler & 0xFFFFFFFF
        return ({"key": key, "seed": np.uint32(seed), "nb": nb,
                 "shard_shift": np.int32(K.bit_length() - 1),
                 "shard_rows": np.int32(capacity // K)}, keys, rows)

    def __init__(self, keys: Optional[np.ndarray] = None,
                 rows: Optional[np.ndarray] = None,
                 sharding=None, host_built=None) -> None:
        # exactly one construction path: fresh (keys, rows) OR a
        # prebuilt host table — passing both invites a mismatched pair
        enforce((host_built is None) != (keys is None),
                "pass either keys/rows or host_built, not both")
        built = dict(host_built if host_built is not None else
                     self.build_host(keys, rows))
        self.nbuckets = built.pop("nb")
        put = (lambda a: jax.device_put(a, sharding)) if sharding is not None \
            else jnp.asarray
        # the bucket arrays go where the caller says, the scalars beside
        # seed wherever jax puts a scalar
        self.state: Dict[str, jax.Array] = {
            k: put(v) if np.ndim(v) else jnp.asarray(v)
            for k, v in built.items()}

    def lookup(self, keys_hi: jax.Array, keys_lo: jax.Array) -> jax.Array:
        return device_hash_lookup(self.state, keys_hi, keys_lo)


# ---------------------------------------------------------------------------
# dynamic (insert/evict-capable) key→row map — the persistent hot tier's
# front half (ps/hot_tier.py). The static cuckoo map above is built once
# per pass; a cross-step tier needs residency to CHANGE cheaply, so this
# map is bucketized LINEAR PROBING: host-side mutations patch a bounded
# probe window, the in-graph probe stays two bucket-row gathers (the
# same layout-friendly pattern as the cuckoo probe — never slot-wise).
#
# BANKS ("Scalable Hash Table for NUMA Systems", PAPERS.md): with
# ``banks > 1`` the bucket array partitions into ``banks`` contiguous
# regions and every key hashes FIRST to its bank (a FIXED seed — bank
# membership survives reseed/grow rebuilds) and then to a bucket inside
# that bank's region; the probe window wraps within the bank. The hot
# tier allocates a key's ROW from the same bank's row block, so a bank
# is a self-contained residency unit: on a GSPMD mesh bank blocks align
# with the row-shard blocks and a key's owner shard is a pure function
# of the key — the ``all_to_all`` id/vector exchange ships each id
# straight to the HBM bank that holds it (the NUMA-local access the
# paper's per-node banks buy on CPUs).
# ---------------------------------------------------------------------------

_EMPTY = np.int32(-1)
_TOMB = np.int32(-2)
#: fixed bank-hash seed — NEVER rotated (rows must not migrate between
#: banks when the probe seed rotates on a rebuild)
_BANK_SEED = 0x243F6A88


def _mix32_np(hi: np.ndarray, lo: np.ndarray, seed: int) -> np.ndarray:
    """numpy mirror of ``_mix32`` — the host mirror and the in-graph
    probe MUST hash identically (uint32 wraparound math)."""
    with np.errstate(over="ignore"):
        h = np.uint32(seed) ^ hi.astype(np.uint32)
        h = h * np.uint32(0x85EBCA6B)
        h = h ^ (h >> np.uint32(13))
        h = h ^ lo.astype(np.uint32)
        h = h * np.uint32(0xC2B2AE35)
        h = h ^ (h >> np.uint32(16))
    return h


def dynamic_probe_buckets(nbuckets: int, keys_hi: jax.Array,
                          keys_lo: jax.Array, seed, probe_buckets: int,
                          banks: int = 1):
    """The probe-window bucket ids ([n] int32 per window step) of a
    :class:`DynamicDeviceKeyMap` — the bank+bucket hash of the probe
    below. With banks, the window wraps WITHIN the key's bank region."""
    hi = keys_hi.astype(jnp.uint32)
    lo = keys_lo.astype(jnp.uint32)
    nbpb = nbuckets // banks          # buckets per bank (both pow2)
    local_mask = jnp.uint32(nbpb - 1)
    base = jnp.uint32(0)
    if banks > 1:
        bank = _mix32(hi, lo, jnp.uint32(_BANK_SEED)) & jnp.uint32(banks - 1)
        base = bank * jnp.uint32(nbpb)
    b0 = _mix32(hi, lo, seed) & local_mask
    return [(base + ((b0 + jnp.uint32(t)) & local_mask)).astype(jnp.int32)
            for t in range(probe_buckets)]


def dynamic_map_lookup(table: Dict[str, jax.Array], keys_hi: jax.Array,
                       keys_lo: jax.Array, probe_buckets: int = 2,
                       banks: int = 1) -> jax.Array:
    """In-graph probe of a :class:`DynamicDeviceKeyMap`: [n] int32 rows
    (−1 = missing). ``probe_buckets`` consecutive bucket-ROW gathers;
    inserts guarantee placement inside that window (else the host
    rebuilt), so no early-exit-on-empty logic is needed."""
    hi = keys_hi.astype(jnp.uint32)
    lo = keys_lo.astype(jnp.uint32)
    found = jnp.full(hi.shape, -1, jnp.int32)
    for b in dynamic_probe_buckets(table["row"].shape[0], hi, lo,
                                   table["seed"], probe_buckets, banks):
        bh = jnp.take(table["hi"], b, axis=0)    # [n, B]
        bl = jnp.take(table["lo"], b, axis=0)
        br = jnp.take(table["row"], b, axis=0)
        match = (bh == hi[:, None]) & (bl == lo[:, None]) & (br >= 0)
        hit = jnp.max(jnp.where(match, br, -1), axis=1)
        found = jnp.where(found >= 0, found, hit)
    return found


class DynamicDeviceKeyMap:
    """Insert/evict-capable feasign→row map living in HBM.

    Generalizes :class:`DeviceKeyMap` from a build-once-per-pass cuckoo
    table to the PERSISTENT tier's front half: the host keeps the
    authoritative mirror (numpy arrays — membership decisions, miss
    detection and eviction bookkeeping are host control-plane work) and
    every mutation queues a bounded set of slot patches that one jitted
    scatter applies to the device arrays before the next step closes
    over them. The hot path — per-batch key→row resolution inside the
    compiled step — is :func:`dynamic_map_lookup`, two bucket-row
    gathers, branch-free.

    Scheme: ``nbuckets × bucket_slots`` slots, bucketized linear probing
    over a ``probe_buckets``-bucket window (load factor ≤ 0.5 by
    construction). An insert that cannot place inside its window — or
    tombstone pressure past 25% — triggers a deterministic REBUILD
    (reseed from a fixed sequence, then grow): layout changes only,
    never values, so rebuilds are invisible to training numerics.

    ``banks`` (power of two) partitions the buckets into per-bank
    regions (see the section comment above): keys hash to a bank with a
    FIXED seed and probe only inside it, so bank membership is stable
    across rebuilds and the hot tier can pin a bank's rows to one HBM
    shard. ``banks=1`` is bit-for-bit the unbanked layout.
    """

    _SEEDS = (0x1234ABCD, 0x9E3779B9, 0xDEADBEEF, 0x2545F491)

    def __init__(self, capacity: int, sharding=None, bucket_slots: int = 8,
                 probe_buckets: int = 2, banks: int = 1) -> None:
        enforce(capacity > 0, "capacity must be positive")
        self.capacity = int(capacity)
        self.bucket_slots = int(bucket_slots)
        self.probe_buckets = int(probe_buckets)
        self.banks = int(banks)
        enforce(self.banks >= 1 and (self.banks & (self.banks - 1)) == 0,
                f"banks must be a power of two, got {banks}")
        self._sharding = sharding
        nb = max(64, self.banks)
        while nb * bucket_slots < 2 * self.capacity:
            nb <<= 1
        self._seed_idx = 0
        self._init_arrays(nb)
        self.rebuilds = 0
        #: mutation counter — bumps on every insert/remove/rebuild, so
        #: callers can cache lookup_host results across a batch window
        #: and invalidate precisely (the hot tier's prefetch→ensure
        #: single-scan optimization)
        self.version = 0
        self._dev: Optional[Dict[str, jax.Array]] = None
        self._patches: list = []   # (bucket, lane) pending device writes
        self._full_upload = True   # first device_state uploads everything

    def _init_arrays(self, nb: int) -> None:
        self.nbuckets = nb
        B = self.bucket_slots
        self.hi = np.zeros((nb, B), np.uint32)
        self.lo = np.zeros((nb, B), np.uint32)
        self.row = np.full((nb, B), _EMPTY, np.int32)
        self.seed = np.uint32(self._SEEDS[self._seed_idx])
        self.used = 0
        self.tombstones = 0

    # -- host mirror ------------------------------------------------------

    def _bank_local_np(self, hi: np.ndarray, lo: np.ndarray):
        """(bank-region base bucket, in-bank probe start) per key — the
        numpy twin of :func:`dynamic_probe_buckets`'s hash math."""
        nbpb = self.nbuckets // self.banks
        local = _mix32_np(hi, lo, self.seed) & np.uint32(nbpb - 1)
        if self.banks == 1:
            return np.zeros_like(local), local
        bank = _mix32_np(hi, lo, np.uint32(_BANK_SEED)) \
            & np.uint32(self.banks - 1)
        return bank * np.uint32(nbpb), local

    def bank_of(self, keys: np.ndarray) -> np.ndarray:
        """[n] int32 bank of each key (fixed hash — stable across
        rebuilds/reseeds; all zeros when banks == 1)."""
        keys = np.ascontiguousarray(keys, np.uint64)
        if self.banks == 1:
            return np.zeros(len(keys), np.int32)
        hi, lo = split_keys(keys)
        return (_mix32_np(hi, lo, np.uint32(_BANK_SEED))
                & np.uint32(self.banks - 1)).astype(np.int32)

    # graftlint: hot-path
    def lookup_host(self, keys: np.ndarray) -> np.ndarray:
        """[n] int32 rows, −1 = missing (vectorized; the control-plane
        twin of the in-graph probe — identical hash math)."""
        if len(keys) == 0:
            return np.zeros(0, np.int32)
        hi, lo = split_keys(keys)
        base, local = self._bank_local_np(hi, lo)
        local_mask = np.uint32(self.nbuckets // self.banks - 1)
        found = np.full(len(keys), -1, np.int32)
        for t in range(self.probe_buckets):
            b = base + ((local + np.uint32(t)) & local_mask)
            match = ((self.hi[b] == hi[:, None]) & (self.lo[b] == lo[:, None])
                     & (self.row[b] >= 0))
            hit = np.max(np.where(match, self.row[b], -1), axis=1)
            found = np.where(found >= 0, found, hit).astype(np.int32)
        return found

    def _place_one(self, hi: np.uint32, lo: np.uint32, row: int) -> bool:
        """Insert one key (must not be present). False = window full."""
        local_mask = np.uint32(self.nbuckets // self.banks - 1)
        base, local = self._bank_local_np(np.asarray([hi], np.uint32),
                                          np.asarray([lo], np.uint32))
        base, b0 = int(base[0]), local[0]
        for t in range(self.probe_buckets):
            b = base + int((b0 + np.uint32(t)) & local_mask)
            for l in range(self.bucket_slots):
                if self.row[b, l] < 0:
                    if self.row[b, l] == _TOMB:
                        self.tombstones -= 1
                    self.hi[b, l] = hi
                    self.lo[b, l] = lo
                    self.row[b, l] = row
                    self.used += 1
                    self._patches.append((b, l))
                    return True
        return False

    def insert(self, keys: np.ndarray, rows: np.ndarray) -> None:
        """Insert keys (absent ones — a present key is an error: the
        tier never re-inserts a resident id). Rebuilds deterministically
        when a probe window fills or tombstones exceed 25% load."""
        enforce(len(keys) == len(rows), "keys/rows length mismatch")
        enforce(self.used + len(keys) <= self.capacity,
                "DynamicDeviceKeyMap over capacity")
        if self.tombstones * 4 > self.nbuckets * self.bucket_slots:
            self._rebuild(grow=False)
        self.version += 1
        hi, lo = split_keys(keys)
        for i in range(len(keys)):
            while not self._place_one(hi[i], lo[i], int(rows[i])):
                self._rebuild(grow=self._seed_idx + 1 >= len(self._SEEDS))

    def remove(self, keys: np.ndarray) -> None:
        """Evict keys (tombstone their slots); missing key = error."""
        if len(keys) == 0:
            return
        self.version += 1
        hi, lo = split_keys(keys)
        local_mask = np.uint32(self.nbuckets // self.banks - 1)
        bases, b0s = self._bank_local_np(hi, lo)
        for i in range(len(keys)):
            placed = False
            for t in range(self.probe_buckets):
                b = int(bases[i]) + int((b0s[i] + np.uint32(t)) & local_mask)
                for l in range(self.bucket_slots):
                    if (self.row[b, l] >= 0 and self.hi[b, l] == hi[i]
                            and self.lo[b, l] == lo[i]):
                        self.row[b, l] = _TOMB
                        self.used -= 1
                        self.tombstones += 1
                        self._patches.append((b, l))
                        placed = True
                        break
                if placed:
                    break
            enforce(placed, f"remove: key {keys[i]} not in map")

    def items(self):
        """(keys u64, rows i32) of every resident entry (rebuild fuel)."""
        live = self.row >= 0
        keys = (self.hi[live].astype(np.uint64) << np.uint64(32)) \
            | self.lo[live].astype(np.uint64)
        return keys, self.row[live].copy()

    def _rebuild(self, grow: bool) -> None:
        # snapshot EVERY resident entry up front — a failed attempt
        # below must retry with this full list, never re-harvest
        # items() from a half-rebuilt table (that drops the tail)
        keys, rows = self.items()
        self.version += 1
        # deterministic layout: re-insert in ascending row order
        order = np.argsort(rows, kind="stable")
        keys, rows = keys[order], rows[order]
        hi, lo = split_keys(keys)
        nb = self.nbuckets * 2 if grow else self.nbuckets
        while True:
            self._seed_idx = (self._seed_idx + 1) % len(self._SEEDS)
            self._init_arrays(nb)
            self.rebuilds += 1
            self._full_upload = True
            self._patches.clear()
            if all(self._place_one(hi[i], lo[i], int(rows[i]))
                   for i in range(len(keys))):
                return
            # pathological seed: rotate again, growing once the seed
            # sequence is exhausted (terminates: load ≤ 0.5 halves
            # every growth)
            if self._seed_idx + 1 >= len(self._SEEDS):
                nb <<= 1

    # -- device arrays ----------------------------------------------------

    def _put(self, a: np.ndarray) -> jax.Array:
        if self._sharding is not None:
            return jax.device_put(a, self._sharding)
        return jnp.asarray(a)

    # graftlint: hot-path
    def device_state(self) -> Dict[str, jax.Array]:
        """Device arrays for the compiled step, refreshed from the host
        mirror: pending slot patches apply as one scatter per array; a
        rebuild re-uploads wholesale. Steady state (no mutations since
        the last call) returns the cached dict untouched."""
        if self._dev is None or self._full_upload:
            self._dev = {"hi": self._put(self.hi), "lo": self._put(self.lo),
                         "row": self._put(self.row),
                         "seed": jnp.asarray(self.seed)}
            self._full_upload = False
            self._patches.clear()
            return self._dev
        if self._patches:
            # host patch lists, not device arrays — no D2H transfer
            b = np.asarray([p[0] for p in self._patches],  # graftlint: ignore[hot-host-transfer]
                           np.int32)
            l = np.asarray([p[1] for p in self._patches],  # graftlint: ignore[hot-host-transfer]
                           np.int32)
            self._dev = {
                "hi": self._dev["hi"].at[b, l].set(self.hi[b, l]),
                "lo": self._dev["lo"].at[b, l].set(self.lo[b, l]),
                "row": self._dev["row"].at[b, l].set(self.row[b, l]),
                "seed": self._dev["seed"],
            }
            self._patches.clear()
        return self._dev

    def lookup(self, keys_hi: jax.Array, keys_lo: jax.Array) -> jax.Array:
        return dynamic_map_lookup(self.device_state(), keys_hi, keys_lo,
                                  self.probe_buckets, self.banks)
