// Static bucketized cuckoo hash build: uint64 feasign -> int32 row.
//
// The TPU-build counterpart of the reference's GPU-resident hashtable
// (paddle/fluid/framework/fleet/heter_ps/hashtable.h:50, vendored cuDF
// concurrent_unordered_map): the reference looks feasigns up on-device
// inside the train loop (HashTable::get kernels, hashtable_inl.h) so the
// host never touches per-batch keys. Here the table is built ON HOST once
// per pass (this file; the HeterComm build_ps bulk-insert analogue) into
// flat arrays the Python layer uploads to HBM, and the per-batch probe
// runs inside the compiled step (ps/device_hash.py) as two fixed bucket
// probes — bounded, branch-free, XLA-friendly.
//
// Layout: nbuckets (power of two) buckets x 4 slots, two arrays. `key`
// is u32[nbuckets, 8]: a bucket's row holds its four keys as halves,
// [hi0 hi1 hi2 hi3 | lo0 lo1 lo2 lo3], so ONE row gather fetches every
// key of the bucket (a gather of <= 8 columns costs the chip the same as
// one of 4: it is paid by the index). `row` is i32[nbuckets, 4]: what
// the caller passed as rows[i] for the key in that slot. Empty slots
// have row == -1 and zero key words. Two hash functions pick candidate
// buckets; insertion uses random-walk eviction. Load factor <= 0.5 by
// construction (python chooses nbuckets), so builds virtually never
// fail; on failure the caller retries with a fresh seed.
//
// Two callers, one build (ps/device_hash.py). EXPLICIT rows: rows[] are
// the cache rows the caller chose, and `row` is uploaded beside `key`.
// IMPLICIT rows (a pass whose slot table fits the cache): rows[] is
// 0..n-1, so `row` comes back as the PLACEMENT (slot -> position in
// keys[]); cuckoo_placement reads from it which slot, and so which cache
// row, each key got, and `row` never leaves the host. The device then tells
// an empty slot from a key by the key words alone, so the host
// overwrites the zero words of the empty slots in the two buckets key 0
// hashes to with a second filler key (DeviceKeyMap.build_host_implicit):
// zero words everywhere else can never match a probe, because a probe
// of key 0 reads only those two buckets.
//
// The 32-bit mixer below must match _mix32 in ps/device_hash.py
// bit-for-bit — the device probe recomputes these hashes with jnp uint32
// arithmetic.
//
// Lock hierarchy (checked by tools/lint/lock_order.py): NONE — the
// build is single-threaded per call and owns its output buffers; there
// are no mutexes in this translation unit. Callers running builds in a
// background thread (DeviceKeyMap.build_host) must not share the output
// arrays until the build returns.

#include <cstdint>
#include <cstring>
#include <random>
#include <vector>

namespace {

constexpr int kSlots = 4;
constexpr int kKeyWords = 2 * kSlots;  // a bucket's row of `key`: hi x4 | lo x4
constexpr int kMaxKicks = 512;

inline uint32_t mix32(uint32_t hi, uint32_t lo, uint32_t seed) {
  uint32_t h = seed;
  h ^= hi;
  h *= 0x85ebca6bu;
  h ^= h >> 13;
  h ^= lo;
  h *= 0xc2b2ae35u;
  h ^= h >> 16;
  return h;
}

}  // namespace

extern "C" {

// Build the table. Returns 0 on success, or the number of keys that could
// not be placed (caller retries with a different seed). Buffers:
//   out_key: nbuckets*8 uint32 (per bucket hi x4 | lo x4);
//   out_row: nbuckets*4 int32.
int64_t cuckoo_build(const uint64_t* keys, const int32_t* rows, int64_t n,
                     int64_t nbuckets, uint32_t seed, uint32_t* out_key,
                     int32_t* out_row) {
  const uint64_t mask = static_cast<uint64_t>(nbuckets) - 1;
  std::memset(out_key, 0, sizeof(uint32_t) * nbuckets * kKeyWords);
  std::memset(out_row, 0xff, sizeof(int32_t) * nbuckets * kSlots);  // -1

  std::mt19937 rng(seed ^ 0x9e3779b9u);
  int64_t failures = 0;

  for (int64_t i = 0; i < n; ++i) {
    uint32_t hi = static_cast<uint32_t>(keys[i] >> 32);
    uint32_t lo = static_cast<uint32_t>(keys[i]);
    int32_t row = rows[i];
    bool placed = false;
    for (int kick = 0; kick < kMaxKicks && !placed; ++kick) {
      uint64_t b1 = mix32(hi, lo, seed) & mask;
      uint64_t b2 = mix32(hi, lo, seed ^ 0x7feb352du) & mask;
      for (uint64_t b : {b1, b2}) {
        for (int s = 0; s < kSlots; ++s) {
          int64_t idx = static_cast<int64_t>(b) * kSlots + s;
          if (out_row[idx] < 0) {
            uint32_t* k = out_key + static_cast<int64_t>(b) * kKeyWords + s;
            k[0] = hi;
            k[kSlots] = lo;
            out_row[idx] = row;
            placed = true;
            break;
          }
        }
        if (placed) break;
      }
      if (!placed) {
        // evict a random slot from a random candidate bucket
        uint64_t b = (rng() & 1) ? b1 : b2;
        int s = static_cast<int>(rng() % kSlots);
        int64_t idx = static_cast<int64_t>(b) * kSlots + s;
        uint32_t* k = out_key + static_cast<int64_t>(b) * kKeyWords + s;
        uint32_t ehi = k[0], elo = k[kSlots];
        int32_t erow = out_row[idx];
        k[0] = hi;
        k[kSlots] = lo;
        out_row[idx] = row;
        hi = ehi;
        lo = elo;
        row = erow;
      }
    }
    if (!placed) ++failures;
  }
  return failures;
}

// Read back the placement of a build whose rows[] were 0..n-1 (IMPLICIT
// rows), in CACHE-ROW order: slot s of bucket b is cache row
// (b mod shards) * shard_rows + (b div shards) * 4 + s, so walking the
// shards, then a shard's buckets, then the slots, meets the rows
// ascending. Writes each occupied slot's key (rebuilt from the bucket's
// words: no random read) and row; returns how many. One forward pass
// over `key` and `row`, strided by `shards` buckets.
int64_t cuckoo_placement(const uint32_t* key, const int32_t* row,
                         int64_t nbuckets, int64_t shards, int64_t shard_rows,
                         uint64_t* out_keys, int32_t* out_rows) {
  int64_t j = 0;
  for (int64_t r = 0; r < shards; ++r) {
    for (int64_t q = 0, b = r; b < nbuckets; ++q, b += shards) {
      const uint32_t* k = key + b * kKeyWords;
      for (int s = 0; s < kSlots; ++s) {
        if (row[b * kSlots + s] < 0) continue;
        out_keys[j] = (static_cast<uint64_t>(k[s]) << 32) | k[kSlots + s];
        out_rows[j] = static_cast<int32_t>(r * shard_rows + q * kSlots + s);
        ++j;
      }
    }
  }
  return j;
}

}  // extern "C"
