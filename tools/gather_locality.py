"""Does the owner-side pull's gather cost follow where the live rows lie?

    chiprun --chips 1 -- python3 tools/gather_locality.py

``cache_pull`` over four sorted request buckets (13,900 real rows padded to
53,256 each, as an owner of ``deepfm_routed_4chip`` receives them) from a
2^25-row shard, the rows drawn from half the block (dense row numbers), the
whole of it (implicit rows: a key's row is its slot), an eighth, slot-major,
with the slots' residues, sorted and not. PERF.md section 6 (PR 32) has the
first reading: 5.25 ms a pull in every layout. Prints ms a pull a case and
one JSON line; on a TPU only (a CPU time is no device number).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ps.embedding_cache import cache_pull

if jax.default_backend() != "tpu" and "--rehearse" not in sys.argv:
    raise SystemExit(f"needs a TPU, found {jax.default_backend()}")

C, K, cap, real = (1 << 16, 4, 532, 139) if "--rehearse" in sys.argv \
    else (1 << 25, 4, 53256, 13900)
rng = np.random.default_rng(0)
state = {"embed_w": jnp.zeros((C, 1), jnp.float32), "embedx_w": jnp.zeros((C, 8), jnp.float32)}
pull = jax.jit(lambda st, r: cache_pull(st, r))

def requests(draw, sort=True, pad=C - 1):
    out = np.full((K, cap), pad, np.int32)
    for k in range(K):
        r = draw(real)
        out[k, :real] = np.sort(r) if sort else r
    return jnp.asarray(out.reshape(-1))

planes = np.asarray([0.865, 0.594, 0.323, 0.143]); planes /= planes.sum()
def slot_major(n):
    s = rng.choice(4, size=n, p=planes)
    return (s * (C // 4) + rng.integers(0, C // 4, n)).astype(np.int32)

slots = np.asarray([0.865, 0.594, 0.323, 0.143]); slots /= slots.sum()
def implicit_like(n):
    # a live row is q*4 + s, slot 0 likeliest: rows crowd residue 0 mod 4
    q = rng.choice(C // 4, n, replace=False)
    return (q * 4 + rng.choice(4, size=n, p=slots)).astype(np.int32)

cases = {
    "implicit_like_sorted_pad0": lambda: requests(implicit_like, pad=0),
    "dense_first_half_sorted_pad0": lambda: requests(lambda n: rng.choice(C // 2, n, replace=False), pad=0),
    "implicit_like_sorted": lambda: requests(implicit_like),
    "dense_first_half_sorted": lambda: requests(lambda n: rng.choice(C // 2, n, replace=False)),
    "whole_block_sorted": lambda: requests(lambda n: rng.choice(C, n, replace=False)),
    "slot_major_sorted": lambda: requests(slot_major),
    "dense_first_half_unsorted": lambda: requests(lambda n: rng.choice(C // 2, n, replace=False), sort=False),
    "whole_block_unsorted": lambda: requests(lambda n: rng.choice(C, n, replace=False), sort=False),
    "first_eighth_sorted": lambda: requests(lambda n: rng.choice(C // 8, n, replace=False)),
}
res = {}
for name, make in cases.items():
    reqs = [make() for _ in range(8)]
    jax.block_until_ready(pull(state, reqs[0]))
    ts = []
    for rep in range(5):
        t = time.perf_counter()
        for r in reqs:
            out = pull(state, r)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t) / len(reqs) * 1e3)
    res[name] = round(float(np.median(ts)), 4)
    print(name, res[name], "ms a pull", flush=True)
print(json.dumps({"device": jax.devices()[0].device_kind, "ms": res}))
