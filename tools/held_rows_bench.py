"""What the bounded buffer's row movement costs a layer, by chunk size and
by how full the buffer is.

    chiprun --chips 1 -- python3 tools/held_rows_bench.py

``parallel/moe.py``'s ``_rows_of_tokens`` and ``_sum_to_tokens`` at the two
held cells' shapes (LFM2: T 16,384, 4 of 32 a token, 8 held, R 32,768;
JoyAI: T 8,192, 8 of 256, 16 held, R 8,192; d 2048), each as the step runs
it: the gather of bf16 token rows and the token sum of f32 expert rows
(forward, and once more when the backward re-runs the form), and their
transposes (the sum of bf16 cotangent rows, the gather of f32 ones). A
layer's row movement is 2 x bf16 gather + f32 gather + 2 x f32 sum + bf16
sum. For ``_HELD_CHUNK`` in 512, 1024, 2048 and R itself (one chunk: the
whole buffer walked whatever is live, what the layer cost before PR 35),
with the buffer half full (even loads, what both cells send) and full
(``n_held`` = R: every chunk walked, the loop's overhead). Prints ms a call
a case and one JSON line; on a TPU only (a CPU time is no device number);
``--rehearse`` runs tiny shapes on the CPU for the control flow alone.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.parallel import moe

REHEARSE = "--rehearse" in sys.argv
if jax.default_backend() != "tpu" and not REHEARSE:
    raise SystemExit(f"needs a TPU, found {jax.default_backend()}")

#: name -> (tokens, a token's experts, router width, experts held, width)
SHAPES = {"lfm2": (16384, 4, 32, 8, 2048), "joyai": (8192, 8, 256, 16, 2048)}
if REHEARSE:
    SHAPES = {"lfm2": (512, 4, 32, 8, 128), "joyai": (256, 8, 256, 16, 128)}
CHUNKS = (512, 1024, 2048)
REPS = 3 if REHEARSE else 20


def routing(T, k, E, count, full, rng):
    """[T, k] experts a token: ``full`` puts twice the even share of a
    token's choices on the held experts 0..count-1 (``n_held`` = R), else
    k distinct experts at random (``n_held`` about R / 2)."""
    if not full:
        return np.argsort(rng.random((T, E)), axis=1)[:, :k].astype(np.int32)
    rows = moe.dispatch_ladder(T, k, E, count)[0]
    per = np.full(T, rows // T)
    per[:rows - per.sum()] += 1
    index = np.empty((T, k), np.int32)
    for t in range(T):
        held = rng.choice(count, per[t], replace=False)
        rest = count + rng.choice(E - count, k - per[t], replace=False)
        index[t] = rng.permutation(np.concatenate([held, rest]))
    return index


def plan_of(index, k, count, rows):
    held = index < count
    order, _ = moe.sort_by_expert(jnp.where(held, index, count))
    return moe._held_plan(order, held, jnp.sum(held, dtype=jnp.int32), rows,
                          k)


def ms_a_call(fn, *args):
    jax.block_until_ready(fn(*args))
    best = []
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t) / REPS * 1e3)
    return float(np.median(best))


res = {}
rng = np.random.default_rng(0)
for name, (T, k, E, count, d) in SHAPES.items():
    for fill in ("half", "full"):
        index = jnp.asarray(routing(T, k, E, count, fill == "full", rng))
        R = moe.dispatch_ladder(T, k, E, count)[0]
        x16 = jnp.asarray(rng.normal(size=(T, d)), jnp.bfloat16)
        x32 = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
        z32 = jnp.asarray(rng.normal(size=(R, d)), jnp.float32)
        z16 = z32.astype(jnp.bfloat16)
        for chunk in CHUNKS + (R,):
            moe._HELD_CHUNK = chunk          # the plan's live chunks read it
            plan = jax.jit(lambda i: plan_of(i, k, count, R))(index)
            gather = lambda x, p: moe._rows_of_tokens(
                x, p, "pt.moe.dispatch", min(chunk, R))
            total = lambda z, p: moe._sum_to_tokens(
                z, p, k, "pt.moe.combine", min(chunk, R))
            part = {"gather_bf16": ms_a_call(gather, x16, plan),
                    "gather_f32": ms_a_call(gather, x32, plan),
                    "sum_f32": ms_a_call(total, z32, plan),
                    "sum_bf16": ms_a_call(total, z16, plan)}
            part["layer"] = 2 * part["gather_bf16"] + part["gather_f32"] \
                + 2 * part["sum_f32"] + part["sum_bf16"]
            part["live_chunks"] = int(plan.live)
            key = f"{name}.{fill}.chunk_{'R' if chunk == R else chunk}"
            res[key] = {n: round(v, 4) for n, v in part.items()}
            print(key, res[key], flush=True)
print(json.dumps({"device": jax.devices()[0].device_kind, "ms": res}))
