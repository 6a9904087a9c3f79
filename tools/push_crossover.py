"""The sparse push's two formulations against each other on the chip:
the table behind ``embedding_cache.SWEEP_MAX_ROWS_PER_SLOT``.

    python3 tools/push_crossover.py [--out chiprun_out/push_crossover.json]
                                    [--capacities 21,23,25,26] [--slots 106496,213024]
                                    [--draw zipf|distinct] [--profile 26,106496]
                                    [--chunks 0,4096,16384] [--rehearse]

For every (capacity C, slots n) it times one jitted, donated
``cache_push`` in both formulations (forced by ``push_mode``) over the
same seeded dispatches, and holds the two to each other: the same
``--dispatches`` pushes from the same seeded table through the sweep and
through the touched rows, whole tables compared on the host — show, click
and ``has_embedx`` equal, weights and optimizer state by their largest
absolute difference, rows no dispatch named bit-equal to the table they
started from. ``--profile C,n`` also, at that shape: the touched rows
traced for a few pushes with the device's operations listed by time, and
the touched rows at other chunk lengths (``--chunks``, 0 = the batch in
one piece).

Rows are drawn as the benchmark's cells see them (``draw_batches``):
n = 106,496 is a 4096 x 26 batch, Zipf 1.05 a slot over a half-full
table, about half its slots repeats; n = 213,024 is what a shard of the
routed cell receives from four such batches, deduplicated at their
source, in four buckets padded with the sentinel. ``--draw distinct``
names no row twice: the sweep's cost does not depend on the rows, the
touched rows' does, and this is its worst case. A builder's tool, not
a metric: needs a TPU (``--rehearse``: tiny sizes on the CPU, which
exercises this script only).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)

COLS = ("show", "click", "embed_w", "embed_state", "embedx_w",
        "embedx_state", "has_embedx")
EXACT = ("show", "click", "has_embedx")
DIM = 8


def fresh_state(C: int, seed: int):
    """A trained-looking table, made on the device from ``seed``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        k = jax.random.split(key, 7)
        u = lambda i, *shape: jax.random.uniform(k[i], shape, jnp.float32)
        return {"show": jnp.floor(u(0, C) * 50.0),
                "click": jnp.floor(u(1, C) * 5.0),
                "embed_w": u(2, C, 1) * 0.2 - 0.1,
                "embed_state": u(3, C, 1) * 4.0,
                "embedx_w": u(4, C, DIM) * 0.2 - 0.1,
                "embedx_state": u(5, C, 1) * 4.0,
                "has_embedx": jnp.floor(u(6, C) * 2.0)}

    return make(jax.random.key(seed))


SLOTS, ZIPF_S, SHARDS = 26, 1.05, 4


def _batch_rows(rng, pool: int, batch: int, distinct: bool):
    """[batch * SLOTS] rows of a table whose first ``SLOTS * pool`` rows
    are a pass's keys: a Zipf(1.05) rank a slot (the draw of
    ``benchmarks/generators/ctr_zipf.py``), scattered over the slot's
    pool by a seeded permutation. ``distinct``: no row twice instead,
    the touched rows' worst case."""
    import numpy as np

    if distinct:
        return np.resize(rng.permutation(SLOTS * pool), batch * SLOTS)
    p = 1.0 / np.arange(1, pool + 1, dtype=np.float64) ** ZIPF_S
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    ranks = np.minimum(np.searchsorted(cdf, rng.random((batch, SLOTS)),
                                       side="right"), pool - 1)
    rows = np.empty((batch, SLOTS), np.int64)
    for c in range(SLOTS):
        rows[:, c] = c * pool + rng.permutation(pool)[ranks[:, c]]
    return rows.reshape(-1)


def draw_batches(C: int, n: int, k: int, seed: int, distinct: bool = False):
    """``k`` pushes of ``n`` slots into a half-full table of ``C`` rows.
    ``n`` a multiple of ``SLOTS``: one batch. Otherwise ``n`` is the
    ``SHARDS`` buckets a shard of a ``SHARDS * C``-row table receives."""
    import numpy as np

    from paddle_tpu.ps.sharded_cache import route_bucket_capacity

    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        if n % SLOTS == 0:
            rows = _batch_rows(rng, C // 2 // SLOTS, n // SLOTS, distinct)
        else:
            m = next(m for m in range(n // SLOTS * SLOTS, 0, -SLOTS)
                     if SHARDS * route_bucket_capacity(m, SHARDS) == n)
            rows = np.full((SHARDS, n // SHARDS), C, np.int64)
            for src in range(SHARDS):
                sent = np.unique(_batch_rows(
                    rng, SHARDS * C // 2 // SLOTS, m // SLOTS, distinct))
                mine = sent[sent % SHARDS == 0] // SHARDS   # round-robin
                rows[src, :len(mine)] = mine
            rows = rows.reshape(-1)
        rows = rows.astype(np.int32)
        rows[:4] = (C - 1, C - 1, 0, C)
        shows = (rng.random(n) < 0.97).astype(np.float32)
        clicks = (rng.random(n) < 0.25).astype(np.float32) * shows
        grads = rng.standard_normal((n, 1 + DIM)).astype(np.float32) * 1e-2
        out.append((rows, grads, shows, clicks))
    return out


def pusher(C: int, mode: str):
    import jax

    from paddle_tpu.ps.embedding_cache import CacheConfig, cache_push

    cfg = CacheConfig(capacity=C, embedx_dim=DIM, embedx_threshold=0.0,
                      push_mode=mode)
    return jax.jit(lambda st, r, g, s, c: cache_push(st, r, g, s, c, cfg),
                   donate_argnums=0)


def time_push(push, C: int, batches, iters: int) -> float:
    """Milliseconds a push, over ``iters`` back-to-back donated calls."""
    import jax

    state = fresh_state(C, 0)
    dev = [jax.device_put(b) for b in batches]
    for i in range(2):
        state = push(state, *dev[i % len(dev)])
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for i in range(iters):
        state = push(state, *dev[i % len(dev)])
    jax.block_until_ready(state)
    return (time.perf_counter() - t0) / iters * 1e3


def run_to_host(push, C: int, batches):
    import jax
    import numpy as np

    state = fresh_state(C, 0)
    for b in batches:
        state = push(state, *b)
    host = {k: np.asarray(v) for k, v in state.items()}
    del state
    return host


def parity(C: int, batches, sweep, touched) -> dict:
    """Whole tables after the same pushes through both formulations."""
    import numpy as np

    a, b = run_to_host(sweep, C, batches), run_to_host(touched, C, batches)
    start = run_to_host(lambda st, *_: st, C, [])
    named = np.zeros(C + 1, bool)
    for rows, *_ in batches:
        named[np.minimum(rows, C)] = True
    named = named[:C]
    out = {"rows_named": int(named.sum()), "max_abs": {}, "exact_equal": {},
           "unnamed_rows_bit_equal": True}
    for k in COLS:
        if k in EXACT:
            out["exact_equal"][k] = bool(np.array_equal(a[k], b[k]))
        else:
            out["max_abs"][k] = float(np.max(np.abs(a[k] - b[k]))) \
                if a[k].size else 0.0
        for got in (a, b):
            same = np.array_equal(got[k][~named].view(np.uint32),
                                  start[k][~named].view(np.uint32))
            out["unnamed_rows_bit_equal"] &= bool(same)
    out["ok"] = bool(all(out["exact_equal"].values())
                     and max(out["max_abs"].values()) <= 1e-6
                     and out["unnamed_rows_bit_equal"])
    return out


def profile_ops(push, C: int, batches, trace_dir: str, top: int = 24):
    """Device operations of a few touched-rows pushes, longest first."""
    import jax
    from harness import trace

    state = fresh_state(C, 0)
    dev = [jax.device_put(b) for b in batches]
    state = push(state, *dev[0])
    jax.block_until_ready(state)
    shutil.rmtree(trace_dir, ignore_errors=True)
    reps = 5
    jax.profiler.start_trace(trace_dir)
    for i in range(reps):
        state = push(state, *dev[i % len(dev)])
    jax.block_until_ready(state)
    jax.profiler.stop_trace()
    events, _ = trace.load_events(trace.find_xplane(trace_dir))
    red = trace.reduce_trace(events)
    if not red["devices"]:          # a rehearsal: the CPU has no device plane
        return {"pushes": reps}
    ops = sorted(red.get("op_self_s", {}).items(), key=lambda kv: -kv[1])
    return {"pushes": reps, "busy_ms_a_push": red["busy_s"] / reps * 1e3,
            "ops_ms_a_push": [[trace.op_label(name), s / reps * 1e3]
                              for name, s in ops[:top]]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--capacities", default="21,23,25,26",
                    help="log2 of the table's rows, comma-separated")
    ap.add_argument("--slots", default="106496,213024")
    ap.add_argument("--dispatches", type=int, default=3)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--draw", choices=("zipf", "distinct"), default="zipf",
                    help="zipf: the cells' repeats; distinct: no row twice")
    ap.add_argument("--profile", default="26,106496")
    ap.add_argument("--chunks", default="0,4096,16384",
                    help="other chunk lengths to time at the profile's "
                         "shape; 0 = the batch in one piece")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "push_crossover.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.capacities, args.slots = "12,14", "10400,20832"
        args.profile, args.chunks, args.iters = "14,10400", "0,4096", 2

    import jax
    import numpy as np

    from paddle_tpu.ps import embedding_cache as ec
    from paddle_tpu.ps.embedding_cache import resolve_push_mode

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 3
    result = {"platform": dev.platform, "device_kind": dev.device_kind,
              "dispatches": args.dispatches, "iters": args.iters,
              "chunk": ec.PUSH_CHUNK, "draw": args.draw, "table": []}
    draw = lambda lg, n: draw_batches(1 << lg, n, args.dispatches,
                                      seed=lg * 1000003 + n,
                                      distinct=args.draw == "distinct")
    for lg in (int(x) for x in args.capacities.split(",")):
        C = 1 << lg
        for n in (int(x) for x in args.slots.split(",")):
            batches = draw(lg, n)
            sweep, touched = pusher(C, "dense"), pusher(C, "sparse")
            distinct = [np.unique(b[0][b[0] < C]).size for b in batches]
            line = {"capacity": C, "slots": n, "rows_per_slot": C / n,
                    "distinct_rows": sum(distinct) / len(distinct),
                    "auto": resolve_push_mode("auto", C, n),
                    "sweep_ms": time_push(sweep, C, batches, args.iters),
                    "touched_ms": time_push(touched, C, batches, args.iters),
                    "parity": parity(C, batches, sweep, touched)}
            print(json.dumps(line), flush=True)
            result["table"].append(line)
    if args.profile:
        lg, n = (int(x) for x in args.profile.split(","))
        C, batches = 1 << lg, draw(lg, n)
        trace_dir = os.path.join(os.path.dirname(args.out), "push_trace")
        prof = result["profile"] = {"capacity": C, "slots": n, "chunks_ms": {}}
        push = pusher(C, "sparse")
        prof["touched"] = dict(profile_ops(push, C, batches, trace_dir),
                               ms=time_push(push, C, batches, args.iters))
        default = ec.PUSH_CHUNK
        for chunk in (int(x) for x in args.chunks.split(",")):
            ec.PUSH_CHUNK = chunk or n
            prof["chunks_ms"][chunk] = time_push(
                pusher(C, "sparse"), C, batches, args.iters)
        ec.PUSH_CHUNK = default
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(prof), flush=True)
    result["ok"] = all(l["parity"]["ok"] for l in result["table"])
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps({"ok": result["ok"], "out": args.out}))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
