"""The expert layer's grouped matmul, two ways, on the chip: the
measurement behind ``parallel/moe.grouped_matmul`` (``PERF.md`` section 6,
PR 26).

    python3 tools/grouped_matmul_bench.py [--out chiprun_out/grouped_matmul.json]
                                          [--rows 65536] [--experts 64]
                                          [--hidden 2048] [--width 1024]
                                          [--tilings 512x1024x1024,...] [--rehearse]

Rows sorted by expert, ``[rows, hidden]`` bf16, against a bank
``[experts, hidden, width]`` bf16 with f32 accumulation, group sizes
uneven (a seeded Dirichlet draw: the largest group several times the
mean, as text routes) and known only at run time. Timed, each as one
jitted call chained ``--calls`` times: the forward product, and forward
plus both gradients (three passes, what a train step pays), for

- ``jax.lax.ragged_dot`` (XLA's own lowering) with a float32 and with a
  bf16 product (and so cotangent),
- the Pallas grouped-matmul kernel that ships with jax
  (``jax.experimental.pallas.ops.tpu.megablox``) under its own VJP, at each
  of ``--tilings``, and
- the program's ``parallel.moe.grouped_matmul``: that kernel at the tile
  the program keeps, under the program's VJP.

The two are held to each other on the forward product and both gradients
(largest absolute difference over the largest entry). Last, in float32
under ``jax.default_matmul_precision("highest")`` at ``--precision-rows``
rows: the program's ``grouped_matmul`` and ``ragged_dot`` against the
mask-every-expert einsum, forward and both gradients — what the
benchmark's float32 check of the expert layer rests on. A builder's tool,
not a metric: needs a TPU (``--rehearse``: tiny sizes on the CPU with the
kernel interpreted, which exercises this script only).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def group_sizes(rows: int, experts: int, seed: int):
    import numpy as np

    rng = np.random.default_rng(seed)
    share = rng.dirichlet(np.full(experts, 0.6))
    sizes = np.floor(share * rows).astype(np.int32)
    sizes[np.argmax(sizes)] += rows - sizes.sum()
    return sizes


def timed(fn, args, calls: int) -> float:
    """Milliseconds a call, ``calls`` of them in flight behind each other."""
    import jax

    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    out = None
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t) / calls * 1e3


def float32_check(args, ragged):
    """{way: largest |difference| over the largest entry of (product, dx,
    dbank)} against the mask-every-expert einsum, all float32, matmul
    precision ``highest``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.parallel.moe import grouped_matmul

    M, E, K, N = args.precision_rows, args.experts, args.hidden, args.width
    gs = jnp.asarray(group_sizes(M, E, args.seed + 1))
    k1, k2, k3 = jax.random.split(jax.random.key(args.seed + 1), 3)
    x = jax.random.normal(k1, (M, K), jnp.float32)
    w = jax.random.normal(k2, (E, K, N), jnp.float32) * 0.02
    g = jax.random.normal(k3, (M, N), jnp.float32)
    owner = jnp.repeat(jnp.arange(E), gs, total_repeat_length=M)

    def masked(x, w, gs):
        return jnp.einsum("me,mk,ekn->mn", jax.nn.one_hot(owner, E), x, w)

    def passes(f):
        out, vjp = jax.vjp(lambda x, w: f(x, w, gs), x, w)
        return [np.asarray(a) for a in (out,) + vjp(g)]

    out = {}
    with jax.default_matmul_precision("highest"):
        want = passes(masked)
        for name, f in (("moe.grouped_matmul", grouped_matmul),
                        ("ragged_dot", ragged)):
            out[name] = [float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                         for a, b in zip(passes(f), want)]
    print(json.dumps({"float32_highest": out}), flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "grouped_matmul.json"))
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--experts", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--width", type=int, default=1024)
    ap.add_argument("--tilings",
                    default="128x128x128,512x512x512,512x1024x512,"
                            "256x1024x1024,1024x512x512")
    ap.add_argument("--precision-rows", type=int, default=8192)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        args.rows, args.experts, args.hidden, args.width = 512, 8, 256, 128
        args.tilings, args.calls, args.precision_rows = "128x128x128", 2, 256

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"needs a TPU, jax found {dev.platform}", file=sys.stderr)
        return 3
    M, E, K, N = args.rows, args.experts, args.hidden, args.width
    gs = jnp.asarray(group_sizes(M, E, args.seed))
    k1, k2, k3 = jax.random.split(jax.random.key(args.seed), 3)
    x = jax.random.normal(k1, (M, K), jnp.bfloat16)
    w = (jax.random.normal(k2, (E, K, N), jnp.float32) * 0.02).astype(
        jnp.bfloat16)
    g = jax.random.normal(k3, (M, N), jnp.float32)
    flops = 2.0 * M * K * N

    def ragged(x, w, gs):
        return jax.lax.ragged_dot(x, w, gs,
                                  preferred_element_type=jnp.float32)

    def ragged_bf16(x, w, gs):
        # bf16 out: the cotangent arrives in bf16 too
        return jax.lax.ragged_dot(x, w, gs,
                                  preferred_element_type=jnp.bfloat16)

    def program(x, w, gs):
        # what the program keeps
        from paddle_tpu.parallel.moe import grouped_matmul

        return grouped_matmul(x, w, gs)

    def kernel(tiling):
        def f(x, w, gs):
            return megablox.gmm(x, w, gs, jnp.float32, tiling, None, None,
                                False, args.rehearse)
        return f

    def three_passes(f):
        def run(x, w, gs, g):
            out, vjp = jax.vjp(lambda x, w: f(x, w, gs), x, w)
            return (out,) + vjp(g.astype(out.dtype))
        return jax.jit(run)

    rec = {"device_kind": dev.device_kind, "rows": M, "experts": E,
           "hidden": K, "width": N, "group_sizes_max_over_mean":
               float(jnp.max(gs)) * E / M, "empty_groups":
               int(jnp.sum(gs == 0)), "forward_flop": flops, "ways": {}}
    base = None
    ways = [("ragged_dot", ragged), ("ragged_dot_bf16_out", ragged_bf16),
            ("moe.grouped_matmul", program)] + [
        (f"megablox_{t}", kernel(tuple(int(v) for v in t.split("x"))))
        for t in args.tilings.split(",")]
    for name, f in ways:
        try:
            fwd_ms = timed(jax.jit(f), (x, w, gs), args.calls)
            passes = three_passes(f)
            all_ms = timed(passes, (x, w, gs, g), args.calls)
            got = [np.asarray(a, np.float32) for a in passes(x, w, gs, g)]
        except Exception as e:      # a tiling the compiler refuses
            rec["ways"][name] = {"error": f"{type(e).__name__}: "
                                          f"{str(e)[:300]}"}
            print(json.dumps({name: rec["ways"][name]}), flush=True)
            continue
        if base is None:
            base = got
        diff = [float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
                for a, b in zip(got, base)]
        rec["ways"][name] = {
            "forward_ms": fwd_ms, "three_passes_ms": all_ms,
            "forward_tflops": flops / fwd_ms / 1e9,
            "three_passes_tflops": 3 * flops / all_ms / 1e9,
            "rel_diff_out_dx_dw_vs_ragged_dot": diff}
        print(json.dumps({name: rec["ways"][name]}), flush=True)
    rec["float32_highest"] = float32_check(args, ragged)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(rec, fh, indent=1)
    print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
