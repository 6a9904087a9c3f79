"""A hyper-connected sublayer's residual path on the chip: the four
token-tiled kernels of ``ops/hyper_connection.py`` one by one, and the whole
path — forward, and value with gradient — fused beside the plain ``jnp``
definitions, at the shapes of ``xing4_29b_a4b_seq4096`` (4,096 tokens, four
streams of 3,584, float32). The measurement behind ``PERF.md`` section 6,
PR 52.

    python3 tools/hc_path_bench.py [--calls 10] [--tile-mib 20 10 40]
    python3 tools/hc_path_bench.py --rehearse       # tiny shapes on the CPU, no times

Per kernel: milliseconds a call (host clock round ``calls`` calls that end
in ``block_until_ready``), the stream-widths it moves (one = [tokens, C]
float32) and what that comes to in GB/s; per ``--tile-mib`` value, which
sets the module's ``_TILE_BYTES`` for that round (a tuning aid of this tool:
the program has one value). Then the path ``X -> X'`` round an elementwise
stand-in for the sublayer (``y = tanh(u)``), plain and fused: forward, and
forward + backward for every leaf; and how far the fused results and
gradients lie from the plain ones ON THIS DEVICE (the projection is a
float32 matmul at precision ``highest`` in both: a Mosaic that ran it in one
bf16 pass would read 1e-3 here, not 1e-6). Writes
``chiprun_out/hc_path_bench.json``; fails without a TPU unless
``--rehearse``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: stream-widths a kernel reads and writes (the [tokens, 24] operands and
#: Phi are left out)
MOVED = {"hc_pre_fwd": 5, "hc_post_fwd": 9, "hc_post_bwd": 14,
         "hc_pre_bwd": 13}
ITERS, EPS, CLAMP, RMS_EPS = 20, 1e-6, (-30.0, 30.0), 1e-6


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--tile-mib", type=float, nargs="*", default=[])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default="chiprun_out/hc_path_bench.json")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import hyper_connection as hc

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        print(f"needs a TPU, jax found {dev.platform}", file=sys.stderr)
        return 3
    tokens, n, c = (40, 4, 128) if args.rehearse else (4096, 4, 3584)
    m = 2 * n + n * n
    rng = np.random.default_rng(0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    x = f32(rng.normal(size=(tokens, n, c)))
    ct = f32(rng.normal(size=(tokens, n, c)))
    phi = f32(rng.normal(size=(n * c, m)) * 0.02)
    b = f32(rng.normal(size=(m,)))
    alpha = f32([0.3, 0.25, 0.35])

    def timed(fn, *a):
        out = jax.block_until_ready(fn(*a))
        if args.rehearse:
            return out, None
        t = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*a)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t) / args.calls * 1e3

    def gbps(name, ms):
        return MOVED[name] * tokens * c * 4 / (ms * 1e-3) / 1e9

    result = {"device": dev.device_kind, "tokens": tokens, "streams": n,
              "width": c, "kernels": [], "path": {}}

    # -- the kernels one by one ------------------------------------------
    flat = x.reshape(tokens, n * c)
    default = hc._TILE_BYTES
    for mib in [default / 2**20] + list(args.tile_mib):
        hc._TILE_BYTES = int(mib * 2**20)
        # a budget is read where a kernel's call is traced: a trace anew
        jax.clear_caches()
        on = dict(n=n, interpret=args.rehearse)
        pre = lambda *a: hc._pre_fwd_call(*a, rms_eps=RMS_EPS, **on)
        (u, z, inv), ms_pre = timed(pre, flat, phi, b, alpha)
        _, h_post, h_res = hc.hc_gates(z, b, alpha, n, ITERS, EPS, CLAMP)
        h = jnp.concatenate([h_post, h_res.reshape(tokens, n * n)], axis=-1)
        y = jnp.tanh(u)
        post = lambda *a: hc._post_fwd_call(*a, **on)
        _, ms_post = timed(post, flat, y, h)
        post_bwd = lambda *a: hc._post_bwd_call(*a, **on)
        (dxr, _, _), ms_post_bwd = timed(
            post_bwd, ct.reshape(tokens, n * c), flat, y, h)
        pre_bwd = lambda *a: hc._pre_bwd_call(*a, **on)
        _, ms_pre_bwd = timed(pre_bwd, flat, phi, b, alpha, z, inv, y,
                              0.1 * z, dxr)
        for name, ms in (("hc_pre_fwd", ms_pre), ("hc_post_fwd", ms_post),
                         ("hc_post_bwd", ms_post_bwd),
                         ("hc_pre_bwd", ms_pre_bwd)):
            row = {"kernel": name, "tile_mib": mib, "ms": ms,
                   "stream_widths": MOVED[name],
                   "gb_per_s": gbps(name, ms) if ms else None}
            result["kernels"].append(row)
            print(json.dumps(row), flush=True)
    hc._TILE_BYTES = default

    # -- the whole path, plain and fused ---------------------------------
    def plain(x, phi, b, alpha):
        h_pre, h_post, h_res = hc.hc_mappings(x, phi, b, alpha, ITERS, EPS,
                                              CLAMP, RMS_EPS)
        y = jnp.tanh(hc.hc_collect(x, h_pre))
        return hc.hc_scatter(x, y, h_post, h_res)

    def fused(x, phi, b, alpha):
        u, z, x = hc.hc_pre(x, phi, b, alpha, RMS_EPS)
        _, h_post, h_res = hc.hc_gates(z, b, alpha, n, ITERS, EPS, CLAMP)
        return hc.hc_post(x, jnp.tanh(u), h_post, h_res)

    got = {}
    for name, path in (("plain", plain), ("fused", fused)):
        fwd = jax.jit(path)
        grad = jax.jit(jax.grad(lambda *a: jnp.sum(path(*a) * ct),
                                argnums=(0, 1, 2, 3)))
        out, ms_fwd = timed(fwd, x, phi, b, alpha)
        grads, ms_grad = timed(grad, x, phi, b, alpha)
        got[name] = (out,) + tuple(grads)
        result["path"][name] = {"forward_ms": ms_fwd,
                                "forward_backward_ms": ms_grad}
    far = {}
    for leaf, p, q in zip(("x_out", "dx", "dphi", "db", "dalpha"),
                          got["plain"], got["fused"]):
        far[leaf] = [float(jnp.max(jnp.abs(p - q))),
                     float(jnp.max(jnp.abs(p)))]
    result["fused_from_plain_abs_and_scale"] = far
    print(json.dumps({k: v for k, v in result.items() if k != "kernels"}),
          flush=True)
    if not args.rehearse:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    worst = max(a / s for a, s in far.values())
    return 0 if worst < 1e-4 else 1


if __name__ == "__main__":
    sys.exit(main())
