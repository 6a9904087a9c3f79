"""The three flash-attention kernels on the chip, one call at a time: the
measurement behind the operand dtype of ``ops/flash_attention.py``
(``PERF.md`` section 5, PR 27).

    python3 tools/flash_bench.py [--against DIR] [--calls 20] [--rehearse]
                                 [--out chiprun_out/flash_bench.json]

At the dense cells' attention shapes — ``[32, 512, 12, 64]``
bidirectional (``ernie_base_seq512``), ``[2, 4096, 16, 128]`` causal
(``olmoe_1b7b_seq4096``) and q, k ``[2, 4096, 32, 192]`` with v
``[2, 4096, 32, 128]`` causal (``joyai_flash_seq4096``: latent attention,
the value side narrower than q.k), float32 in and out as the models call
it — one
jitted value-and-gradient of ``flash_attention`` is run ``--calls`` times
under the device profiler. Per kernel (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``, found in the trace by name): milliseconds a call,
beside the two floors of a v5e computed from what the kernel is HANDED —
the bytes of every operand and result of its ``pallas_call`` as the
jaxpr states them (minor dimension padded to 128 lanes already), each
counted once, over 819 GB/s (``bytes_ms``; ``bytes_reread_ms`` counts a
block again for every grid step that fetches it anew: k and v once a q
block in ``flash_fwd`` and ``flash_bwd_dq``, q, do and the statistics once
a k block in ``flash_bwd_dkv``), and 2 FLOP a multiply-add of its matmuls
over the blocks the causal mask leaves, over 197 TFLOP/s (``flop_ms``).
``roofline_share`` = the larger floor over the measured time: of what the
kernel is HANDED (192 padded to 256 lanes, whole blocks on the diagonal).
``required_share`` is the benchmark's own count
(``benchmarks/harness/flops_mla.flash_kernel_floor``, what the
``flash_*_roofline_share`` metrics read: logical widths, the positions the
mask leaves, each operand once) over the same time. Beside
them ``layout_ms``: every other device operation of the call (the
transposes, pads, converts and slices round the kernels, delta and the
statistics).

``--against DIR`` measures the tree at DIR (a ``git archive`` of the
parent, say ``.archive/parent``) the same way, DIR first and last, each
tree in a process of its own, and prints both beside each other. Because
the bytes are read off each tree's own jaxpr, the table follows whatever
formulation the tree has. A builder's tool, not a metric: needs a TPU
(``--rehearse``: tiny shapes on the CPU, kernels interpreted, no times).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.flops_mla import KERNEL_MATMULS as MATMULS  # noqa: E402
from harness.kernels import KERNEL_RE as _KERNEL_RE  # noqa: E402

#: (name, q's and k's [B, L, H, D], causal, v's width): the attention calls
#: of the dense cells
SHAPES = (("ernie_base_seq512", (32, 512, 12, 64), False, 64),
          ("olmoe_1b7b_seq4096", (2, 4096, 16, 128), True, 128),
          ("joyai_flash_seq4096", (2, 4096, 32, 192), True, 128))
REHEARSAL = (("rehearsal", (1, 1024, 1, 24), True, 16),)


def handed(step, args):
    """{kernel: {"grid", "operands": [(dtype, shape)], "results": [...]}}
    of every ``pallas_call`` in the jaxpr of ``step(*args)``: the array
    operands (the scalar-prefetch vector left out) and the results."""
    import jax

    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                avals = lambda vs: [(v.aval.dtype, tuple(v.aval.shape))
                                    for v in vs if v.aval.ndim == 3]
                found[eqn.params["name"]] = {
                    "grid": tuple(eqn.params["grid_mapping"].grid),
                    "operands": avals(eqn.invars),
                    "results": avals(eqn.outvars)}
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(step)(*args).jaxpr)
    return found


def floors(name, call, causal, peaks):
    """Bytes and FLOP of one kernel call from its shapes, as milliseconds
    at the device's peaks."""
    import numpy as np

    nbytes = lambda a: int(np.prod(a[1])) * np.dtype(a[0]).itemsize
    once = sum(nbytes(a) for a in call["operands"] + call["results"])
    # Grids: fwd and dq (BH, q blocks, k blocks), dkv (BH, k blocks, q
    # blocks). An operand whose block follows the innermost axis (k and v,
    # operands 1 and 2, in fwd and dq; all the others in dkv) is fetched
    # anew at every grid step — masked-out steps too: the pipeline fetches
    # before the body decides — unless that axis has one block.
    BH, outer, inner = call["grid"]
    dkv = name == "flash_bwd_dkv"
    reread = sum(nbytes(a) for a in call["results"])
    for n, a in enumerate(call["operands"]):
        follows_inner = (n in (1, 2)) != dkv
        reread += nbytes(a) * (outer if follows_inner and inner > 1 else 1)
    nq, nk = (inner, outer) if dkv else (outer, inner)
    (_, (_, Lq, D)), (_, (_, Lk, _)), (_, (_, _, Dv)) = call["operands"][:3]
    bq, bk = Lq // nq, Lk // nk
    pairs = sum(1 for i in range(nq) for j in range(nk)
                if not causal or i * bq + bq - 1 >= j * bk)
    n_qk, n_v = MATMULS[name]
    flop = 2.0 * BH * pairs * bq * bk * (n_qk * D + n_v * Dv)
    return {"bytes": once, "bytes_reread": reread, "flop": flop,
            "bytes_ms": once / peaks["hbm_bytes_per_s"] * 1e3,
            "bytes_reread_ms": reread / peaks["hbm_bytes_per_s"] * 1e3,
            "flop_ms": flop / peaks["bf16_flops"] * 1e3}


def measure(args) -> dict:
    """This process's tree (``--tree``), every shape."""
    sys.path.insert(0, args.tree)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    from harness import device, flops_mla, trace

    import paddle_tpu
    from paddle_tpu.ops.flash_attention import flash_attention

    if os.path.dirname(os.path.dirname(os.path.abspath(
            paddle_tpu.__file__))) != os.path.abspath(args.tree):
        raise SystemExit(f"no paddle_tpu under {args.tree}: imported "
                         f"{paddle_tpu.__file__}")
    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, jax found {dev.platform}")
    peaks = device.peaks("TPU v5 lite" if args.rehearse else dev.device_kind)
    rec = {"tree": args.tree, "device_kind": dev.device_kind,
           "calls": args.calls, "shapes": {}}
    for cell, shape, causal, dv in (REHEARSAL if args.rehearse else SHAPES):
        keys = jax.random.split(jax.random.key(args.seed), 3)
        q, k = (jax.random.normal(kk, shape, jnp.float32) for kk in keys[:2])
        v = jax.random.normal(keys[2], shape[:-1] + (dv,), jnp.float32)
        widths = {"num_attention_heads": shape[2], "v_head_dim": dv,
                  "qk_nope_head_dim": shape[3], "qk_rope_head_dim": 0}

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        try:
            calls = handed(step, (q, k, v))
        except (TypeError, ValueError) as e:
            # a tree from before PR 30 has one width for q, k and v
            rec["shapes"][cell] = {"error": f"{type(e).__name__}: {e}"[:200]}
            print(json.dumps({cell: rec["shapes"][cell]}), flush=True)
            continue
        jax.block_until_ready(step(q, k, v))
        jax.block_until_ready(step(q, k, v))
        trace_dir = os.path.join(ROOT, ".bench_out", "flash_bench")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t = time.perf_counter()
        try:
            out = None
            for _ in range(args.calls):
                out = step(q, k, v)
            jax.block_until_ready(out)
        finally:
            wall_ms = (time.perf_counter() - t) / args.calls * 1e3
            jax.profiler.stop_trace()
        events, _ = trace.load_events(trace.find_xplane(trace_dir))
        ops = [e for e in events if e["line"] == trace.OPS_LINE]
        kernel_ms = {name: 0.0 for name in MATMULS}
        other = 0.0
        for e in ops:
            m = _KERNEL_RE.match(e["name"])
            if m:
                kernel_ms[m.group(1)] += e["dur"] * 1e3 / args.calls
            else:
                other += e["dur"] * 1e3 / args.calls
        # no device line off the chip: a time is never a CPU's
        row = {"shape": list(shape), "v_dim": dv, "causal": causal,
               "wall_ms": wall_ms if ops else None,
               "layout_ms": other if ops else None, "kernels": {}}
        for name, call in calls.items():
            f = floors(name, call, causal, peaks)
            ms = kernel_ms[name] if ops else None
            need = flops_mla.flash_kernel_floor(
                name, widths, shape[0], shape[1], peaks, causal=causal)
            row["kernels"][name] = {
                "ms": ms, **f,
                "roofline_share": (max(f["bytes_ms"], f["flop_ms"]) / ms
                                   if ms else None),
                "required_ms": need["floor_s"] * 1e3,
                "required_share": need["floor_s"] * 1e3 / ms if ms else None,
                "operands": [f"{d}{list(sh)}" for d, sh in call["operands"]],
                "results": [f"{d}{list(sh)}" for d, sh in call["results"]]}
        rec["shapes"][cell] = row
        print(json.dumps({cell: row}), flush=True)
    return rec


def table(runs) -> str:
    """Markdown: one row a (cell, kernel), one column group a tree (its
    runs' times side by side; the share is of the fastest)."""
    trees = list(dict.fromkeys(r["tree"] for r in runs))
    head = ["cell", "kernel"]
    for tree in trees:
        tag = os.path.relpath(tree, ROOT)
        head += [f"{tag}: ms a call", "bytes_ms", "reread_ms", "flop_ms",
                 "roofline_share", "required_share"]
    lines = ["| " + " | ".join(head) + " |", "|" + " --- |" * len(head)]
    fmt = lambda x: "not measured" if x is None else f"{x:.3f}"
    for cell in runs[0]["shapes"]:
        if any("error" in r["shapes"][cell] for r in runs):
            lines.append(f"| {cell} | a tree cannot run this shape | "
                         + " | ".join(r["shapes"][cell].get("error", "runs")
                                      for r in runs) + " |")
            continue
        for name in list(MATMULS) + ["layout", "wall"]:
            row = [cell, name]
            for tree in trees:
                shapes = [r["shapes"][cell] for r in runs if r["tree"] == tree]
                if name in MATMULS:
                    ks = [s["kernels"][name] for s in shapes]
                    best = max(ks, key=lambda k: k["roofline_share"] or 0.0)
                    row += [" / ".join(fmt(k["ms"]) for k in ks),
                            fmt(best["bytes_ms"]),
                            fmt(best["bytes_reread_ms"]),
                            fmt(best["flop_ms"]), fmt(best["roofline_share"]),
                            fmt(best["required_share"])]
                else:
                    row += [" / ".join(fmt(s[name + "_ms"]) for s in shapes),
                            "", "", "", "", ""]
            lines.append("| " + " | ".join(row) + " |")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "flash_bench.json"))
    ap.add_argument("--against", help="a second checkout to measure the "
                    "same way (the parent's `git archive`)")
    ap.add_argument("--tree", default=ROOT, help=argparse.SUPPRESS)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        args.calls = 2
    if not args.against:
        runs = [measure(args)]
    else:
        # a chip belongs to one process at a time: this one stays off jax
        runs = []
        other = os.path.abspath(args.against)
        for tree in (other, ROOT, ROOT, other):
            part = f"{args.out}.{len(runs)}"
            cmd = [sys.executable, os.path.abspath(__file__), "--tree", tree,
                   "--out", part, "--calls", str(args.calls),
                   "--seed", str(args.seed)]
            subprocess.run(cmd + (["--rehearse"] if args.rehearse else []),
                           check=True)
            with open(part) as fh:
                runs += json.load(fh)["runs"]
            os.remove(part)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump({"runs": runs}, fh, indent=1)
    print(table(runs), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
