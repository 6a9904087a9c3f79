"""The three flash-attention kernels on the chip, one call at a time: the
measurement behind the operand dtype of ``ops/flash_attention.py``
(``PERF.md`` section 5, PR 27).

    python3 tools/flash_bench.py [--against DIR] [--calls 20] [--rehearse]
                                 [--out chiprun_out/flash_bench.json]
    python3 tools/flash_bench.py --equal [--rehearse]

At the dense cells' attention shapes — ``[32, 512, 12, 64]`` bidirectional
(``ernie_base_seq512``), ``[2, 4096, 16, 128]`` causal
(``olmoe_1b7b_seq4096``) and q, k ``[2, 4096, 32, 192]`` with v ``[2, 4096,
32, 128]`` causal (``joyai_flash_seq4096``: latent attention, the value side
narrower than q.k), ``[4, 4096, 32, 64]`` causal (``lfm2_8b_a1b_seq4096``: k
and v as the model repeats them to its 32 query heads) and ``[1, 16384, 28,
128]`` causal twice, global and under a 4096-key ``window``
(``smallthinker_21b_seq16384``'s two kinds of layer: k and v repeated to 28
heads), float32 in and out as the models call it — one jitted
value-and-gradient of ``flash_attention`` is run ``--calls`` times under the
device profiler. Per kernel (``flash_fwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``, found in the trace by name): milliseconds a call and
``us_a_pair``, that time over the grid steps of the call (``BH`` x the pairs
of a list, or x the rectangle's steps: the grid as the jaxpr states it),
beside the two floors of a v5e computed from what the kernel is HANDED — the
bytes of every operand and result of its ``pallas_call`` as the jaxpr states
them (minor dimension padded to 128 lanes already), each counted once, over
819 GB/s (``bytes_ms``; ``bytes_reread_ms`` counts a block again for every
grid step that fetches it anew: k and v once a q block in ``flash_fwd`` and
``flash_bwd_dq``, q, do and the statistics once a k block in
``flash_bwd_dkv``), and 2 FLOP a multiply-add of its matmuls over the blocks
the causal mask leaves, over 197 TFLOP/s (``flop_ms``).
``roofline_share`` = the larger floor over the measured time: of what the
kernel is HANDED (192 padded to 256 lanes, whole blocks on the diagonal).
The grid is read off the jaxpr too: a causal call's pair list
(``(BH, pairs)``, PR 41) fetches per walked pair, the rectangle per step.
``required_share`` is the benchmark's own count
(``benchmarks/harness/flops_mla.flash_kernel_floor``, what the
``flash_*_roofline_share`` metrics read: logical widths, the positions the
mask leaves, each operand once; SmallThinker's two by
``harness/flops_swa.flash_kernel_floor``, k and v at their 4 heads, as
``swa_flash_*_roofline`` reads them) over the same time. Beside
them ``layout_ms``: every other device operation of the call (the
transposes, pads, converts and slices round the kernels, delta and the
statistics).

``--against DIR`` measures the tree at DIR (a ``git archive`` of the
parent, say ``.archive/parent``) the same way, DIR first and last, each
tree in a process of its own, and prints both beside each other. Because
the bytes are read off each tree's own jaxpr, the table follows whatever
formulation the tree has. A builder's tool, not a metric: needs a TPU
(``--rehearse``: tiny shapes on the CPU, kernels interpreted, no times).

``--equal`` times nothing: it shows ON THE CHIP that a causal call's pair
list (``ops/flash_attention._causal_pairs``) gives what the rectangle gives.
The rectangle is a list too — every ``nq·nk`` pair through the same tables,
the emptied ones skipped by the body's own ``pl.when`` — so the tool traces
each case three times: with ``_causal_pairs`` answering all-true (the
reference), as the program does, and with it answering None (the rectangle's
own ``(BH, nq, nk)`` grid, what the tree before PR 41 compiled), and
compares ``out``, ``lse``, ``dq``, ``dk``, ``dv`` bit for bit: at the causal
cells' window shapes AND their checks' (OLMoE's and JoyAI's checks run ONE
sequence; SmallThinker's two kinds of layer), operands from ``jax.random``
at two seeds, through ``flash_attention`` under a gradient (the step's
path), outside one (the ``routing`` program's: forward alone) and through
``flash_attention_with_lse`` with an lse cotangent. Each program runs twice
with ERNIE's bidirectional call between the runs: a block read before it is
written holds other bytes the second time. Exit 1 where anything differs.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

from harness.flops_mla import KERNEL_MATMULS as MATMULS  # noqa: E402
from harness.kernels import KERNEL_RE as _KERNEL_RE  # noqa: E402

#: (name, q's and k's [B, L, H, D], causal, v's width, the window's keys,
#: the model's key-value heads where its floor counts k and v at those —
#: SmallThinker's, by ``harness/flops_swa``; None: ``harness/flops_mla``):
#: the attention calls of the dense cells
SHAPES = (("ernie_base_seq512", (32, 512, 12, 64), False, 64, None, None),
          ("olmoe_1b7b_seq4096", (2, 4096, 16, 128), True, 128, None, None),
          ("joyai_flash_seq4096", (2, 4096, 32, 192), True, 128, None, None),
          ("lfm2_8b_a1b_seq4096", (4, 4096, 32, 64), True, 64, None, None),
          ("smallthinker_21b_seq16384 global",
           (1, 16384, 28, 128), True, 128, None, 4),
          ("smallthinker_21b_seq16384 window 4096",
           (1, 16384, 28, 128), True, 128, 4096, 4))
REHEARSAL = (("rehearsal", (1, 1024, 1, 24), True, 16, None, None),
             ("rehearsal window 300", (1, 1024, 1, 24), True, 16, 300, 1))
#: the causal calls `--equal` compares: the cells' windows and their
#: checks — (name, shape, v's width, the window's keys)
EQUAL_SHAPES = (("olmoe_1b7b_seq4096 window", (2, 4096, 16, 128), 128, None),
                ("olmoe_1b7b_seq4096 check", (1, 4096, 16, 128), 128, None),
                ("joyai_flash_seq4096 window", (2, 4096, 32, 192), 128, None),
                ("joyai_flash_seq4096 check", (1, 4096, 32, 192), 128, None),
                ("lfm2_8b_a1b_seq4096 window and check",
                 (4, 4096, 32, 64), 64, None),
                ("smallthinker_21b_seq16384 global",
                 (1, 16384, 28, 128), 128, None),
                ("smallthinker_21b_seq16384 window 4096",
                 (1, 16384, 28, 128), 128, 4096))
EQUAL_REHEARSAL = (("rehearsal", (2, 1024, 2, 24), 16, None),
                   ("rehearsal window 300", (2, 1024, 2, 24), 16, 300))


def handed(step, args):
    """{kernel: {"grid", "blocks": (bq, bk), "operands": [(dtype, shape)],
    "results": [...]}} of every ``pallas_call`` in the jaxpr of
    ``step(*args)``: the array operands (the scalar-prefetch operands left
    out), the results, and the rows of q's and k's blocks."""
    import jax

    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                avals = lambda vs: [(v.aval.dtype, tuple(v.aval.shape))
                                    for v in vs if v.aval.ndim == 3]
                grid = eqn.params["grid_mapping"]
                found[eqn.params["name"]] = {
                    "grid": tuple(grid.grid),
                    "blocks": tuple(m.block_shape[1].block_size
                                    for m in grid.block_mappings[:2]),
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
    (_, (_, Lq, D)), (_, (_, Lk, _)), (_, (_, _, Dv)) = call["operands"][:3]
    bq, bk = call["blocks"]
    nq, nk = Lq // bq, Lk // bk
    BH, steps = call["grid"][0], math.prod(call["grid"][1:])
    # a list names the pairs its mask (and window) leaves; the rectangle
    # steps through all, and a causal one multiplies on the diagonal's side
    pairs = steps if len(call["grid"]) == 2 else sum(
        1 for i in range(nq) for j in range(nk)
        if not causal or i * bq + bq - 1 >= j * bk)
    # Grids: the rectangle's — fwd and dq (BH, q blocks, k blocks), dkv (BH,
    # k blocks, q blocks) — or a pair list's (BH, pairs). An operand whose
    # block follows the run's inner index (k and v, operands 1 and 2, in
    # fwd and dq; all the others in dkv) is fetched anew at every grid step
    # of a head — in the rectangle the masked-out steps too: the pipeline
    # fetches before the body decides — unless that index has one block.
    dkv = name == "flash_bwd_dkv"
    inner = nq if dkv else nk
    reread = sum(nbytes(a) for a in call["results"])
    for n, a in enumerate(call["operands"]):
        follows_inner = (n in (1, 2)) != dkv
        reread += nbytes(a) * (steps / inner
                               if follows_inner and inner > 1 else 1)
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
    from harness import device, flops_mla, flops_swa, trace

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
    for cell, shape, causal, dv, window, kv_heads in (
            REHEARSAL if args.rehearse else SHAPES):
        keys = jax.random.split(jax.random.key(args.seed), 3)
        q, k = (jax.random.normal(kk, shape, jnp.float32) for kk in keys[:2])
        v = jax.random.normal(keys[2], shape[:-1] + (dv,), jnp.float32)
        widths = {"num_attention_heads": shape[2], "v_head_dim": dv,
                  "qk_nope_head_dim": shape[3], "qk_rope_head_dim": 0}

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           window=window) ** 2)

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
               "window": window, "wall_ms": wall_ms if ops else None,
               "layout_ms": other if ops else None, "kernels": {}}
        for name, call in calls.items():
            f = floors(name, call, causal, peaks)
            ms = kernel_ms[name] if ops else None
            if kv_heads:
                need = flops_swa.flash_kernel_floor(
                    name, {"num_attention_heads": shape[2],
                           "num_key_value_heads": kv_heads,
                           "head_dim": shape[3]},
                    shape[0], shape[1], window, peaks)
            else:
                need = flops_mla.flash_kernel_floor(
                    name, widths, shape[0], shape[1], peaks, causal=causal)
            steps = math.prod(call["grid"])
            row["kernels"][name] = {
                "ms": ms, "grid_steps": steps,
                "us_a_pair": ms * 1e3 / steps if ms else None, **f,
                "roofline_share": (max(f["bytes_ms"], f["flop_ms"]) / ms
                                   if ms else None),
                "required_ms": need["floor_s"] * 1e3,
                "required_share": need["floor_s"] * 1e3 / ms if ms else None,
                "operands": [f"{d}{list(sh)}" for d, sh in call["operands"]],
                "results": [f"{d}{list(sh)}" for d, sh in call["results"]]}
        rec["shapes"][cell] = row
        print(json.dumps({cell: row}), flush=True)
    return rec


def equal(args) -> int:
    """``--equal``: the pair list against the whole rectangle walked as a
    list and against the rectangle's own grid, bit for bit (module
    docstring). Prints one JSON line a case and returns the number of
    cases that differ."""
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import flash_attention as fa

    dev = jax.devices()[0]
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, jax found {dev.platform}")
    # every case's keywords; the loop below sets each cell's ``window``
    kw = {"block_q": 256, "block_k": 256} if args.rehearse else {}
    listed = fa._causal_pairs

    def whole(*a):
        keep = listed(*a)
        return keep if keep is None else np.ones_like(keep)

    # the first is the reference the others are held to
    walks = (("rectangle_as_list", whole), ("list", listed),
             ("rectangle_grid", lambda *a: None))

    def step(q, k, v, w):
        def loss(q, k, v):
            out = fa.flash_attention(q, k, v, causal=True, **kw)
            return jnp.sum(out ** 2), out
        (_, out), g = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return dict(zip(("out", "dq", "dk", "dv"), (out,) + g))

    def forward(q, k, v, w):
        return {"out": fa.flash_attention(q, k, v, causal=True, **kw)}

    def with_lse(q, k, v, w):
        def loss(q, k, v):
            out, lse = fa.flash_attention_with_lse(
                q, k, v, causal=True, **kw)
            return jnp.sum(out ** 2) + jnp.sum(lse * w), (out, lse)
        (_, aux), g = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return dict(zip(("out", "lse", "dq", "dk", "dv"), aux + g))

    def other_kernel():
        # ERNIE's bidirectional call: another program over the same VMEM
        ks = jax.random.split(jax.random.key(99), 3)
        shape = (2, 256, 2, 24) if args.rehearse else (32, 512, 12, 64)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)
        jax.block_until_ready(jax.grad(lambda q: jnp.sum(fa.flash_attention(
            q, k, v, **dict(kw, window=None)) ** 2))(q))

    bits = lambda a: jax.lax.bitcast_convert_type(a, jnp.uint32)
    same = lambda a, b: bool(jnp.all(bits(a) == bits(b)))
    differ = 0
    for cell, shape, dv, window in (EQUAL_REHEARSAL if args.rehearse
                                    else EQUAL_SHAPES):
        kw["window"] = window
        for seed in (args.seed, args.seed + 1):
            ks = jax.random.split(jax.random.key(seed), 4)
            q, k = (jax.random.normal(kk, shape, jnp.float32)
                    for kk in ks[:2])
            v = jax.random.normal(ks[2], shape[:-1] + (dv,), jnp.float32)
            w = jax.random.normal(ks[3], shape[:3], jnp.float32)
            for case in (step, forward, with_lse):
                row = {"cell": cell, "shape": list(shape), "v_dim": dv,
                       "window": window, "seed": seed, "case": case.__name__,
                       "device_kind": dev.device_kind, "grids": {},
                       "equal": {}}
                ref = None
                for walk, pairs in walks:
                    fa._causal_pairs = pairs
                    try:
                        # a function of its own, or jit hands back the
                        # first walk's trace
                        fn = jax.jit(lambda *a: case(*a))
                        row["grids"][walk] = {
                            n: list(c["grid"]) for n, c in
                            handed(fn, (q, k, v, w)).items()}
                        runs = [jax.block_until_ready(fn(q, k, v, w))]
                        other_kernel()
                        runs.append(jax.block_until_ready(fn(q, k, v, w)))
                    finally:
                        fa._causal_pairs = listed
                    if ref is None:
                        ref = runs[0]
                        row["finite"] = all(bool(jnp.isfinite(a).all())
                                            for a in ref.values())
                    for n, run in enumerate(runs, 1):
                        row["equal"][f"{walk}_run{n}"] = {
                            name: same(run[name], ref[name]) for name in ref}
                    del runs
                row["ok"] = row["finite"] and all(
                    all(e.values()) for e in row["equal"].values())
                differ += not row["ok"]
                print(json.dumps(row), flush=True)
    print(json.dumps({"equal_cases_that_differ": differ}), flush=True)
    return differ


def table(runs) -> str:
    """Markdown: one row a (cell, kernel), one column group a tree (its
    runs' times side by side; the share is of the fastest)."""
    trees = list(dict.fromkeys(r["tree"] for r in runs))
    head = ["cell", "kernel"]
    for tree in trees:
        tag = os.path.relpath(tree, ROOT)
        head += [f"{tag}: ms a call", "us_a_pair", "bytes_ms", "reread_ms",
                 "flop_ms", "roofline_share", "required_share"]
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
                            fmt(best["us_a_pair"]), fmt(best["bytes_ms"]),
                            fmt(best["bytes_reread_ms"]),
                            fmt(best["flop_ms"]), fmt(best["roofline_share"]),
                            fmt(best["required_share"])]
                else:
                    row += [" / ".join(fmt(s[name + "_ms"]) for s in shapes),
                            "", "", "", "", "", ""]
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
    ap.add_argument("--equal", action="store_true",
                    help="no times: the causal pair list against the whole "
                    "rectangle walked as a list, bit for bit")
    args = ap.parse_args()
    if args.equal:
        return 1 if equal(args) else 0
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
