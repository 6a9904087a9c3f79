"""A linear layer's weight-gradient fusions on the chip, several backward
statements of ``nn.functional.linear``'s amp branch beside each other:
the measurement behind its stated backward (``PERF.md`` section 6, PR 48)
and behind ``nn.functional.lm_head`` (PR 49).

    python3 tools/linear_bwd_bench.py [--calls 10] [--out chiprun_out/linear_bwd_bench.json]
    python3 tools/linear_bwd_bench.py --cases smallthinker_head olmoe_head --variants parent tree
    python3 tools/linear_bwd_bench.py --described     # no chip: the compiler's own model
    python3 tools/linear_bwd_bench.py --rehearse      # tiny shapes on the CPU, no times

Six programs, each one jitted train step (value and gradient, AdamW of
``paddle_tpu.optimizer``, parameters and moments donated) in float32 with
bf16 matmul operands, as the decoder cells run:

- ``evabyte_block``: two decoder blocks at EvaByte's widths (hidden 4096,
  intermediate 11008, one sequence of 8192 tokens), each under
  ``jax.checkpoint`` as ``recompute="blocks"`` has them — the four
  ``[4096, 4096]`` projections round an elementwise stand-in for the
  attention kernels (``q * sigmoid(k) + v``: the kernels have their own
  backward and are not this tool's), then RMSNorm and the SwiGLU FFN with
  its residual. The first block's cotangent is the second block's backward.
- the decoder cells' heads, each behind the final RMSNorm with the
  float32 cross-entropy behind it, at the cell's (batch x length, hidden,
  vocab) — the batch as the cell's traffic has it, for a batch of two
  compiles to another program than one sequence of twice the length
  (``PERF.md`` section 6, PR 49): ``smallthinker_head`` (1 x 16384, 2560,
  18992); ``olmoe_head`` (2 x 4096, 2048, 50304); ``joyai_heads`` (2 x
  4096, 2048, 16160), ONE weight called twice as ``models/joyai.py`` calls
  it (the trunk's logits and the prediction module's, an elementwise
  stand-in for the module between them); ``lfm2_tied_head`` (4 x 4096,
  2048, 16384), the head ``embed.T`` of the table the stream was gathered
  from, so the weight's gradient is the embedding's; ``evabyte_heads`` (1
  x 8192, 4096, 2560), eight byte heads of 320 in one weight, a
  cross-entropy a head.

Each is built with the linear layer stated these ways (``VARIANTS``):

- ``parent``: ``matmul(x.astype(bf16), w.astype(bf16),
  preferred_element_type=f32)`` with the backward left to ``jax.grad`` (the
  tree before PR 48);
- ``stated``: a ``custom_vjp`` whose backward casts the cotangent once for
  both matmuls, reads the forward's own ``x16`` and leaves in float32;
- ``stated_x16_held``: the same with ``x16`` behind
  ``lax.optimization_barrier`` in the backward;
- ``parent_leaf_barrier``: ``parent`` with every gradient leaf behind a
  barrier of its own before AdamW (the optimizer's layer: the update held
  out of the matmul's fusion) — not landed, the next step's reading;
- ``tree``: this checkout's own functions under ``amp.auto_cast`` —
  ``F.linear`` in the block, ``F.lm_head`` for every head (a model whose
  head keeps ``F.linear`` runs ``parent`` there: the head's weight is
  narrower than 4096): for a weight 4096 wide on both sides, and for a
  head, ``stated_x16_held`` with ``dx`` handed on through a barrier it
  shares with ``dW`` (left free, XLA's scheduler parks the weight-gradient
  matmuls, and the bf16 operands they read, at the end of a whole
  decoder's step), for any other weight ``parent``; ``tree_is`` names the
  variant whose jaxpr it equals, if any.

Per variant: milliseconds a call of every device operation whose first
result is a weight's ``f32[in, out]`` (the weight-gradient fusions, read
from the device trace by the instruction's own text), what the compiled
text says is inside each (the convolution's operand dtypes, the fusion's
kind, its instruction count, the compiler's ``estimated_cycles``), the whole
step's device milliseconds, and the program's memory
(``memory_analysis()``: temporaries, and arguments + results - aliases +
temporaries). Beside them the floor of one such fusion on a v5e: ``2 * T *
in * out`` over 197 TFLOP/s plus AdamW's 24 bytes a parameter over 819 GB/s.
A builder's tool, not a metric: needs a TPU. ``--described`` compiles for
a described ``v5e:2x2`` device instead (the issue's study: cycles are the
compiler's model, never a time); ``--rehearse`` runs tiny shapes on the CPU.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

VARIANTS = ("parent", "stated", "stated_x16_held", "parent_leaf_barrier",
            "tree")
#: (case, (batch, length), hidden, the other width): EvaByte's block, then
#: the five decoder cells' heads (the other width is the vocabulary's), the
#: batch as the cell's configuration has it (``batch_per_chip``). The
#: rehearsal keeps the batch and the ratios and nothing of the size.
CASES = (("evabyte_block", (1, 8192), 4096, 11008),
         ("smallthinker_head", (1, 16384), 2560, 18992),
         ("olmoe_head", (2, 4096), 2048, 50304),
         ("joyai_heads", (2, 4096), 2048, 16160),
         ("lfm2_tied_head", (4, 4096), 2048, 16384),
         ("evabyte_heads", (1, 8192), 4096, 2560))
REHEARSAL = (("evabyte_block", (1, 256), 128, 384),
             ("smallthinker_head", (1, 512), 128, 640),
             ("olmoe_head", (2, 128), 128, 1536),
             ("joyai_heads", (2, 128), 128, 512),
             ("lfm2_tied_head", (4, 128), 128, 512),
             ("evabyte_heads", (1, 256), 256, 160))
BLOCKS = 2
#: ``evabyte_heads``: byte heads in the one weight (``models/evabyte.py``)
PRED_HEADS = 8
#: ``joyai_heads``: the prediction module's loss beside the trunk's
MTP_LOSS_WEIGHT = 0.3

_FUSION_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(?[a-z0-9]+\[[0-9,]*\].*?)\s+"
    r"fusion\(.*?kind=(k\w+).*?calls=%?([\w.\-]+)")
_INSTRUCTION_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\(?([a-z0-9]+)\[[0-9,]*\]\S*\s+"
    r"([a-z][\w\-]*)\((.*)$")
_COMPUTATION_RE = re.compile(r"^\s*%?([\w.\-]+)\s+\(.*\)\s*->.*\{\s*$")
_FIRST_SHAPE_RE = re.compile(r"\(?([a-z0-9]+\[[0-9,]*\])")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_CYCLES_RE = re.compile(r'"estimated_cycles":"?(\d+)')


def computations(text: str) -> dict:
    """{name: [instruction lines]} of every computation in an HLO module's
    text."""
    out, name = {}, None
    for line in text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m and not line.lstrip().startswith("ROOT"):
            name = m.group(1)
            out[name] = []
        elif line.strip() == "}":
            name = None
        elif name is not None:
            out[name].append(line)
    return out


def fusions(text: str) -> dict:
    """{instruction name: {"result": first result shape, "kind",
    "instructions", "convolutions": [[operand, …]] with an operand as
    ``dtype:opcode`` of the instruction that makes it inside the fusion
    (``bf16:parameter`` is a buffer the convolution reads; ``bf16:fusion``
    or ``f32:convert`` a producer rebuilt a tile; a layout ``bitcast`` is
    looked through), "narrows": the converts to a type narrower than
    float32 that read the convolution's result, "round_trips": how many of
    those are widened back to float32 (a gradient rounded: the transpose
    of ``w.astype``), "estimated_cycles"}} of
    every ``fusion`` in a compiled module's text, read with what it calls."""
    comps = computations(text)
    found = {}
    for line in text.splitlines():
        m = _FUSION_RE.match(line)
        if not m:
            continue
        name, result, kind, called = m.groups()
        made = {}       # instruction -> (dtype, opcode, its operands' names)
        for inner in comps.get(called, []):
            i = _INSTRUCTION_RE.match(inner)
            if i:
                calls = _CALLS_RE.search(inner)
                opcode = i.group(3)
                if opcode == "fusion" and calls and calls.group(
                        1).startswith("bitcast_fusion"):
                    opcode = "bitcast"
                made[i.group(1)] = (i.group(2), opcode,
                                    _NAME_RE.findall(i.group(4).split(
                                        "), ")[0]))

        def source(n):
            while made[n][1] == "bitcast":
                n = made[n][2][0]
            return "%s:%s" % made[n][:2]

        convs = {n: [source(o) for o in ops]
                 for n, (_, opcode, ops) in made.items()
                 if opcode == "convolution"}
        cycles = _CYCLES_RE.search(line)
        narrows = {n: d for n, (d, opcode, ops) in made.items()
                   if opcode == "convert" and d != "f32"
                   and any(o in convs for o in ops)}
        found[name] = {
            "result": _FIRST_SHAPE_RE.match(result).group(1), "kind": kind,
            "instructions": len(made), "convolutions": list(convs.values()),
            "narrows": list(narrows.values()),
            "round_trips": sum(
                opcode == "convert" and d == "f32"
                and any(o in narrows for o in ops)
                for d, opcode, ops in made.values()),
            "estimated_cycles": int(cycles.group(1)) if cycles else None}
    return found


def weight_gradient_fusions(found: dict, weights) -> dict:
    """The fusions of ``found`` (:func:`fusions`) that hold a convolution
    and whose first result is a weight's ``f32[in,out]`` (``weights``: the
    shapes): a weight's gradient matmul, with whatever is fused round it."""
    want = {"f32[%d,%d]" % tuple(w) for w in weights}
    return {n: f for n, f in found.items()
            if f["result"] in want and f["convolutions"]}


def linear_of(variant: str, head: bool = False):
    """(linear(x, w), whether every gradient leaf goes behind a barrier);
    ``head``: the call is a model's vocabulary head."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    bf16, f32 = jnp.bfloat16, jnp.float32

    def parent(x, w):
        return jnp.matmul(x.astype(bf16), w.astype(bf16),
                          preferred_element_type=f32)

    def stated(hold: bool):
        @jax.custom_vjp
        def linear(x, w):
            return parent(x, w)

        def fwd(x, w):
            x16 = x.astype(bf16)
            return jnp.matmul(x16, w.astype(bf16),
                              preferred_element_type=f32), (x16, w)

        def bwd(res, g):
            x16, w = res
            if hold:
                x16 = lax.optimization_barrier(x16)
            g16 = g.astype(bf16)
            dx = jnp.matmul(g16, w.astype(bf16).T, preferred_element_type=f32)
            dw = jnp.matmul(x16.reshape(-1, x16.shape[-1]).T,
                            g16.reshape(-1, g16.shape[-1]),
                            preferred_element_type=f32)
            return dx, dw

        linear.defvjp(fwd, bwd)
        return linear

    def tree(x, w):
        from paddle_tpu import amp
        from paddle_tpu.nn import functional as F

        with amp.auto_cast(enable=True):
            return F.lm_head(x, w) if head else F.linear(x, w)

    if variant == "tree":
        return tree, False
    if variant.startswith("parent"):
        return parent, variant == "parent_leaf_barrier"
    return stated(variant == "stated_x16_held"), False


def build(case: str, batch, h: int, other: int, variant: str):
    """(jitted step, its arguments' shapes as a function of a key, the
    weights' shapes) of ``case`` under ``variant``; ``batch``: the input's
    (sequences, length)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu import optimizer
    from paddle_tpu.nn import functional as F

    linear, leaf_barrier = linear_of(variant, head=case != "evabyte_block")
    opt = optimizer.AdamW(learning_rate=3e-4, weight_decay=0.1, beta2=0.95)
    normal = lambda key, *shape: 0.02 * jax.random.normal(
        key, shape, jnp.float32)

    if case == "evabyte_block":
        weights = [(h, h), (h, other), (other, h)]

        def init(key):
            ks = iter(jax.random.split(key, 7 * BLOCKS + 1))
            blocks = [{"norm_attn": jnp.zeros((h,)),
                       "norm_ffn": jnp.zeros((h,)),
                       **{n: normal(next(ks), h, h)
                          for n in ("wq", "wk", "wv", "wo")},
                       "w_gate": normal(next(ks), h, other),
                       "w_up": normal(next(ks), h, other),
                       "w_down": normal(next(ks), other, h)}
                      for _ in range(BLOCKS)]
            return {"blocks": blocks}, (
                jax.random.normal(next(ks), batch + (h,), jnp.float32),)

        def block(p, x):
            u = F.rms_norm(x, 1.0 + p["norm_attn"], 1e-5)
            q, k, v = (linear(u, p[n]) for n in ("wq", "wk", "wv"))
            x = x + linear(q * jax.nn.sigmoid(k) + v, p["wo"])
            u = F.rms_norm(x, 1.0 + p["norm_ffn"], 1e-5)
            return x + linear(jax.nn.silu(linear(u, p["w_gate"]))
                              * linear(u, p["w_up"]), p["w_down"])

        def loss_fn(params, x):
            for p in params["blocks"]:
                x = jax.checkpoint(lambda x, p=p: block(p, x))(x)
            return jnp.mean(jnp.square(x))
    else:
        # a head: ``other`` is the vocabulary. The scale stands for the
        # stack below: the input's gradient has a reader, so the dx matmul
        # stays in the program
        tied = case == "lfm2_tied_head"
        # tied: the gradient is the table's, made as the head's [h, vocab]
        # where dx waits for it (``tree``) and transposed after
        weights = [(other, h), (h, other)] if tied else [(h, other)]

        def init(key):
            ks = jax.random.split(key, 3)
            params = {"scale": jnp.ones((h,)), "norm_f": jnp.ones((h,))}
            if tied:
                params["embed"] = normal(ks[0], *weights[0])
                first = jax.random.randint(ks[1], batch, 0, other)
            else:
                params["head"] = normal(ks[0], *weights[0])
                first = jax.random.normal(ks[1], batch + (h,), jnp.float32)
            if case == "joyai_heads":
                params["norm_mtp"] = jnp.ones((h,))
            labels, vocab = batch, other
            if case == "evabyte_heads":
                labels, vocab = batch + (PRED_HEADS,), other // PRED_HEADS
            return params, (first, jax.random.randint(ks[2], labels, 0,
                                                      vocab))

        def loss_fn(params, x, labels):
            w = params["embed"].T if tied else params["head"]
            if tied:
                x = jnp.take(params["embed"], x, axis=0)
            u = F.rms_norm(x * params["scale"], params["norm_f"], 1e-5)
            logits = linear(u, w)
            if case == "evabyte_heads":
                logits = logits.reshape(labels.shape + (-1,))
            loss = F.cross_entropy(logits, labels, ignore_index=-1)
            if case == "joyai_heads":
                y = F.rms_norm(jax.nn.silu(u), params["norm_mtp"], 1e-5)
                loss = loss + MTP_LOSS_WEIGHT * F.cross_entropy(
                    linear(y, w), labels, ignore_index=-1)
            return loss

    def step(params, opt_state, *batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
        if leaf_barrier:
            grads = jax.tree_util.tree_map(lax.optimization_barrier, grads)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, loss

    def arguments(key):
        params, batch = init(key)
        return (params, opt.init(params)) + batch

    return jax.jit(step, donate_argnums=(0, 1)), arguments, weights


def floor_ms(T: int, w, peaks) -> float:
    """One weight-gradient matmul with AdamW behind it, at the peaks."""
    return (2.0 * T * w[0] * w[1] / peaks["bf16_flops"]
            + 24.0 * w[0] * w[1] / peaks["hbm_bytes_per_s"]) * 1e3


def measure(args) -> dict:
    if args.rehearse or args.described:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    from harness import device, trace

    dev = jax.devices()[0]
    if args.described:
        from jax.experimental import topologies
        dev = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    elif not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, jax found {dev.platform}")
    measured = not (args.rehearse or args.described)
    peaks = device.peaks(dev.device_kind if dev.platform == "tpu"
                         else "TPU v5 lite")
    rec = {"device_kind": dev.device_kind, "described": args.described,
           "calls": args.calls, "cases": {}}
    for case, batch, h, other in (REHEARSAL if args.rehearse else CASES):
        if case not in args.cases:
            continue
        T = batch[0] * batch[1]
        rows, jaxprs = {}, {}
        for variant in args.variants:
            step, arguments, weights = build(case, batch, h, other, variant)
            shapes = jax.eval_shape(arguments, jax.random.key(0))
            if args.described:
                sharding = jax.sharding.SingleDeviceSharding(dev)
                shapes = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                   sharding=sharding), shapes)
            traced = step.trace(*shapes)
            jaxprs[variant] = str(traced.jaxpr)
            compiled = traced.lower().compile()
            found = fusions(compiled.as_text())
            m = compiled.memory_analysis()
            row = {"weights": {"f32[%d,%d]" % w: floor_ms(T, w, peaks)
                               for w in weights},
                   "temp_gib": m.temp_size_in_bytes / 2**30,
                   "live_gib": (m.argument_size_in_bytes
                                + m.output_size_in_bytes
                                - m.alias_size_in_bytes
                                + m.temp_size_in_bytes) / 2**30,
                   "fusions": weight_gradient_fusions(found, weights),
                   "estimated_cycles_all_fusions": sum(
                       f["estimated_cycles"] or 0 for f in found.values())}
            if not args.described:
                row.update(run(step, arguments, args, trace, measured))
                op_ms = row.pop("op_ms")
                for name, f in row["fusions"].items():
                    f["ms"] = op_ms.get(name) if measured else None
            rows[variant] = row
            print(json.dumps({case: {variant: row}}), flush=True)
        tree_is = [v for v in jaxprs
                   if v != "tree" and jaxprs[v] == jaxprs.get("tree")]
        rec["cases"][case] = {"tokens": T, "batch": list(batch),
                              "variants": rows, "tree_is": tree_is}
    return rec


def run(step, arguments, args, trace, measured: bool) -> dict:
    """Two warm calls, then ``--calls`` under the device profiler: ms a
    call of every device operation by name, and of the whole step."""
    import jax

    state = arguments(jax.random.key(args.seed))
    params, opt_state, batch = state[0], state[1], state[2:]
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, *batch)
    jax.block_until_ready(loss)
    trace_dir = os.path.join(ROOT, ".bench_out", "linear_bwd_bench")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t = time.perf_counter()
    try:
        for _ in range(args.calls):
            params, opt_state, loss = step(params, opt_state, *batch)
        jax.block_until_ready(loss)
    finally:
        wall_ms = (time.perf_counter() - t) / args.calls * 1e3
        jax.profiler.stop_trace()
    if not measured:      # no device line off the chip: a time is never a CPU's
        return {"loss": float(loss), "wall_ms": None, "device_ms": None,
                "op_ms": {}, "peak_bytes_in_use": None}
    events, _ = trace.load_events(trace.find_xplane(trace_dir))
    op_ms = {}
    for e in events:
        if e["line"] == trace.OPS_LINE:
            name = trace.op_name(e["name"])
            op_ms[name] = op_ms.get(name, 0.0) + e["dur"] * 1e3 / args.calls
    top = sorted(op_ms.items(), key=lambda kv: -kv[1])[:12]
    stats = jax.devices()[0].memory_stats() or {}
    return {"loss": float(loss), "wall_ms": wall_ms,
            "device_ms": sum(op_ms.values()), "op_ms": op_ms,
            "top_ops_ms": [[n, ms] for n, ms in top],
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}


def table(rec: dict) -> str:
    """Markdown: a row a (case, weight shape), a column a variant — the ms
    of each fusion of that shape (the compiler's M cycles where
    ``--described``), then the step and the memory."""
    lines = []
    for case, c in rec["cases"].items():
        variants = list(c["variants"])
        lines += [f"### {case} (T = {c['batch'][0]} x {c['batch'][1]}; "
                  f"tree is "
                  f"{', '.join(c['tree_is']) or 'none of these'})", "",
                  "| | floor ms | " + " | ".join(variants) + " |",
                  "| --- | --- |" + " --- |" * len(variants)]
        first = c["variants"][variants[0]]
        unit = "cycles" if rec["described"] else "ms"
        for shape, floor in first["weights"].items():
            row = [f"{shape} {unit} a fusion", f"{floor:.2f}"]
            for v in variants:
                got = sorted(
                    (f["estimated_cycles"] / 1e6 if rec["described"]
                     else f["ms"]) for f in c["variants"][v]["fusions"].values()
                    if f["result"] == shape and (rec["described"]
                                                 or f["ms"] is not None))
                row.append(" / ".join(f"{x:.2f}" for x in got)
                           or "not measured")
            lines.append("| " + " | ".join(row) + " |")
            row = [f"{shape} convolution operands", ""]
            for v in variants:
                row.append(" / ".join(sorted({
                    " x ".join(cv)
                    for f in c["variants"][v]["fusions"].values()
                    if f["result"] == shape for cv in f["convolutions"]})))
            lines.append("| " + " | ".join(row) + " |")
        for key, label in (("device_ms", "step, device ms"),
                           ("estimated_cycles_all_fusions",
                            "all fusions, M cycles"),
                           ("temp_gib", "temporaries GiB"),
                           ("live_gib", "program GiB")):
            vals = [c["variants"][v].get(key) for v in variants]
            if key == "estimated_cycles_all_fusions":
                vals = [x / 1e6 if x else None for x in vals]
            lines.append("| " + " | ".join(
                [label, ""] + ["not measured" if x is None else f"{x:.3f}"
                               for x in vals]) + " |")
        lines.append("")
    return "\n".join(lines)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "linear_bwd_bench.json"))
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variants", nargs="+", default=list(VARIANTS),
                    choices=VARIANTS)
    ap.add_argument("--cases", nargs="+", default=[c[0] for c in CASES],
                    choices=[c[0] for c in CASES])
    ap.add_argument("--described", action="store_true",
                    help="compile for a described v5e, run nothing")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        args.calls = 2
    rec = measure(args)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as fh:
        json.dump(rec, fh, indent=1)
    text = table(rec)
    with open(os.path.splitext(args.out)[0] + ".md", "w") as fh:
        fh.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
