"""The second reading behind a cell's ``correct`` tolerances: the plain
reference computed in the nearest precision BELOW the one the
configuration states, judged by the reference's own ``compare``. It has
to come out as not correct, or the tolerances would let a step in that
precision pass (``PERF.md`` section 6, PR 26).

    python3 tools/reference_precision.py [--workload olmoe_1b7b_seq4096]
                                         [--seed 1] [--steps 40] [--rehearse]

Builds the cell's system as ``benchmarks/run.py`` does, trains ``--steps``
dispatches, then on the check's own sequences: the reference in float32
with the routing the float32 reference chooses, and the same function
with every matmul operand rounded to ``float8_e4m3fn`` (bf16's 8 bits of
mantissa against 3), same routing. Prints what ``compare(fp8, f32,
"amp")`` says. A builder's tool: full widths need the TPU
(``--rehearse``: the cell's tiny sizes on the CPU)."""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="olmoe_1b7b_seq4096")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from harness import spec

    cell = spec.Cell(spec.load_benchmark(), args.workload,
                     rehearse=args.rehearse)
    devices = jax.devices()[:cell.chips]
    if not args.rehearse and devices[0].platform != "tpu":
        print(f"needs a TPU, jax found {devices[0].platform}",
              file=sys.stderr)
        return 3
    system = cell.adapter().build(cell, args.seed, devices, args.rehearse,
                                  cell.generator(), {})
    feeder = system.feeder()
    for _ in range(args.steps):
        handle = system.dispatch(next(feeder))
    feeder.close()
    jax.block_until_ready(handle)
    system.trainer.opt_state = None          # room for two sets of gradients
    reference = cell.reference()
    ids, labels = system.check_items
    params = system.trainer.state["params"]
    # a reference whose router has a bias buffer is handed the trained one
    kw = ({"buffers": system.trainer.state["buffers"]} if "buffers" in
          inspect.signature(reference.loss_and_grads).parameters else {})
    ref = reference.loss_and_grads(params, ids, labels, cell.config, **kw)
    low = reference.loss_and_grads(params, ids, labels, cell.config,
                                   expert_index=ref["expert_index"],
                                   operand_dtype=jnp.float8_e4m3fn, **kw)
    verdict = reference.compare(low, ref, "amp")
    if hasattr(reference, "leaf_table"):
        print(json.dumps({"fp8_leaf_table": reference.leaf_table(low, ref)}),
              flush=True)
    print(json.dumps({"workload": cell.name, "steps": args.steps,
                      "platform": devices[0].platform,
                      "float8_e4m3fn_against_float32": verdict}), flush=True)
    return 0 if not verdict["ok"] else 1     # passing would be the fault


if __name__ == "__main__":
    sys.exit(main())
