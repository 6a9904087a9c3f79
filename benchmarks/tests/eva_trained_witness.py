"""Second witnesses for ``evabyte_6b5_seq8192``'s ``correct`` at the
TRAINED parameters: every gradient leaf of the check's sequence by routes
that share no kernel, each against ``configs/evabyte-6.5b.reference.py`` in
float32, after the window's number of steps and at the seed's initial
parameters.

    python3 benchmarks/tests/eva_trained_witness.py --seed N [--steps 26]
                                                    [--rehearse]

Routes (``{leaf: [largest |reference gradient|, its root-mean-square
entry — what the reference's ``GRADIENT_FLOOR`` is a floor on —, then each
route's L2 error over the leaf's L2 norm]}``):

- ``kernels_f32``: the system's float32 function through the flash kernels
  (the cell's check (i));
- ``einsum_f32``: the same with ``attn_impl = "einsum"`` — no kernel, no
  pair list;
- ``amp``: the system's function under ``amp`` (bf16 operands);
- ``reference_bf16``: the REFERENCE with its matmul operands rounded to
  bf16 — no line of the system in it;
- ``reference_reblocked``: the reference in float32 with its queries in
  blocks of another size (another order of the same sums).

Where ``einsum_f32`` and ``reference_reblocked`` read what ``kernels_f32``
reads, and ``reference_bf16`` what ``amp`` reads, the gap is the leaf's own
conditioning at that state and not the kernels'. A builder's tool: full
widths need the TPU. Writes ``chiprun_out/eva_witness_<seed>.json``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="evabyte_6b5_seq8192")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--steps", type=int, default=26,
                    help="5 warm-up dispatches and 21 of a 10 s window")
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
    adapter = spec.load_module("adapters", "causal_eva_lm")
    reference = cell.reference()
    ids, labels = system.check_items
    cfg, model = system.cfg, system.model
    losses = [float(system.trainer.train_step(*item)) for item in
              itertools.islice(itertools.cycle(system.host_items),
                               args.steps)]
    system.trainer.opt_state = None      # room for two sets of gradients

    def einsum(state, ids, labels, run={}):
        was, model.cfg.attn_impl = model.cfg.attn_impl, "einsum"
        try:
            if not run:
                run[0] = adapter.function_of(model, system.loss_fn, False)
            return run[0](state, ids, labels)
        finally:
            model.cfg.attn_impl = was

    def reblocked(state, ids, labels):
        was = reference._QUERY_BLOCK
        reference._QUERY_BLOCK, compiled = was // 2, dict(reference._COMPILED)
        reference._COMPILED.clear()
        try:
            return reference.loss_and_grads(state["params"], ids, labels, cfg)
        finally:
            reference._QUERY_BLOCK = was
            reference._COMPILED.clear()
            reference._COMPILED.update(compiled)

    routes = {
        "kernels_f32": adapter.function_of(model, system.loss_fn, False),
        "einsum_f32": einsum,
        "amp": adapter.function_of(model, system.loss_fn, True),
        "reference_bf16": lambda state, ids, labels: reference.loss_and_grads(
            state["params"], ids, labels, cfg, operand_dtype=jnp.bfloat16),
        "reference_reblocked": reblocked}

    def read(state):
        ref = reference.loss_and_grads(state["params"], ids, labels, cfg)
        table = {k: [float(jnp.max(jnp.abs(g))),
                     float(jnp.sqrt(jnp.mean(jnp.square(g))))]
                 for k, g in ref["grads"].items()}
        rest = {}
        for name, route in routes.items():
            got = route(state, ids, labels)
            for k, (_, _, norm, err, _) in reference.leaf_table(
                    got, ref).items():
                table[k].append(err / norm if norm else float(err > 0))
            rest[name] = {
                "loss_rel": abs(got["loss"] - ref["loss"]) / abs(ref["loss"]),
                "logit_rel": float(jnp.max(jnp.abs(
                    jnp.asarray(got["logits"], jnp.float32) - ref["logits"]))
                    / jnp.max(jnp.abs(ref["logits"])))}
            del got
        return {"loss": ref["loss"], "routes": list(routes), "rest": rest,
                "leaves": table}

    out = {"workload": cell.name, "seed": args.seed,
           "platform": devices[0].platform, "steps": args.steps,
           "window_losses": losses,
           "trained": read(system.trainer.state)}
    system.trainer.state = None
    out["initial"] = read(system._initial_state())
    os.makedirs("chiprun_out", exist_ok=True)
    path = f"chiprun_out/eva_witness_{args.seed}.json"
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    for state in ("trained", "initial"):
        print(json.dumps({"state": state, "loss": out[state]["loss"],
                          **out[state]["rest"]}), flush=True)
        for k, row in out[state]["leaves"].items():
            print(f"  {k:30s}" + "".join(f" {x:9.2e}" for x in row),
                  flush=True)
    print(json.dumps({"written": path}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
