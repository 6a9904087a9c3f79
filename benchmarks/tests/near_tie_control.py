"""The upper reading of ``near_tie_excess`` at the OLMoE cell's own size: the
check run on a system that takes, at ONE token whose 8th and 9th router
probabilities lie within ``gap``, the nearest expert OUTSIDE the tie (the
reference's (k+2)-th in place of its k-th) — the least any wrong expert at a
near-tie can read. It has to come out as not correct.

    python3 benchmarks/tests/near_tie_control.py --seed N [--steps 40]
                                                 [--rehearse]

Builds the cell's system as ``run.py`` does, trains ``--steps`` dispatches,
plants the fault in what the float32 step hands to the comparison and prints
the check's ``f32_routing`` and ``f32``. A builder's tool: full widths need
the TPU (``--rehearse``: the cell's tiny sizes on the CPU). Exit 0 when the
check refused the fault.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))


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
    import numpy as np

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
    system.trainer.opt_state = None      # room for the reference's gradients
    reference = cell.reference()
    k = cell.config["num_experts_per_tok"]
    ids, labels = system.check_items
    own = reference.loss_and_grads(system.trainer.state["params"], ids,
                                   labels, cell.config)
    del own["grads"]
    near = own["gap"] <= reference.TOL["f32"]["gap"]
    ranked = -np.sort(-own["router_probs"], axis=-1)
    to_next = np.where(near, ranked[..., k - 1] - ranked[..., k + 1], np.inf)
    layer, token = np.unravel_index(np.argmin(to_next), to_next.shape)
    if not np.isfinite(to_next[layer, token]):
        print(json.dumps({"near_ties": 0}), flush=True)
        return 4                # no token to plant it at: another seed
    order = np.argsort(-own["router_probs"][layer, token], kind="stable")

    real = system._step_and_routing

    def step(amp, ids, labels):
        got = real(amp, ids, labels)
        if not amp:
            index = np.array(got["expert_index"])
            index[layer, token] = list(order[:k - 1]) + [order[k + 1]]
            got = dict(got, expert_index=index)
        return got

    system._step_and_routing = step
    out = system.check_reference(reference)
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "steps": args.steps,
        "platform": devices[0].platform, "near_ties": int(near.sum()),
        "to_the_next_expert": {
            "planted": float(to_next[layer, token]),
            "median_over_near_ties": float(np.median(to_next[near]))},
        "f32_routing": out["f32_routing"], "f32": out["f32"],
        "ok": out["ok"]}), flush=True)
    return 0 if not out["f32_routing"]["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
