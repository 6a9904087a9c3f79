"""The upper readings behind ``smallthinker_21b_seq16384``'s ``correct``:
the five wrong programs ISSUE 44 names, each planted in the model at the
cell's own size and judged by the float32 comparison of the cell's check
(``adapters/causal_swa_moe_lm`` (i): the routers' logits, the loss and every
gradient leaf against ``configs/smallthinker-21b-a3b.reference.py``). Each
has to come out as not correct.

    python3 benchmarks/tests/swa_fault_control.py --seed N [--faults a,b]
                                                  [--rehearse]

- ``window_left_out_of_one_layer``: layer 2 attends to every earlier key;
- ``rotary_given_to_the_global_layer``: layer 0's q and k turn;
- ``silu_for_relu``: the experts' gate;
- ``router_fed_the_post_attention_stream``: the route made after the
  attention block, from its output;
- ``bf16_where_the_file_says_float32``: the same call under ``amp``.

Builds the cell's system as ``run.py`` does (set-up's router preparation
included, no window), computes the reference with its own routing once —
and once more GIVEN a program's choice of experts where the two resolve a
near-tie differently, as the check does — and compiles one float32 program
a fault. A builder's tool: full widths need
the TPU (``--rehearse``: the cell's tiny sizes on the CPU;
``tests/test_smallthinker.py`` plants the same five at a small size).
Exit 0 when every fault was refused and the sound program was not.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

FAULTS = ("none", "window_left_out_of_one_layer",
          "rotary_given_to_the_global_layer", "silu_for_relu",
          "router_fed_the_post_attention_stream",
          "bf16_where_the_file_says_float32")


@contextlib.contextmanager
def planted(system, fault: str):
    """``fault`` in ``system.model`` for the length of the block."""
    import jax

    from paddle_tpu.models import smallthinker as st
    from paddle_tpu.parallel import moe

    blocks = system.model.blocks
    undo = []

    def put(obj, name, value):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault == "window_left_out_of_one_layer":
        put(blocks[2].attn, "window", None)
    elif fault == "rotary_given_to_the_global_layer":
        put(blocks[0].attn, "rope", True)
    elif fault == "silu_for_relu":
        put(st, "held_moe", lambda *a, activation, **kw: moe.held_moe(
            *a, activation=jax.nn.silu, **kw))
    elif fault == "router_fed_the_post_attention_stream":
        def forward(self, x):
            with jax.named_scope("pt.attn"):
                x = x + self.attn(self.norm_attn(x))
            route = self.moe.route(x)
            y, route = self.moe(self.norm_ffn(x), route)
            return x + y, route

        put(st.SmallThinkerBlock, "forward", forward)
    try:
        yield
    finally:
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)


def f32_function(system, state, ids, labels, use_amp: bool):
    """``system._f32_grads_and_routing`` as the check calls it, or the
    same call under ``amp`` at the default matmul precision (Mosaic
    refuses a bf16 grouped matmul asked for at ``highest``)."""
    import jax

    from paddle_tpu import amp

    precision = contextlib.nullcontext() if use_amp \
        else jax.default_matmul_precision("highest")
    with system._attention("einsum"), system._recomputed(), precision, \
            amp.step_ctx(use_amp):
        return system._f32_grads_and_routing(state, ids, labels)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="smallthinker_21b_seq16384")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

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
    system.trainer.opt_state = None      # room for two sets of gradients
    reference = cell.reference()
    ids, labels = system.check_items
    state = system.trainer.state
    ref = reference.loss_and_grads(state["params"], ids, labels, system.cfg)
    wrong = 0
    for fault in args.faults.split(","):
        with planted(system, fault):
            got = f32_function(system, state, ids, labels,
                               fault == "bf16_where_the_file_says_float32")
        routing = reference.compare_routing(got, ref, "f32")
        against = ref
        if routing["near_ties_resolved_differently"]:
            # as the check does: this reference on the program's choices
            against = reference.loss_and_grads(
                state["params"], ids, labels, system.cfg,
                expert_index=got["expert_index"])
        verdict = reference.compare(got, against, "f32")
        del got, against
        refused = not (routing["ok"] and verdict["ok"])
        wrong += refused == (fault == "none")
        print(json.dumps({
            "fault": fault, "refused": refused,
            "logit_abs": routing["logit_abs"],
            "topk_match_where_clear": routing["topk_match_where_clear"],
            "near_ties_resolved_differently":
                routing["near_ties_resolved_differently"],
            "near_tie_excess": routing["near_tie_excess"],
            "loss_rel": verdict["loss_rel"],
            "grad_leaf_l2": verdict["grad_leaf_l2"],
            "worst_leaf_l2": verdict["worst_leaf_l2"],
            "grad_leaf_rel": verdict["grad_leaf_rel"],
            "worst_leaf": verdict["worst_leaf"],
            "tol": reference.TOL["f32"]}), flush=True)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "platform": devices[0].platform,
                      "verdicts_that_are_wrong": wrong}), flush=True)
    return 0 if wrong == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
