"""The upper readings behind ``evabyte_6b5_seq8192``'s ``correct``: the
four wrong programs ISSUE 46 names and the two lower precisions, each at
the cell's own size, judged as the cell's check judges. Each has to come
out as not correct.

    python3 benchmarks/tests/eva_fault_control.py --seed N [--faults a,b]
                                                  [--steps S] [--rehearse]

Planted in the model and judged by the float32 comparison of the cell's
check (``adapters/causal_eva_lm`` (i): the loss, the eight heads' logits
and every gradient leaf against ``configs/evabyte-6.5b.reference.py`` —
on the chip through the flash kernels with float32 operands):

- ``bf16_pooling_softmax``: the chunk softmax's logits and weights in bf16;
- ``mu_dropped``: the pooled key without ``adaptive_mu_k``;
- ``summary_one_window_early``: a row reads the summaries of its OWN
  window's chunks and of those before it but the first's (the chunks of
  windows 1..n where those of 0..n-1 belong);
- ``seven_heads``: the loss over seven of the eight prediction heads;
- ``bf16_where_the_file_says_float32``: the same call under ``amp``.

And the reference itself in ``float8_e4m3fn`` against the reference in
float32, judged by the ``amp`` limits (``reference_in_float8``): the
nearest precision below the one the configuration states.

Builds the cell's system as ``run.py`` does (no window) and compiles one
float32 program a fault. ``--steps S`` trains S steps first (26: a 10 s
window and its warm-up) and judges as the check judges its TRAINED state,
the leaves under the reference's ``GRADIENT_FLOOR`` left out — there the
faults of the pooling hide in leaves that are rounding, which is what the
initial state is compared for. A builder's tool: full widths need the TPU
(``--rehearse``: the cell's tiny sizes on the CPU; ``tests/test_evabyte.py``
plants the same at a small size). Exit 0 when every fault was refused and
the sound program was not (with ``--steps``: when the sound program was
not; the faults' readings are printed).
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

FAULTS = ("none", "bf16_pooling_softmax", "mu_dropped",
          "summary_one_window_early", "seven_heads",
          "bf16_where_the_file_says_float32", "reference_in_float8")


@contextlib.contextmanager
def planted(system, fault: str):
    """``fault`` in the program ``system`` runs, for the length of the
    block."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import eva

    undo = []

    def put(obj, name, value):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    sound = eva.chunk_summaries
    if fault == "bf16_pooling_softmax":
        def pooled(k, v, phi, mu, chunk, scale):
            B, L, H, d = k.shape
            kc = k.reshape(B, L // chunk, chunk, H, d)
            vc = v.reshape(B, L // chunk, chunk, H, d)
            logits = (jnp.sum(kc * phi, axis=-1) * scale).astype(jnp.bfloat16)
            w = jax.nn.softmax(logits, axis=2).astype(jnp.float32)[..., None]
            return jnp.sum(w * kc, axis=2) + mu, jnp.sum(w * vc, axis=2)

        put(eva, "chunk_summaries", pooled)
    elif fault == "mu_dropped":
        put(eva, "chunk_summaries", lambda k, v, phi, mu, chunk, scale: sound(
            k, v, phi, jnp.zeros_like(mu), chunk, scale))
    elif fault == "summary_one_window_early":
        def with_shifted(k, v, phi, mu, window, chunk, scale):
            # the summaries of windows 1..n-1 where those of 0..n-2 belong
            seen = k.shape[1] - window
            ks, vs = sound(k[:, window:], v[:, window:], phi, mu, chunk,
                           scale)
            assert ks.shape[1] * chunk == seen
            return (jnp.concatenate([ks.astype(k.dtype), k], axis=1),
                    jnp.concatenate([vs.astype(v.dtype), v], axis=1))

        put(eva, "_with_summaries", with_shifted)
    elif fault == "seven_heads":
        loss = system.loss_fn
        put(system, "loss_fn", lambda logits, labels: loss(
            logits[:, :, :-1], labels))
    try:
        yield
    finally:
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)


def f32_function(system, state, ids, labels, use_amp: bool):
    """The float32 side of the cell's check
    (``adapters/causal_eva_lm.loss_logits_grads``: its
    ``float32_function``), or the same program under ``amp`` at the
    default precision."""
    from harness import spec

    run = spec.load_module("adapters", "causal_eva_lm").loss_logits_grads
    return run(system.model, system.loss_fn, state, ids, labels, use_amp)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="evabyte_6b5_seq8192")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--steps", type=int, default=0)
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
    for item in itertools.islice(itertools.cycle(system.host_items),
                                 args.steps):
        system.trainer.train_step(*item)
    system.trainer.opt_state = None      # room for two sets of gradients
    reference = cell.reference()
    ids, labels = system.check_items
    state = system.trainer.state
    ref = reference.loss_and_grads(state["params"], ids, labels, system.cfg)
    wrong = 0
    for fault in args.faults.split(","):
        mode = "f32"
        if fault == "reference_in_float8":
            mode = "amp"
            got = reference.loss_and_grads(
                state["params"], ids, labels, system.cfg,
                operand_dtype=jnp.float8_e4m3fn)
        else:
            with planted(system, fault):
                got = f32_function(
                    system, state, ids, labels,
                    fault == "bf16_where_the_file_says_float32")
        verdict = reference.compare(got, ref, mode, args.steps > 0)
        del got
        refused = not verdict["ok"]
        # at a trained state only the sound program's verdict is held to
        wrong += refused == (fault == "none") and (
            fault == "none" or not args.steps)
        print(json.dumps({
            "fault": fault, "judged_by": mode, "steps": args.steps,
            "refused": refused,
            **{k: verdict[k] for k in (
                "loss_rel", "logit_rel", "grad_leaf_l2", "worst_leaf_l2",
                "grad_leaf_rel", "worst_leaf")},
            "tol": reference.TOL[mode]}), flush=True)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "platform": devices[0].platform,
                      "verdicts_that_are_wrong": wrong}), flush=True)
    return 0 if wrong == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
