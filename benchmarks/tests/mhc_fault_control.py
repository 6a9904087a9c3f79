"""The upper readings behind ``xing4_29b_a4b_seq4096``'s ``correct``: the
five wrong programs ISSUE 51 names and the reference in the nearest
precision below, each at the cell's own size, judged as the cell's check
judges. Each has to come out as not correct.

    python3 benchmarks/tests/mhc_fault_control.py --seed N [--faults a,b]
                                                  [--rehearse]

Planted in the program and judged by the float32 comparison of the cell's
check (``adapters/causal_mhc_mla_moe_lm``: the loss, the logits, every
gradient leaf and the ``H_res`` error against
``configs/xing4.0-29b-a4b.reference.py`` GIVEN the program's own expert
index, so that a fault is refused for its arithmetic and not for the
routing it leads to):

- ``one_sinkhorn_step``: ``hc_sinkhorn_iters`` 1 where the row says 20;
- ``h_res_transposed``: ``X'_i = sum_j H_res[j, i] X_j``;
- ``h_post_without_its_2``: ``H_post = sigmoid(.)``;
- ``mappings_in_bf16``: the projection's operands, the sigmoids and the
  Sinkhorn steps in bf16;
- ``plain_rotary``: no YaRN — ``theta^(-2i/64)`` and ``192^(-1/2)``.

And the reference itself in ``float8_e4m3fn`` against the reference in
float32, judged by the ``amp`` limits (``reference_in_float8``). ``none``
is the sound program, which has to pass.

Builds the cell's system as ``run.py`` does (no window) and compiles one
float32 program a fault. A builder's tool: full widths need the TPU
(``--rehearse``: the cell's tiny sizes on the CPU; ``tests/test_xing4.py``
plants the same at a small size). Exit 0 when every fault was refused and
the sound program was not.
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

FAULTS = ("none", "one_sinkhorn_step", "h_res_transposed",
          "h_post_without_its_2", "mappings_in_bf16", "plain_rotary",
          "reference_in_float8")


def mappings_in_bf16(x, phi, b, alpha, iters, eps, clamp, rms_eps):
    """``ops.hyper_connection.hc_mappings`` with bf16 where it says
    float32: the streams and Phi rounded for the projection, and H~, the
    sigmoids and every Sinkhorn step in bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    n, c = x.shape[-2:]
    low = jnp.bfloat16
    flat = x.reshape(*x.shape[:-2], n * c).astype(low)
    wide = flat.astype(jnp.float32)
    inv_rms = lax.rsqrt(jnp.mean(wide * wide, axis=-1, keepdims=True)
                        + rms_eps)
    z = jnp.dot(flat, phi.astype(low),
                preferred_element_type=jnp.float32) * inv_rms
    gate = alpha.astype(jnp.float32)[np.repeat(np.arange(3), (n, n, n * n))]
    z = (z * gate + b.astype(jnp.float32)).astype(low)
    m = jnp.exp(jnp.clip(z[..., 2 * n:].reshape(*z.shape[:-1], n, n),
                         clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + low(eps))
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + low(eps))
    f32 = lambda a: a.astype(jnp.float32)
    return (f32(jax.nn.sigmoid(z[..., :n])),
            f32(2 * jax.nn.sigmoid(z[..., n:2 * n])), f32(m))


@contextlib.contextmanager
def planted(model, fault: str):
    """``fault`` in the program ``model`` runs, for the length of the
    block."""
    import jax.numpy as jnp

    from paddle_tpu.models import transformer

    undo = []

    def put(obj, name, value):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    if fault == "one_sinkhorn_step":
        put(model.cfg, "hc_sinkhorn_iters", 1)
    elif fault == "h_res_transposed":
        sound = transformer.hc_scatter
        put(transformer, "hc_scatter", lambda x, y, h_post, h_res: sound(
            x, y, h_post, jnp.swapaxes(h_res, -1, -2)))
    elif fault == "h_post_without_its_2":
        sound = transformer.hc_mappings

        def halved(*args):
            h_pre, h_post, h_res = sound(*args)
            return h_pre, 0.5 * h_post, h_res

        put(transformer, "hc_mappings", halved)
    elif fault == "mappings_in_bf16":
        put(transformer, "hc_mappings", mappings_in_bf16)
    elif fault == "plain_rotary":
        put(model.cfg, "rope_scaling", None)
    else:
        assert fault == "none", fault
    try:
        yield
    finally:
        for obj, name, value in reversed(undo):
            setattr(obj, name, value)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="xing4_29b_a4b_seq4096")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS))
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
    system.trainer.opt_state = None      # room for two sets of gradients
    reference = cell.reference()
    ids, labels = system.check_items
    state = system.trainer.state
    buffers = jax.device_get(state["buffers"])
    wrong = 0
    for fault in args.faults.split(","):
        mode = "f32"
        if fault == "reference_in_float8":
            mode = "amp"
            ref = reference.loss_and_grads(state["params"], ids, labels,
                                           system.cfg, buffers=buffers)
            got = reference.loss_and_grads(
                state["params"], ids, labels, system.cfg, buffers=buffers,
                expert_index=ref["expert_index"],
                operand_dtype=jnp.float8_e4m3fn)
            got.pop("logits")        # the amp step hands out none
        else:
            with planted(system.model, fault), \
                    system._attention("einsum"), \
                    jax.default_matmul_precision("highest"):
                got = system._f32_grads_and_routing(state, ids, labels)
            ref = reference.loss_and_grads(
                state["params"], ids, labels, system.cfg, buffers=buffers,
                expert_index=got["expert_index"])
        verdict = reference.compare(got, ref, mode)
        del got, ref
        refused = not verdict["ok"]
        wrong += refused == (fault == "none")
        print(json.dumps({
            "fault": fault, "judged_by": mode, "refused": refused,
            **{k: verdict[k] for k in (
                "loss_rel", "logit_rel", "grad_leaf_rel", "worst_leaf",
                "hc_abs", "worst_leaves") if k in verdict},
            "bias_experts_wrong": verdict["bias"]["experts_wrong"],
            "tol": verdict["tol"]}), flush=True)
    print(json.dumps({"workload": cell.name, "seed": args.seed,
                      "platform": devices[0].platform,
                      "verdicts_that_are_wrong": wrong}), flush=True)
    return 0 if wrong == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
