"""The system under test for a hyper-connected latent-attention expert
decoder (``xing4.0-29b-a4b``): ``executor.Trainer`` driving the repo's
``Joyai`` — under ``hc_mult`` 4, YaRN, no prediction module and every block
recomputed — one compiled step per dispatch, fed by the device prefetcher.

It IS ``adapters/causal_mla_moe_lm.py``'s system (the router balance at
set-up, the feeder, the state check, the two-sided comparison with the
reference and its stages, which form of the held dispatch ran) with what
this configuration changes stated here: how the model is built, ONE loss
(``transformer.next_token_loss``), no prediction module among the expert
layers, the logits in the float32 comparison, and the residual path's
counter ``hc_res_err`` beside the routing record — in every dispatch's
outcome and on both sides of ``correct`` — and WHEN the check's three
programs are compiled: together, ahead of the comparison
(``_compiled_together``), the two float32 ones with the compiler told to
spend the least on code that runs once (``CHECK_COMPILER_OPTIONS``).

Program surface held on to beyond that file's (all public):
``models.joyai.JoyaiConfig(hc_mult=, hc_sinkhorn_iters=, hc_eps=,
hc_clamp=, rope_scaling=, recompute=, num_mtp=0)``,
``models.transformer.next_token_loss``, the buffer ``hc_res_err``.
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List

import numpy as np

from harness import spec

_base = spec.load_module("adapters", "causal_mla_moe_lm")
CHECK_LEARNING_RATE = _base.CHECK_LEARNING_RATE
_BIAS = _base._BIAS
#: the largest ``|row sum - 1|`` or ``|column sum - 1|`` of ``H_res`` a
#: dispatch may count: ``hc_eps`` 1e-6 and float32 sums of four leave 1e-6;
#: a constraint that does not hold (a Sinkhorn that stopped early) reads
#: 1e-2 and more. A dispatch above it counts as failed.
HC_RES_ERR_LIMIT = 1e-4
#: how the check's two float32 programs (the float32 function and the
#: reference) are compiled: they run ONCE a run, so the compiler is told to
#: spend the least on their running time. For a described v5e (CPU, PR 51)
#: that compiles the float32 function in 37.5 s for 129.2 and the reference
#: in 45.4 for about 105 (170 CPU-seconds for 1250), the temporaries the
#: same 2.4-2.5 GiB; what they compute (float32, matmul precision
#: ``highest``) is not the compiler's to change. The step as measured is
#: compiled as the window's was: no option.
CHECK_COMPILER_OPTIONS = {"exec_time_optimization_effort": -1.0}


def _model_cfg(cfg):
    from paddle_tpu.models.joyai import JoyaiConfig

    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert cfg["scoring_func"] == "sigmoid" and cfg["norm_topk_prob"] \
        and cfg["topk_method"] == "noaux_tc" and cfg["moe_layer_freq"] == 1 \
        and cfg["rope_scaling"]["type"] == "yarn" \
        and cfg["num_nextn_predict_layers"] == 0
    return JoyaiConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_layers=cfg["num_hidden_layers"],
        first_dense=cfg["first_k_dense_replace"],
        dense_size=cfg["intermediate_size"], q_rank=cfg["q_lora_rank"],
        kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
        rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        num_experts=cfg["router_width"],
        experts_per_token=cfg["num_experts_per_tok"],
        expert_size=cfg["moe_intermediate_size"],
        num_shared=cfg["n_shared_experts"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scale=cfg["routed_scaling_factor"],
        held=(cfg["held_first"], cfg["n_routed_experts"]),
        num_mtp=cfg["num_nextn_predict_layers"],
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        bias_update_rate=cfg["bias_update_rate"],
        init_std=cfg["initializer_range"],
        total_layers=cfg["published"]["num_hidden_layers"],
        rope_scaling=dict(cfg["rope_scaling"]), hc_mult=cfg["hc_mult"],
        hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"], hc_eps=cfg["hc_eps"],
        hc_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                  float(cfg["mhc_h_res_clamp_max"])),
        recompute=cfg["recompute"])


class CausalMhcMlaMoeLmSystem(_base.CausalMlaMoeLmSystem):

    def __init__(self, cell, seed, devices, sizes, gen, spans) -> None:
        import paddle_tpu as pt
        from paddle_tpu import optimizer
        from paddle_tpu.executor import Trainer
        from paddle_tpu.models import joyai
        from paddle_tpu.models.transformer import next_token_loss

        cfg = self.cfg = cell.config
        model_cfg = _model_cfg(cfg)     # a program without the residual
        #                                 path fails here, at once
        self.loss_fn = next_token_loss
        self.seed, self.spans = seed, spans
        self.seq = int(cell.traffic["seq_len"])
        assert self.seq <= cfg["max_position_embeddings"]
        B = self.batch = sizes["batch_per_chip"] * len(devices)
        self.units_per_dispatch = B * self.seq
        # one batch more than the window cycles: the check's sequences,
        # drawn like the traffic and never trained on
        data = gen.generate(cell.traffic, seed, vocab=cfg["vocab_size"],
                            batches=sizes["host_dispatches"] + 1, batch=B)
        self.host_items = list(zip(data["ids"][:-1], data["labels"][:-1]))
        n = sizes["check_sequences"]
        assert n <= B
        self.check_items = (data["ids"][-1][:n], data["labels"][-1][:n])
        pt.seed(seed)
        self.model = joyai.Joyai(model_cfg)
        self.trainer = Trainer(
            self.model,
            optimizer.AdamW(learning_rate=cfg["learning_rate"],
                            weight_decay=cfg["weight_decay"],
                            beta1=cfg["beta1"], beta2=cfg["beta2"],
                            epsilon=cfg["epsilon"]),
            self.loss_fn, amp=cfg["amp"])
        # the Layer now names the trainer's arrays, not a second copy of
        # the parameters (2.8 GiB at full widths)
        self.trainer.sync_model()
        t = time.perf_counter()
        self.balance = self._balance_router()
        spans["balance_s"] = time.perf_counter() - t
        self.load_max_over_mean = None
        self.tokens_dropped = None
        self.held_assignment_share = None
        self.held_assignments_per_dispatch = None
        self.rungs_run = None
        self.hc_res_err = None
        self._ahead = {}    # the check's programs, compiled ahead

    def _bias_names(self) -> List[str]:
        cfg = self.cfg
        return [f"blocks.{i}.moe.{_BIAS}" for i in range(
            cfg["first_k_dense_replace"], cfg["num_hidden_layers"])]

    def dispatch(self, item):
        import jax.numpy as jnp

        handles = super().dispatch(item)
        return handles + (jnp.copy(
            self.trainer.state["buffers"]["hc_res_err"]),)

    def outcomes(self, handles):
        """The base's outcomes of a dispatch, and: it fails too where
        ``H_res`` is further than ``HC_RES_ERR_LIMIT`` from doubly
        stochastic (or the counter is not finite)."""
        import jax

        failed, losses = super().outcomes([h[:-1] for h in handles])
        errs = np.asarray(jax.device_get([h[-1] for h in handles]),
                          np.float64)
        self.hc_res_err = float(np.max(errs)) if len(errs) else None
        over = ~(errs <= HC_RES_ERR_LIMIT)
        print(json.dumps({"hc_res_err_max": self.hc_res_err,
                          "dispatches_over_the_limit": int(over.sum())}),
              flush=True)
        # a dispatch the base already counted may be counted again here:
        # ``failed`` is compared with 0 only
        return failed + int(over.sum()), losses

    def _f32_function(self):
        """The float32 function on a state, ONE jitted program: the
        gradients of the loss through ``nn.functional_call``, the logits,
        the routers' scores and expert index and the buffers after the
        step (biases, the rows of the form each layer ran, the largest
        ``H_res`` error)."""
        import jax

        from paddle_tpu import nn

        def both(state, ids, labels):
            def total(params):
                (logits, routes), new = nn.functional_call(
                    self.model, {"params": params,
                                 "buffers": state["buffers"]},
                    ids, output_routing=True, training=True)
                return self.loss_fn(logits, labels), (
                    logits, routes, new["buffers"])

            return jax.value_and_grad(total, has_aux=True)(state["params"])

        return jax.jit(both)

    def _f32_grads_and_routing(self, state, ids, labels):
        import jax.numpy as jnp

        run = self._ahead.pop("f32", None) or self._f32_function()
        (loss, (logits, routes, buffers)), grads = run(
            state, jnp.asarray(ids), jnp.asarray(labels))
        return dict(self._routing_record(loss, routes, buffers),
                    loss=float(loss), logits=logits, grads=grads,
                    hc_res_err=float(buffers["hc_res_err"]))

    def _measured_step(self, state, ids, labels):
        """``adapters/causal_mla_moe_lm``'s step as measured, with this
        cell's loss: (the step ``executor.make_train_step`` builds for
        ``Trainer`` — the model as configured (recomputed blocks, flash
        kernels, ``amp``) inside the layer that records its routing, AdamW
        at ``CHECK_LEARNING_RATE`` —, its arguments at the window's batch
        on the check's sequences but for the moments, the optimizer,
        AdamW's numbers)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu import optimizer
        from paddle_tpu.executor import make_train_step

        cfg = self.cfg
        reps = self.batch // ids.shape[0]
        assert reps * ids.shape[0] == self.batch
        ids_w = jnp.asarray(np.tile(ids, (reps, 1)))
        labels_w = jnp.asarray(np.tile(labels, (reps, 1)))
        hyper = {"lr": CHECK_LEARNING_RATE, "beta1": cfg["beta1"],
                 "beta2": cfg["beta2"], "eps": cfg["epsilon"],
                 "weight_decay": cfg["weight_decay"]}
        opt = optimizer.AdamW(learning_rate=hyper["lr"],
                              weight_decay=hyper["weight_decay"],
                              beta1=hyper["beta1"], beta2=hyper["beta2"],
                              epsilon=hyper["eps"])
        step = make_train_step(_base._records_its_routing(self.model), opt,
                               self.loss_fn, donate=True,
                               amp=bool(cfg["amp"]))
        inside = lambda tree: {"model." + k: v for k, v in tree.items()}
        args = ({"params": inside(state["params"]),
                 "buffers": inside(state["buffers"])},
                jax.random.key(0), (ids_w,), (labels_w,))
        return step, args, opt, hyper

    def _step_as_measured(self, reference, state, ids, labels):
        """The step as measured at zero moments; gradients read out of the
        first moment, the update held to the reference's AdamW, the routing
        and the ``H_res`` error THIS program's. ``state`` is given up to
        the step (donated)."""
        import jax

        step, (inner, key, ids_w, labels_w), opt, hyper = \
            self._measured_step(state, ids, labels)
        step = self._ahead.pop("step", None) or step
        reps = self.batch // ids.shape[0]
        before = jax.device_get(state["params"])    # host: the step takes
        #                                             the device's copy
        new_state, new_opt, total = step(
            inner, opt.init(inner["params"]), key, ids_w, labels_w)
        outside = lambda tree: {k[len("model."):]: v for k, v in tree.items()
                                if k.startswith("model.")}
        buffers = new_state["buffers"]
        got = self._routing_record(
            total, {"logits": buffers["router_logits"],
                    "index": buffers["expert_index"]}, outside(buffers))
        got["hc_res_err"] = float(buffers["model.hc_res_err"])
        # the copies of a sequence must have been routed alike
        n = ids.size
        index = got["expert_index"].reshape(
            got["expert_index"].shape[0], reps, n, -1)
        got["copies_routed_alike"] = bool((index == index[:, :1]).all())
        got["expert_index"] = index[:, 0]
        got["router_scores"] = got["router_scores"][:, :n]
        slots = new_opt["slots"]
        got["update"] = reference.compare_update(
            before, outside(new_state["params"]), outside(slots["m"]),
            outside(slots["v"]), hyper)
        got["grads"] = jax.jit(lambda m: jax.tree_util.tree_map(
            lambda x: x / (1.0 - hyper["beta1"]), m))(outside(slots["m"]))
        return got, before

    def _compiled_together(self, reference, state, ids, labels):
        """The check's three full-width programs — the step as measured,
        the float32 function, the reference — lowered here one after the
        other and each handed to a thread of its own to compile while the
        next is lowered. Compiled one after the other, as
        ``adapters/causal_mla_moe_lm._compare`` meets them, they took
        120-134 + 100-115 + 63-80 s of a run (my chip runs, PR 51: nearly
        all of it the compiler) and a whole run 372-417 s where the driver
        allows 360; at once, 224-227 s and a run 309 s (the machine's
        cores are then all busy); with ``CHECK_COMPILER_OPTIONS`` on the
        two float32 programs besides, see PERF.md section 6. What is
        compiled and from which arguments is what ``_compare`` compiled on
        its way. The reference's goes to ``reference.adopt``; the other
        two wait in ``self._ahead`` for the calls that use them."""
        import jax
        import jax.numpy as jnp
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(3) as pool:
            def compiling(lowered, options=None):
                return pool.submit(lowered.compile, compiler_options=options)

            step, (inner, key, ids_w, labels_w), opt, _ = \
                self._measured_step(state, ids, labels)
            ahead = {"step": compiling(step.lower(
                inner, jax.eval_shape(opt.init, inner["params"]), key,
                ids_w, labels_w))}  # the moments as shapes: no 5.6 GiB yet
            with self._attention("einsum"), \
                    jax.default_matmul_precision("highest"):
                ahead["f32"] = compiling(self._f32_function().lower(
                    state, jnp.asarray(ids), jnp.asarray(labels)),
                    CHECK_COMPILER_OPTIONS)
            ref = compiling(reference.lowered(
                state["params"], ids, labels, self.cfg,
                buffers=jax.device_get(state["buffers"])),
                CHECK_COMPILER_OPTIONS)
            reference.adopt(self.cfg, ref.result())
            return {name: f.result() for name, f in ahead.items()}

    def _compare(self, reference, state, ids, labels):
        t = time.perf_counter()
        self._ahead = self._compiled_together(reference, state, ids, labels)
        took = round(time.perf_counter() - t, 1)
        try:
            out = super()._compare(reference, state, ids, labels)
        finally:
            self._ahead = {}
        out["stage_s"] = {"compiled_together": took, **out["stage_s"]}
        return out


def build(cell, seed: int, devices: List[Any], rehearse: bool, gen,
          spans: Dict[str, float]) -> CausalMhcMlaMoeLmSystem:
    return CausalMhcMlaMoeLmSystem(cell, seed, devices, cell.sizes, gen,
                                   spans)
