"""The system under test for sliding-window expert-decoder training:
``executor.Trainer`` driving the repo's ``SmallThinker`` one compiled step
per dispatch, fed by the device prefetcher as
``Trainer.train_from_dataset`` feeds it — as
``adapters/causal_conv_moe_lm.py`` drives ``Lfm2``, and BY
``adapters/causal_mla_moe_lm.py``'s code wherever that knows nothing of a
model, and by ``adapters/causal_conv_moe_lm.py``'s where that knows only
``self.model`` and ``self.loss_fn``: this system is a
``CausalConvMoeLmSystem`` whose model, loss, set-up and check are its own
(the feeder, the dispatch, the window's outcomes, the state check, the form
test, the float32 function and the step as measured are inherited). Its router has no bias to
balance at set-up: ``_balance_router`` here takes gradient steps of the
router's own load-balance term on the routers alone (``assumed.routing``).

Program surface held on to (all public): ``paddle_tpu.seed``,
``optimizer.AdamW``, ``nn.functional_call``, ``amp.step_ctx``,
``executor.Trainer``
(``train_step``, ``.state``, ``.opt_state``, ``sync_model``),
``executor.make_train_step``, ``models.smallthinker.{SmallThinker,
SmallThinkerConfig, smallthinker_loss}`` (``forward(ids,
output_routing=True)``, ``cfg.attn_impl``, ``cfg.recompute``, the buffers
``expert_counts``, ``held_assignments``, ``dispatch_rung``,
``tokens_dropped``), ``parallel.moe.dispatch_ladder``,
``data.prefetcher.device_prefetch``.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List

import numpy as np

from harness import spec

_shared = spec.load_module("adapters", "causal_conv_moe_lm")
_outside_the_compile_cache = _shared._outside_the_compile_cache

#: for the check alone, the held experts' columns of the FIRST layer's
#: router are multiplied by this: their logits are then 16 times as wide
#: as the absent experts', every held expert with a positive logit
#: outranks every absent one, and about four of a token's six choices land
#: here — 4 T assignments against the buffer's 1.5 T — so that layer runs
#: the every-expert form while the others keep the buffer and ``correct``
#: holds BOTH forms to the reference in every run. (This router has no
#: bias to add to, as JoyAI's and LFM2's checks do.) The reference is given
#: the same weights; the trainer gets its own back.
CHECK_ROUTER_PAST_THE_BUFFER = 16.0
_FORCED = "blocks.0.moe.router_w"
#: gradient steps of the routers' load-balance term at set-up, and the rate
#: they are annealed from, by ``ROUTER_DECAY`` a step (``assumed.routing``)
ROUTER_STEPS = 192
ROUTER_RATE = 0.01
ROUTER_DECAY = 0.99


def _model_cfg(cfg):
    from paddle_tpu.models.smallthinker import SmallThinkerConfig

    assert cfg["norm_topk_prob"] and cfg["moe_primary_router_apply_softmax"] \
        and cfg["rope_scaling"] is None and not cfg["tie_word_embeddings"]
    return SmallThinkerConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window_layout=tuple(cfg["sliding_window_layout"]),
        rope_layout=tuple(cfg["rope_layout"]),
        sliding_window_size=cfg["sliding_window_size"],
        num_layers=cfg["num_hidden_layers"], first_layer=cfg["first_layer"],
        router_width=cfg["router_width"],
        experts_per_token=cfg["moe_num_active_primary_experts"],
        expert_size=cfg["moe_ffn_hidden_size"],
        held=(cfg["held_first"], cfg["moe_num_primary_experts"]),
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        init_std=cfg["initializer_range"],
        total_layers=cfg["published"]["num_hidden_layers"],
        recompute=cfg["recompute"])


class CausalSwaMoeLmSystem(_shared.CausalConvMoeLmSystem):
    """Inherited as they stand, from ``CausalMlaMoeLmSystem``: ``feeder``,
    ``dispatch``, ``outcomes`` (what fails a dispatch of the window),
    ``check_state``, ``_attention``, ``_ran_every_form``,
    ``compiled_text``, ``finish``; from ``CausalConvMoeLmSystem``:
    ``_f32_grads_and_routing`` (one program: the gradients of
    ``self.loss_fn`` through ``nn.functional_call`` with the routing) and
    ``_step_as_measured`` (the step ``make_train_step`` builds — model,
    loss, ``amp``, the flash kernels under both masks, AdamW at that
    file's ``CHECK_LEARNING_RATE`` 4e-4 from zero moments — on a whole
    batch of the window's size, gradients read out of the first moment,
    the update held to the reference's ``adamw_first_step``); both reach
    the model through ``self.model`` / ``self.loss_fn`` / ``self.cfg`` and
    the record through ``_routing_record``, this class's own.
    ``__init__``, ``_balance_router`` (this router has no bias to
    balance), ``check_reference`` and ``_compare`` are this model's."""

    def __init__(self, cell, seed, devices, sizes, gen, spans) -> None:
        import paddle_tpu as pt
        from paddle_tpu import optimizer
        from paddle_tpu.executor import Trainer
        from paddle_tpu.models import smallthinker  # a program without it
        #                                             fails here, at once

        # the inherited methods read the held experts and the experts a
        # token under the other configuration's keys
        cfg = self.cfg = dict(
            cell.config,
            n_routed_experts=cell.config["moe_num_primary_experts"],
            num_experts_per_tok=cell.config["moe_num_active_primary_experts"])
        self.seed, self.spans = seed, spans
        self.seq = int(cell.traffic["seq_len"])
        assert self.seq <= cfg["max_position_embeddings"]
        B = self.batch = sizes["batch_per_chip"] * len(devices)
        self.units_per_dispatch = B * self.seq
        # one batch more than the window cycles: the check's, drawn like
        # the traffic and never trained on
        data = gen.generate(cell.traffic, seed, vocab=cfg["vocab_size"],
                            batches=sizes["host_dispatches"] + 1, batch=B)
        self.host_items = list(zip(data["ids"][:-1], data["labels"][:-1]))
        self.check_items = (data["ids"][-1], data["labels"][-1])
        pt.seed(seed)
        self.model = smallthinker.SmallThinker(_model_cfg(cfg))
        self.loss_fn = smallthinker.smallthinker_loss
        self.trainer = Trainer(
            self.model,
            optimizer.AdamW(learning_rate=cfg["learning_rate"],
                            weight_decay=cfg["weight_decay"],
                            beta1=cfg["beta1"], beta2=cfg["beta2"],
                            epsilon=cfg["epsilon"]),
            self.loss_fn, amp=cfg["amp"])
        # the Layer now names the trainer's arrays, not a second copy of
        # the parameters (1.4 GiB at full widths)
        self.trainer.sync_model()
        t = time.perf_counter()
        self.balance = self._balance_router()
        spans["balance_s"] = time.perf_counter() - t
        self.load_max_over_mean = None
        self.tokens_dropped = None
        self.held_assignment_share = None
        self.held_assignments_per_dispatch = None
        self.rungs_run = None

    def _router_names(self) -> List[str]:
        return [f"blocks.{i}.moe.router_w"
                for i in range(self.cfg["num_hidden_layers"])]

    def _balance_router(self) -> Dict[str, Any]:
        """The routers as a job past its first steps has them. From random
        weights the stream entering a layer is mostly ONE vector: without
        positions the global layer's attention is the mean of its prefix,
        the same for every token, the next layer's norm and attention hand
        it on amplified (its squared length over a token's own part: 0.02,
        0.5, 8, 36 entering layers 0..3 at full widths, CPU, PR 44), and a
        router that reads the un-normed stream then sends EVERY token to
        the same six experts. Whether two of those six are among the eight
        held is the seed's luck: a layer's held share read 0.44 and 0.04
        side by side, and one layer in four left its buffer in every
        dispatch of the first seed run (my chip run, PR 44). This router has
        no bias to balance (``CausalMlaMoeLmSystem._balance_router``'s
        way), and training cures the collapse through the routers'
        weights; set-up does the same and nothing else:
        ``ROUTER_STEPS`` plain gradient steps of ``topk_route``'s
        load-balance term ``lb`` ALONE (summed over the layers; the step's
        own forward pass, ``amp`` and recomputation as configured), on the
        ROUTERS' weights alone, the rate annealed from ``ROUTER_RATE`` by
        ``ROUTER_DECAY`` a step, the window's batches taken in turn — the
        common vector differs from one 16,384-token sequence to the next
        by more than a token's own part, so no routing balanced on the
        first batch is balanced on the second, with a router's whole
        weights (busiest over mean 1.0 there, 3 to 6 on the next) or with
        64 offsets alone (1.9 against 7.0; CPU, PR 44): what set-up
        reaches is a routing fitted to the window's own batches — in one
        compiled loop, one program whatever the seed, the batches being
        an argument. 48 steps kept every layer-dispatch in its buffer but
        left the rate 0.63% apart on two seeds, 192 0.24% on three (my
        chip runs, PR 44). Returns what it reached."""
        import json

        import jax
        import jax.numpy as jnp
        from jax import lax

        from paddle_tpu import nn
        from paddle_tpu.amp import step_ctx

        cfg, tr, names = self.cfg, self.trainer, self._router_names()
        batches = jnp.asarray(np.stack([ids for ids, _ in self.host_items]))
        n = batches.shape[0]

        def lb_of(routers, state, ids):
            with step_ctx(bool(cfg["amp"])):
                (_, routes), new = nn.functional_call(
                    self.model,
                    {"params": dict(state["params"], **routers),
                     "buffers": state["buffers"]},
                    ids, output_routing=True, training=True)
            return jnp.sum(routes["lb"]), new["buffers"]["expert_counts"]

        def run(state, batches):
            def step(i, carry):
                routers, seen = carry
                ids = lax.dynamic_index_in_dim(batches, i % n, keepdims=False)
                grads, counts = jax.grad(lb_of, has_aux=True)(
                    routers, state, ids)
                rate = ROUTER_RATE * ROUTER_DECAY ** i.astype(jnp.float32)
                return ({k: w - rate * grads[k] for k, w in routers.items()},
                        lax.dynamic_update_index_in_dim(seen, counts, i % n,
                                                        0))

            seen = jnp.zeros((n,) + tr.state["buffers"]["expert_counts"].shape,
                             jnp.int32)
            return lax.fori_loop(
                0, ROUTER_STEPS, step,
                ({k: state["params"][k] for k in names}, seen))

        routers, seen = jax.jit(run)(tr.state, batches)
        tr.state["params"].update(routers)
        tr.sync_model()
        # each batch's counts at its last visit, under the routers of then
        seen = np.asarray(seen, np.float64)           # [batches, layers, E]
        first, count = cfg["held_first"], cfg["n_routed_experts"]
        held = seen[..., first:first + count].sum(-1) / seen.sum(-1)
        out = {"steps": ROUTER_STEPS,
               "load_max_over_mean": float(np.max(
                   seen.max(-1) / seen.mean(-1))),
               "held_share_least_most": [float(held.min()),
                                         float(held.max())]}
        print(json.dumps({"router_balance": out}), flush=True)
        return out

    @contextlib.contextmanager
    def _recomputed(self):
        """Every block rebuilt in the backward pass: the float32 function
        over 16,384 tokens keeps 2.4 GB a layer otherwise. Memory, not
        arithmetic."""
        was = self.model.cfg.recompute
        self.model.cfg.recompute = "blocks"
        try:
            yield
        finally:
            self.model.cfg.recompute = was

    @staticmethod
    def _routing_record(loss, routes, buffers):
        import jax

        routes = jax.device_get(routes)
        return {"loss": float(loss),
                "router_logits": routes["logits"].astype(np.float64),
                "expert_index": routes["index"],
                "rows": np.asarray(buffers["dispatch_rung"]).tolist(),
                "dropped": int(buffers["tokens_dropped"])}

    def check_reference(self, reference) -> Dict[str, Any]:
        """At full widths and the full 16,384 positions, on a seeded
        sequence the window never trained on, against the plain reference
        (``configs/smallthinker-21b-a3b.reference.py`` has the tolerances
        and their reasons; the step's rate is ``CHECK_LEARNING_RATE`` of
        ``adapters/causal_conv_moe_lm.py``, 4e-4), with the first layer's held experts made
        popular enough to leave its buffer
        (``CHECK_ROUTER_PAST_THE_BUFFER``), so that both forms of the held
        dispatch are compared:
        (i) the float32 function (``amp`` off, einsum attention under both
        masks, matmul precision ``highest``): the routers' logits, top-k
        sets where the k-th and (k+1)-th choice are clear, the loss and
        every gradient leaf against the reference's own routing;
        (ii) the step as measured (``_step_as_measured``): the share of a
        token's experts that are the reference's own, then the loss the
        step returned, every gradient leaf and the parameters and second
        moments AdamW leaves, against the reference GIVEN the step's own
        expert index.
        The trainer is finished by now: its Adam moments are released
        first, so that the check fits beside the parameters."""
        import jax

        tr = self.trainer
        tr.opt_state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            tr.opt_state)       # shapes stay for ``Trainer.compiled_text``
        ids, labels = self.check_items
        first, count = self.cfg["held_first"], self.cfg["n_routed_experts"]
        held_back = jax.device_get(tr.state["buffers"])    # the step as
        #                                 measured takes the device's copy
        own_router = jax.device_get(tr.state["params"][_FORCED])
        params = tr.state["params"].copy()      # the same kind of mapping:
        #                       the moments' tree is shaped like the trainer's
        params[_FORCED] = params[_FORCED].at[:, first:first + count].multiply(
            CHECK_ROUTER_PAST_THE_BUFFER)
        state = {"params": params, "buffers": tr.state["buffers"]}
        with _outside_the_compile_cache():
            out = self._compare(reference, state, ids, labels)
        # the trainer gets back what the step took, its own router with it
        params = out.pop("params")
        params[_FORCED] = own_router
        tr.state = jax.device_put({"params": params, "buffers": held_back})
        return out

    def _compare(self, reference, state, ids, labels):
        import jax

        cfg = self.cfg
        took, t = {}, time.perf_counter()

        def lap(name):      # seconds since the last lap, compiles included
            nonlocal t
            took[name] = round(time.perf_counter() - t, 1)
            t = time.perf_counter()

        params = state["params"]
        with self._attention("einsum"), self._recomputed(), \
                jax.default_matmul_precision("highest"):
            got = self._f32_grads_and_routing(state, ids, labels)
        lap("f32_step")
        ref = reference.loss_and_grads(params, ids, labels, cfg)
        lap("reference")
        out = {"f32_routing": reference.compare_routing(got, ref, "f32")}
        flipped = out["f32_routing"]["near_ties_resolved_differently"]
        if flipped:
            # the same function: this reference on the system's choices
            del ref["grads"]
            ref = reference.loss_and_grads(
                params, ids, labels, cfg, expert_index=got["expert_index"])
            out["f32_routing"] = dict(
                reference.compare_routing(got, ref, "f32"),
                near_ties_resolved_differently=flipped)
        out["f32"] = reference.compare(got, ref, "f32")
        rows = {"f32": got["rows"]}     # which form ran, a layer
        dropped = got["dropped"]
        del ref["grads"], got       # 2 x 1.4 GiB the next step needs
        del params
        lap("f32_compare")
        got, host_params = self._step_as_measured(reference, state, ids,
                                                  labels)
        del state
        lap("step_as_measured")
        ref = reference.loss_and_grads(host_params, ids, labels, cfg,
                                       expert_index=got["expert_index"])
        # the reference's own choice on the hidden states it computed
        out["amp_routing"] = reference.compare_routing(got, ref, "amp")
        out["amp"] = reference.compare(got, ref, "amp")
        out["update"] = got["update"]
        rows["step"] = got["rows"]
        # both forms of the held dispatch must have been compared (where
        # the buffer can overflow at all) and nothing dropped
        forms = {"rows": rows, "dropped": [dropped, got["dropped"]]}
        forms["ok"] = bool(
            self._ran_every_form(rows["f32"], ids.size)
            and self._ran_every_form(rows["step"], ids.size)
            and forms["dropped"] == [0, 0])
        out["forms"] = forms
        out["ok"] = all(v["ok"] for v in out.values())
        lap("amp_compare")
        out["stage_s"] = took
        out["tokens"] = {"f32": int(ids.size), "step": int(ids.size)}
        out["params"] = host_params
        return out


def build(cell, seed: int, devices: List[Any], rehearse: bool, gen,
          spans: Dict[str, float]) -> CausalSwaMoeLmSystem:
    return CausalSwaMoeLmSystem(cell, seed, devices, cell.sizes, gen, spans)
