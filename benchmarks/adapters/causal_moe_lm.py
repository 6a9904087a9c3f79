"""The system under test for sparse-expert causal language-model training:
``executor.Trainer`` driving the repo's ``Olmoe`` one compiled step per
dispatch, fed by the device prefetcher as ``Trainer.train_from_dataset``
feeds it.

Program surface held on to (all public): ``paddle_tpu.seed``,
``optimizer.AdamW/SGD``, ``nn.functional.cross_entropy``,
``nn.functional_call``, ``amp.auto_cast``,
``executor.{Trainer, make_train_step}`` (``train_step``, ``.state``,
``.opt_state``, ``sync_model``), ``models.olmoe.{Olmoe, OlmoeConfig}``
(``forward(ids, output_routing=True)``, ``cfg.attn_impl``, the buffers
``expert_counts`` and ``tokens_dropped``), ``nn.Layer`` (``_buffers``),
``data.prefetcher.device_prefetch``.
"""

from __future__ import annotations

import contextlib
import itertools
from typing import Any, Dict, List

import numpy as np

#: ``Trainer.train_from_dataset(prefetch_depth=2)``
PREFETCH_DEPTH = 2
#: learning rate of the SGD step that the check reads gradients out of:
#: gradient = (parameters before - parameters after) / lr. A large power of
#: two: the division is exact, and a weight of 1.0 (every norm) does not
#: swallow a gradient of 1e-5 in the subtraction's float32 rounding, which
#: at lr 1 would cost 6e-3 of that leaf — over the float32 check's 1e-4.
CHECK_LR = 2.0 ** 16


def _model_cfg(cfg):
    from paddle_tpu.models.olmoe import OlmoeConfig

    return OlmoeConfig(vocab_size=cfg["vocab_size"],
                       hidden_size=cfg["hidden_size"],
                       num_heads=cfg["num_attention_heads"],
                       num_layers=cfg["num_hidden_layers"],
                       num_experts=cfg["num_experts"],
                       experts_per_token=cfg["num_experts_per_tok"],
                       expert_size=cfg["intermediate_size"],
                       max_seq_len=cfg["max_position_embeddings"],
                       rope_theta=float(cfg["rope_theta"]),
                       rms_eps=cfg["rms_norm_eps"],
                       lb_coef=cfg["router_aux_loss_coef"],
                       z_coef=cfg["router_z_loss_coef"],
                       init_std=cfg["initializer_range"])


class CausalMoeLmSystem:
    unit = "tokens"
    steps_per_dispatch = 1
    table_rows = None

    def __init__(self, cell, seed, devices, sizes, gen, spans) -> None:
        import paddle_tpu as pt
        from paddle_tpu import nn, optimizer
        from paddle_tpu.executor import Trainer
        from paddle_tpu.models.olmoe import Olmoe   # a program without it
        #                                             fails here, at once

        cfg = self.cfg = cell.config
        assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
        self.seed, self.spans = seed, spans
        self.seq = int(cell.traffic["seq_len"])
        assert self.seq <= cfg["max_position_embeddings"]
        B = self.batch = sizes["batch_per_chip"] * len(devices)
        self.units_per_dispatch = B * self.seq
        # one batch more than the window cycles: the check's sequences,
        # drawn like the traffic (the same seeded permutation of the ids)
        # and never trained on
        data = gen.generate(cell.traffic, seed, vocab=cfg["vocab_size"],
                            batches=sizes["host_dispatches"] + 1, batch=B)
        self.host_items = list(zip(data["ids"][:-1], data["labels"][:-1]))
        n = sizes["check_sequences"]
        assert n <= B
        self.check_items = (data["ids"][-1][:n], data["labels"][-1][:n])
        pt.seed(seed)
        self.model = Olmoe(_model_cfg(cfg))
        self.trainer = Trainer(
            self.model,
            optimizer.AdamW(learning_rate=cfg["learning_rate"],
                            weight_decay=cfg["weight_decay"],
                            beta1=cfg["beta1"], beta2=cfg["beta2"],
                            epsilon=cfg["epsilon"]),
            nn.functional.cross_entropy, amp=cfg["amp"])
        # the Layer now names the trainer's arrays, not a second copy of
        # the parameters (2.3 GiB at full widths)
        self.trainer.sync_model()
        self.load_max_over_mean = None
        self.tokens_dropped = None

    def feeder(self):
        from paddle_tpu.data.prefetcher import device_prefetch

        return device_prefetch(itertools.cycle(self.host_items),
                               depth=PREFETCH_DEPTH)

    def dispatch(self, item):
        import jax.numpy as jnp

        loss = self.trainer.train_step(item[0], item[1])
        # the step's counters, copied: the next step is given (donated)
        # the buffers they live in
        buffers = self.trainer.state["buffers"]
        return (loss, jnp.copy(buffers["expert_counts"]),
                jnp.copy(buffers["tokens_dropped"]))

    def outcomes(self, handles):
        """(dispatches that failed, the loss of each dispatch). A dispatch
        fails on a loss that is not finite, a dropped assignment, or
        expert counts that do not sum to tokens x experts a token in every
        layer."""
        import jax

        want = self.units_per_dispatch * self.cfg["num_experts_per_tok"]
        losses, loads, failed, dropped = [], [], 0, 0
        for loss, counts, drop in jax.device_get(handles):
            losses.append(float(loss))
            dropped += int(drop)
            loads.append(float(np.max(counts.max(axis=1)
                                      / counts.mean(axis=1))))
            failed += int(not np.isfinite(loss) or int(drop) != 0
                          or not (counts.sum(axis=1) == want).all())
        self.tokens_dropped = dropped
        self.load_max_over_mean = float(np.mean(loads))
        return failed, losses

    def compiled_text(self) -> str:
        return ""        # harness/scopes.py asks the trainer itself

    def check_state(self) -> Dict[str, Any]:
        """Parameters after the window are finite."""
        import jax
        import jax.numpy as jnp

        ok = bool(jax.jit(lambda t: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(t)])
        ))(self.trainer.state["params"]))
        return {"ok": ok, "steps_counted": int(self.trainer.global_step)}

    @contextlib.contextmanager
    def _attention(self, impl: str):
        """The model's attention choice for steps traced inside (every
        sublayer reads the one ``OlmoeConfig``)."""
        was = self.model.cfg.attn_impl
        self.model.cfg.attn_impl = impl
        try:
            yield
        finally:
            self.model.cfg.attn_impl = was

    def _step_and_routing(self, amp: bool, ids, labels):
        """One train step (SGD, ``CHECK_LR``) on the parameters as they
        stand: loss, every gradient leaf (on the device) and the routers'
        record — router logits and expert index of the pass the step
        itself differentiated, left in two buffers by a layer round the
        model (another program's forward pass may name the other expert
        of a tie: PERF.md section 6, PR 43)."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu import nn, optimizer
        from paddle_tpu.executor import make_train_step

        model = self.model

        class RecordsItsRouting(nn.Layer):
            def __init__(self) -> None:
                super().__init__()
                self.model = model

            def forward(self, ids):
                logits, routes = self.model(ids, output_routing=True)
                self._buffers["router_logits"] = routes["logits"]
                self._buffers["expert_index"] = routes["index"]
                return logits

        inside = lambda tree: {"model." + k: v for k, v in tree.items()}
        state = {k: inside(v) for k, v in self.trainer.state.items()}
        opt = optimizer.SGD(learning_rate=CHECK_LR)
        step = make_train_step(RecordsItsRouting(), opt,
                               nn.functional.cross_entropy, donate=False,
                               amp=amp)
        new_state, _, loss = step(state, opt.init(state["params"]),
                                  jax.random.key(0), (jnp.asarray(ids),),
                                  (jnp.asarray(labels),))
        grads = jax.jit(lambda before, after: {
            k: (b - after["model." + k]) / CHECK_LR
            for k, b in before.items()})(
                self.trainer.state["params"], new_state["params"])
        routes = jax.device_get({k: new_state["buffers"][k] for k in
                                 ("router_logits", "expert_index")})
        del new_state
        return {"loss": float(loss), "grads": grads,
                "router_logits": routes["router_logits"],
                "expert_index": routes["expert_index"]}

    def check_reference(self, reference) -> Dict[str, Any]:
        """On seeded sequences at full widths, against the plain reference
        (``configs/olmoe-1b-7b.reference.py`` has the tolerances and their
        reasons):
        (a) the float32 step (``amp`` off, einsum attention, matmul
        precision ``highest``): router logits and top-k sets against the
        reference's own routing — equal where that is clear, inside the
        tie where its k-th and (k+1)-th probabilities lie within ``gap``
        — then loss and every gradient leaf against the reference taking
        the system's experts at those near-ties and its own everywhere
        else (one pass: the same function, whichever expert of a tie the
        system's ``top_k`` names);
        (b) the step as measured (``amp`` as configured, the attention the
        trainer used): the share of tokens whose top-k set equals the
        reference's, then loss and every gradient leaf against the
        reference GIVEN the system's own expert index.
        Each step's routing record is its OWN (``_step_and_routing``).
        The trainer is finished by now: its Adam moments are released
        first, so that the check fits beside the parameters."""
        import jax

        cfg = self.cfg
        tr = self.trainer
        tr.opt_state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding),
            tr.opt_state)       # shapes stay for ``Trainer.compiled_text``
        ids, labels = self.check_items
        params = tr.state["params"]

        with self._attention("einsum"), \
                jax.default_matmul_precision("highest"):
            got = self._step_and_routing(False, ids, labels)
        ref = reference.loss_and_grads(
            params, ids, labels, cfg, expert_index=got["expert_index"],
            within_gap=reference.TOL["f32"]["gap"])
        out = {"f32": reference.compare(got, ref, "f32"),
               "f32_routing": reference.compare_routing(got, ref, "f32")}
        del ref["grads"], got       # 2 x 2.3 GiB the next step needs
        got = self._step_and_routing(bool(cfg["amp"]), ids, labels)
        out["amp_routing"] = reference.compare_routing(got, ref, "amp")
        ref = reference.loss_and_grads(params, ids, labels, cfg,
                                       expert_index=got["expert_index"])
        out["amp"] = reference.compare(got, ref, "amp")
        out["ok"] = all(v["ok"] for v in out.values())
        out["tokens"] = int(ids.size)
        return out

    def finish(self, flush: bool) -> Dict[str, Any]:
        return {"ok": True}


def build(cell, seed: int, devices: List[Any], rehearse: bool, gen,
          spans: Dict[str, float]) -> CausalMoeLmSystem:
    return CausalMoeLmSystem(cell, seed, devices, cell.sizes, gen, spans)
