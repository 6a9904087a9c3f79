"""The system under test for dense encoder training: ``executor.Trainer``
driving the repo's ``Ernie`` one compiled step per dispatch, fed by the
device prefetcher as ``Trainer.train_from_dataset`` feeds it.

Program surface held on to (all public): ``paddle_tpu.seed``,
``optimizer.Adam/SGD``, ``nn.functional.cross_entropy``,
``executor.{Trainer, make_train_step}`` (``train_step``, ``.state``),
``models.ernie.{Ernie, ErnieConfig}``, ``data.prefetcher.device_prefetch``.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List

import numpy as np

#: ``Trainer.train_from_dataset(prefetch_depth=2)``
PREFETCH_DEPTH = 2


def _model_cfg(cfg):
    from paddle_tpu.models.ernie import ErnieConfig

    return ErnieConfig(vocab_size=cfg["vocab_size"],
                       hidden_size=cfg["hidden_size"],
                       num_heads=cfg["num_attention_heads"],
                       ffn_size=cfg["intermediate_size"],
                       num_layers=cfg["num_hidden_layers"],
                       max_seq_len=cfg["max_position_embeddings"])


class DenseLmSystem:
    unit = "tokens"
    steps_per_dispatch = 1
    table_rows = None

    def __init__(self, cell, seed, devices, sizes, gen, spans) -> None:
        import paddle_tpu as pt
        from paddle_tpu import nn, optimizer
        from paddle_tpu.executor import Trainer
        from paddle_tpu.models.ernie import Ernie

        cfg = self.cfg = cell.config
        self.seed, self.spans = seed, spans
        self.check_sequences = sizes["check_sequences"]
        self.seq = int(cell.traffic["seq_len"])
        assert self.seq <= cfg["max_position_embeddings"]
        B = self.batch = sizes["batch_per_chip"] * len(devices)
        self.units_per_dispatch = B * self.seq
        data = gen.generate(cell.traffic, seed, vocab=cfg["vocab_size"],
                            batches=sizes["host_dispatches"], batch=B)
        self.host_items = list(zip(data["ids"], data["labels"]))
        pt.seed(seed)
        self.model = Ernie(_model_cfg(cfg))
        self.trainer = Trainer(
            self.model, optimizer.Adam(learning_rate=cfg["learning_rate"]),
            nn.functional.cross_entropy, amp=cfg["amp"])

    def feeder(self):
        from paddle_tpu.data.prefetcher import device_prefetch

        return device_prefetch(itertools.cycle(self.host_items),
                               depth=PREFETCH_DEPTH)

    def dispatch(self, item):
        return (self.trainer.train_step(item[0], item[1]),)

    def outcomes(self, handles):
        """(steps whose loss is not finite, the loss of each dispatch)."""
        import jax

        losses = [float(h[0]) for h in jax.device_get(handles)]
        return sum(not np.isfinite(x) for x in losses), losses

    def compiled_text(self) -> str:
        return ""        # no per-layer metric of this family reads the HLO

    def check_state(self) -> Dict[str, Any]:
        """Parameters after the window are finite."""
        import jax
        import jax.numpy as jnp

        ok = bool(jax.jit(lambda t: jnp.all(jnp.stack(
            [jnp.all(jnp.isfinite(x)) for x in jax.tree_util.tree_leaves(t)])
        ))(self.trainer.state["params"]))
        return {"ok": ok, "steps_counted": int(self.trainer.global_step)}

    def check_reference(self, reference) -> Dict[str, Any]:
        """The configured step (same ``amp``, same attention choice) on a
        seeded sample of sequences at full width and depth, against the
        plain reference: loss and every gradient leaf. The step runs
        SGD(lr=1), so gradient = parameters before - parameters after."""
        import jax
        import jax.numpy as jnp

        from paddle_tpu import nn, optimizer
        from paddle_tpu.executor import make_train_step

        cfg = self.cfg
        n = self.check_sequences
        rng = np.random.default_rng(self.seed + 3)
        ids = rng.integers(0, cfg["vocab_size"], (n, self.seq), dtype=np.int32)
        labels = rng.integers(0, cfg["vocab_size"], (n, self.seq),
                              dtype=np.int32)
        state = self.trainer.state
        opt = optimizer.SGD(learning_rate=1.0)
        step = make_train_step(self.model, opt, nn.functional.cross_entropy,
                               donate=False, amp=cfg["amp"])
        new_state, _, loss = step(state, opt.init(state["params"]),
                                  jax.random.key(0), (jnp.asarray(ids),),
                                  (jnp.asarray(labels),))
        before = {k: np.asarray(v) for k, v in state["params"].items()}
        grads = {k: before[k] - np.asarray(v)
                 for k, v in new_state["params"].items()}
        ref = reference.loss_and_grads(
            before, ids, labels, layers=cfg["num_hidden_layers"],
            heads=cfg["num_attention_heads"])
        return reference.compare({"loss": float(loss), "grads": grads}, ref)

    def finish(self, flush: bool) -> Dict[str, Any]:
        return {"ok": True}


def build(cell, seed: int, devices: List[Any], rehearse: bool, gen,
          spans: Dict[str, float]) -> DenseLmSystem:
    return DenseLmSystem(cell, seed, devices, cell.sizes, gen, spans)
