"""Compiled train/eval steps.

TPU replacement for the reference's executor stack (classic ``Executor``,
``InterpreterCore``, trainer/device-worker loops — SURVEY §3.1): instead of
interpreting a program op-by-op, the whole step (forward + backward +
optimizer update + metric math) is traced once and compiled by XLA into a
single device program. The ``Trainer`` below keeps dygraph ergonomics —
construct eagerly, call ``trainer.train_step(batch)`` — while every call
after the first runs one fused XLA executable with donated buffers (no
host round-trips inside the step).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import nn
from .core.enforce import PreconditionNotMetError
from .core.flags import flag
from .core.nan_inf import check_numerics
from .core.profiler import RecordEvent
from .optimizer import Optimizer

__all__ = ["Trainer", "make_train_step", "make_eval_step", "auxiliary_loss"]


def auxiliary_loss(buffers: Dict[str, Any]) -> Optional[jax.Array]:
    """What a model reports as auxiliary loss: the sum of every buffer
    whose own name is ``aux_loss`` (``blocks.3.ffn.aux_loss``), as its
    forward left it — each layer applies its own coefficient. None for a
    model that reports none."""
    terms = [v for name, v in buffers.items()
             if name.rsplit(".", 1)[-1] == "aux_loss"]
    return sum(terms[1:], terms[0]) if terms else None


def make_train_step(
    model: nn.Layer,
    optimizer: Optimizer,
    loss_fn: Callable[..., jax.Array],
    donate: bool = True,
    amp: bool = False,
    amp_dtype: str = "bfloat16",
):
    """Build a pure, jitted train step:

        step(state, opt_state, rng, *batch) -> (new_state, new_opt_state, loss)

    where ``state = {"params":…, "buffers":…}`` (see nn.get_state) and
    ``batch = (*inputs, *labels)`` with ``loss_fn(outputs, *labels)``.

    ``amp=True``: the step body traces under ``amp.auto_cast`` — dense
    contractions (linear/conv) run in ``amp_dtype`` with f32
    accumulation, params/grads/updates stay f32. Putting the context
    INSIDE the traced body (rather than around the first call) makes
    the mode a property of the step, immune to auto_cast's trace-time
    call-site pitfall.

    The loss differentiated is ``loss_fn``'s plus the model's
    ``auxiliary_loss`` (router losses of expert layers); the loss returned
    is ``loss_fn``'s alone. A model that reports none compiles to the step
    it always did.
    """
    from .amp import step_ctx

    def step(state, opt_state, rng, inputs, labels):
        with step_ctx(amp, amp_dtype):
            def compute_loss(params):
                out, new_state = nn.functional_call(
                    model,
                    {"params": params, "buffers": state["buffers"]},
                    *inputs,
                    rng=rng,
                    training=True,
                )
                with jax.named_scope("pt.loss"):
                    loss = loss_fn(out, *labels)
                # AMP loss scaling: grads are taken of the scaled loss;
                # the AMPOptimizer unscales them inside update
                # (amp.GradScaler)
                aux = auxiliary_loss(new_state["buffers"])
                total = loss if aux is None else loss + aux
                scaled = (optimizer.scale_loss(total, opt_state)
                          if hasattr(optimizer, "scale_loss") else total)
                return scaled, (loss, new_state["buffers"])

            (_, (loss, new_buffers)), grads = jax.value_and_grad(
                compute_loss, has_aux=True)(state["params"])
        new_params, new_opt_state = optimizer.update(grads, opt_state, state["params"])
        return {"params": new_params, "buffers": new_buffers}, new_opt_state, loss

    donate_argnums = (0, 1) if donate else ()
    return jax.jit(step, donate_argnums=donate_argnums)


def make_eval_step(model: nn.Layer, metric_fn: Optional[Callable[..., Any]] = None):
    def step(state, inputs, labels):
        out, _ = nn.functional_call(model, state, *inputs, training=False)
        if metric_fn is None:
            return out
        return metric_fn(out, *labels)

    return jax.jit(step)


def _as_tuple(x) -> Tuple:
    if isinstance(x, (tuple, list)):
        return tuple(x)
    return (x,)


class Trainer:
    """Stateful convenience wrapper over the functional step.

    Mirrors the role of the reference's device-worker train loop
    (``HogwildWorker::TrainFiles``): owns the model/optimizer state across
    steps, feeds batches, exposes loss. Parameters live on device as
    pytrees between steps; ``sync_model()`` writes them back into the
    Layer for checkpointing/state_dict interop.
    """

    def __init__(
        self,
        model: nn.Layer,
        optimizer: Optimizer,
        loss_fn: Callable[..., jax.Array],
        seed: int = 0,
        amp=False,
        amp_dtype: str = "bfloat16",
    ) -> None:
        self.model = model
        self.loss_fn = loss_fn
        # ``amp`` accepts hapi's level strings too: "O0"/False,
        # "O1"/True (bf16 contractions), "O2" (bf16 PARAM STORAGE with
        # f32 masters — optimizer auto-wrapped in MasterWeights)
        o2 = amp == "O2"
        if isinstance(amp, str):
            from .core.enforce import enforce as _enforce

            _enforce(amp in ("O0", "O1", "O2"),
                     f"amp must be bool or O0/O1/O2, got {amp!r}")
            amp = amp != "O0"
        # copy the initial state: the jitted step donates its input buffers,
        # and donating the arrays still referenced by the Layer would leave
        # the model holding deleted buffers on TPU (donation is a no-op on
        # CPU, so only hardware runs would crash)
        self.state = jax.tree_util.tree_map(jnp.array, nn.get_state(model))
        if o2:
            from .optimizer import decorate_o2

            optimizer, self.opt_state, self.state["params"] = decorate_o2(
                optimizer, self.state["params"])
        else:
            self.opt_state = optimizer.init(self.state["params"])
        self.optimizer = optimizer
        self._rng = jax.random.key(seed)
        self._train_step = make_train_step(model, optimizer, loss_fn,
                                           amp=amp, amp_dtype=amp_dtype)
        self._eval_step = make_eval_step(model)
        self.global_step = 0
        self._dump_fh = None
        self._dump_fields: Tuple[str, ...] = ()

    def set_dump_config(self, dump_path: str, fields=("loss",),
                        trainer_id: int = 0) -> None:
        """Worker debug dumps (trainer.h ParseDumpConfig / DeviceWorker
        DumpField): append selected per-step values to a per-trainer
        file. Field syntax: "loss", "param:<name>", "buffer:<name>",
        "input:<i>", "label:<i>". Disable with ``dump_path=None``."""
        if self._dump_fh is not None:
            self._dump_fh.close()
            self._dump_fh = None
        self._dump_fields = tuple(fields)
        if dump_path:
            import os

            os.makedirs(dump_path, exist_ok=True)
            self._dump_fh = open(
                f"{dump_path}/trainer-{trainer_id:03d}.dump", "a")

    def _dump(self, inputs, labels, loss) -> None:
        import numpy as np

        def fmt(v):
            a = np.asarray(v).reshape(-1)
            head = " ".join(f"{x:.6g}" for x in a[:16])
            return f"{head}{' ...' if a.size > 16 else ''}"

        for f in self._dump_fields:
            if f == "loss":
                val = loss
            elif f.startswith("param:"):
                val = self.state["params"].get(f[6:])
            elif f.startswith("buffer:"):
                val = self.state["buffers"].get(f[7:])
            elif f.startswith("input:"):
                val = inputs[int(f[6:])]
            elif f.startswith("label:"):
                val = labels[int(f[6:])]
            else:
                val = None
            if val is not None:
                self._dump_fh.write(f"{self.global_step}\t{f}\t{fmt(val)}\n")
        self._dump_fh.flush()

    def train_from_dataset(self, dataset, feed, batch_size: int = 256,
                           epochs: int = 1, prefetch_depth: int = 2,
                           drop_last: bool = True):
        """Reference ``Executor.train_from_dataset`` (executor.py:2389 →
        RunFromDataset → MultiTrainer device-worker loop): drive every
        batch of ``dataset`` (an InMemoryDataset/QueueDataset) through
        the compiled step via the async device prefetcher.

        ``feed(batch_dict) -> (inputs, labels)`` adapts the dataset's
        {slot: (values, lengths)} columns to the model. Returns the mean
        loss per epoch (list of floats). For the sparse/PS path use
        ``ps.ps_trainer.CtrPassTrainer`` (the PSGPUTrainer analogue).
        """
        import inspect

        from .data.prefetcher import device_prefetch

        # QueueDataset.batch_iter has no drop_last (streaming can't know
        # the tail in advance); pass it only where supported
        kw = ({"drop_last": drop_last}
              if "drop_last" in inspect.signature(dataset.batch_iter).parameters
              else {})

        epoch_losses = []
        for _ in range(int(epochs)):
            # device_prefetch moves array leaves to device IN the
            # producer thread — that's the transfer/compute overlap
            pf = device_prefetch(
                (feed(b) for b in dataset.batch_iter(batch_size, **kw)),
                depth=prefetch_depth)
            losses = []
            try:
                for inputs, labels in pf:
                    losses.append(self.train_step(inputs, labels))
            finally:
                pf.close()
            epoch_losses.append(
                float(jnp.mean(jnp.stack(losses))) if losses else float("nan"))
        return epoch_losses

    def train_step(self, inputs, labels) -> jax.Array:
        """Run one compiled step; returns the loss as a device array.

        The return is NOT synced to host — JAX async dispatch keeps the
        device pipeline full while the host prepares the next batch. Call
        ``float(loss)`` (or log every N steps) to materialize.
        """
        inputs, labels = _as_tuple(inputs), _as_tuple(labels)
        self._rng, sub = jax.random.split(self._rng)
        with RecordEvent("train_step"):
            self.state, self.opt_state, loss = self._train_step(
                self.state, self.opt_state, sub, inputs, labels
            )
        self.global_step += 1
        if flag("check_nan_inf"):
            check_numerics({"loss": loss}, f"step {self.global_step}")
        if self._dump_fh is not None:
            self._dump(inputs, labels, loss)
        return loss

    def compiled_text(self, inputs, labels) -> str:
        """Optimised HLO text of the train step on these arguments, with
        each instruction's ``op_name`` (the ``pt.*`` scopes of
        ``core/profiler.DEVICE_SCOPES``): what a profile's operation
        names are grouped by. A step that already ran on such arguments
        is in the compile cache, so this costs a load."""
        return self._train_step.lower(
            self.state, self.opt_state, self._rng, _as_tuple(inputs),
            _as_tuple(labels)).compile().as_text()

    def predict(self, inputs):
        inputs = _as_tuple(inputs)
        with RecordEvent("eval_step"):
            return self._eval_step(self.state, inputs, ())

    def sync_model(self) -> nn.Layer:
        """Write the live pytree state back into the Layer object."""
        nn.set_state(self.model, self.state)
        return self.model

    def state_dict(self) -> Dict[str, Any]:
        self.sync_model()
        return self.model.state_dict()
