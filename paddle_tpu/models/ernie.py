"""ERNIE-style transformer encoder — the flagship collective-parallel model.

Reference ladder rung: "ERNIE-1.0 pretraining (Fleet collective DP)"
(/root/repo/BASELINE.json configs; reference ERNIE runs through Fleet's
meta-parallel stack: mp_layers.py TP layers, pipeline_parallel.py,
sharding). Here the whole hybrid stack is one model family:

- **TP (mp axis)**: vocab-parallel embedding + head, head-sharded
  attention (QKV column-parallel, output row-parallel), FFN
  column→row parallel — semantics of
  ``fleet/meta_parallel/parallel_layers/mp_layers.py:30-259`` and the
  ``c_embedding``/``c_softmax_with_cross_entropy`` ops.
- **CP (cp axis)**: ring attention over the sequence shard (absent in the
  reference — SURVEY §2.6 marks CP as a required TPU-first addition).
- **EP (ep axis)**: optional MoE FFN with gshard top-2 gating and
  all-to-all expert exchange (``incubate/distributed/models/moe``).
- **PP**: blocks are structurally identical so they stack into
  ``parallel.pipeline.PipelineLayer`` stages.

Convention (differs from parallel/mp_layers.py, which builds per-rank
shards): parameters here are created at **global** shapes; the forward
derives per-rank extents from the *actual* array shapes, so the same
layer runs serially (eager/single chip) and inside ``shard_map`` where
the in_specs from :func:`partition_spec` hand it local shards. That keeps
one checkpoint format (global arrays) for every parallel layout.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import nn
from ..core.enforce import enforce, enforce_eq
from ..nn.layer import Layer
from ..ops import collectives as coll
from ..ops.flash_attention import flash_attention, mxu_rounded
from ..parallel.mp_layers import _axis_active
from ..parallel.moe import top1_gate, top2_gate
from ..parallel.ring_attention import (local_attention, ring_attention,
                                       ring_flash_attention)
from .transformer import attention_impl

__all__ = ["ErnieConfig", "ErnieEmbedding", "ErnieBlock", "ErnieStage",
           "ErnieHead", "Ernie", "parallel_cross_entropy", "partition_spec"]


@dataclasses.dataclass
class ErnieConfig:
    vocab_size: int = 8192
    hidden_size: int = 256
    num_heads: int = 8
    ffn_size: int = 1024
    num_layers: int = 4
    max_seq_len: int = 512
    causal: bool = False          # False = encoder (ERNIE); True = GPT-style
    dropout: float = 0.0
    # MoE: 0 = dense FFN in every block
    num_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_gate: str = "gshard"      # gshard=top2, switch=top1
    # mesh axis names (None disables that parallelism even under shard_map)
    mp_axis: Optional[str] = "mp"
    cp_axis: Optional[str] = "cp"
    ep_axis: Optional[str] = "ep"
    # attention impl: "auto" = Pallas flash kernel on TPU, einsum elsewhere
    attn_impl: str = "auto"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _take_rows(table: jax.Array, ids: jax.Array, total_rows: int,
               axis: Optional[str]) -> jax.Array:
    """Row lookup on a (possibly) row-sharded table: each rank owns rows
    [rank*per, (rank+1)*per); out-of-range ids contribute zeros; partials
    summed over the axis (c_embedding_op semantics)."""
    if not _axis_active(axis) or table.shape[0] == total_rows:
        return jnp.take(table, ids, axis=0)
    per = table.shape[0]
    start = lax.axis_index(axis) * per
    local = ids - start
    ok = (local >= 0) & (local < per)
    out = jnp.take(table, jnp.clip(local, 0, per - 1), axis=0)
    out = jnp.where(ok[..., None], out, 0.0)
    return lax.psum(out, axis)


def parallel_cross_entropy(logits: jax.Array, labels: jax.Array,
                           vocab_size: int, axis: Optional[str] = "mp",
                           pinned_vjp: bool = False) -> jax.Array:
    """Per-token CE over vocab-sharded logits (c_softmax_with_cross_entropy
    semantics; see parallel/mp_layers.py ParallelCrossEntropy). Works on
    full logits too (serial path).

    ``pinned_vjp``: the two differentiated mp reductions use the
    identity-VJP psum (``coll.psum_replicated``). REQUIRED inside a
    ``check_vma=False`` shard_map where all cross-rank reductions are
    explicit (hybrid's step): there a plain psum transposes into
    another psum and would scale the logits gradient — and everything
    upstream — by the mp size (the exact constant-×mp gradient error
    test_hybrid_grads_match_serial pins down). Under the default
    ``check_vma=True`` both forms give the same gradient."""
    per = logits.shape[-1]
    if not _axis_active(axis) or per == vocab_size:
        return nn.functional.cross_entropy(logits, labels, reduction="none")
    psum = coll.psum_replicated if pinned_vjp else lax.psum
    start = lax.axis_index(axis) * per
    local_max = lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    gmax = lax.pmax(local_max, axis)
    lse = jnp.log(psum(
        jnp.sum(jnp.exp(logits - gmax), axis=-1, keepdims=True), axis)) + gmax
    local = labels - start
    ok = (local >= 0) & (local < per)
    picked = jnp.take_along_axis(logits, jnp.clip(local, 0, per - 1)[..., None], axis=-1)[..., 0]
    picked = psum(jnp.where(ok, picked, 0.0), axis)
    return lse[..., 0] - picked


class ErnieEmbedding(Layer):
    """Token (vocab-parallel over mp) + position embeddings, LN, dropout.
    Position ids are offset by the cp rank's sequence-shard start."""

    def __init__(self, cfg: ErnieConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.create_parameter(
            "word_emb", (cfg.vocab_size, h),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) * (1.0 / np.sqrt(h)))
        self.create_parameter(
            "pos_emb", (cfg.max_seq_len, h),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) * 0.02)
        self.ln = nn.LayerNorm(h)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, ids: jax.Array) -> jax.Array:
        cfg = self.cfg
        with jax.named_scope("pt.embed"):
            x = _take_rows(self.word_emb, ids, cfg.vocab_size, cfg.mp_axis)
            L = ids.shape[-1]
            pos = jnp.arange(L)
            if _axis_active(cfg.cp_axis):
                pos = pos + lax.axis_index(cfg.cp_axis) * L
            x = x + jnp.take(self.pos_emb, pos, axis=0)
            return self.drop(self.ln(x))


class _SelfAttention(Layer):
    """Head-sharded attention. QKV weight is column-parallel with
    head-major layout ``(h, H*3*D)`` so a contiguous mp split hands each
    rank whole heads; output projection is row-parallel with an mp psum.
    Sequence parallelism: ring attention over cp when active."""

    def __init__(self, cfg: ErnieConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h, D = cfg.hidden_size, cfg.head_dim
        s = 1.0 / np.sqrt(h)
        self.create_parameter(
            "qkv_w", (h, cfg.num_heads * 3 * D),
            initializer=lambda k, sh, d: jax.random.normal(k, sh, d) * s)
        self.create_parameter("qkv_b", (cfg.num_heads * 3 * D,),
                              init_value=np.zeros(cfg.num_heads * 3 * D, np.float32))
        self.create_parameter(
            "proj_w", (h, h),
            initializer=lambda k, sh, d: jax.random.normal(k, sh, d) * s)
        self.create_parameter("proj_b", (h,), init_value=np.zeros(h, np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        D = cfg.head_dim
        lead = x.shape[:-2]            # arbitrary leading dims
        L = x.shape[-2]
        x2 = x.reshape((-1, L, cfg.hidden_size))
        impl = attention_impl(cfg.attn_impl)
        y = x2 @ self.qkv_w + self.qkv_b            # [B, L, H_local*3*D]
        if impl == "flash":
            # the flash kernels read q, k, v in their MXU dtype: round here,
            # where QKV is written, so the split and the transposes to the
            # kernels' layout move the narrow bytes under this scope's name
            y = mxu_rounded(y)
        H_local = y.shape[-1] // (3 * D)
        y = y.reshape(y.shape[0], L, H_local, 3, D)
        q, k, v = y[..., 0, :], y[..., 1, :], y[..., 2, :]
        if _axis_active(cfg.cp_axis):
            ring = ring_flash_attention if impl == "flash" else ring_attention
            out = ring(q, k, v, axis=cfg.cp_axis, causal=cfg.causal)
        elif impl == "flash":
            out = flash_attention(q, k, v, causal=cfg.causal)
        else:
            out = local_attention(q, k, v, causal=cfg.causal)
        out = out.reshape(out.shape[0], L, H_local * D)  # local-head concat
        # row-parallel projection: proj_w sharded (h/mp, h) inside shard_map
        proj = out @ self.proj_w
        if _axis_active(cfg.mp_axis) and self.proj_w.shape[0] != cfg.hidden_size:
            proj = lax.psum(proj, cfg.mp_axis)
        proj = proj + self.proj_b
        return proj.reshape(*lead, L, cfg.hidden_size)


class _DenseFFN(Layer):
    """Column→row parallel MLP with mp psum on the way back."""

    def __init__(self, cfg: ErnieConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h, f = cfg.hidden_size, cfg.ffn_size
        self.create_parameter(
            "w_in", (h, f),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) / np.sqrt(h))
        self.create_parameter("b_in", (f,), init_value=np.zeros(f, np.float32))
        self.create_parameter(
            "w_out", (f, h),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) / np.sqrt(f))
        self.create_parameter("b_out", (h,), init_value=np.zeros(h, np.float32))

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        y = jax.nn.gelu(x @ self.w_in + self.b_in)
        y = y @ self.w_out
        if _axis_active(cfg.mp_axis) and self.w_out.shape[0] != cfg.ffn_size:
            y = lax.psum(y, cfg.mp_axis)
        return y + self.b_out


class _MoEFFN(Layer):
    """Expert-parallel FFN with global-shape expert banks ``(E, h, f)``
    sharded over ep (moe_layer.py semantics; gate math from parallel.moe).
    Tokens dispatch densely to capacity buffers, all-to-all over ep, run
    the local expert bank as one batched einsum, and return."""

    def __init__(self, cfg: ErnieConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h, f, E = cfg.hidden_size, cfg.ffn_size, cfg.num_experts
        self.create_parameter(
            "gate_w", (h, E),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) * 0.01)
        self.create_parameter(
            "w_in", (E, h, f),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) / np.sqrt(h))
        self.create_parameter(
            "w_out", (E, f, h),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) / np.sqrt(f))
        self.register_buffer("aux_loss", jnp.zeros(()))
        self.gate_fn = top2_gate if cfg.moe_gate == "gshard" else top1_gate

    def forward(self, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        E = cfg.num_experts
        lead = x.shape[:-1]
        xt = x.reshape(-1, cfg.hidden_size)          # [T, h]
        T = xt.shape[0]
        top_k = 2 if self.gate_fn is top2_gate else 1
        C = max(4, int(np.ceil(T * top_k * cfg.moe_capacity_factor / E)))
        dispatch, combine, aux = self.gate_fn(xt @ self.gate_w, C)
        self._buffers["aux_loss"] = aux
        buf = jnp.einsum("tec,td->ecd", dispatch, xt)  # [E, C, h]
        active = _axis_active(cfg.ep_axis) and self.w_in.shape[0] != E
        if active:
            buf = coll.all_to_all(buf, cfg.ep_axis, split_axis_=0, concat_axis=1)
        hmid = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, self.w_in))
        out = jnp.einsum("ecf,efd->ecd", hmid, self.w_out)
        if active:
            out = coll.all_to_all(out, cfg.ep_axis, split_axis_=1, concat_axis=0)
        y = jnp.einsum("tec,ecd->td", combine, out)
        return y.reshape(*lead, cfg.hidden_size)


class ErnieBlock(Layer):
    """Pre-LN transformer block; FFN is MoE when num_experts > 0 so every
    block (and hence every pipeline stage) is structurally identical."""

    def __init__(self, cfg: ErnieConfig) -> None:
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = _SelfAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.ffn = _MoEFFN(cfg) if cfg.num_experts > 0 else _DenseFFN(cfg)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x: jax.Array) -> jax.Array:
        # each sublayer's scope takes its pre-LN and its residual add, so
        # that the two scopes cover the block
        with jax.named_scope("pt.attn"):
            x = x + self.drop(self.attn(self.ln1(x)))
        with jax.named_scope("pt.ffn"):
            return x + self.drop(self.ffn(self.ln2(x)))


class ErnieStage(Layer):
    """A pipeline stage: k consecutive blocks (all stages identical)."""

    def __init__(self, cfg: ErnieConfig, blocks_per_stage: int) -> None:
        super().__init__()
        self.blocks = nn.LayerList([ErnieBlock(cfg) for _ in range(blocks_per_stage)])

    def forward(self, x: jax.Array) -> jax.Array:
        for b in self.blocks:
            x = b(x)
        return x


class ErnieHead(Layer):
    """Final LN + vocab projection; weight column-parallel over mp so the
    logits come out vocab-sharded, feeding parallel_cross_entropy."""

    def __init__(self, cfg: ErnieConfig) -> None:
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.ln = nn.LayerNorm(h)
        self.create_parameter(
            "w", (h, cfg.vocab_size),
            initializer=lambda k, s, d: jax.random.normal(k, s, d) / np.sqrt(h))

    def forward(self, x: jax.Array) -> jax.Array:
        with jax.named_scope("pt.head_loss"):
            return self.ln(x) @ self.w


class Ernie(Layer):
    """Whole model (serial/compile-check form): embed → blocks → head."""

    def __init__(self, cfg: ErnieConfig) -> None:
        super().__init__()
        self.cfg = cfg
        self.embed = ErnieEmbedding(cfg)
        self.blocks = nn.LayerList([ErnieBlock(cfg) for _ in range(cfg.num_layers)])
        self.head = ErnieHead(cfg)

    def forward(self, ids: jax.Array) -> jax.Array:
        x = self.embed(ids)
        for b in self.blocks:
            x = b(x)
        return self.head(x)

    def loss(self, ids: jax.Array, labels: jax.Array) -> jax.Array:
        logits = self(ids)
        with jax.named_scope("pt.head_loss"):
            ce = parallel_cross_entropy(logits, labels, self.cfg.vocab_size,
                                        self.cfg.mp_axis)
            return jnp.mean(ce)


# ---------------------------------------------------------------------------
# Partition specs: name-pattern → PartitionSpec for any Ernie state pytree.
# ---------------------------------------------------------------------------

_SPEC_RULES = {
    "word_emb": ("mp", None),
    "pos_emb": (None, None),
    "qkv_w": (None, "mp"),
    "qkv_b": ("mp",),
    "proj_w": ("mp", None),
    "gate_w": (None, None),
    "w_in": (None, "mp"),        # dense FFN; 3-D MoE bank handled by ndim
    "b_in": ("mp",),
    "w_out": ("mp", None),
    "w": (None, "mp"),           # ErnieHead vocab projection
}


def partition_spec(name: str, arr, cfg: ErnieConfig,
                   leading_pp: bool = False) -> P:
    """PartitionSpec for parameter/buffer ``name`` with value ``arr``.

    ``leading_pp``: the array is stage-stacked state (the pipeline trainer
    stacks per-stage states on a new leading axis) — dim 0 is sharded over
    ``pp`` and the rules apply to the trailing dims.
    """
    ndim = getattr(arr, "ndim", 0) - (1 if leading_pp else 0)
    base = name.rsplit(".", 1)[-1]
    dims: tuple = tuple([None] * ndim)
    if base in ("w_in", "w_out") and ndim == 3:
        dims = (cfg.ep_axis, None, None)              # MoE expert bank
    elif base in _SPEC_RULES:
        spec = _SPEC_RULES[base]
        if len(spec) == ndim:
            dims = tuple(cfg.mp_axis if a == "mp" else a for a in spec)
    if leading_pp:
        dims = ("pp",) + dims
    return P(*dims)
