"""Bytes the residual path of a hyper-connected decoder
(``xing4.0-29b-a4b``) REQUIRES, from the equations alone.

A sublayer's path is ``u = H_pre X``, ``y = F(N(u))``, ``X' = H_res X +
H_post y`` with the mappings made from ``X``: whatever computes it reads
the ``n`` streams ``X`` and the sublayer's result ``y`` and writes the
sublayer's input ``u`` and the streams ``X'`` — each stream-sized operand
ONCE a pass — and its backward reads ``dX'`` and ``du`` and writes ``dX``
and ``dy`` likewise: ``(2n + 2) C`` elements each way, the streams and ``u``
and ``y`` alike in the step's float32 activations. The mappings themselves
([n] + [n] + [n, n] a token), Phi, b and alpha are left out, and so is what
an implementation reads a second time (``X`` and ``y`` again for the
mappings' gradients, every pass of a block's recomputation): this is a
floor, so a share of it cannot pass 100%."""

from __future__ import annotations

from typing import Mapping

#: bytes of an element of the streams, of a sublayer's input and of its
#: result: float32 under this repo's amp (bf16 is the matmuls' operands')
ACTIVATION_BYTES = 4


def sublayer_bytes_per_token(cfg: Mapping[str, int]) -> float:
    """One sublayer, ONE direction (forward, or backward)."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return float((2 * n + 2) * c * ACTIVATION_BYTES)


def train_bytes_per_token(cfg: Mapping[str, int]) -> float:
    """Every sublayer (two a block), forward and backward."""
    return 2.0 * (2 * cfg["num_hidden_layers"]) * sublayer_bytes_per_token(cfg)
