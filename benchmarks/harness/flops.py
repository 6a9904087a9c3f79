"""Operations the algorithm needs, from shapes alone.

Model FLOPs are those the forward and backward passes REQUIRE: nothing a
program recomputes counts, and gathers, norms and softmax count as zero.
A matmul of [m, k] x [k, n] is 2*m*k*n; backward is twice forward."""

from __future__ import annotations

from typing import Mapping


def encoder_train_flops_per_token(cfg: Mapping[str, int], seq: int) -> float:
    """Forward + backward FLOPs per token of a BERT/ERNIE-1.0 style encoder
    with a full-vocabulary output head, bidirectional attention over
    ``seq`` positions.

    Per layer and token, forward: QKV 2*h*3h, attention scores 2*seq*h and
    weighted values 2*seq*h (every head attends to every position: seq
    keys x head_dim x heads = seq*h multiply-adds each), output projection
    2*h*h, feed-forward 2*h*f twice. Once per token: the head 2*h*vocab.
    Embedding lookups are gathers (zero)."""
    h = cfg["hidden_size"]
    f = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    vocab = cfg["vocab_size"]
    per_layer = 2 * h * 3 * h + 4 * seq * h + 2 * h * h + 4 * h * f
    forward = layers * per_layer + 2 * h * vocab
    return 3.0 * forward
