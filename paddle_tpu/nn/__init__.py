"""Neural-network layer API (``paddle.nn`` analogue), functional-first."""

from . import functional
from .layer import (
    Layer,
    LayerList,
    Sequential,
    functional_call,
    get_state,
    global_seed,
    next_rng_key,
    rng_guard,
    set_state,
)
from .rnn import GRU, LSTM
from .layers import (
    AdaptiveAvgPool2D,
    AvgPool2D,
    BatchNorm1D,
    BatchNorm2D,
    BCEWithLogitsLoss,
    Conv2D,
    CrossEntropyLoss,
    Dropout,
    Embedding,
    Flatten,
    GELU,
    LayerNorm,
    Linear,
    MaxPool2D,
    MSELoss,
    ReLU,
    RMSNorm,
    Sigmoid,
    Softmax,
    Tanh,
)
