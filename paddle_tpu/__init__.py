"""paddle_tpu — TPU-native distributed training framework.

A ground-up rebuild of the DistPsArch/Paddle reference's capabilities
(fleet collective/hybrid parallelism + trillion-feature parameter-server
stack) designed for TPU: JAX/XLA/pjit for compiled whole-step execution,
Pallas for hot sparse/attention kernels, XLA collectives over ICI in place
of NCCL/brpc, and C++ for host-side native components (slot parsing,
feasign sharding, host tables). See SURVEY.md for the reference map.
"""

__version__ = "0.3.0"  # round 3

from . import core, data, io, metrics, models, nn, optimizer
from .core import (
    CPUPlace,
    TPUPlace,
    get_device,
    get_flags,
    set_device,
    set_flags,
)
from .executor import Trainer, make_eval_step, make_train_step
from .nn.layer import global_seed as seed

save = io.save
load = io.load
