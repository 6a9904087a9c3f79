"""Core runtime: flags, errors, places, mesh, profiler, numerics guard.

TPU-native replacement for the reference's L1/L2 platform layer
(``paddle/fluid/platform``, ``paddle/phi/backends``): XLA owns device
memory, streams and kernels, so what remains native here is process-wide
configuration and diagnostics, plus the mesh topology that replaces ring
registries.
"""

from . import flags as _flags  # defines core flags on import
from .enforce import (
    AlreadyExistsError,
    EnforceNotMet,
    ExecutionTimeoutError,
    InvalidArgumentError,
    NotFoundError,
    OutOfRangeError,
    PreconditionNotMetError,
    UnavailableError,
    UnimplementedError,
    enforce,
    enforce_eq,
    enforce_ge,
    enforce_gt,
    enforce_le,
    enforce_lt,
    enforce_ne,
    enforce_not_none,
)
from .flags import define_flag, flag, get_flags, set_flags
from .mesh import (
    HYBRID_AXES,
    current_mesh,
    make_hybrid_mesh,
    make_mesh,
    mesh_axis_size,
    named_sharding,
    replicated,
    use_mesh,
)
from .nan_inf import check_numerics, count_nonfinite, nan_inf_enabled
from .places import (
    CPUPlace,
    CUDAPlace,
    Place,
    TPUPlace,
    device_count,
    get_device,
    is_compiled_with_tpu,
    set_device,
)
from .profiler import (
    RecordEvent,
    host_event_stats,
    host_spans,
    reset_host_events,
)

# The bare `enforce` check function shadows the submodule name on the
# package; keep an explicit module alias for introspection/tests.
from . import enforce as _  # noqa: F401  (import executes the module)
import sys as _sys

enforce_module = _sys.modules[__name__ + ".enforce"]
