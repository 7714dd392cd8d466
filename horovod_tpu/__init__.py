"""horovod_tpu: a TPU-native distributed training framework with the
Horovod capability set.

Public API parity with the reference (carsonwang/horovod v0.19.1,
``horovod/torch/__init__.py`` / ``horovod/tensorflow/__init__.py``):
``init/shutdown/rank/size/local_rank/local_size``, sync+async
``allreduce/allgather/broadcast`` with handles, ``join``,
``DistributedOptimizer``, ``DistributedGradientTape``, ``Compression``,
``broadcast_parameters/optimizer_state/object`` — plus in-trace
collectives for compiled (shard_map/pjit) train steps under
:mod:`horovod_tpu.ops.collectives`.

Typical use::

    import horovod_tpu as hvd
    hvd.init()
    opt = hvd.DistributedOptimizer(optax.adam(1e-3 * hvd.size()))
    params = hvd.broadcast_parameters(params, root_rank=0)
"""

__version__ = "0.1.0"

# The first thing the package does (docs/flight-recorder.md): say when
# this process started and span the package's own import, so a slow
# start that never reached ``hvd.init()`` is in the ring too.
from horovod_tpu.runtime import flight as _flight

_flight.record_process()
_import_span = _flight.span("hvd_import")
_import_span.__enter__()      # closed at the end of this file

from horovod_tpu.common.basics import (  # noqa: E402,F401
    ccl_built,
    cross_rank,
    cross_size,
    data_mesh,
    data_parallel_size,
    ddl_built,
    gloo_built,
    gloo_enabled,
    ici_enabled,
    init,
    is_homogeneous,
    is_initialized,
    lead_device,
    local_mesh,
    local_rank,
    local_size,
    mpi_built,
    mpi_enabled,
    mpi_threads_supported,
    nccl_built,
    rank,
    shutdown,
    size,
    world_mesh,
    xla_built,
)
from horovod_tpu.ops.collectives import (  # noqa: F401
    Adasum,
    Average,
    Sum,
    grouped_quantized_allreduce,
    grouped_reducescatter,
    hierarchical_allgather,
    hierarchical_allreduce,
    quantized_allreduce,
)
from horovod_tpu.common.types import (  # noqa: F401
    HorovodTpuError,
    RanksDownError,
    StalledError,
)
from horovod_tpu.parallel.mesh import (  # noqa: F401
    hierarchical_mesh,
    make_mesh,
    parse_mesh_spec,
)
from horovod_tpu.ops import collectives  # noqa: F401  (in-trace API)
from horovod_tpu.ops.compression import Compression  # noqa: F401
from horovod_tpu.ops.eager import (  # noqa: F401
    allgather,
    allgather_async,
    allreduce,
    allreduce_,
    allreduce_async,
    allreduce_async_,
    alltoall,
    barrier,
    broadcast,
    broadcast_,
    broadcast_async,
    broadcast_async_,
    join,
    poll,
    reducescatter,
    reducescatter_async,
    synchronize,
)
from horovod_tpu.optim.distributed import (  # noqa: F401
    DistributedGradientTape,
    DistributedOptimizer,
    Zero3Params,
    allreduce_gradients,
    broadcast_global_variables,
    broadcast_object,
    broadcast_optimizer_state,
    broadcast_parameters,
    grad,
    params_from_host,
    params_to_host,
    sharded_state_specs,
    sharded_state_to_global,
    zero3_full_params,
    zero3_params_from_host,
    zero3_params_specs,
    zero3_params_to_global,
    zero3_params_to_host,
    zero3_shard_params,
)
# Cross-slice local-SGD / DiLoCo outer loop (docs/local-sgd.md):
# hvd.LocalSGD wraps DistributedOptimizer so inner steps reduce over
# ICI only and every H-th step syncs pseudo-gradients over DCN.
from horovod_tpu.optim.local_sgd import (  # noqa: F401
    LocalSGD,
    LocalSGDOptimizer,
    LocalSGDState,
)
# Pallas-fused optimizer tail (docs/zero.md): hvd.fused_update.sgd /
# hvd.fused_update.adam build optax optimizers tagged for the
# HOROVOD_FUSED_UPDATE=1 fused kernel path.
from horovod_tpu.optim import fused_update  # noqa: E402,F401
from horovod_tpu.runtime.metrics import (  # noqa: F401
    data_wait,
    metrics,
    trace_step,
    wrap_data_loader,
)
# Flight recorder (docs/flight-recorder.md): dump this rank's event
# ring to HOROVOD_FLIGHT_DIR on demand (crash paths dump by themselves).
from horovod_tpu.runtime.flight import (  # noqa: F401
    dump as dump_flight_recorder,
)
# Training-health plane (docs/health.md): hvd.health.observe_loss
# feeds the divergence sentinels and the compression guardrail's
# primary signal; hvd.health.monitor() is the host-side state.
from horovod_tpu.runtime import health  # noqa: E402,F401
from horovod_tpu import keras  # noqa: E402,F401  (callbacks subpackage)
from horovod_tpu import elastic  # noqa: E402,F401  (hvd.elastic.run)

_import_span.__exit__(None, None, None)
del _import_span
