"""The paper's stencil application: gol3d + distributed halo exchange."""

from .gol3d import Gol3d, Gol3dConfig  # noqa: F401
from .pipeline import (  # noqa: F401
    DistributedPipeline, ResidentPipeline, VMEM_BUDGET_BYTES,
    checkpoint_bytes_per_interval, checkpoint_traffic_fraction,
    distributed_bytes_per_step, exchange_bytes_per_step, exchange_face_items,
    exchange_items_per_exchange, fused_items_per_launch, fused_vmem_bytes,
    resident_bytes_per_step,
)
from .domain import Decomposition3D, make_stencil_mesh, STENCIL_AXES  # noqa: F401
from .halo import (  # noqa: F401
    exchange_shell, make_distributed_step, shard_boundary_flags, shard_state,
    shard_substeps, stencil_block_kind, unshard_state,
)
from .runner import (  # noqa: F401
    CheckpointedRun, RunHealthError, RunHooks, health_check,
)
