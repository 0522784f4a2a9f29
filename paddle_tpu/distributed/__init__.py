"""paddle.distributed parity, TPU-native.

Reference: python/paddle/distributed/ (§2.5 of SURVEY.md). The NCCL
ring_id world becomes a jax.sharding.Mesh whose named axes ARE the
parallel dimensions (dp/tp/pp/sp/ep); collectives are XLA ops inside
compiled programs, exposed eagerly through this package's API for
dygraph-style parity.
"""
from .env import (  # noqa: F401
    ParallelEnv, get_rank, get_world_size, init_parallel_env,
    is_initialized)
from .mesh import (  # noqa: F401
    Mesh, get_mesh, set_mesh, create_mesh, mesh_axis_size,
    dcn_slice_count, slice_size)
from . import membership  # noqa: F401
from .membership import (  # noqa: F401
    SliceMembership, DcnCollectiveGuard, SliceLostError)
from .collective import (  # noqa: F401
    all_reduce, all_gather, reduce, broadcast, scatter, barrier,
    all_to_all, send, recv, split, ReduceOp, new_group)
from .parallel import DataParallel  # noqa: F401
from . import parallel_layers  # noqa: F401
from .parallel_layers import (  # noqa: F401
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from . import fleet  # noqa: F401
from . import spmd  # noqa: F401
from .spmd import SpmdTrainer, dp_train_step, StepResult  # noqa: F401
from . import async_dispatch  # noqa: F401
from .async_dispatch import (  # noqa: F401
    LazyValue, host_sync_count, reset_host_sync_count)
from .recompute import recompute, RecomputeWrapper  # noqa: F401
from . import moe  # noqa: F401
from .moe import (  # noqa: F401
    MoELayer, ExpertParallelFFN, collect_aux_losses, add_aux_loss)
from . import ring_attention as ring_attention_mod  # noqa: F401
from .ring_attention import (  # noqa: F401
    ring_attention, ring_attention_local, sequence_parallel_attention)
from . import checkpoint  # noqa: F401
from .checkpoint import (  # noqa: F401
    save_trainer, load_trainer, latest_checkpoint)
from . import resilience  # noqa: F401
from .resilience import CheckpointManager, PreemptionGuard  # noqa: F401
from . import launch as launch_mod  # noqa: F401
from .spawn import spawn  # noqa: F401
from . import overlap  # noqa: F401
from .overlap import overlap_enabled  # noqa: F401
