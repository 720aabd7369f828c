"""`paddle.distributed` surface over jax.sharding / XLA collectives
(reference: python/paddle/distributed/; SURVEY.md §2.3, §5.8)."""

from .api import (  # noqa: F401
    apply_placement_rules, dtensor_from_fn, reshard, shard_layer,
    shard_tensor,
)
from .capability import (  # noqa: F401
    has_multiprocess_collectives, has_pinned_host_memory,
)
from .collective import (  # noqa: F401
    Group, ReduceOp, all_gather, all_gather_object, all_reduce, all_to_all,
    alltoall, barrier, broadcast, gather, new_group, ppermute, recv, reduce,
    reduce_scatter, scatter, send,
)
from .env import (  # noqa: F401
    device_count, get_rank, get_world_size, init_parallel_env,
    is_initialized, local_device_count,
)
from .mesh import ProcessMesh, get_mesh, init_mesh, set_mesh  # noqa: F401
from .placement import (  # noqa: F401
    Partial, Placement, Replicate, Shard, named_sharding,
    placements_to_spec, spec_to_placements,
)
from .sharded_step import ShardedTrainStep  # noqa: F401
from .recompute import recompute, recompute_sequential  # noqa: F401
from . import fleet  # noqa: F401
from . import checkpoint  # noqa: F401
from .moe import MoELayer, TopKGate  # noqa: F401
from .parallel import DataParallel, spawn  # noqa: F401
from .ring_attention import ring_attention  # noqa: F401
from .pipeline import PipelineDecoderLM  # noqa: F401
from .watchdog import (  # noqa: F401
    CollectiveWatchdog, FlightRecorder, get_watchdog, watch_step,
)
from .compat import (  # noqa: F401,E402
    CountFilterEntry, DistAttr, DistModel, InMemoryDataset, ParallelEnv,
    ParallelMode, ProbabilityEntry, QueueDataset, ReduceType,
    ShardingStage1, ShardingStage2, ShardingStage3, ShowClickEntry,
    Strategy, alltoall_single, broadcast_object_list,
    destroy_process_group, get_backend, get_group, gloo_barrier,
    gloo_init_parallel_env, gloo_release, irecv, is_available, isend,
    load_state_dict, save_state_dict, scatter_object_list,
    shard_dataloader, shard_optimizer, shard_scaler, split, to_static,
    unshard_dtensor, wait,
)
from . import launch  # noqa: F401,E402
from . import io  # noqa: F401,E402
from . import rpc  # noqa: F401,E402
from . import communication  # noqa: F401,E402
from .communication import stream  # noqa: F401,E402
