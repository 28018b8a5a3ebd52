"""ray_tpu.train — distributed training orchestration, TPU-first.

Reference: Ray Train (``python/ray/train/``, SURVEY §2.3/§3.4). The
reference spawns N single-GPU worker processes and wires them into a
torch NCCL process group; TPU-native the unit of placement is the *host*
and the unit of computation is ONE jitted SPMD program over a
`jax.sharding.Mesh` — so `JaxTrainer` gangs one worker actor per host,
grants its process the host's chips (``ScalingConfig(use_tpu=True)``) and
runs the user's ``train_loop_per_worker`` in it; the loop builds its mesh
over the host's devices.

Parallelism (dp/fsdp/tp/sp/pp/ep) is a `MeshSpec`, not a wrapper class —
see ``ray_tpu.parallel``.
"""

from .checkpoint import Checkpoint  # noqa: F401
from .config import (  # noqa: F401
    CheckpointConfig,
    FailureConfig,
    RunConfig,
    ScalingConfig,
)
from .result import Result  # noqa: F401
from .session import (  # noqa: F401
    get_checkpoint,
    get_context,
    get_dataset_shard,
    report,
)
from .trainer import JaxTrainer  # noqa: F401
