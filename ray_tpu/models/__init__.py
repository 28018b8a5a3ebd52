"""ray_tpu.models — TPU-native model zoo.

Flagship: a decoder-only transformer (``gpt.py``) covering the GPT-2 and
Llama families through config switches, written as pure-JAX functional
code with logical-axis sharding (``ray_tpu.parallel.sharding``) so every
parallelism strategy (dp/fsdp/tp/sp/pp/ep) is a mesh change, not a model
change. Training step + optimizer live in ``training.py``.
"""

from .gpt import (  # noqa: F401
    GPT,
    GPTConfig,
    gpt2_small,
    gpt2_medium,
    llama_tiny,
)
from .training import (  # noqa: F401
    TrainState,
    make_optimizer,
    make_train_step,
    init_train_state,
)
