"""ray_tpu.ops — Pallas TPU kernels and their reference implementations.

The hot ops of the compute path. Each op ships (a) a pure-jnp reference
implementation (used on CPU and as the ground truth in tests) and (b) a
Pallas TPU kernel tuned for MXU/VMEM, selected automatically on TPU
backends: softmax attention (`attention`, `ring_attention`), the gated
delta rule (`delta_rule`) and the Gated DeltaNet layer's two passes around
it (`gated_deltanet`: convolution + SiLU + q/k normalisation, and the gated
RMSNorm), each pair forward and backward; and the sum of rows sorted by
segment into their segments (`segment_sum`), which returns the held
experts' rows to token order in `models.moe`, the K largest of each row
of a router's probabilities (`router_topk`), the SwiGLU product of rows
whose up and gate halves come out of one matmul, times a weight a row
(`swiglu`: its backward pass writes both halves' gradients as one array,
and the product again),
and a learned choice of keys for sparse attention (`sparse_index`: index
scores and each query's best causal keys, which `attention`'s forward kernel
then walks under, forward only).
"""

from .attention import dot_product_attention, flash_attention  # noqa: F401
from .delta_rule import (gated_delta_rule,  # noqa: F401
                         gated_delta_rule_reference)
from .gated_deltanet import (gdn_conv, gdn_conv_reference,  # noqa: F401
                             gdn_gated_norm, gdn_gated_norm_reference)
from .ring_attention import ring_attention  # noqa: F401
from .router_topk import router_topk  # noqa: F401
from .segment_sum import (sorted_segment_sum,  # noqa: F401
                          sorted_segment_sum_reference)
from .swiglu import (weighted_swiglu, weighted_swiglu_bwd,  # noqa: F401
                     weighted_swiglu_bwd_reference)
from .sparse_index import (Selection, sparse_index,  # noqa: F401
                           sparse_index_reference)
