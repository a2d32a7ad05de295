from .collectives import (  # noqa: F401
    Adasum,
    Average,
    Max,
    Min,
    Product,
    ReduceOp,
    Sum,
    allgather,
    allgather_async,
    allreduce,
    allreduce_async,
    alltoall,
    alltoall_async,
    barrier,
    broadcast,
    broadcast_async,
    grouped_allgather,
    grouped_allgather_async,
    grouped_allreduce,
    grouped_allreduce_async,
    grouped_reducescatter,
    grouped_reducescatter_async,
    join,
    masked_allreduce,
    poll,
    reducescatter,
    reducescatter_async,
    synchronize,
)
from .adasum import adasum_allreduce, hierarchical_adasum  # noqa: F401
from .autotune import (  # noqa: F401
    OnlineTuner,
    ParameterManager,
    SPMDStepTuner,
)
from .fusion import (  # noqa: F401
    flatten_pytree_buckets,
    fuse_apply,
    model_fingerprint,
)
from . import overlap  # noqa: F401  (backward-interleaved scheduler)
# pallas kernel family (TPU-first hot ops; interpret-mode off-TPU)
from .pallas_attention import (  # noqa: F401
    flash_attention,
    flash_attention_bhtd,
    make_flash_attention_fn,
)
from .fused_cross_entropy import (  # noqa: F401
    fused_causal_lm_loss,
    fused_linear_cross_entropy,
)
from .sparse import (  # noqa: F401
    IndexedSlices,
    dense_to_sparse,
    sparse_allreduce,
    sparse_to_dense,
)
