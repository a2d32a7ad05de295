"""Names the compiled train step carries for its parts.

``jax.named_scope`` writes a name into every traced operation's
``op_name`` (``jit(step_fn)/shard_map/hvd_pack/concatenate``). It is
metadata and costs nothing at run time, so the scopes are always on.
Flax already writes each module's name there (``block_3/attn``,
``ln_mlp``, ``tok_emb``); these name what no module covers. The
benchmark's readers (``benchmarks/scopes.py``) import the same
constants: a name changed here changes there.
"""

# final hidden state to the loss, both directions, fused or dense
LOSS_HEAD = "loss_head"
# DistributedOptimizer.update at n > 1 (optim/distributed.py)
HVD_PACK = "hvd_pack"            # gradients into fusion buckets
HVD_ALLREDUCE = "hvd_allreduce"  # the collectives, barriers, casts, scaling
HVD_UNPACK = "hvd_unpack"        # buckets back into a gradient tree
HVD_INNER_UPDATE = "hvd_inner_update"  # the wrapped optimizer's update

# models/moe.py, both directions: the router's scores, the choice, the
# renormalisation, the ordering of the (token, choice) pairs, the gather
# of their rows and the weighted combine; and the three expert products
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
# The scopes that are a layer's own: the benchmark's reduction
# (benchmarks/scopes.classify) looks for these after the scopes above
# and before Flax's module names, so `.../mlp/moe_experts/...` is layer
# `moe_experts` and not `mlp`
LAYER_SCOPES = (MOE_DISPATCH, MOE_EXPERTS)
