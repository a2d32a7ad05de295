"""Names the compiled train step carries for its parts.

``jax.named_scope`` writes a name into every traced operation's
``op_name`` (``jit(step_fn)/shard_map/hvd_pack/concatenate``). It is
metadata and costs nothing at run time, so the scopes are always on.
Flax already writes each module's name there (``block_3/attn``,
``ln_mlp``, ``tok_emb``); these name what no module covers. The
benchmark's readers (``benchmarks/scopes.py``) import the same
constants: a name changed here changes there.

The rule the readers depend on (``tests/test_step_scopes.py`` holds
it): **a flash call (``FLASH_FWD`` / ``FLASH_BWD``) sits outside every
``LAYER_SCOPES`` name; the q/k pass's call (``QK_PREP_FWD`` /
``QK_PREP_BWD``) sits inside ``ATTN_PREP``, the state-space scan's
(``SSD_SCAN_FWD`` / ``SSD_SCAN_BWD``) inside ``MAMBA_SCAN``, and the
routed experts' products' (``GROUPED_MATMUL_FWD`` /
``GROUPED_MATMUL_DW``) inside ``MOE_EXPERTS``.** A flash call's
``op_name`` is ``.../attn/flash_fwd/pallas_call``: it stays in Flax's
layer ``attn``, where the kernels' readers look (they take every Mosaic
call of that layer for a flash kernel), and what is left in layer
``attn`` outside the kernels is the plain-XLA attention's einsums and
softmax. The q/k pass's is ``.../attn/attn_prep/qk_prep_fwd/pallas_call``:
layer ``attn_prep``, whose time it is; the scan's is
``.../mamba/mamba_scan/ssd_scan_fwd/pallas_call``: layer ``mamba_scan``,
likewise; an expert product's is
``.../mlp/moe_experts/grouped_matmul/pallas_call``: layer
``moe_experts``, whose reader takes the layer by its scope (a call
outside it would be layer ``other``, and the layer's roofline share
would count work whose time it does not see).
"""

# final hidden state to the loss, both directions, fused or dense
LOSS_HEAD = "loss_head"
# DistributedOptimizer.update at n > 1 (optim/distributed.py)
HVD_PACK = "hvd_pack"            # gradients into fusion buckets
HVD_ALLREDUCE = "hvd_allreduce"  # the collectives, barriers, casts, scaling
HVD_UNPACK = "hvd_unpack"        # buckets back into a gradient tree
HVD_INNER_UPDATE = "hvd_inner_update"  # the wrapped optimizer's update

# models/moe.py, both directions: the router's scores (softmax, or
# sigmoid with the choice's correction), the choice, the
# renormalisation and the scale, the ordering of the (token, choice)
# pairs, the gather of their rows, the weighted combine and the sum with
# the shared expert's result; and the three expert products
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
# the shared expert every token goes through beside the routed ones:
# its three products and its activation, and nothing else
MOE_SHARED = "moe_shared"
# models/transformer.Attention's DenseGeneral calls (query, key, value,
# out and, where the model has an output gate, gate), both directions,
# and nothing else
ATTN_PROJ = "attn_proj"
# What attention does that is neither a projection nor a flash kernel:
# the q/k norms and apply_rope, as array passes or as the one Pallas
# pass a direction of ops/attention_prep.py (whose calls, QK_PREP_*
# below, stand inside this scope) with the gather of rope's rows, and
# everything ops/pallas_attention.py does around its two pallas_calls,
# forward rule and backward rule (transposes into and out of the
# kernels' layout, pads and slices, the sum of partial dk/dv over a
# group's query heads, delta, casts); and the output gate's sigmoid and
# its multiply with the heads' output
ATTN_PREP = "attn_prep"
# the norms on a block's two branches before they join the residual
# (models/transformer.Block with `post_norms`: Flax modules
# `ln_post_attn`, `ln_post_mlp`), both directions. `ln_attn`, `ln_mlp`
# and `ln_final` are Flax's names and the benchmark's layer `norm`
POST_NORM = "post_norm"
# models/mamba.Mamba2Mixer, both directions; every operation of the Flax
# module `mamba` lies in exactly one of the four (tests/test_step_scopes.py).
# The input and output projections and nothing else
MAMBA_PROJ = "mamba_proj"
# the causal depthwise convolution with its bias, silu, the splits of
# the input projection's result and of the convolution's, dt's softplus
MAMBA_CONV = "mamba_conv"
# everything from x, dt, B, C to y: a * dt and its cumulative sums, the
# decay tiles, the products inside a chunk, the states between chunks,
# D x: as the two kernels of ops/ssd_scan.py (SSD_SCAN_* below) with
# the cumulative sums, the layouts of dt and D x around them, or as
# plain `jnp` / `lax` where the shape is not one the kernels take. The
# kernels' calls stand INSIDE this scope, forward rule and backward
# rule: `mamba_scan_ms` selects by this layer and nothing else, so a
# call outside it would be layer `other` to the readers, and every
# operation of the module lies in one of the four
MAMBA_SCAN = "mamba_scan"
# the gate y * silu(z) and the norm over the whole inner width
MAMBA_GATE = "mamba_gate"
# The scopes that are a layer's own: the benchmark's reduction
# (benchmarks/scopes.classify) looks for these after the scopes above
# and before Flax's module names, so `.../mlp/moe_experts/...` is layer
# `moe_experts` and not `mlp`, and `.../attn/attn_proj/query/...` is
# layer `attn_proj` and not `attn`, and everything under the Flax module
# `mamba` is one of the four `mamba_*` layers (a Mosaic call of layer
# `attn` is a flash kernel to the kernels' readers, so the state-space
# mixer's module is never named `attn`). The dense MLP needs none: Flax's
# `mlp` is its layer, and in a routed model `mlp` is what RoutedMlp
# does outside its three scopes
LAYER_SCOPES = (MOE_DISPATCH, MOE_EXPERTS, ATTN_PROJ, ATTN_PREP,
                MAMBA_PROJ, MAMBA_CONV, MAMBA_SCAN, MAMBA_GATE,
                MOE_SHARED, POST_NORM)

# Kernel names, not layer scopes: the `name=` of the program's
# `pl.pallas_call`s. The TPU compiler names a Mosaic call by it
# (`flash_fwd.3`) and it is the innermost scope of the call's `op_name`.
# First the two flash calls (ops/pallas_attention.py). A forward call
# in the backward phase is one a rematerialised block runs again
FLASH_FWD = "flash_fwd"
FLASH_BWD = "flash_bwd"
# The one pass between the q and k projections and the flash kernels
# (ops/attention_prep.py), forward and backward. Unlike the flash calls
# these stand INSIDE `ATTN_PREP`: their time is attention's layout work
QK_PREP_FWD = "qk_prep_fwd"
QK_PREP_BWD = "qk_prep_bwd"
# The chunked state-space recurrence (ops/ssd_scan.py), forward and
# backward. Like the q/k pass's these stand INSIDE their layer's scope,
# `MAMBA_SCAN`: their time is the scan's, and the scan's reader
# (benchmarks/layer_metrics/mamba_scan_ms.py) takes the layer whole. A
# forward call in the backward phase is a rematerialised block's
SSD_SCAN_FWD = "ssd_scan_fwd"
SSD_SCAN_BWD = "ssd_scan_bwd"
# The routed experts' products (ops/grouped_matmul.py): the product
# itself (gate, up, down; in the backward phase a rematerialised
# block's, or with the weight transposed the gradient into the rows)
# and the gradient into the weights. These stand INSIDE `MOE_EXPERTS`,
# forward rule and backward rule: the layer's reader
# (benchmarks/layer_metrics/moe_experts_ms.py) takes the layer by its
# scope
GROUPED_MATMUL_FWD = "grouped_matmul"
GROUPED_MATMUL_DW = "grouped_matmul_dw"
