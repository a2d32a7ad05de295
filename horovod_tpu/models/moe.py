"""The routed MLP: a layer that is told which of the router's experts
it holds.

The reference provides the expert-parallel *primitive*, alltoall with
uneven splits (/root/reference/horovod/common/operations.cc:1858,
SURVEY.md §2.5 row "Alltoall (EP building block)"), and no layer on top
of it. Here the layer is the model's own (`models/transformer.Block`
builds it where `num_experts > 0`), in the form expert parallelism asks
of it anyway:

* the router keeps its published width: scores `softmax(W_r x)` in
  float32 over all `num_experts`, the top `experts_per_token` of them,
  their weights renormalised over the chosen where `norm_topk_prob`.
  With `score_func` "sigmoid" the scores are `s = sigmoid(W_r x)`, each
  expert's own, and the choice is the top k of `s + b`: `b`
  (`expert_bias`) is one float32 number an expert that corrects the
  choice and never the weight, zeros at the seed, a leaf of the
  parameter tree behind `stop_gradient` (so it travels with the
  parameters and no optimizer moves it: its gradient is zero; the rule
  that moves it between steps by the experts' load is a trainer's and
  is not built here). The weights are `s` at the chosen, over their sum
  where `norm_topk_prob`, times `routed_scaling_factor`;
* `shared_experts` > 0: one SwiGLU MLP of `shared_experts * mlp_dim`
  that every token goes through (`shared_gate`, `shared_up`,
  `shared_down`), computed once a chip and added to the routed sum. In
  a deployment every chip computes it alike, and it counts once;
* a share (`experts_held < num_experts`) has no exchange, and two things
  follow from that. **Its router is not trained**: the gradient through
  a token's weights needs the result of every expert the token chose,
  and the absent ones' never arrive; what one chip can compute of it
  alone (the held experts' terms) is not a part of the deployment's
  update but a pull towards the experts held here, which by twenty
  steps at 1e-4 sends this chip five of a token's eight choices
  (PERF.md section 6, PR 33). So the scores are constants of the
  backward pass there; a layer that holds every expert trains its
  router as usual. **Its seeded router is even over the chips**
  (`share_router_init`): the `experts_held` seeded columns, repeated for
  every chip of the deployment, so that a token's scores repeat chip by
  chip and its top `experts_per_token` are that many a chip, whatever
  the token: the rows routed here are the even share on every seed,
  which is what a trained router gives on average and seeded normal
  columns do not (a layer's positions share most of their residual
  stream at seeded weights and choose alike: 0 to 3 times the even
  share by the seed). Weights that are loaded are taken as they are;
* the layer holds `experts_held` SwiGLU experts, the router's experts
  `first_expert .. first_expert + experts_held - 1`, and returns for
  every token the sum, over its choices **that live here**, of weight x
  `W_down(silu(W_gate x) * W_up x)`. A token with no choice here gets
  zero; what the absent experts would add is left out, and nothing
  stands in for the chips that hold them or for their exchange;
* it is exact for any routing: no capacity, no token dropped;
* its cost follows the rows routed here, not tokens x choices. The
  (token, choice) pairs are ordered held-expert-major by one sort of
  integers; the rows of the first `rows_static` of them are gathered
  and go through three grouped products whose groups are the experts
  (`expert_product`: the Pallas kernels of `ops/grouped_matmul.py`,
  which read the float32 experts in place, where the shape is one they
  take; `jax.lax.ragged_dot` behind a cast of the experts where it is
  not: a width that is no whole lane tile, rows that are no whole row
  tile), and each row's result is added to its token's, weighted.
  `rows_static` is the rows even routing sends here (all of them where
  every expert is held); rows short of it ride the last group with
  weight zero. Pairs beyond `rows_static` are taken by further products
  of the same size, each under `lax.cond` and rematerialised, which run
  only when routing sends that many here: a layer sent twice the even
  share runs two products, one sent a tenth of it runs one;
* each token's choices are sown into the Flax collection `choices`
  (int32 `[..., experts_per_token]`); no auxiliary term is sown.

Scopes (`utils/scopes.LAYER_SCOPES`): `moe_dispatch` names the router
(the sigmoid, the correction, the renormalisation and the scale too),
the choice, the ordering, the gather, the weighted combine and the sum
with the shared expert; `moe_experts` the three expert products, the
kernels' calls among them in both directions (`GROUPED_MATMUL_FWD`,
`GROUPED_MATMUL_DW`; no scope is opened in `ops/grouped_matmul.py`:
this layer's reaches the forward rule and, carried by JAX to the
call's transpose, the backward rule); `moe_shared` the shared expert's
three products and its activation.
Trace-time gauges (`RoutedMlp._report`, what the last traced call of a
routed MLP was built for): `hvd_moe_experts_held`,
`hvd_moe_router_width`, `hvd_moe_rows_expected`, `hvd_moe_rows_static`,
`hvd_moe_shared_experts`, `hvd_moe_score_func`; and of the model
(`models/transformer._report`, from `RoutedMlp.experts_as_kernels`):
`hvd_moe_expert_kernel_layers` / `hvd_moe_expert_plain_layers`.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from ..ops import grouped_matmul
from ..utils import metrics, scopes

# rows_static is a multiple of this (the MXU's rows, and a tile of
# every dtype)
ROWS_MULTIPLE = 512


def rows_static(tokens: int, experts_per_token: int, experts_held: int,
                num_experts: int) -> tuple:
    """(rows even routing sends here, rows one expert product is sized
    for, the most that any routing sends here). A product is sized for
    the even share: what routing sends beyond it is the further
    products', whose cost a layer pays only when it is sent that much.
    Routing need not be even (PERF.md section 6, PR 33: seeded normal
    router columns sent a layer 900 to 35,054 rows of an even 16,384 on
    the chip), and a constant above one here would make every layer pay
    for the unluckiest."""
    most = tokens * min(experts_per_token, experts_held)
    expected = tokens * experts_per_token * experts_held / num_experts
    if experts_held == num_experts:
        return expected, most, most  # every pair is routed here
    static = math.ceil(expected / ROWS_MULTIPLE) * ROWS_MULTIPLE
    return expected, min(static, most), most


def share_router_init(stddev: float, experts_held: int):
    """A share's seeded router `[hidden, num_experts]`: `experts_held`
    normal columns, repeated once for every chip of the deployment. A
    token's scores then repeat with the chips, so where a token's
    choices are a multiple of the chips its top choices are as many on
    every chip and the rows routed here are exactly the even share.
    Nothing else of the forward pass knows: the product, the softmax and
    the top k run at the router's published width."""
    normal = nn.initializers.normal(stddev)

    def init(key, shape, dtype=jnp.float32):
        hidden, experts = shape
        if experts % experts_held:
            return normal(key, shape, dtype)
        return jnp.tile(normal(key, (hidden, experts_held), dtype),
                        (1, experts // experts_held))
    return init


def expert_product(rows, weights, groups):
    """`[rows, a] x [experts, a, c] -> [rows, c]`: each row through the
    matrix of the expert whose group it lies in (`groups` are the
    experts' row counts, in order). Both sides in the rows' dtype,
    accumulated in float32 on the MXU, returned in the rows' dtype.
    Which form runs is decided by what the operands show and nothing
    else: the kernels of `ops/grouped_matmul.py`, which round each
    expert in VMEM as the cast does, where `supports` takes the shape;
    `lax.ragged_dot` behind the cast where it does not. One function,
    so that a control can stand a lower precision in its place
    (`scripts/routed_readings.py`)."""
    if grouped_matmul.supports(*rows.shape, weights.shape[2], rows.dtype):
        return grouped_matmul.grouped_matmul(rows, weights, groups)
    return lax.ragged_dot(rows, weights.astype(rows.dtype), groups,
                          preferred_element_type=rows.dtype)


def experts_run_as_kernels(tokens: int, experts_per_token: int,
                           experts_held: int, num_experts: int,
                           hidden: int, mlp_dim: int, dtype) -> bool:
    """Whether a `RoutedMlp` over `tokens` tokens hands its three
    products to the kernels of `ops/grouped_matmul.py`: decided by
    shapes alone (`supports` asks the same of `hidden -> mlp_dim` and of
    `mlp_dim -> hidden`)."""
    static = rows_static(tokens, experts_per_token, experts_held,
                         num_experts)[1]
    return grouped_matmul.supports(static, hidden, mlp_dim, dtype)


class RoutedMlp(nn.Module):
    """[..., hidden] -> [..., hidden]: this chip's part of a routed
    SwiGLU MLP. `num_experts` is the router's width, `experts_held` how
    many of its experts live here, from `first_expert`."""

    num_experts: int
    experts_held: int
    experts_per_token: int
    mlp_dim: int
    norm_topk_prob: bool = True
    first_expert: int = 0
    dtype: Any = jnp.bfloat16
    # "softmax" over all experts, or "sigmoid" of each with the choice
    # corrected by `expert_bias`; what the chosen weights are multiplied
    # by; shared experts of `mlp_dim` every token goes through
    score_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    shared_experts: int = 0

    def experts_as_kernels(self, tokens: int, hidden: int) -> bool:
        """`experts_run_as_kernels` of this layer over `tokens` tokens
        of width `hidden`."""
        return experts_run_as_kernels(
            tokens, self.experts_per_token, self.experts_held,
            self.num_experts, hidden, self.mlp_dim, self.dtype)

    def _report(self, rows_expected: float, rows_static: int) -> None:
        """The trace-time gauges of one routed MLP: arithmetic on the
        last traced call's shapes, nothing inside the step."""
        for name, help, value in (
                ("hvd_moe_experts_held",
                 "Experts of the router's that the routed MLP holds",
                 self.experts_held),
                ("hvd_moe_router_width",
                 "Experts the routed MLP's router scores",
                 self.num_experts),
                ("hvd_moe_rows_expected",
                 "Rows even routing sends the held experts in one call",
                 rows_expected),
                ("hvd_moe_rows_static",
                 "Rows one expert product of the routed MLP is sized for",
                 rows_static),
                ("hvd_moe_shared_experts",
                 "Shared experts every token of the routed MLP goes "
                 "through", self.shared_experts)):
            metrics.trace_gauge(name, help, value)
        # the function that scores is the gauge's label; its value is 1
        metrics.trace_gauge(
            "hvd_moe_score_func",
            "The function the routed MLP's router scores by (label)", 1,
            score_func=self.score_func)

    @nn.compact
    def __call__(self, x):
        *lead, h = x.shape
        e, held, k = self.num_experts, self.experts_held, \
            self.experts_per_token
        if not 0 < k <= e or not 0 < held <= e - self.first_expert:
            raise ValueError(
                f"experts_per_token {k} and experts_held {held} from "
                f"first_expert {self.first_expert} of num_experts {e}")
        tokens = x.reshape(-1, h)
        t = tokens.shape[0]
        expected, static, most = rows_static(t, k, held, e)
        self._report(expected, static)

        # every expert a xavier-uniform matrix of its own
        init = nn.initializers.xavier_uniform(
            in_axis=1, out_axis=2, batch_axis=(0,))
        w_gate = self.param("gate", init, (held, h, self.mlp_dim),
                            jnp.float32)
        w_up = self.param("up", init, (held, h, self.mlp_dim), jnp.float32)
        w_down = self.param("down", init, (held, self.mlp_dim, h),
                            jnp.float32)

        with jax.named_scope(scopes.MOE_DISPATCH):
            # bf16 activations are exact in float32, and at the highest
            # precision so are their products with the float32 kernel:
            # the scores differ from a float32 model's by the
            # activations' rounding alone (2 x t x h x e operations)
            logits = nn.Dense(
                e, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32, precision=lax.Precision.HIGHEST,
                kernel_init=nn.initializers.normal(0.02) if held == e
                else share_router_init(0.02, held), name="router",
            )(tokens.astype(jnp.float32))
            if held < e:
                # no exchange, no gradient through the scores (above)
                logits = lax.stop_gradient(logits)
            if self.score_func == "sigmoid":
                scores = jax.nn.sigmoid(logits)
                bias = self.param("expert_bias", nn.initializers.zeros,
                                  (e,), jnp.float32)
                _, chosen = lax.top_k(
                    scores + lax.stop_gradient(bias), k)
                weights = jnp.take_along_axis(scores, chosen, -1)
            else:
                weights, chosen = lax.top_k(jax.nn.softmax(logits, -1), k)
            if self.norm_topk_prob:
                weights = weights / jnp.sum(weights, -1, keepdims=True)
            if self.routed_scaling_factor != 1.0:
                weights = weights * self.routed_scaling_factor
            if not self.is_initializing():
                # an initialisation returns every collection: a choice
                # sown there would keep the whole forward pass alive in
                # a program that is asked for the parameters alone
                self.sow("choices", "experts", chosen.reshape(*lead, k))
            # the (token, choice) pairs, held-expert-major: a pair's key
            # is its expert's place here, `held` where it lives elsewhere
            local = chosen.reshape(-1) - self.first_expert
            key = jnp.where((local >= 0) & (local < held), local, held)
            order = jnp.argsort(key).astype(jnp.int32)
            sizes = jnp.sum(key[:, None] == jnp.arange(held)[None],
                            axis=0, dtype=jnp.int32)
            ends = jnp.cumsum(sizes)
            routed = ends[-1]
            chunks = -(-most // static)
            order = jnp.pad(order, (0, max(0, chunks * static - t * k)))
            pair_weight = weights.reshape(-1)

        def chunk(c):
            """The pairs `[c * static, (c + 1) * static)` of the order:
            their tokens, and their experts' weighted results
            `[static, h]` in float32."""
            with jax.named_scope(scopes.MOE_DISPATCH):
                lo = c * static
                pairs = lax.dynamic_slice_in_dim(order, lo, static)
                real = lo + jnp.arange(static) < routed
                token = pairs // k
                rows = tokens[token].astype(self.dtype)
                scale = jnp.where(real, pair_weight[pairs], 0.0)
                # each expert's rows inside the chunk; the rows past the
                # routed ones ride the last group at weight zero
                inside = jnp.clip(ends - lo, 0, static)
                groups = jnp.diff(inside, prepend=0)
                groups = groups.at[-1].add(static - inside[-1])
            with jax.named_scope(scopes.MOE_EXPERTS):
                hidden = nn.silu(expert_product(rows, w_gate, groups)) \
                    * expert_product(rows, w_up, groups)
                out = expert_product(hidden, w_down, groups)
            with jax.named_scope(scopes.MOE_DISPATCH):
                return token, out.astype(jnp.float32) * scale[:, None]

        def combine(total, token, out):
            with jax.named_scope(scopes.MOE_DISPATCH):
                return total.at[token].add(out)

        y = combine(jnp.zeros((t, h), jnp.float32), *chunk(0))
        if chunks > 1:
            # Pairs past the first product's, where routing sends so many
            # here: one product of the same size for each `static` of
            # them, added into the same sum. Both conditions hand the sum
            # on untouched where they do not hold. An iteration, its
            # condition included, is rematerialised: it keeps nothing
            # for the backward pass but its number, and what never
            # changes (the tokens, the order, the float32 experts the
            # kernels read in place) stays the loop's constants. With
            # the condition outside the rematerialised part, its
            # results carried what a taken branch kept: a scan stacks
            # those iteration by iteration, the experts among them, and
            # where routing sends no further rows here the outer
            # condition's other branch has to hand the stacks on as
            # zeros: 7 GB of them a step in `sdar_bd_s4096`, 11 with
            # the experts in float32 (PERF.md section 6, PR 49)
            @jax.checkpoint
            def further(total, c):
                return lax.cond(
                    c * static < routed,
                    lambda total: combine(total, *chunk(c)),
                    lambda total: total, total)

            y = lax.cond(
                routed > static,
                lambda y: lax.scan(lambda total, c: (further(total, c), None),
                                   y, jnp.arange(1, chunks))[0],
                lambda y: y, y)
        if self.shared_experts:
            with jax.named_scope(scopes.MOE_SHARED):
                dense = functools.partial(
                    nn.Dense, use_bias=False, dtype=self.dtype,
                    param_dtype=jnp.float32,
                    kernel_init=nn.initializers.xavier_uniform())
                width = self.shared_experts * self.mlp_dim
                rows = tokens.astype(self.dtype)
                hidden = nn.silu(dense(width, name="shared_gate")(rows)) \
                    * dense(width, name="shared_up")(rows)
                shared = dense(h, name="shared_down")(hidden)
            with jax.named_scope(scopes.MOE_DISPATCH):
                y = y + shared.astype(jnp.float32)
        with jax.named_scope(scopes.MOE_DISPATCH):
            return y.astype(x.dtype).reshape(*lead, h)
