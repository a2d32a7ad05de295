"""Transformer model family: GPT-2, BERT-Large, Llama, and stacks whose
layers differ in kind (`TransformerConfig.layer_types`: an `attention`
layer, a `window_attention` layer whose queries see a sliding window of
keys, or a `mamba2` state-space layer, models/mamba.py), with a routed
MLP (models/moe.py) behind leading dense layers where the model has
experts.

Benchmark vehicles from BASELINE.json configs: BERT-Large pretraining
(tokens/sec/chip), Adasum on Llama-2-7B, elastic GPT-2. The reference
repo has no transformer implementations of its own (it wraps torchvision /
keras / user models) — these are TPU-first implementations built for this
framework's benchmarks and examples.

TPU-first choices:
  * bfloat16 activations/weights (LM head included) with float32 layernorm; logits upcast to float32 inside the loss
  * shapes padded to MXU tiles (head_dim multiples of 128 recommended)
  * pluggable attention: `attention_fn` lets the parallel layer swap in
    ring attention (parallel/ring_attention.py) or Ulysses all-to-all
    (parallel/ulysses.py) without touching model code
  * optional per-block remat (`jax.checkpoint`) for HBM-bound configs:
    every block's forward runs again in the backward pass; the last
    block, whose backward runs first, keeps its kernel calls' and
    matrix products' results where the caller takes the hidden state
    to its own head (`TransformerConfig.remat`)
  * per-head q/k norms and rope run as one Pallas pass a direction, in
    the flash kernels' layout, where a head is whole lane tiles and the
    attention function offers that layout (`fuses_qk_prep`,
    ops/attention_prep.py); as array passes everywhere else
  * params stay plain arrays; tensor/FSDP sharding rules live externally
    in parallel/sharding.py (path-pattern → PartitionSpec over dp/fsdp/tp
    axes) so pjit shards them and XLA inserts the collectives.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops import attention_prep
from ..utils import metrics, scopes
from .mamba import Mamba2Mixer
from .moe import RoutedMlp

# the kinds of layer a `Block` builds, as `layer_types` names them (the
# spelling of the benchmark's `model` group and of its
# `benchmarks/layer_kinds/<kind>.py`)
ATTENTION, MAMBA2, WINDOW_ATTENTION = \
    "attention", "mamba2", "window_attention"
LAYER_KINDS = (ATTENTION, MAMBA2, WINDOW_ATTENTION)
# the kinds whose mixer is `Attention`
ATTENTION_KINDS = (ATTENTION, WINDOW_ATTENTION)


class LayerTypes(tuple):
    """`TransformerConfig.layer_types` as it is kept: a tuple of kinds'
    names (hashable, as a frozen configuration has to be) that equals
    the list a JSON file holds of the same names, so a configuration
    built from a file's keyword arguments reads back equal to them."""

    def __eq__(self, other):
        return tuple.__eq__(self, tuple(other)) \
            if isinstance(other, list) else tuple.__eq__(self, other)

    def __ne__(self, other):
        return not self == other

    __hash__ = tuple.__hash__


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50257
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: Optional[int] = None  # GQA; None = MHA
    hidden_size: int = 768
    mlp_ratio: float = 4.0
    max_seq_len: int = 1024
    dtype: Any = jnp.bfloat16
    # architecture switches
    norm: str = "layernorm"  # "layernorm" | "rmsnorm"
    position: str = "learned"  # "learned" | "rope" | "none"
    activation: str = "gelu"  # "gelu" | "swiglu"
    causal: bool = True
    tie_embeddings: bool = True
    # rematerialise the blocks (`build_block`): a block keeps only
    # its input and its forward runs again right before its backward.
    # The last block's backward is the first to run, with nothing
    # between its two runs but the final norm and the head, so where
    # `__call__(return_hidden=True)` hands the hidden state to a head of
    # the caller's (the fused cross entropy, whose live memory is
    # O(rows x one vocabulary block)) it keeps its flash calls' and
    # matrix products' results from its first run and rebuilds only the
    # elementwise passes (`_last_block_keeps`; a last block kept WHOLE
    # holds every float32 intermediate of rope and the norms at once
    # and needs 3% more HBM than the rematerialised step). Where the
    # model builds the [B, T, V] logits itself the step's peak sits at
    # the head and anything kept adds to it, so there every block is
    # rematerialised.
    remat: bool = False
    rope_theta: float = 10000.0
    layernorm_epsilon: float = 1e-5
    # one head's width where the model states it apart from
    # hidden_size // num_heads (the projections are then
    # hidden_size x num_heads * head_dim); None = that quotient
    head_dim: Optional[int] = None
    # RMS norm of every head's q and k (one scale of the head's width
    # shared by the heads), before rope
    qk_norm: bool = False
    # block-diffusion training: the input is [noisy ; clean], 2T
    # positions, and b = diffusion_block the block length of the mask
    # (`diffusion_mask`) that stands in place of `causal`; 0 = no such mask
    diffusion_block: int = 0
    # routed MLP (models/moe.py) where num_experts > 0, in every layer
    # after the first `dense_layers` (those keep the plain MLP): the
    # router's width, how many of its experts this chip holds (None =
    # all), the experts a token is sent to, one expert's width (None =
    # mlp_dim), whether a token's chosen weights are renormalised, how
    # the router scores ("softmax" over all experts, or "sigmoid" of
    # each with a correction of the choice, models/moe.py), what the
    # chosen weights are multiplied by, and how many experts of that
    # width every token goes through beside the routed ones (one SwiGLU
    # MLP of `shared_experts * expert_mlp_dim`)
    num_experts: int = 0
    experts_held: Optional[int] = None
    experts_per_token: int = 0
    expert_mlp_dim: Optional[int] = None
    norm_topk_prob: bool = False
    dense_layers: int = 0
    score_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    shared_experts: int = 0
    # the kind of each layer's mixer, one name a layer in order
    # (LAYER_KINDS): None = every layer `attention`. A `mamba2` layer
    # has a state-space mixer (models/mamba.py) where an `attention`
    # layer has its `Attention`; a `window_attention` layer is an
    # `attention` layer whose query q sees key k where 0 <= q - k <
    # `sliding_window` (under `causal`). The norms, the residuals and
    # the MLP are the same
    layer_types: Optional[tuple] = None
    sliding_window: int = 0
    # the kinds of layer whose attention rotates q and k where
    # `position` is "rope" (None = every attention layer): a model may
    # give the position code to its window layers alone
    rope_kinds: Optional[tuple] = None
    # the heads' output times sigmoid(W_g x), W_g `hidden x heads *
    # head_dim` without bias on the normed input that q, k and v are
    # made of, before the output projection
    attn_output_gate: bool = False
    # four norms a block: `x + N2(Attn(N1 x))`, `x + N4(Mlp(N3 x))`
    # (N2 and N4 on the two branches before they join the residual)
    # where False is `x + Attn(N1 x)`, `x + Mlp(N3 x)`
    post_norms: bool = False
    # the state-space mixer's sizes: heads of the recurrence, a head's
    # width, the state's width N, d_inner over hidden_size (which has to
    # equal heads x width), taps of the depthwise convolution, groups
    # that share B and C, positions of a chunk of the scan
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_expand: int = 2
    mamba_d_conv: int = 4
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    # four constants some models scale their streams by; each default
    # is neutral and adds no operation. The token embedding's output x
    # this; every block's two branches x this before they join the
    # residual; the attention scores x this in place of 1/sqrt(head
    # width) (None = that); the final norm's output / this, in front of
    # the head whoever runs it (the model's logits, or a caller's fused
    # cross entropy on `return_hidden=True`)
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0

    def __post_init__(self):
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"score_func {self.score_func!r}: the routed MLP scores "
                f"by 'softmax' or 'sigmoid'")
        if not 0 <= self.dense_layers <= self.num_layers:
            raise ValueError(
                f"dense_layers {self.dense_layers} of num_layers "
                f"{self.num_layers}")
        if self.rope_kinds is not None:
            rope_kinds = LayerTypes(self.rope_kinds)
            if set(rope_kinds) - set(ATTENTION_KINDS) \
                    or self.position != "rope":
                raise ValueError(
                    f"rope_kinds {list(rope_kinds)} with position "
                    f"{self.position!r}: it names the kinds among "
                    f"{ATTENTION_KINDS} that rotate q and k under "
                    f"position 'rope'")
            object.__setattr__(self, "rope_kinds", rope_kinds)
        if self.layer_types is None:
            return
        kinds = LayerTypes(self.layer_types)
        unknown = sorted(set(kinds) - set(LAYER_KINDS))
        if unknown or len(kinds) != self.num_layers:
            raise ValueError(
                f"layer_types names {len(kinds)} layers"
                + (f", {unknown} among them," if unknown else "")
                + f" and num_layers is {self.num_layers}: it is one of "
                f"{LAYER_KINDS} a layer, in order")
        if MAMBA2 in kinds and self.mamba_expand * self.hidden_size \
                != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError(
                f"mamba_expand {self.mamba_expand} x hidden_size "
                f"{self.hidden_size} is not mamba_n_heads "
                f"{self.mamba_n_heads} x mamba_d_head "
                f"{self.mamba_d_head}: the inner stream has one width")
        if WINDOW_ATTENTION in kinds and (
                self.sliding_window <= 0 or not self.causal
                or self.diffusion_block):
            raise ValueError(
                f"a `window_attention` layer sees the sliding_window "
                f"latest positions under a causal mask: sliding_window "
                f"{self.sliding_window}, causal {self.causal}, "
                f"diffusion_block {self.diffusion_block}")
        object.__setattr__(self, "layer_types", kinds)

    @property
    def layer_kinds(self) -> tuple:
        """The kind of each of the `num_layers` layers, in order."""
        return self.layer_types or (ATTENTION,) * self.num_layers

    def rotates(self, kind: str) -> bool:
        """Whether a layer of `kind` rotates its q and k (rope)."""
        return self.position == "rope" and (
            self.rope_kinds is None or kind in self.rope_kinds)

    def routes(self, layer: int) -> bool:
        """Whether layer `layer` has the routed MLP (the first
        `dense_layers` keep the plain one)."""
        return bool(self.num_experts) and layer >= self.dense_layers

    @property
    def head_width(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def mlp_dim(self) -> int:
        return int(self.hidden_size * self.mlp_ratio)

    def routed_mlp(self, **kw) -> RoutedMlp:
        """The routed MLP of these sizes: a routed layer's (`Block`
        names it `mlp`), or one built only to be asked what it would
        run (`parent=None`)."""
        return RoutedMlp(
            num_experts=self.num_experts,
            experts_held=self.experts_held or self.num_experts,
            experts_per_token=self.experts_per_token,
            mlp_dim=self.expert_mlp_dim or self.mlp_dim,
            norm_topk_prob=self.norm_topk_prob,
            score_func=self.score_func,
            routed_scaling_factor=self.routed_scaling_factor,
            shared_experts=self.shared_experts, dtype=self.dtype, **kw)

    def mamba2_mixer(self, **kw) -> Mamba2Mixer:
        """The state-space mixer of these sizes, as `routed_mlp`."""
        return Mamba2Mixer(
            hidden_size=self.hidden_size, n_heads=self.mamba_n_heads,
            d_head=self.mamba_d_head, d_state=self.mamba_d_state,
            d_conv=self.mamba_d_conv, n_groups=self.mamba_n_groups,
            chunk_size=self.mamba_chunk_size,
            epsilon=self.layernorm_epsilon, dtype=self.dtype, **kw)


# -- named configs ----------------------------------------------------------

GPT2_SMALL = TransformerConfig(
    vocab_size=50257, num_layers=12, num_heads=12, hidden_size=768,
    max_seq_len=1024,
)
GPT2_MEDIUM = dataclasses.replace(
    GPT2_SMALL, num_layers=24, num_heads=16, hidden_size=1024
)
GPT2_LARGE = dataclasses.replace(
    GPT2_SMALL, num_layers=36, num_heads=20, hidden_size=1280
)
BERT_BASE = TransformerConfig(
    vocab_size=30522, num_layers=12, num_heads=12, hidden_size=768,
    max_seq_len=512, causal=False,
)
BERT_LARGE = dataclasses.replace(
    BERT_BASE, num_layers=24, num_heads=16, hidden_size=1024
)
LLAMA2_7B = TransformerConfig(
    vocab_size=32000, num_layers=32, num_heads=32, hidden_size=4096,
    mlp_ratio=11008 / 4096, max_seq_len=4096, norm="rmsnorm",
    position="rope", activation="swiglu", tie_embeddings=False,
)
LLAMA3_8B = TransformerConfig(
    vocab_size=128256, num_layers=32, num_heads=32, num_kv_heads=8,
    hidden_size=4096, mlp_ratio=14336 / 4096, max_seq_len=8192,
    norm="rmsnorm", position="rope", activation="swiglu",
    tie_embeddings=False, rope_theta=500000.0,
)


# -- the layer pattern -------------------------------------------------------

# what `remat` does with a block (`LayerSpec.remat`; None = nothing):
# its whole forward runs again in the backward pass, or it keeps from
# its first run what `_last_block_keeps` keeps and rebuilds the rest
REBUILD_ALL, KEEP_PRODUCTS = "rebuild_all", "keep_products"


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """What one layer of the stack is built as. `layer_specs` decides
    it and `build_block` builds it; whoever walks the layers (the model,
    ops/overlap's stages, parallel/pipeline's stage, `cache_gaps`)
    reads these, and not `layer_types`, `dense_layers`, `num_experts`
    or `remat` of the configuration."""

    index: int
    kind: str  # the mixer, one of LAYER_KINDS
    routed: bool  # the routed MLP (models/moe.py) and not the plain one
    remat: Optional[str] = None  # None, REBUILD_ALL or KEEP_PRODUCTS


def layer_specs(cfg: TransformerConfig,
                callers_head: bool = False) -> tuple:
    """The `LayerSpec` of each of `cfg`'s layers, in order.
    `callers_head`: the caller takes the hidden state to a head of its
    own (`Transformer.__call__(return_hidden=True)`), so that under
    `remat` the last block, whose backward runs first, right after the
    head's, keeps its dear results (see `TransformerConfig.remat`);
    where the [B, T, V] logits are built every block is rebuilt whole."""
    kept = int(cfg.remat and callers_head and cfg.num_layers > 0)
    rematerialised = cfg.num_layers - kept if cfg.remat else 0
    return tuple(
        LayerSpec(i, kind, cfg.routes(i),
                  REBUILD_ALL if i < rematerialised
                  else KEEP_PRODUCTS if kept else None)
        for i, kind in enumerate(cfg.layer_kinds))


# -- building blocks --------------------------------------------------------

class RMSNorm(nn.Module):
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        xf = x.astype(jnp.float32)
        scale = self.param(
            "scale", nn.initializers.ones, (x.shape[-1],), jnp.float32
        )
        y = xf * jax.lax.rsqrt(
            jnp.mean(xf * xf, axis=-1, keepdims=True) + self.epsilon
        )
        return (y * scale).astype(self.dtype)


class _NormScale(nn.Module):
    """`RMSNorm`'s parameter under `RMSNorm`'s path (``<name>/scale``,
    float32 ones) without its arithmetic, for a caller that runs the
    norm inside a pass of its own (`Attention`'s fused q/k pass)."""

    @nn.compact
    def __call__(self, width: int):
        return self.param("scale", nn.initializers.ones, (width,),
                          jnp.float32)


def _norm(cfg: TransformerConfig, name: str):
    if cfg.norm == "rmsnorm":
        return RMSNorm(epsilon=cfg.layernorm_epsilon, dtype=cfg.dtype,
                       name=name)
    return nn.LayerNorm(epsilon=cfg.layernorm_epsilon, dtype=cfg.dtype,
                        param_dtype=jnp.float32, name=name)


def _joined(cfg: TransformerConfig, x, branch, name: str):
    """The residual stream `x` with a block's `branch` joined to it;
    with `post_norms` the branch goes through a norm of its own first
    (the Flax module `name`, under the scope `POST_NORM`)."""
    if cfg.post_norms:
        with jax.named_scope(scopes.POST_NORM):
            branch = _norm(cfg, name)(branch)
    return x + scaled(branch, cfg.residual_multiplier)


def rope_frequencies(head_dim: int, max_len: int, theta: float):
    inv = 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))
    t = np.arange(max_len)
    freqs = np.outer(t, inv)  # [T, D/2]
    return jnp.asarray(np.cos(freqs)), jnp.asarray(np.sin(freqs))


def apply_rope(x, cos, sin, positions):
    """x: [B, T, H, D]; positions: [B, T] absolute positions (so sequence-
    parallel shards pass their global offsets)."""
    c = cos[positions][:, :, None, :]  # [B, T, 1, D/2]
    s = sin[positions][:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def cached_attention(q, k, v, valid):
    """Attention of new-token queries over a KV cache slice.

    q ``[B, T, H, D]`` (the T tokens being appended this call — the
    whole prompt at prefill, one token at decode); k/v ``[B, KH, M, D]``
    (the cache layout's per-layer slice, already containing the new
    rows); ``valid`` ``[B, T, M]`` bool — cache position j is
    attendable by query t iff ``j <= position(t)``, which is both the
    causal mask and the "written yet" mask (rows above a slot's length
    hold stale bytes from the slot's previous occupant).

    float32 softmax accumulation like :func:`dot_product_attention`;
    masked positions get -1e30 so stale-but-finite cache rows
    contribute exactly zero probability.
    """
    B, T, H, D = q.shape
    KH = k.shape[1]
    if KH != H:  # GQA: repeat kv heads
        rep = H // KH
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bthd,bhmd->bhtm", q, k).astype(jnp.float32) * scale
    logits = jnp.where(valid[:, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhtm,bhmd->bthd", probs, v)


def diffusion_mask(positions: int, block: int):
    """[P, P] bool, which keys a query sees under block diffusion: the
    P = 2T positions are [noisy ; clean] and position i lies in block
    (i mod T) // block. A noisy query sees the noisy keys of its own
    block and the clean keys of earlier blocks; a clean query sees the
    clean keys of its own and earlier blocks and no noisy key."""
    t = positions // 2
    i = jnp.arange(positions)
    noisy, blk = i < t, (i % t) // block
    q_noisy, k_noisy = noisy[:, None], noisy[None, :]
    q_blk, k_blk = blk[:, None], blk[None, :]
    return ((q_noisy & k_noisy & (q_blk == k_blk))
            | (q_noisy & ~k_noisy & (k_blk < q_blk))
            | (~q_noisy & ~k_noisy & (k_blk <= q_blk)))


def dot_product_attention(q, k, v, *, causal: bool, mask=None,
                          diffusion_block: int = 0, window: int = 0):
    """Default attention: q,k,v [B, T, H, D] -> [B, T, H, D].

    float32 softmax accumulation on bf16 inputs (TPU-stable). Swappable via
    `attention_fn` for ring/Ulysses sequence parallelism. With
    `diffusion_block` the mask is `diffusion_mask` and `causal` is not
    read. With `window` w a causal query q sees key k where
    0 <= q - k < w.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    # GQA: repeat kv heads
    if k.shape[2] != H:
        rep = H // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / np.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if diffusion_block:
        logits = jnp.where(diffusion_mask(Tq, diffusion_block)[None, None],
                           logits, -1e30)
    elif causal:
        cm = jnp.tril(jnp.ones((Tq, Tk), dtype=bool))
        if window:
            cm = cm & ~jnp.tril(jnp.ones((Tq, Tk), dtype=bool), -window)
        logits = jnp.where(cm[None, None], logits, -1e30)
    elif window:
        raise ValueError(f"a window of {window} positions is a causal "
                         f"mask's")
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def fuses_qk_prep(cfg: TransformerConfig, attention_fn,
                  kv_cache=None, kind: str = ATTENTION) -> bool:
    """Whether the `Attention` of a layer of `kind` runs its q/k norms,
    rope and the transposes into the flash kernels' layout as the one
    pass of `ops/attention_prep.py` and not as array passes. Decided by
    what the call can observe: there is such work (q/k norms, or rope in
    a layer of this kind), a head
    is whole lane tiles (the pass slices heads out of lanes), the
    attention function offers the kernels' layout (`from_bhtd`, which
    `make_flash_attention_fn`'s has; ring, Ulysses and the default
    attention take the model's layout), and no cache is being filled
    (serving appends k in the model's layout)."""
    return bool((cfg.qk_norm or cfg.rotates(kind))
                and kv_cache is None
                and attention_prep.supports(cfg.head_width)
                and hasattr(attention_fn, "from_bhtd"))


# what serving lacks for the forms below (`cache_gaps`, which
# `Transformer.__call__(kv_cache=)` and serving/decode.py refuse by)
WINDOW_HAS_NO_CACHE = (
    "a `window_attention` layer cannot decode through the key-value "
    "cache: serving/decode's slotted cache keeps every position of a "
    "slot and its validity mask is the causal one; a window's cache (the "
    "sliding_window latest rows a layer, and a mask that forgets) is not "
    "built")
GATE_HAS_NO_CACHE = (
    "an attention output gate (`attn_output_gate`) is not run through "
    "the key-value cache: serving/decode has been held to no model with "
    "one")


class Attention(nn.Module):
    cfg: TransformerConfig
    attention_fn: Optional[Callable] = None
    # ATTENTION or WINDOW_ATTENTION (ATTENTION_KINDS)
    kind: str = ATTENTION

    @nn.compact
    def __call__(self, x, positions, mask=None, kv_cache=None, layer=0):
        cfg = self.cfg
        B, T, _ = x.shape
        H, KH, D = cfg.num_heads, cfg.kv_heads, cfg.head_width
        window = cfg.sliding_window if self.kind == WINDOW_ATTENTION else 0
        fused = fuses_qk_prep(cfg, self.attention_fn, kv_cache, self.kind)
        dense = functools.partial(
            nn.DenseGeneral, dtype=cfg.dtype, param_dtype=jnp.float32,
            use_bias=cfg.norm == "layernorm",
        )
        # the scopes are names in the step's `op_name`s (utils/scopes.py),
        # not modules: parameter paths are what they were
        with jax.named_scope(scopes.ATTN_PROJ):
            q = dense(features=(H, D), name="query",
                      kernel_init=nn.initializers.xavier_uniform())(x)
            k = dense(features=(KH, D), name="key",
                      kernel_init=nn.initializers.xavier_uniform())(x)
            v = dense(features=(KH, D), name="value",
                      kernel_init=nn.initializers.xavier_uniform())(x)
            if cfg.attn_output_gate:
                # the gate's product among the layer's projections: the
                # benchmark counts it with them (`attn_proj_roofline`)
                gate = dense(features=(H, D), name="gate",
                             kernel_init=nn.initializers.xavier_uniform())(x)
        with jax.named_scope(scopes.ATTN_PREP):
            rope = rope_frequencies(D, cfg.max_seq_len, cfg.rope_theta) \
                if cfg.rotates(self.kind) else None
            if fused:
                # one pass: q and k come back normed and rotated in the
                # kernels' [B, H, T, D]; the parameters are RMSNorm's
                q_scale = k_scale = None
                if cfg.qk_norm:
                    q_scale = _NormScale(name="q_norm")(D)
                    k_scale = _NormScale(name="k_norm")(D)
                q, k = attention_prep.qk_prep(
                    q, k, q_scale, k_scale,
                    rope and attention_prep.rope_rows(*rope, positions),
                    cfg.layernorm_epsilon)
                v = v.transpose(0, 2, 1, 3)
            else:
                if cfg.qk_norm:
                    q = RMSNorm(epsilon=cfg.layernorm_epsilon,
                                dtype=cfg.dtype, name="q_norm")(q)
                    k = RMSNorm(epsilon=cfg.layernorm_epsilon,
                                dtype=cfg.dtype, name="k_norm")(k)
                if rope:
                    q = apply_rope(q, *rope, positions)
                    k = apply_rope(k, *rope, positions)
            if cfg.attention_multiplier is not None:
                # every attention here scales its scores by 1/sqrt(D):
                # the model's own scale rides on q (0.125 for a
                # multiplier of 1/64 at D = 64, exact in any dtype)
                q = q * jnp.asarray(
                    cfg.attention_multiplier * np.sqrt(D), q.dtype)
        if kv_cache is not None:
            # autoregressive serving path (serving/decode.py): the
            # new tokens' K/V append into the slotted cache (quantized
            # there when the cache is int8 — rows are quantized once,
            # on write, never re-quantized) and attention runs over
            # the full cache slice under the position-validity mask
            if mask is not None:
                raise ValueError(
                    "kv_cache decoding derives its own validity mask "
                    "from positions; an explicit padding mask is not "
                    "composable with it")
            k_full, v_full, valid = kv_cache.update(
                layer, k, v, positions)
            out = cached_attention(q, k_full, v_full, valid)
        elif self.attention_fn is None:
            attn = functools.partial(
                dot_product_attention, causal=cfg.causal,
                diffusion_block=cfg.diffusion_block, window=window)
            out = attn(q, k, v, mask=mask)
        else:
            attn = self.attention_fn.from_bhtd if fused \
                else self.attention_fn
            if mask is not None:
                raise ValueError(
                    "a custom attention_fn (flash/ring/Ulysses) takes only "
                    "(q, k, v) and would silently drop the padding mask; "
                    "pre-mask the inputs or use the default attention"
                )
            # a window is an argument of the call: the layers of a
            # model share one function (`make_flash_attention_fn`), and
            # one that takes no window (ring, Ulysses) says so itself
            out = attn(q, k, v, window=window) if window else attn(q, k, v)
        if cfg.attn_output_gate:
            with jax.named_scope(scopes.ATTN_PREP):
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(cfg.dtype)
        with jax.named_scope(scopes.ATTN_PROJ):
            out = nn.DenseGeneral(
                features=cfg.hidden_size, axis=(-2, -1), dtype=cfg.dtype,
                param_dtype=jnp.float32, use_bias=cfg.norm == "layernorm",
                name="out",
                kernel_init=nn.initializers.xavier_uniform(),
            )(out)
        return out


class Mlp(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        dense = functools.partial(
            nn.Dense, dtype=cfg.dtype, param_dtype=jnp.float32,
            use_bias=cfg.norm == "layernorm",
        )
        if cfg.activation == "swiglu":
            gate = dense(cfg.mlp_dim, name="gate",
                         kernel_init=nn.initializers.xavier_uniform())(x)
            up = dense(cfg.mlp_dim, name="up",
                       kernel_init=nn.initializers.xavier_uniform())(x)
            h = nn.silu(gate) * up
        else:
            h = dense(cfg.mlp_dim, name="fc1",
                      kernel_init=nn.initializers.xavier_uniform())(x)
            h = nn.gelu(h)
        return dense(cfg.hidden_size, name="fc2",
                     kernel_init=nn.initializers.xavier_uniform())(h)


# what serving lacks for a state-space layer (`cache_gaps`; `Block`
# raises it too where a cache is handed to one)
MAMBA2_HAS_NO_CACHE = (
    "a `mamba2` layer cannot decode through a key-value cache: it keeps "
    "no keys and values but a recurrent state (heads x d_head x d_state) "
    "and the convolution's last d_conv - 1 inputs a sequence, and "
    "serving/decode's slotted cache holds neither")


ROUTED_FORMS_HAVE_NO_CACHE = (
    "serving/decode has been held to no routed MLP that scores by "
    "sigmoid with a corrected choice, scales its weights, has a shared "
    "expert or stands behind leading dense layers")


def cache_gaps(cfg: TransformerConfig) -> list:
    """What of `cfg` the key-value cache path (serving/decode,
    `Transformer.__call__(kv_cache=)`) cannot run, each by the layers'
    or the field's name with what is missing; empty where it can run
    all of it."""
    specs = layer_specs(cfg)

    def layers(kind):
        return [spec.index for spec in specs if spec.kind == kind]

    gaps = []
    if layers(MAMBA2):
        gaps.append(f"layers {layers(MAMBA2)} of this model are "
                    f"state-space layers: " + MAMBA2_HAS_NO_CACHE)
    if layers(WINDOW_ATTENTION):
        gaps.append(f"layers {layers(WINDOW_ATTENTION)} are "
                    f"`window_attention` layers: " + WINDOW_HAS_NO_CACHE)
    if cfg.attn_output_gate:
        gaps.append("attn_output_gate: " + GATE_HAS_NO_CACHE)
    forms = {"score_func": cfg.score_func != "softmax",
             "routed_scaling_factor": cfg.routed_scaling_factor != 1.0,
             "shared_experts": cfg.shared_experts > 0,
             "dense_layers": cfg.dense_layers > 0}
    named = [f"{name} {getattr(cfg, name)!r}"
             for name, stated in forms.items() if stated]
    if cfg.num_experts and named:
        gaps.append(", ".join(named) + ": " + ROUTED_FORMS_HAVE_NO_CACHE)
    return gaps


def scaled(x, multiplier: float):
    """`x` times one of the model's stream multipliers
    (`embedding_multiplier`, `residual_multiplier`, 1 /
    `logits_scaling`), in `x`'s dtype; a neutral 1.0 adds no
    operation."""
    if multiplier == 1.0:
        return x
    return x * jnp.asarray(multiplier, x.dtype)


class Block(nn.Module):
    cfg: TransformerConfig
    # the layer's mixer (`spec.kind`): `Attention` under the module name
    # `attn` (of that kind: a window layer's is told so) or the
    # state-space mixer under `mamba`; its MLP under `mlp`, the routed
    # one where `spec.routed`
    spec: LayerSpec
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, positions, mask=None, kv_cache=None):
        cfg, spec = self.cfg, self.spec
        y = _norm(cfg, "ln_attn")(x)
        if spec.kind == MAMBA2:
            if kv_cache is not None:
                raise ValueError(MAMBA2_HAS_NO_CACHE)
            mixed = cfg.mamba2_mixer(name="mamba")(y)
        else:
            mixed = Attention(cfg, attention_fn=self.attention_fn,
                              kind=spec.kind,
                              name="attn")(y, positions, mask,
                                           kv_cache=kv_cache,
                                           layer=spec.index)
        x = _joined(cfg, x, mixed, "ln_post_attn")
        y = _norm(cfg, "ln_mlp")(x)
        mlp = cfg.routed_mlp(name="mlp") if spec.routed \
            else Mlp(cfg, name="mlp")
        return _joined(cfg, x, mlp(y), "ln_post_mlp")


def _last_block_keeps(prim, *_, **params) -> bool:
    """`jax.checkpoint` policy of the last block under `remat`: is this
    primitive's result kept from the block's first run? Kept is what is
    dear to rebuild and small to hold: the two flash calls' results
    (attention's out and lse), the matrix products' (the projections,
    the router's scores) and the router's choice (`top_k` and the sort
    of the pairs, about a MiB each). Rebuilt are the elementwise passes
    (norms, rope, SwiGLU), whose float32 intermediates are 2-4 times an
    activation each and would all be alive at once if kept, and the
    routed MLP's rows and expert products (compiled for the chip, the
    step with `ragged_dot_general` kept too needs 1.1 GiB more: the
    same primitive runs in the scan over further products; PERF.md
    section 6, PR 40). A kernel call is kept by its name and not as a
    `pallas_call`: the one pass of `ops/attention_prep.py` is a call
    too, and its results (q and k after norm and rope in the kernels'
    layout, 144 MiB in `sdar_bd_s4096`) are of the rebuilt kind, cheap
    to make again from the kept projections (compiled with them kept:
    +0.80% `step_hbm_gib` for at most ~2 ms; PR 40). The state-space
    scan's forward kernel (`ops/ssd_scan.py`) is of the kept kind, as
    the plain scan's products were: y and the states between chunks
    (128 + 64 MiB in `granite_h_lm`), so that the last block does not
    run it a second time."""
    if prim.name == "pallas_call":
        return params.get("name") in (scopes.FLASH_FWD, scopes.FLASH_BWD,
                                      scopes.SSD_SCAN_FWD)
    return prim.name in ("dot_general", "top_k", "sort")


def build_block(cfg: TransformerConfig, spec: LayerSpec,
                attention_fn: Optional[Callable] = None,
                name: Optional[str] = None) -> Block:
    """The block of layer `spec`, rematerialised as the spec says: the
    one place the package wraps a `Block` in `nn.remat`. Inside a
    compact method it is that module's child `name`; with no parent
    (ops/overlap's stages, parallel/pipeline's stage) it is applied
    alone over the layer's sub-tree of the parameters."""
    block = Block
    if spec.remat is not None:
        block = nn.remat(
            Block, static_argnums=(),
            policy=_last_block_keeps if spec.remat == KEEP_PRODUCTS
            else None)
    return block(cfg, spec, attention_fn=attention_fn, name=name)


# The stack's two ends, each written once as a function that makes its
# Flax children in the compact method that calls it: `Transformer`'s
# own (so that `tok_emb`, `pos_emb`, `ln_final` and `lm_head` stay at
# the top of the parameter tree, beside the `block_<i>`), or `Embedding`
# / `LmHead`'s, which run one end alone over its sub-tree.

def _token_embedding(cfg: TransformerConfig) -> nn.Embed:
    """`tok_emb`: the tokens' embedding and, under `tie_embeddings`,
    the head's matrix. A compact method builds it once and hands it to
    whichever ends it runs."""
    return nn.Embed(
        cfg.vocab_size, cfg.hidden_size, dtype=cfg.dtype,
        param_dtype=jnp.float32, name="tok_emb",
        embedding_init=nn.initializers.normal(0.02),
    )


def _embed(module: nn.Module, emb: nn.Embed, tokens, positions):
    """Tokens to the stream the first block reads; `module` is the
    compact method's, which holds `pos_emb`."""
    cfg = module.cfg
    x = scaled(emb(tokens), cfg.embedding_multiplier)
    if cfg.position == "learned":
        pos_emb = module.param(
            "pos_emb",
            nn.initializers.normal(0.02),
            (cfg.max_seq_len, cfg.hidden_size),
            jnp.float32,
        )
        x = x + pos_emb[positions].astype(cfg.dtype)
    return x


def _final_norm(cfg: TransformerConfig, x):
    """The last block's output as a head reads it: the scaling is on
    the hidden state, so that the model's own head and a caller's fused
    cross entropy read the same state."""
    return scaled(_norm(cfg, "ln_final")(x), 1.0 / cfg.logits_scaling)


def _logits(cfg: TransformerConfig, emb: Optional[nn.Embed], x):
    """The LM head on `_final_norm`'s output (`emb` is read under
    `tie_embeddings` alone). The matmul stays in the model compute
    dtype (bf16 on the MXU fast path — an f32 [B,T,H]x[H,V] here is
    the single largest matmul in the model at a fraction of peak); the
    loss fns upcast the logits to f32 for logsumexp stability."""
    with jax.named_scope(scopes.LOSS_HEAD):
        if cfg.tie_embeddings:
            return emb.attend(x)
        return nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name="lm_head",
            kernel_init=nn.initializers.normal(0.02),
        )(x)


class Embedding(nn.Module):
    """`Transformer`'s embedding alone, over the `embedding_keys` of
    its parameters."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions):
        return _embed(self, _token_embedding(self.cfg), tokens, positions)


class LmHead(nn.Module):
    """`Transformer`'s final norm and head alone, over the `head_keys`
    of its parameters."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        emb = _token_embedding(cfg) if cfg.tie_embeddings else None
        return _logits(cfg, emb, _final_norm(cfg, x))


def embedding_keys(cfg: TransformerConfig) -> tuple:
    """The top-level keys of the parameters that `_embed` reads."""
    return ("tok_emb",) + (("pos_emb",) if cfg.position == "learned"
                           else ())


def head_keys(cfg: TransformerConfig) -> tuple:
    """The top-level keys that `_final_norm` and `_logits` read: an
    untied head never reads `tok_emb`."""
    return ("ln_final", "tok_emb" if cfg.tie_embeddings else "lm_head")


def _report(cfg: TransformerConfig, specs: tuple, attention_fn, kv_cache,
            n_tokens: int, n_positions: int) -> None:
    """The stack's trace-time gauges (docs/metrics.md): what the last
    traced call of the model built, over `n_tokens` tokens in sequences
    of `n_positions`; arithmetic on the specs and the shapes, nothing
    inside the step."""
    if not metrics.enabled():
        return
    # blocks whose whole forward runs again in the backward pass, and
    # those that keep their kernel calls' and matrix products' results
    # (the last one, before a caller's head); 0 and 0 without `remat`
    metrics.trace_gauge(
        "hvd_remat_blocks",
        "Blocks whose whole forward runs again in the backward pass",
        sum(spec.remat == REBUILD_ALL for spec in specs))
    metrics.trace_gauge(
        "hvd_remat_blocks_kept",
        "Blocks under remat that keep their kernel and matmul results "
        "(the last, before a caller's head)",
        sum(spec.remat == KEEP_PRODUCTS for spec in specs))
    kinds = [spec.kind for spec in specs]
    for kind in dict.fromkeys(kinds):
        metrics.trace_gauge(
            "hvd_layers", "Layers of the model by the kind of their mixer",
            kinds.count(kind), kind=kind)
    # attention layers that run q/k norms, rope and the transposes into
    # the flash kernels' layout as the one pass of ops/attention_prep.py,
    # and those that leave them to array passes (or have none)
    fused = [fuses_qk_prep(cfg, attention_fn, kv_cache, kind)
             for kind in kinds if kind in ATTENTION_KINDS]
    metrics.trace_gauge(
        "hvd_attn_prep_fused_layers",
        "Attention layers whose q/k norms, rope and layout are one "
        "Pallas pass", sum(fused))
    metrics.trace_gauge(
        "hvd_attn_prep_plain_layers",
        "Attention layers that leave q/k norms, rope and layout to "
        "array passes", len(fused) - sum(fused))
    # A model's state-space layers, and its routed ones, are all one
    # form or all the other (by shapes alone), and the gauges are set
    # only for a model that has such layers
    state_space_layers = kinds.count(MAMBA2)
    if state_space_layers:
        as_kernels = cfg.mamba2_mixer(parent=None).scans_as_kernels(
            n_positions)
        metrics.trace_gauge(
            "hvd_mamba_scan_kernel_layers",
            "State-space layers whose recurrence is one Pallas kernel a "
            "direction", state_space_layers * as_kernels)
        metrics.trace_gauge(
            "hvd_mamba_scan_plain_layers",
            "State-space layers whose recurrence is plain array "
            "operations", state_space_layers * (not as_kernels))
    routed_layers = sum(spec.routed for spec in specs)
    if routed_layers:
        as_kernels = cfg.routed_mlp(parent=None).experts_as_kernels(
            n_tokens, cfg.hidden_size)
        metrics.trace_gauge(
            "hvd_moe_expert_kernel_layers",
            "Routed layers whose expert products are the grouped-matmul "
            "Pallas kernels", routed_layers * as_kernels)
        metrics.trace_gauge(
            "hvd_moe_expert_plain_layers",
            "Routed layers whose expert products are ragged_dot behind a "
            "cast of the experts", routed_layers * (not as_kernels))


class Transformer(nn.Module):
    """Decoder/encoder stack with LM head; covers GPT-2 (causal + learned
    pos), BERT (bidirectional) and Llama (causal + rope/rms/swiglu)."""

    cfg: TransformerConfig
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, tokens, positions=None, mask=None,
                 return_hidden=False, kv_cache=None):
        """``kv_cache`` opens the autoregressive serving path: a
        duck-typed cache carrier (``update(layer, k, v, positions) ->
        (k_full, v_full, valid)``, serving/decode.SlottedKVCache) whose
        buffers the caller threads through its compiled step. With it,
        ``tokens`` are the NEW tokens only (the whole prompt at
        prefill, one token per sequence at decode) and ``positions``
        their absolute positions; attention runs over the cache, not
        the ``tokens`` window. ``None`` (every training/one-shot path)
        is byte-identical to the pre-cache model."""
        cfg = self.cfg
        B, T = tokens.shape
        gaps = cache_gaps(cfg) if kv_cache is not None else ()
        if gaps:
            raise ValueError("; ".join(gaps))
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
        emb = _token_embedding(cfg)
        x = _embed(self, emb, tokens, positions)
        specs = layer_specs(cfg, callers_head=return_hidden)
        _report(cfg, specs, self.attention_fn, kv_cache, B * T, T)
        for spec in specs:
            block = build_block(cfg, spec, self.attention_fn,
                                name=f"block_{spec.index}")
            if kv_cache is None:
                # training/one-shot path: exact pre-cache call shape so
                # remat'd and jitted programs lower identically
                x = block(x, positions, mask)
            else:
                x = block(x, positions, mask, kv_cache=kv_cache)
        x = _final_norm(cfg, x)
        if return_hidden:
            # pre-head activations for the fused LM-head cross-entropy
            # (ops/fused_cross_entropy.py) — the [B, T, V] logits are
            # never materialized on that path. Initialize with the
            # default return_hidden=False so head params exist.
            return x
        return _logits(cfg, emb, x)


# -- task heads / losses ----------------------------------------------------

def _gather_nll(lg, targets):
    """Per-position cross-entropy via gather: logsumexp(lg) - lg[target].
    One pass over the [B, T, V] logits instead of materializing a
    [B, T, V] float32 one-hot AND a log_softmax copy — at BERT/GPT vocab
    sizes those intermediates are hundreds of MB of pure HBM traffic."""
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return lse - tgt


@jax.named_scope(scopes.LOSS_HEAD)
def causal_lm_loss(logits, tokens, ignore_index: int = -1):
    """Next-token cross-entropy; returns (loss, n_tokens). float32."""
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    valid = targets != ignore_index
    # out-of-range ids (sentinels, padding artifacts) must not index the
    # gather — one_hot gave them a zero row, i.e. zero contribution
    in_range = (targets >= 0) & (targets < lg.shape[-1])
    nll = _gather_nll(lg, jnp.where(in_range, targets, 0))
    nll = jnp.where(valid & in_range, nll, 0.0)
    n = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(nll) / n, n


@jax.named_scope(scopes.LOSS_HEAD)
def mlm_loss(logits, labels, mask_positions):
    """BERT masked-LM loss: `labels` at `mask_positions` (bool [B,T])."""
    lg = logits.astype(jnp.float32)
    in_range = (labels >= 0) & (labels < lg.shape[-1])
    nll = _gather_nll(lg, jnp.where(in_range, labels, 0))
    nll = jnp.where(mask_positions & in_range, nll, 0.0)
    n = jnp.maximum(jnp.sum(mask_positions), 1)
    return jnp.sum(nll) / n, n


def GPT2(cfg: TransformerConfig = GPT2_SMALL, **kw) -> Transformer:
    return Transformer(cfg, **kw)


def Bert(cfg: TransformerConfig = BERT_LARGE, **kw) -> Transformer:
    return Transformer(cfg, **kw)


def Llama(cfg: TransformerConfig = LLAMA2_7B, **kw) -> Transformer:
    return Transformer(cfg, **kw)
