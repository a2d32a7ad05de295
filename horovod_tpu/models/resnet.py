"""ResNet v1.5 family (ResNet-50/101/152) — the synthetic-benchmark model.

Reference vehicle: examples/pytorch/pytorch_synthetic_benchmark.py and
examples/tensorflow2/tensorflow2_synthetic_benchmark.py (torchvision /
keras ResNet50; BASELINE.md rows 1-4 are ResNet/Inception/VGG scaling).

TPU-first choices: NHWC layout (TPU conv native), bfloat16 compute with
float32 batch-norm statistics and parameters, v1.5 stride placement
(stride on the 3x3, like torchvision), SyncBatchNorm optional via
horovod_tpu.optim.sync_batch_norm (the reference ships hvd.SyncBatchNorm,
torch/sync_batch_norm.py:40).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef
    act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = self.act(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.filters * 4, (1, 1), self.strides, name="conv_proj"
            )(residual)
            residual = self.norm(name="norm_proj")(residual)
        return self.act(residual + y)


def space_to_depth(x, block: int = 2):
    """[B, H, W, C] → [B, H/b, W/b, C·b²] (pixel-shuffle inverse)."""
    B, H, W, C = x.shape
    if H % block or W % block:
        raise ValueError(
            f"space_to_depth needs H and W divisible by {block}; "
            f"got {H}x{W} (pad or resize the input)"
        )
    x = x.reshape(B, H // block, block, W // block, block, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        B, H // block, W // block, C * block * block
    )


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    norm_cls: Optional[ModuleDef] = None  # override e.g. with SyncBatchNorm
    # "conv" = the paper's 7x7/s2 stem; "space_to_depth" rewrites it as
    # a 2x2 pixel-unshuffle + 4x4/s1 conv on 12 channels — equivalent
    # downsampling with an 8x8 effective footprint (the MLPerf transform
    # zero-pads the 7x7 kernel to 8x8), and the MXU sees 12 input
    # channels instead of 3 (a 3-channel conv leaves >95% of the lanes
    # idle)
    stem: str = "conv"

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(
            nn.Conv, use_bias=False, dtype=self.dtype, padding="SAME"
        )
        if self.norm_cls is not None:
            norm = functools.partial(self.norm_cls, use_running_average=not train)
        else:
            norm = functools.partial(
                nn.BatchNorm,
                use_running_average=not train,
                momentum=0.9,
                epsilon=1e-5,
                dtype=self.dtype,
                param_dtype=jnp.float32,
            )
        x = x.astype(self.dtype)
        if self.stem == "space_to_depth":
            x = space_to_depth(x, 2)
            x = conv(self.num_filters, (4, 4), (1, 1), name="conv_init")(x)
        elif self.stem == "conv":
            x = conv(self.num_filters, (7, 7), (2, 2), name="conv_init")(x)
        else:
            raise ValueError(
                f"unknown stem {self.stem!r}: expected 'conv' or "
                "'space_to_depth'"
            )
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(
                    filters=self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    act=nn.relu,
                )(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32,
                     param_dtype=jnp.float32)(x)
        return x


ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3])
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3])
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3])
