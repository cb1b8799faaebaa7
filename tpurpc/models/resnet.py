"""ResNet-50 in flax.linen — the serving flagship (BASELINE.json config #5:
"JAX ResNet-50 inference server: image request tensors zero-copy RDMA→HBM").

Standard bottleneck-v1.5 architecture (stride-2 on the 3x3), NHWC layout —
the TPU-native choice: XLA's conv tiling prefers channels-last, and bfloat16
activations keep the MXU at full rate. The reference has no models at all
(SURVEY.md §2.7); this exists to put a real MXU-bound workload behind the RPC
plane, per BASELINE.

Inference entry: :func:`resnet50`, then ``model.apply({'params': p}, x)``.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int]
    conv: ModuleDef
    norm: ModuleDef

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = nn.relu(y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    stage_sizes: Sequence[int]
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x, train: bool = False):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        norm = functools.partial(nn.BatchNorm, use_running_average=not train,
                                 momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2),
                 padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="bn_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = BottleneckBlock(self.num_filters * 2 ** i, strides,
                                    conv, norm)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


def resnet50(num_classes: int = 1000, dtype=jnp.float32) -> ResNet:
    return ResNet(stage_sizes=(3, 4, 6, 3), num_classes=num_classes,
                  dtype=dtype)


def resnet18_thin(num_classes: int = 1000, dtype=jnp.float32) -> ResNet:
    """Small stand-in with the same code path for fast tests/compile checks."""
    return ResNet(stage_sizes=(1, 1), num_classes=num_classes,
                  num_filters=8, dtype=dtype)


def init_resnet(key, model: ResNet, image_size: int = 224,
                batch: int = 1):
    x = jnp.zeros((batch, image_size, image_size, 3), jnp.float32)
    # one jitted program, not one eager dispatch (and one compile) per
    # initializer: ResNet-50 has 161 parameter arrays
    return jax.jit(functools.partial(model.init, train=False))(key, x)


def make_infer_fn(model: ResNet) -> Callable:
    """Jittable (variables, images) → logits, inference mode."""
    def infer(variables, images):
        return model.apply(variables, images, train=False)
    return infer
