"""ResNet v1/v2 (ImageNet) and CIFAR ResNet.

Reference: ``example/image-classification/symbols/resnet.py:1`` (the v2
pre-activation symbol used for the published throughput/convergence baselines,
BASELINE rows ResNet-152) and ``python/mxnet/gluon/model_zoo/vision/resnet.py``
(v1 + v2 block zoo).  CIFAR variant (depth 20/56/110, 6n+2 basic blocks,
16/32/64 channels) matches ``train_cifar10.py``'s network.

The flagship model for the elastic baseline is ResNet-50 v1
(``example/dynamic-training/train_resnet.py``).
"""

from typing import Any, Sequence, Tuple

import flax.linen as linen
import jax
import jax.numpy as jnp

from dt_tpu.models.common import bn as _bn
from dt_tpu.ops import nn as ops


class BasicBlockV1(linen.Module):
    features: int
    strides: Tuple[int, int] = (1, 1)
    downsample: bool = False
    dtype: Any = jnp.float32

    @linen.compact
    def __call__(self, x, training: bool = True):
        residual = x
        y = linen.Conv(self.features, (3, 3), self.strides, padding="SAME",
                       use_bias=False, dtype=self.dtype)(x)
        y = _bn(training, self.dtype)(y)
        y = jax.nn.relu(y)
        y = linen.Conv(self.features, (3, 3), padding="SAME", use_bias=False,
                       dtype=self.dtype)(y)
        y = _bn(training, self.dtype)(y)
        if self.downsample:
            residual = linen.Conv(self.features, (1, 1), self.strides,
                                  use_bias=False, dtype=self.dtype)(x)
            residual = _bn(training, self.dtype)(residual)
        return jax.nn.relu(y + residual)


class BottleneckV1(linen.Module):
    features: int  # bottleneck width; output is 4x
    strides: Tuple[int, int] = (1, 1)
    downsample: bool = False
    dtype: Any = jnp.float32

    @linen.compact
    def __call__(self, x, training: bool = True):
        residual = x
        y = linen.Conv(self.features, (1, 1), use_bias=False, dtype=self.dtype)(x)
        y = _bn(training, self.dtype)(y)
        y = jax.nn.relu(y)
        y = linen.Conv(self.features, (3, 3), self.strides, padding="SAME",
                       use_bias=False, dtype=self.dtype)(y)
        y = _bn(training, self.dtype)(y)
        y = jax.nn.relu(y)
        y = linen.Conv(self.features * 4, (1, 1), use_bias=False,
                       dtype=self.dtype)(y)
        y = _bn(training, self.dtype)(y)
        if self.downsample:
            residual = linen.Conv(self.features * 4, (1, 1), self.strides,
                                  use_bias=False, dtype=self.dtype)(x)
            residual = _bn(training, self.dtype)(residual)
        return jax.nn.relu(y + residual)


class BasicBlockV2(linen.Module):
    """Pre-activation block (He et al. 2016), the reference's default symbol."""
    features: int
    strides: Tuple[int, int] = (1, 1)
    downsample: bool = False
    dtype: Any = jnp.float32

    @linen.compact
    def __call__(self, x, training: bool = True):
        y = _bn(training, self.dtype)(x)
        y = jax.nn.relu(y)
        residual = x
        if self.downsample:
            residual = linen.Conv(self.features, (1, 1), self.strides,
                                  use_bias=False, dtype=self.dtype)(y)
        y = linen.Conv(self.features, (3, 3), self.strides, padding="SAME",
                       use_bias=False, dtype=self.dtype)(y)
        y = _bn(training, self.dtype)(y)
        y = jax.nn.relu(y)
        y = linen.Conv(self.features, (3, 3), padding="SAME", use_bias=False,
                       dtype=self.dtype)(y)
        return y + residual


class BottleneckV2(linen.Module):
    features: int
    strides: Tuple[int, int] = (1, 1)
    downsample: bool = False
    dtype: Any = jnp.float32

    @linen.compact
    def __call__(self, x, training: bool = True):
        y = _bn(training, self.dtype)(x)
        y = jax.nn.relu(y)
        residual = x
        if self.downsample:
            residual = linen.Conv(self.features * 4, (1, 1), self.strides,
                                  use_bias=False, dtype=self.dtype)(y)
        y = linen.Conv(self.features, (1, 1), use_bias=False, dtype=self.dtype)(y)
        y = _bn(training, self.dtype)(y)
        y = jax.nn.relu(y)
        y = linen.Conv(self.features, (3, 3), self.strides, padding="SAME",
                       use_bias=False, dtype=self.dtype)(y)
        y = _bn(training, self.dtype)(y)
        y = jax.nn.relu(y)
        y = linen.Conv(self.features * 4, (1, 1), use_bias=False,
                       dtype=self.dtype)(y)
        return y + residual


_SPECS = {
    18: ("basic", [2, 2, 2, 2]),
    34: ("basic", [3, 4, 6, 3]),
    50: ("bottleneck", [3, 4, 6, 3]),
    101: ("bottleneck", [3, 4, 23, 3]),
    152: ("bottleneck", [3, 8, 36, 3]),
}
_FILTERS = [64, 128, 256, 512]


class ResNet(linen.Module):
    depth: int = 50
    num_classes: int = 1000
    version: int = 1
    dtype: Any = jnp.float32
    # Per-BLOCK rematerialization (the reference's
    # MXNET_BACKWARD_DO_MIRROR memory mirror, applied at the residual-
    # block granularity its planner used): each block's activations are
    # recomputed during backward instead of stored, so live activation
    # memory is ~one block deep instead of the whole network.  Wrapping
    # the WHOLE forward in jax.checkpoint would NOT save memory (the
    # rematerialized forward is all live at once) — block granularity is
    # what makes it real.
    remat: bool = False

    @linen.compact
    def __call__(self, x, training: bool = True):
        block_type, stages = _SPECS[self.depth]
        if self.version == 1:
            block = BasicBlockV1 if block_type == "basic" else BottleneckV1
        else:
            block = BasicBlockV2 if block_type == "basic" else BottleneckV2
        base_name = block.__name__  # before wrapping: explicit names keep
        # the param tree identical with/without remat (checkpoints
        # interchange; linen.remat's auto-prefix would rename every block)
        if self.remat:
            block = linen.remat(block, static_argnums=(2,))

        x = linen.Conv(64, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                       use_bias=False, dtype=self.dtype)(x)
        if self.version == 1:
            x = _bn(training, self.dtype)(x)
            x = jax.nn.relu(x)
        x = ops.max_pool2d(x, 3, 2, padding=1)

        expansion = 1 if block_type == "basic" else 4
        in_features = 64
        blk_idx = 0
        for stage, (nblk, f) in enumerate(zip(stages, _FILTERS)):
            for i in range(nblk):
                strides = (2, 2) if (i == 0 and stage > 0) else (1, 1)
                down = (i == 0) and (strides != (1, 1) or
                                     in_features != f * expansion)
                x = block(f, strides, down, self.dtype,
                          name=f"{base_name}_{blk_idx}")(x, training)
                blk_idx += 1
                in_features = f * expansion

        if self.version == 2:
            x = _bn(training, self.dtype)(x)
            x = jax.nn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return linen.Dense(self.num_classes, dtype=self.dtype)(x)


class CifarResNet(linen.Module):
    """6n+2 CIFAR ResNet (20/56/110), v2 pre-activation like the reference's
    ``train_cifar10.py`` default (BASELINE config #1).

    ``stochastic_depth``: death rate of the DEEPEST residual block
    (reference ``example/stochastic-depth/sd_cifar10.py``/``sd_module.py``
    — Huang et al. 2016): block l's death probability ramps linearly to
    this value; at train time an identity-shortcut block is skipped with
    that probability (one Bernoulli per block per batch, via the
    ``dropout`` rng stream inside jit — TPU-native, where the reference
    sampled outside the graph and re-bound modules), at eval its
    residual is scaled by the survival probability.  Downsampling blocks
    always run (their shortcut changes shape)."""
    depth: int = 20
    num_classes: int = 10
    dtype: Any = jnp.float32
    remat: bool = False  # per-block memory mirror (see ResNet.remat)
    stochastic_depth: float = 0.0

    @linen.compact
    def __call__(self, x, training: bool = True):
        assert (self.depth - 2) % 6 == 0, "CIFAR ResNet depth must be 6n+2"
        n = (self.depth - 2) // 6
        block = linen.remat(BasicBlockV2, static_argnums=(2,)) \
            if self.remat else BasicBlockV2
        x = linen.Conv(16, (3, 3), padding="SAME", use_bias=False,
                       dtype=self.dtype)(x)
        in_f = 16
        blk_idx = 0
        total = 3 * n
        for stage, f in enumerate([16, 32, 64]):
            for i in range(n):
                strides = (2, 2) if (i == 0 and stage > 0) else (1, 1)
                down = (i == 0) and (strides != (1, 1) or in_f != f)
                # explicit names: param tree identical with/without remat
                y = block(f, strides, down, self.dtype,
                          name=f"BasicBlockV2_{blk_idx}")(x, training)
                if self.stochastic_depth > 0 and not down:
                    # y == x + F(x) for identity-shortcut blocks, so
                    # (y - x) recovers the residual branch
                    p_death = self.stochastic_depth * (blk_idx + 1) / total
                    if training:
                        keep = jax.random.bernoulli(
                            self.make_rng("dropout"), 1.0 - p_death)
                        x = x + jnp.where(keep, y - x, 0.0).astype(x.dtype)
                    else:
                        x = x + ((1.0 - p_death)
                                 * (y - x)).astype(x.dtype)
                else:
                    x = y
                blk_idx += 1
                in_f = f
        x = _bn(training, self.dtype)(x)
        x = jax.nn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return linen.Dense(self.num_classes, dtype=self.dtype)(x)
