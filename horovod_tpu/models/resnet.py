"""ResNet v1.5 family, TPU-first.

The benchmark model of both the reference's headline numbers
(docs/benchmarks.rst: ResNet-101 @ 512 GPUs ~90% scaling;
examples/pytorch_synthetic_benchmark.py defaults to torchvision resnet50)
and this repo's BASELINE.md target (ResNet-50 images/sec/chip).

TPU-first choices:
* NHWC layout (XLA:TPU's native conv layout; NCHW forces transposes).
* ``compute_dtype=bfloat16`` runs convs/matmuls on the MXU at full rate
  while parameters and batch-norm statistics stay fp32.
* v1.5 stride placement (stride in the 3x3, not the 1x1) — the variant the
  reference benchmarks actually run (torchvision's resnet50).
* Optional cross-replica batch norm via horovod_tpu.parallel.SyncBatchNorm.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .. import scopes

ModuleDef = Any


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3(stride) -> 1x1 with projection shortcut (v1.5)."""

    features: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.features, (1, 1), use_bias=False, name="conv1")(x)
        y = self.norm(name="bn1")(y)
        y = self.act(y)
        y = self.conv(
            self.features, (3, 3), self.strides, use_bias=False, name="conv2"
        )(y)
        y = self.norm(name="bn2")(y)
        y = self.act(y)
        y = self.conv(
            self.features * 4, (1, 1), use_bias=False, name="conv3"
        )(y)
        # zero-init the last BN scale: identity residual at init (the
        # standard trick the reference's Keras example enables via
        # resnet50's `zero_gamma`; helps large-batch warmup)
        y = self.norm(name="bn3", scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.features * 4,
                (1, 1),
                self.strides,
                use_bias=False,
                name="proj_conv",
            )(residual)
            residual = self.norm(name="proj_bn")(residual)
        return self.act(residual + y)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 (ResNet-18/34)."""

    features: int
    strides: Tuple[int, int] = (1, 1)
    conv: ModuleDef = nn.Conv
    norm: ModuleDef = nn.BatchNorm
    act: Callable = nn.relu

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(
            self.features, (3, 3), self.strides, use_bias=False, name="conv1"
        )(x)
        y = self.norm(name="bn1")(y)
        y = self.act(y)
        y = self.conv(self.features, (3, 3), use_bias=False, name="conv2")(y)
        y = self.norm(name="bn2", scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(
                self.features,
                (1, 1),
                self.strides,
                use_bias=False,
                name="proj_conv",
            )(residual)
            residual = self.norm(name="proj_bn")(residual)
        return self.act(residual + y)


def space_to_depth(x: jnp.ndarray, block: int = 2) -> jnp.ndarray:
    """NHWC space-to-depth: (N, H, W, C) -> (N, H/b, W/b, C*b*b).

    The MLPerf-era TPU stem trick: folding 2x2 spatial patches into channels
    turns the 7x7/s2 stem conv (3 input channels — 3/128ths of an MXU column)
    into a 4x4/s1 conv over 12 channels, quadrupling stem MXU utilization.
    """
    n, h, w, c = x.shape
    x = x.reshape(n, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(n, h // block, w // block, c * block * block)


class ResNet(nn.Module):
    """Configurable ResNet (stage sizes select 18/34/50/101/152)."""

    stage_sizes: Sequence[int]
    block: ModuleDef = BottleneckBlock
    num_classes: int = 1000
    num_filters: int = 64
    compute_dtype: jnp.dtype = jnp.bfloat16
    axis_name: Optional[str] = None  # set for cross-replica batch norm
    # Space-to-depth stem (MLPerf TPU ResNet recipe): same receptive-field
    # family as the 7x7/s2 stem but MXU-dense. Off by default so the
    # headline model matches the reference architecture exactly.
    s2d_stem: bool = False
    # Inter-block activation storage dtype (e.g. jnp.float8_e4m3fn): the
    # step is HBM-bandwidth-bound (docs/performance.md), so storing the
    # block-boundary activations at 1 B/elt halves the dominant traffic.
    # Lossy — changes the numerics contract — so opt-in only.
    act_store_dtype: Optional[Any] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.axis_name is not None:
            from ..parallel.sync_batch_norm import SyncBatchNorm  # noqa: PLC0415

            norm = partial(
                SyncBatchNorm,
                axis_name=self.axis_name,
                use_running_average=not train,
                momentum=0.9,
            )
        else:
            # dtype=compute_dtype keeps the normalize/scale/shift elementwise
            # chain in bf16 (half the HBM traffic of f32 activations, and it
            # fuses with the surrounding convs); flax still computes the
            # batch statistics in f32 internally and stores running stats in
            # f32, so numerics match the reference's fp32-stats BN.
            norm = partial(
                nn.BatchNorm,
                use_running_average=not train,
                momentum=0.9,
                dtype=self.compute_dtype,
            )
        conv = partial(nn.Conv, dtype=self.compute_dtype, param_dtype=jnp.float32)
        if self.act_store_dtype is not None:
            # Quantized ReLU: every conv input (= every ReLU output) is
            # materialized at 1 B/elt in HBM; convs read f8 and widen
            # in-register to the compute dtype.  (Quantizing the backward
            # cotangent to e5m2 via a custom VJP was tried and rejected:
            # it stalled XLA:TPU compilation for >9 minutes.)
            def act(y):
                return jnp.asarray(
                    jnp.asarray(nn.relu(y), self.act_store_dtype),
                    self.compute_dtype,
                )
        else:
            act = nn.relu

        # flax names the blocks (``stage<i>_block<j>``); what the model
        # does outside any submodule (the input cast, the max-pool, the
        # global mean) gets a scope here, so a device trace has no
        # nameless part.  Scopes are metadata: the parameter tree is
        # ``conv_init``, ``bn_init``, the blocks and ``head`` as before.
        with jax.named_scope(scopes.STEM):
            x = jnp.asarray(x, self.compute_dtype)
            if self.s2d_stem:
                x = space_to_depth(x, 2)
                x = conv(
                    self.num_filters,
                    (4, 4),
                    (1, 1),
                    padding=[(1, 2), (1, 2)],
                    use_bias=False,
                    name="conv_init",
                )(x)
            else:
                x = conv(
                    self.num_filters,
                    (7, 7),
                    (2, 2),
                    padding=[(3, 3), (3, 3)],
                    use_bias=False,
                    name="conv_init",
                )(x)
            x = norm(name="bn_init")(x)
            x = act(x)
            x = nn.max_pool(x, (3, 3), strides=(2, 2),
                            padding=((1, 1), (1, 1)))
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = self.block(
                    self.num_filters * 2**i,
                    strides=strides,
                    conv=conv,
                    norm=norm,
                    act=act,
                    name=f"stage{i+1}_block{j+1}",
                )(x)
        with jax.named_scope(scopes.HEAD):
            x = jnp.mean(x, axis=(1, 2))
            x = nn.Dense(
                self.num_classes, dtype=jnp.float32,
                param_dtype=jnp.float32, name="head",
            )(jnp.asarray(x, jnp.float32))
            return x


ResNet18 = partial(ResNet, stage_sizes=[2, 2, 2, 2], block=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=[3, 4, 6, 3], block=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=[3, 4, 23, 3], block=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=[3, 8, 36, 3], block=BottleneckBlock)
