"""ResNet family — the reference ImageNet example's flagship model.

Reference being rebuilt (path unverified, SURVEY.md provenance):
〔examples/imagenet/models/resnet50.py〕 — the ResNet-50 used for the
north-star benchmark (BASELINE.json configs[1], configs[4]; the "ImageNet in
15 minutes" model of arXiv:1711.04325).

TPU-native design notes:

* NHWC layout (XLA's native TPU conv layout) with a ``dtype`` knob so the
  convs/matmuls run in bfloat16 on the MXU while parameters and BatchNorm
  statistics stay float32 (``param_dtype``).
* BatchNorm uses *local* per-device statistics during training — the
  reference's semantics (SURVEY.md §7 hard part 5); running stats live in
  the ``batch_stats`` collection and are synced on demand by
  ``AllreducePersistent``, never psum-ed inside the step.
* The generic :class:`ResNet` also yields ResNet-18/34/101/152 from stage
  sizes, and a width knob small enough to unit-test on the CPU mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

ModuleDef = Any

#: remat_policy zoo swept by ``benchmarks/run_configs.py --tune-remat``.
REMAT_POLICIES = ("none", "block", "norm")


def _tag(x):
    """Name conv outputs (= norm inputs) for checkpoint policies.

    Identity unless a ``remat_policy="norm"`` wrapper references the name:
    that policy saves exactly these boundaries and recomputes the cheap
    normalize/ReLU tail in the backward pass.
    """
    return checkpoint_name(x, "norm_in")


def _norm_relu(norm: ModuleDef, x, **kwargs):
    """norm -> ReLU, fused into one kernel when the norm class supports it.

    ``ops.FusedBatchNormAct`` advertises ``supports_fused_relu`` and takes
    the ReLU along on the same HBM traversal; any other ``norm_cls`` (the
    default ``nn.BatchNorm`` included) keeps the reference unfused path.
    """
    cls = norm.func if isinstance(norm, partial) else norm
    if getattr(cls, "supports_fused_relu", False):
        return norm(fuse_relu=True, **kwargs)(x)
    return nn.relu(norm(**kwargs)(x))


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with projection shortcut on shape change."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = _tag(self.conv(self.filters, (1, 1))(x))
        y = _norm_relu(self.norm, y)
        y = _tag(self.conv(self.filters, (3, 3), self.strides)(y))
        y = _norm_relu(self.norm, y)
        y = _tag(self.conv(self.filters * 4, (1, 1))(y))
        # Zero-init the last BN scale so each block starts as identity —
        # standard large-batch ResNet recipe (matches the reference era's
        # training tricks for the 32k-batch runs).  No ReLU here: the
        # activation lands after the residual add.
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = _tag(self.conv(self.filters * 4, (1, 1), self.strides,
                                      name="conv_proj")(residual))
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 residual block (ResNet-18/34)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = _tag(self.conv(self.filters, (3, 3), self.strides)(x))
        y = _norm_relu(self.norm, y)
        y = _tag(self.conv(self.filters, (3, 3))(y))
        y = self.norm(scale_init=nn.initializers.zeros_init())(y)
        if residual.shape != y.shape:
            residual = _tag(self.conv(self.filters, (1, 1), self.strides,
                                      name="conv_proj")(residual))
            residual = self.norm(name="norm_proj")(residual)
        return nn.relu(residual + y)


class ResNet(nn.Module):
    """Generic ResNet over NHWC inputs.

    ``__call__(x, train=True)``; when ``train`` the BatchNorm layers use the
    minibatch (local-device) statistics and update ``batch_stats``.
    """

    stage_sizes: Sequence[int]
    block_cls: ModuleDef = BottleneckBlock
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.float32
    momentum: float = 0.9
    norm_cls: Any = None  # default nn.BatchNorm; swap for perf probes/variants
    remat_policy: str = "none"  # "none" | "block" (full nn.remat) | "norm"
    #  ("norm" saves only the checkpoint_name'd conv outputs at norm
    #   boundaries and recomputes the normalize/ReLU tail in backward —
    #   swept by benchmarks/run_configs.py --tune-remat)

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = partial(nn.Conv, use_bias=False, dtype=self.dtype,
                       param_dtype=jnp.float32, padding="SAME")
        norm = partial(self.norm_cls or nn.BatchNorm,
                       use_running_average=not train,
                       momentum=self.momentum, epsilon=1e-5,
                       dtype=self.dtype, param_dtype=jnp.float32)
        x = x.astype(self.dtype)
        x = conv(self.num_filters, (7, 7), (2, 2), name="conv_init")(x)
        x = _norm_relu(norm, _tag(x), name="bn_init")
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        if self.remat_policy == "none":
            block_cls = self.block_cls
        elif self.remat_policy == "block":
            block_cls = nn.remat(self.block_cls)
        elif self.remat_policy == "norm":
            block_cls = nn.remat(
                self.block_cls,
                policy=jax.checkpoint_policies.save_only_these_names(
                    "norm_in"))
        else:
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r}: "
                f"expected one of {REMAT_POLICIES}")
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block_cls(self.num_filters * 2 ** i,
                              conv=conv, norm=norm, strides=strides)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32)(x)
        return x.astype(jnp.float32)


ResNet18 = partial(ResNet, stage_sizes=(2, 2, 2, 2), block_cls=BasicBlock)
ResNet34 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block_cls=BasicBlock)
ResNet50 = partial(ResNet, stage_sizes=(3, 4, 6, 3), block_cls=BottleneckBlock)
ResNet101 = partial(ResNet, stage_sizes=(3, 4, 23, 3), block_cls=BottleneckBlock)
ResNet152 = partial(ResNet, stage_sizes=(3, 8, 36, 3), block_cls=BottleneckBlock)
