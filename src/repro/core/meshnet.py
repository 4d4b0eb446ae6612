"""MeshNet — the paper's volumetric segmentation model (Table I / Fig. 2).

A feed-forward 3-D CNN whose layers are 3x3x3 *dilated* convolutions with
dilation schedule 1,2,4,8,16,8,4,2,1 followed by a 1x1x1 classifier head.
Each hidden layer = Conv3d -> BatchNorm3d -> ReLU -> Dropout3d.

The network is intentionally tiny (the paper's GWM full-volume model is
0.022 MB / 5.6k params) — the whole point of Brainchop is that a model this
small, with a receptive field this large, segments a full 256^3 volume in
one pass inside a memory-constrained runtime.

Layout convention: volumes are channels-last ``(B, D, H, W, C)`` — channels
on the minor (lane) axis, which is what the Pallas kernel wants on TPU.

Params are a list-of-dicts pytree (one entry per layer) so the streaming
executor (core/streaming.py) can stack them and ``lax.scan`` layer-by-layer,
mirroring Brainchop's progressive layer-wise inference with disposal.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Params = Any  # pytree


@dataclasses.dataclass(frozen=True)
class MeshNetConfig:
    """Hyperparameters for a MeshNet model.

    Defaults reproduce Table I (the "typical GWM" stride-1 model):
    9 dilated 3^3 conv layers at 5 channels + a 1^3 head to 3 classes
    (background / gray matter / white matter).
    """

    in_channels: int = 1
    channels: int = 5
    num_classes: int = 3
    dilations: Sequence[int] = (1, 2, 4, 8, 16, 8, 4, 2, 1)

    def __post_init__(self):
        # Keep the config hashable (it crosses jit boundaries as a static
        # argument in core/executors.py) even when dilations arrive as a list.
        object.__setattr__(self, "dilations", tuple(self.dilations))
    kernel_size: int = 3
    dropout_rate: float = 0.0  # inference default; training uses >0
    use_batchnorm: bool = True
    dtype: Any = jnp.float32

    @property
    def num_layers(self) -> int:
        return len(self.dilations) + 1  # + classifier head

    def param_count(self) -> int:
        """Conv parameters only — the paper's convention: the GWM light
        model reports 5598 = 140 + 8*680 + 18 (Table IV excludes BN)."""
        k = self.kernel_size ** 3
        n = self.in_channels * self.channels * k + self.channels  # layer 1
        for _ in self.dilations[1:]:
            n += self.channels * self.channels * k + self.channels
        n += self.channels * self.num_classes + self.num_classes  # 1x1x1 head
        return n


# Paper model zoo (Table IV): name -> (channels, dilations, classes).
# Layer counts in Table IV count BN/activation stages; here a "layer" is one
# conv block. 5.6k ~= channels=5 GWM; 23k ~= channels=10 "large"; the
# failsafe/subvolume variants use wider channels (96k ~= 21ch).
# atlas_104 is Table IV's 86,372 exactly under param_count: 21 channels and
# 8 convs (588 + 7 * 11,928 + 2,288), the only width and depth that give it.
# The paper states no schedule for 8 convs; this one is Table I's less its
# last layer (dilations move neither the params nor the FLOPs, only halos).
PAPER_MODELS = {
    "gwm_light": MeshNetConfig(channels=5, num_classes=3),
    "gwm_large": MeshNetConfig(channels=10, num_classes=3),
    "brain_mask_fast": MeshNetConfig(channels=5, num_classes=2),
    "brain_mask_high_acc": MeshNetConfig(channels=10, num_classes=2),
    "extract_brain_fast": MeshNetConfig(channels=5, num_classes=2),
    "subvolume_gwm_failsafe": MeshNetConfig(channels=21, num_classes=3),
    "atlas_50": MeshNetConfig(channels=10, num_classes=50),
    "atlas_104": MeshNetConfig(
        channels=21, num_classes=104, dilations=(1, 2, 4, 8, 16, 8, 4, 2)
    ),
}


def _conv_init(key, kshape, dtype):
    fan_in = int(np.prod(kshape[:-1]))
    std = float(np.sqrt(2.0 / fan_in))  # He init for ReLU nets
    return jax.random.normal(key, kshape, dtype) * jnp.asarray(std, dtype)


def init(key: jax.Array, cfg: MeshNetConfig) -> Params:
    """Initialize MeshNet params: list of per-layer dicts."""
    k = cfg.kernel_size
    layers = []
    in_ch = cfg.in_channels
    keys = jax.random.split(key, len(cfg.dilations) + 1)
    for i, _ in enumerate(cfg.dilations):
        layer = {
            "w": _conv_init(keys[i], (k, k, k, in_ch, cfg.channels), cfg.dtype),
            "b": jnp.zeros((cfg.channels,), cfg.dtype),
        }
        if cfg.use_batchnorm:
            layer["bn_scale"] = jnp.ones((cfg.channels,), cfg.dtype)
            layer["bn_bias"] = jnp.zeros((cfg.channels,), cfg.dtype)
            # Running stats (inference-mode BN). Updated by the trainer.
            layer["bn_mean"] = jnp.zeros((cfg.channels,), cfg.dtype)
            layer["bn_var"] = jnp.ones((cfg.channels,), cfg.dtype)
        layers.append(layer)
        in_ch = cfg.channels
    head = {
        "w": _conv_init(keys[-1], (1, 1, 1, cfg.channels, cfg.num_classes), cfg.dtype),
        "b": jnp.zeros((cfg.num_classes,), cfg.dtype),
    }
    return {"layers": layers, "head": head}


def dilated_conv3d(x: jax.Array, w: jax.Array, b: jax.Array, dilation: int) -> jax.Array:
    """'Same'-padded 3-D dilated convolution, channels-last.

    x: (B, D, H, W, Cin); w: (k, k, k, Cin, Cout). Padding = dilation so the
    output shape equals the input shape for k=3 (Table I pads == dilations).
    """
    k = w.shape[0]
    pad = dilation * (k - 1) // 2
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(1, 1, 1),
        padding=[(pad, pad)] * 3,
        rhs_dilation=(dilation,) * 3,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
    )
    return out + b


def batchnorm(x, layer, *, training: bool, eps: float = 1e-5):
    """BatchNorm3d over (B, D, H, W); returns (y, batch_mean, batch_var)."""
    if training:
        mean = jnp.mean(x, axis=(0, 1, 2, 3))
        var = jnp.var(x, axis=(0, 1, 2, 3))
    else:
        mean, var = layer["bn_mean"], layer["bn_var"]
    y = (x - mean) * jax.lax.rsqrt(var + eps) * layer["bn_scale"] + layer["bn_bias"]
    return y, mean, var


def apply_layer(layer, x, dilation, cfg: MeshNetConfig, *, training=False, rng=None):
    """One MeshNet block: conv -> BN -> ReLU -> dropout."""
    x = dilated_conv3d(x, layer["w"], layer["b"], dilation)
    new_stats = None
    if cfg.use_batchnorm:
        x, mean, var = batchnorm(x, layer, training=training)
        new_stats = (mean, var)
    x = jax.nn.relu(x)
    if training and cfg.dropout_rate > 0.0 and rng is not None:
        keep = 1.0 - cfg.dropout_rate
        # Dropout3d: drop whole channels (per sample), like torch Dropout3d.
        mask = jax.random.bernoulli(rng, keep, (x.shape[0], 1, 1, 1, x.shape[-1]))
        x = x * mask / keep
    return x, new_stats


def apply(
    params: Params,
    x: jax.Array,
    cfg: MeshNetConfig,
    *,
    training: bool = False,
    rng: jax.Array | None = None,
) -> jax.Array:
    """Full forward pass -> logits (B, D, H, W, num_classes).

    The plain (non-streaming) executor; core/streaming.py provides the
    scan-over-layers version used for memory-constrained inference.
    """
    if x.ndim == 4:  # (B, D, H, W) -> add channel
        x = x[..., None]
    rngs = (
        jax.random.split(rng, len(cfg.dilations))
        if (rng is not None and training and cfg.dropout_rate > 0)
        else [None] * len(cfg.dilations)
    )
    for i, dilation in enumerate(cfg.dilations):
        x, _ = apply_layer(params["layers"][i], x, dilation, cfg, training=training, rng=rngs[i])
    head = params["head"]
    logits = dilated_conv3d(x, head["w"], head["b"], dilation=1)
    return logits


def apply_with_stats(params, x, cfg: MeshNetConfig, rng=None):
    """Training forward that also returns fresh BN batch statistics.

    Returns (logits, stats) where stats is a list of (mean, var) per layer —
    the trainer folds these into the running estimates with momentum.
    """
    if x.ndim == 4:
        x = x[..., None]
    rngs = (
        jax.random.split(rng, len(cfg.dilations))
        if (rng is not None and cfg.dropout_rate > 0)
        else [None] * len(cfg.dilations)
    )
    stats = []
    for i, dilation in enumerate(cfg.dilations):
        x, st = apply_layer(params["layers"][i], x, dilation, cfg, training=True, rng=rngs[i])
        stats.append(st)
    head = params["head"]
    return dilated_conv3d(x, head["w"], head["b"], dilation=1), stats


def predict(params, x, cfg: MeshNetConfig) -> jax.Array:
    """Hard segmentation labels (B, D, H, W) int32."""
    return jnp.argmax(apply(params, x, cfg), axis=-1).astype(jnp.int32)
