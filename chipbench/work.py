"""Operations and compulsory bytes of one MeshNet forward, from the
configuration alone, so the count stays the same whatever implements the
forward (tiles, halos, segments and padding are the implementation's, and
are not counted).

* FLOPs: the dense convolution count at published widths, 2 x (conv
  weights per voxel) x voxels. Biases, BatchNorm and ReLU are left out.
  gwm_light: 2 x (5,598 params - 48 biases) = 11,100 per voxel.
* Bytes: the input once, one value per voxel and class once, and the
  weights (with biases) once, at the storage widths of the precision
  policy.
"""

from __future__ import annotations

import math

#: bytes per stored value: (conformed input, weights, logits)
WIDTHS = {"fp32": (4, 4, 4), "bf16": (2, 2, 2), "int8w": (1, 1, 2)}


def conv_weights(model: dict) -> int:
    k3 = int(model["kernel_size"]) ** 3
    cin, c, n = int(model["in_channels"]), int(model["channels"]), int(model["num_classes"])
    hidden = len(model["dilations"])
    return cin * c * k3 + (hidden - 1) * c * c * k3 + c * n


def biases(model: dict) -> int:
    return len(model["dilations"]) * int(model["channels"]) + int(model["num_classes"])


def flops_per_voxel(model: dict) -> int:
    return 2 * conv_weights(model)


def forward_flops(model: dict, shape) -> int:
    return flops_per_voxel(model) * math.prod(shape)


def forward_bytes(model: dict, shape, precision: str) -> int:
    w_in, w_w, w_out = WIDTHS[precision]
    vox = math.prod(shape)
    return (vox * int(model["in_channels"]) * w_in
            + vox * int(model["num_classes"]) * w_out
            + (conv_weights(model) + biases(model)) * w_w)
