"""Pallas kernel: fused per-class Dice count accumulator.

Brainchop computes Dice from binary masks per label (eq. 2). Materialising
C one-hot masks of a 256^3 volume costs C x 67 MB of HBM traffic; this
kernel streams the two int label volumes once, as lane-dense (rows, 128)
blocks, accumulating per-class (intersection, |pred|, |truth|) counts
across sequential grid steps into a single VMEM-resident block of 128-lane
partial sums (grid-carried accumulation — TPU grids execute sequentially,
the canonical Pallas reduction pattern).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def _dice_kernel(pred_ref, truth_ref, out_ref, *, num_classes: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    pred = pred_ref[...]
    truth = truth_ref[...]
    # row 3c + s of the accumulator holds per-lane partial counts of
    # statistic s (intersection, |pred|, |truth|) of class c
    for c in range(num_classes):
        p1 = (pred == c).astype(jnp.int32)
        t1 = (truth == c).astype(jnp.int32)
        for s, v in enumerate((p1 * t1, p1, t1)):
            row = 3 * c + s
            out_ref[row: row + 1, :] += jnp.sum(v, axis=0, keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("num_classes", "block_rows", "interpret")
)
def dice_counts(
    pred: jax.Array,
    truth: jax.Array,
    num_classes: int,
    *,
    block_rows: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """(C, 3) int32 counts [intersection, |pred_c|, |truth_c|] per class.

    Both volumes stream as (rows, 128) int32 blocks of ``block_rows`` rows
    (a multiple of 8); the accumulator keeps one 128-lane row of partial
    counts per (class, statistic), summed over the lanes at the end."""
    pred = pred.reshape(-1).astype(jnp.int32)
    truth = truth.reshape(-1).astype(jnp.int32)
    n = pred.shape[0]
    block_rows = min(block_rows, -(-n // (8 * LANE)) * 8)
    pad = (-n) % (block_rows * LANE)
    if pad:
        # Pad with class -1 (matches no class) on both sides.
        pred = jnp.concatenate([pred, jnp.full((pad,), -1, jnp.int32)])
        truth = jnp.concatenate([truth, jnp.full((pad,), -2, jnp.int32)])
    pred = pred.reshape(-1, LANE)
    truth = truth.reshape(-1, LANE)
    acc_rows = -(-3 * num_classes // 8) * 8
    block = pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))
    partial = pl.pallas_call(
        functools.partial(_dice_kernel, num_classes=num_classes),
        grid=(pred.shape[0] // block_rows,),
        in_specs=[block, block],
        out_specs=pl.BlockSpec((acc_rows, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((acc_rows, LANE), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pred, truth)
    return jnp.sum(partial[: 3 * num_classes], axis=1).reshape(num_classes, 3)


def dice_from_counts(counts: jax.Array, eps: float = 1e-7) -> jax.Array:
    """Macro Dice from (C, 3) counts; empty classes score 1."""
    inter = counts[:, 0].astype(jnp.float32)
    denom = (counts[:, 1] + counts[:, 2]).astype(jnp.float32)
    per_class = jnp.where(denom == 0, 1.0, 2.0 * inter / (denom + eps))
    return jnp.mean(per_class)
