"""Pallas TPU kernel for MeshNet's hot spot: one 3^3 *dilated* 3-D conv.

Why a custom kernel (hardware adaptation, DESIGN.md §2)
-------------------------------------------------------
Brainchop's WebGL backend runs this conv as fragment-shader passes over 2-D
texture tilings of the volume; the cost model there is texture bandwidth.
On TPU the equivalent wall is HBM->VMEM traffic: a 256^3 x 5ch f32 volume is
335 MB, read 27x by a naive gather-per-tap schedule. The kernel tiles the
volume into VMEM-resident (Z, Y) tiles of whole rows and reads, per output
tile, exactly the haloed neighbourhood it needs — a single DMA.

One conv is a one-layer segment of the depth-first megakernel
(kernels/megakernel.py): the same plane layout (channels on the sublanes,
each Z plane flattened row-major onto the lanes), the same haloed DMA, the
same 27-tap stacked matmul and the same fused affine+ReLU epilogue, which
folds inference-mode BatchNorm and activation into the conv's output tile
while it is still in VMEM. ``pallas_fused`` runs the whole MeshNet stack
as one such segment per layer.

Its tile is the megakernel planner's: ``megakernel.plan`` of the one
layer at the call's own array dtypes, which prices the working set as
the buffers occupy it (``vmem_bytes``). An explicit ``block`` that would
exceed ``VMEM_BUDGET`` is refused before the ``pallas_call``.

Validated in interpret mode on CPU against kernels/ref.py for every
(shape, dtype, dilation, channels) in the test sweep, and compiled for a
v5e chip by tests/test_tpu_compile.py.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.kernels import megakernel

#: The planner's VMEM budget (kernels/megakernel.py).
VMEM_BUDGET = megakernel.VMEM_BUDGET


@functools.partial(
    jax.jit, static_argnames=("dilation", "block", "interpret", "fuse_affine")
)
def dilated_conv3d(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    *,
    dilation: int = 1,
    scale: jax.Array | None = None,
    offset: jax.Array | None = None,
    block: int | None = None,
    interpret: bool = False,
    fuse_affine: bool = False,
) -> jax.Array:
    """'Same'-padded 3-D dilated conv via Pallas.

    x: (B, D, H, W, Cin); w: (3, 3, 3, Cin, Cout); b: (Cout,).
    If ``fuse_affine``: returns relu(conv(x) * scale + offset) — the folded
    inference BatchNorm epilogue. ``block`` forces a (block, block)
    (Z, Y) output tile (default: the planner's tile); any spatial shape
    works (the tile grid is padded). ``interpret`` runs the Pallas
    interpreter, the CPU correctness path.
    """
    B, D, H, W, Cin = x.shape
    Cout = w.shape[-1]
    widths = (x.dtype.itemsize, w.dtype.itemsize) + (x.dtype.itemsize,) * 2
    pln = megakernel.plan((dilation,), Cin, Cout, 0, (D, H, W), widths=widths)
    if block is not None:
        tile = pln.segments[0].tile
        pln = dataclasses.replace(pln, segments=(
            dataclasses.replace(pln.segments[0], tile=(block, block)),))
        need = megakernel._segment_vmem_bytes(pln.segments[0], widths=widths, width=W)
        if need > VMEM_BUDGET:
            raise ValueError(
                f"dilated_conv3d block={block} cin={Cin} cout={Cout} "
                f"dilation={dilation} needs {need / 2**20:.1f} MiB of VMEM, "
                f"over the {VMEM_BUDGET / 2**20:.0f} MiB budget — the "
                f"planner's tile is {tile}"
            )
    seg = pln.segments[0]
    if fuse_affine:
        scale = jnp.ones((Cout,), jnp.float32) if scale is None else scale
        offset = jnp.zeros((Cout,), jnp.float32) if offset is None else offset
    else:
        scale = offset = None
    pz, py = pln.padded(seg)
    act = megakernel.to_planes(
        x, (dilation, dilation + pz - D), (dilation, dilation + py - H)
    )
    out = megakernel.run_segment(
        act, seg, pln, 0, [(w, b, scale, offset)],
        interpret=interpret, relu=fuse_affine,
    )
    return megakernel.from_planes(out, (D, H, W, Cout))


def vmem_bytes(
    block: int,
    cin: int,
    cout: int,
    dilation: int = 16,
    dtype_bytes: int = 4,
    weight_bytes: int | None = None,
    width: int = 256,
) -> int:
    """VMEM bytes of one (block, block) grid step on volumes ``width``
    voxels wide, as megakernel._segment_vmem_bytes prices the one-layer
    segment; ``weight_bytes`` defaults to ``dtype_bytes``."""
    return megakernel._segment_vmem_bytes(
        megakernel.Segment(start=0, dilations=(dilation,), cin=cin,
                           channels=cout, tile=(block, block)),
        widths=(dtype_bytes, weight_bytes or dtype_bytes, dtype_bytes, dtype_bytes),
        width=width,
    )
