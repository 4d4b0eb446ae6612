"""Depth-first tiled MeshNet megakernel — the whole stack per VMEM tile.

A per-layer schedule writes and re-reads the full activation volume once
per layer, so a 9-layer MeshNet forward moves ~10 full volumes of HBM
traffic even with a perfect conv. This module inverts the loop order:
instead of *breadth-first* (each layer over the whole volume), it runs
*depth-first* — partition the output into tiles, and for each tile run
**all** hidden layers back-to-back inside a single ``pallas_call``,
keeping the activation tile in VMEM across layers. Each tile loads its
haloed input region once (halo inflated by the sum of the 3^3 dilations
it crosses, so the final tile is exact), and hidden activations never
touch HBM at all. The 1x1x1 classifier head folds into the last call, so
a whole forward is: read the haloed input tiles, write the logits. See
DESIGN.md §2 (depth-first tiling & HBM traffic model) and EXPERIMENTS.md
§Perf H9.

Plane layout (what the TPU compiler accepts)
--------------------------------------------
A TPU vector register is (8 sublanes x 128 lanes), and Mosaic only DMAs
windows whose two minor dims are whole (8, 128) tiles. A channels-last
volume puts C = 1, 5 or 21 on the lanes, so every haloed window is
refused. Every array the kernel touches is therefore in the *plane
layout* ``(B, Z, Cp, Y * Xp)``:

  * the channels ride the sublanes, zero-padded to ``Cp = ceil(C, 8)``;
  * a Z plane is flattened row-major onto the lanes, each row padded to
    ``Xp = ceil(X, 128)`` lanes, so a Y offset is a whole number of lane
    tiles and the rows of a window are one aligned lane slice;
  * Z is the leading dim, which a DMA may slice anywhere.

Tiles split Z and Y (with halos); X is never split, so a tile spans whole
rows and the X boundary is the volume's own edge. One 3^3 tap of the
conv is then a leading-dim index (Z), an aligned lane offset of ``ty*d``
rows (Y) and a lane rotation by ``tx*d`` with an edge mask (X), and the
27 taps of a layer stack along the sublanes into ONE
``(Cout_p, 27*Cin_p) @ (27*Cin_p, rows*Xp)`` matmul per block of rows.
``to_planes``/``from_planes`` convert at the boundary of a forward.

Exactness (including the volume boundary)
-----------------------------------------
After the haloed DMA and after every in-tile layer, positions outside the
true volume are masked back to zero, reproducing per-layer 'same' padding
bit-for-bit — the same trick as core/spatial_shard.py's halo exchange,
applied inside VMEM. This also means the HBM staging buffers between
segments can carry uninitialised (never-written) halo borders: whatever
they hold is masked out as the next segment reads it.

Segmentation — the overlap-add fallback
---------------------------------------
The full schedule's halo (sum(1,2,4,8,16,8,4,2,1) = 46 per side) inflates
a tile's working set past the VMEM budget, so ``plan`` splits the layer
stack into consecutive *segments*, each run depth-first with its own
(smaller) halo, with one full activation round-trip between segments:
one segment per layer is the per-layer fused path (``pallas_fused``,
``plan_for_config(per_layer=True)``); one segment for the whole stack is
the pure megakernel. The planner chooses segment boundaries and (Z, Y)
tile shapes by dynamic programming over the modeled device time, subject
to ``_segment_vmem_bytes`` — every buffer priced at the bytes it really
occupies in VMEM, padded rows and lanes included — staying under the
budget.

The time model is ``computed positions x T_POS + HBM bytes / HBM_BW``.
On the chip the kernel's time follows the positions its layers evaluate,
halo rings and clamped row blocks included, far more than its bytes: a
deep segment skips an HBM round trip but fits VMEM only on small tiles,
so every layer recomputes the rest of the segment's halo. Pricing bytes
alone chose deep segments that computed every voxel ~2-3 times.
``MegakernelPlan.hbm_bytes`` stays the traffic model the benchmarks and
telemetry report (telemetry/traffic.py); ``MegakernelPlan.recompute`` is
how far a plan computes past the lane-padded volume.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import quantize

#: TPU vector register geometry: lanes (minor dim) x sublanes.
LANE = 128
SUBLANE = 8

#: Planning budget for one kernel's priced buffers. A v5e TensorCore has
#: 128 MiB of VMEM (the compiler's default scoped limit is only 16 MiB,
#: so every call passes ``vmem_limit_bytes`` explicitly).
VMEM_BUDGET = 64 * 1024 * 1024

#: What each call asks the compiler for on top of the planned budget:
#: room for Mosaic's own temporaries (spills, the rotated tap slices).
VMEM_HEADROOM = 16 * 1024 * 1024

#: Cap on the f32 im2col operand of one row block (27 stacked tap slices).
STACK_BYTES = 2 * 1024 * 1024

#: HBM bandwidth of one v5e chip, B/s (Google Cloud, "TPU v5e": 819 GB/s);
#: the planner's price of a byte moved.
HBM_BW = 819e9

#: Device seconds one computed position costs per input sublane of its
#: layer (the 27 taps' load, cast, roll, mask and stack, and the matmul):
#: fitted on a v5e to the per-segment device times of the served bf16
#: plans of gwm_light, gwm_large (256^3) and atlas_104 (192^3), 8 to 24
#: sublanes, less their bytes at ``HBM_BW`` (PERF.md §5).
T_POS = 35e-12

#: Per-axis (Z, Y) tile-size candidates. Neither axis meets an alignment
#: rule (Z is a leading dim, a Y row is a whole lane-tile multiple), so
#: the grid is chosen for the DP's resolution only.
TILE_CANDIDATES = (8, 16, 24, 32, 40, 48, 56, 64, 80, 96, 128, 160, 192, 256)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def rows(c: int) -> int:
    """Sublane rows a C-channel plane occupies (channels padded to 8)."""
    return _ceil_to(c, SUBLANE)


def lanes(x: int) -> int:
    """Lanes one padded row of an X-wide plane occupies."""
    return _ceil_to(x, LANE)


#: per-role HBM/VMEM byte widths of one plan: (activation/compute,
#: weights, input volume, inter-segment staging). ``None`` anywhere a
#: legacy uniform ``dtype_bytes`` is meant.
Widths = tuple[int, int, int, int]


def plan_widths(
    precision: Optional[str],
    dtype_bytes: int = 4,
    int8_staging: Optional[bool] = None,
) -> Widths:
    """The (act, weight, input, staging) byte widths a plan prices.

    ``precision=None`` reproduces the legacy uniform-``dtype_bytes``
    model exactly (what fp32 also does at dtype_bytes=4). int8w stages
    int8 only when activation bounds exist (``int8_staging`` — BatchNorm
    statistics or a calibration pass, kernels/quantize.py); without them
    staging stays at the bf16 compute width.
    """
    if precision is None:
        return (dtype_bytes,) * 4
    act = quantize.act_bytes(precision)
    stg = quantize.staging_bytes(precision)
    if precision == "int8w" and int8_staging is False:
        stg = act
    return (act, quantize.weight_bytes(precision), quantize.input_bytes(precision), stg)


@dataclasses.dataclass(frozen=True)
class Segment:
    """A consecutive run of hidden layers executed depth-first per tile.
    ``tile`` is the (Z, Y) output tile; X is never split."""

    start: int  # index of the first layer in cfg.dilations
    dilations: tuple[int, ...]
    cin: int  # input channels (in_channels for the first segment)
    channels: int  # hidden width C
    tile: tuple[int, int]
    fuse_head: bool = False  # apply the 1x1x1 head after the last layer
    num_classes: int = 0

    @property
    def halo(self) -> int:
        return sum(self.dilations)

    @property
    def cout(self) -> int:
        return self.num_classes if self.fuse_head else self.channels

    def buffer_sizes(self) -> list[tuple[int, int]]:
        """Per-layer valid (Z, Y) extents: S_0 = tile + 2*halo shrinking
        by 2*d per layer down to S_k = tile exactly."""
        sizes = [tuple(t + 2 * self.halo for t in self.tile)]
        for d in self.dilations:
            sizes.append(tuple(s - 2 * d for s in sizes[-1]))
        assert sizes[-1] == self.tile, (sizes, self)
        return sizes


def _row_block(s_y: int, cin: int, xp: int) -> int:
    """Rows of one loop iteration: as many as keep the stacked f32 im2col
    operand (27 * Cin_p x rows * Xp) under ``STACK_BYTES``."""
    per_row = 27 * rows(cin) * xp * 4
    return max(1, min(s_y, STACK_BYTES // per_row))


def _ntiles(seg: Segment, padded: tuple[int, int]) -> int:
    """Grid steps of ``seg`` over its tile-multiple (Z, Y) region."""
    return math.prod(pp // t for pp, t in zip(padded, seg.tile))


def _tile_positions(seg: Segment, width: int) -> tuple[int, int]:
    """(conv, head) work of one grid step of ``seg`` on a ``width``-wide
    volume, as the kernel loops: each layer evaluates every plane of its
    buffer in blocks of ``_row_block`` rows (the clamped last block
    counted whole), ``lanes(width)`` positions a row. A conv position
    counts ``rows(cin)`` — its input sublanes; the fused head counts the
    last layer's positions once."""
    xp = lanes(width)
    conv = head = 0
    for li, s in enumerate(seg.buffer_sizes()[1:]):
        cin = seg.cin if li == 0 else seg.channels
        r = _row_block(s[1], cin, xp)
        pos = s[0] * -(-s[1] // r) * r * xp
        conv += pos * rows(cin)
        head = pos
    return conv, head if seg.fuse_head else 0


def _segment_seconds(seg: Segment, padded: tuple[int, int], width: int,
                     hbm_bytes: int, batch: int = 1) -> float:
    """Modeled device seconds of one segment: its computed positions at
    ``T_POS`` plus ``hbm_bytes`` at ``HBM_BW``. A fused head is the
    matmul it is: ``rows(num_classes) x rows(C)`` a position, against a
    conv's ``rows(C) x 27 * rows(cin)`` that costs ``rows(cin)``, so
    ``rows(num_classes) / 27`` sublanes."""
    conv, head = _tile_positions(seg, width)
    work = conv + head * rows(seg.num_classes) / 27
    return batch * _ntiles(seg, padded) * work * T_POS + hbm_bytes / HBM_BW


def _segment_vmem_bytes(
    seg: Segment,
    dtype_bytes: int = 4,
    widths: Optional[Widths] = None,
    width: int = 256,
) -> int:
    """VMEM bytes one grid step of ``seg`` really occupies, for volumes
    ``width`` voxels wide in X — every buffer at its padded plane-layout
    size (``rows(C)`` sublanes by ``lanes(width)`` per row):

      * the DMA'd input window (at the input or staging width);
      * the ping/pong activation buffers of the in-tile layers, the fused
        head's logits tile, and for int8 staging the quantized output
        tile (at their storage widths);
      * the weight, bias and epilogue blocks, double-buffered by the
        pipeline, and the two per-lane coordinate rows;
      * the widest layer's per-iteration temporaries: the stacked f32
        im2col operand, its MXU-width copy, and the f32 accumulator.
    """
    act, wt, inp, stg = widths or (dtype_bytes,) * 4
    xp = lanes(width)
    ib = inp if seg.start == 0 else stg
    k = len(seg.dilations)
    sizes = seg.buffer_sizes()
    ci, c = rows(seg.cin), rows(seg.channels)
    quant_out = not seg.fuse_head and stg < act

    def plane(s):
        return s[0] * s[1] * xp

    total = plane(sizes[0]) * ci * ib
    # layer li writes sizes[li + 1]: ping for even li, pong for odd li;
    # the last layer writes the logits / quantized tile instead
    writes = range(k - 1) if (seg.fuse_head or quant_out) else range(k)
    for parity in (0, 1):
        outs = [plane(sizes[li + 1]) for li in writes if li % 2 == parity]
        total += max(outs, default=0) * c * act
    if seg.fuse_head:
        total += plane(seg.tile) * rows(seg.num_classes) * act
    if quant_out:
        total += plane(seg.tile) * c * stg
    wgt = 27 * ci * c * wt + 27 * c * c * wt * (k - 1)
    wgt += k * 4 * c * LANE * 4  # (C, 1) f32 epilogue columns, lane-padded
    if seg.fuse_head:
        wgt += rows(seg.num_classes) * _ceil_to(c, LANE) * wt
    total += 2 * wgt
    lmax = max(_row_block(s[1], seg.cin if li == 0 else seg.channels, xp)
               for li, s in enumerate(sizes[1:])) * xp
    total += 2 * 2 * SUBLANE * lmax * 4  # lane coordinate rows
    temps = 0
    for li, s in enumerate(sizes[1:]):
        cin_l = ci if li == 0 else c
        L = _row_block(s[1], seg.cin if li == 0 else seg.channels, xp) * xp
        temps = max(temps, 27 * cin_l * L * (4 + act) + 2 * c * L * 4)
    return total + temps


def _segment_hbm_bytes(
    seg: Segment,
    padded: tuple[int, int],
    width: int,
    dtype_bytes: int,
    widths: Optional[Widths] = None,
    batch: int = 1,
) -> int:
    """Modeled HBM bytes of one segment: haloed window reads (whole
    padded rows of ``rows(cin)`` planes), per-spatial-tile weight
    streams, and the central-region write. The ONE formula shared by
    ``MegakernelPlan.hbm_bytes`` (what telemetry/benchmarks report) and
    the bytes term of the planner's time objective (int8 staging aside:
    the DP prices staging at the compute width, ``plan``). ``widths``
    prices each tensor role at its policy byte width.

    ``batch`` scales only the data terms, NOT the weight stream: the
    launch grid iterates batch innermost, so each segment's weight blocks
    stay resident across the whole batch loop and are re-fetched only
    when the spatial tile advances — one weight stream per launch,
    amortized over all N members."""
    act, wt, inp, stg = widths or (dtype_bytes,) * 4
    xp = lanes(width)
    ib = inp if seg.start == 0 else stg
    ob = act if seg.fuse_head else stg
    ci, c = rows(seg.cin), rows(seg.channels)
    ntiles = _ntiles(seg, padded)
    window = math.prod(t + 2 * seg.halo for t in seg.tile) * xp
    wgt = 27 * ci * c * wt + 27 * c * c * wt * (len(seg.dilations) - 1)
    if seg.fuse_head:
        wgt += c * rows(seg.num_classes) * wt
    data = ntiles * window * ci * ib
    data += math.prod(padded) * xp * rows(seg.cout) * ob
    return batch * data + ntiles * wgt


def _in_bytes(seg: Segment, padded: tuple[int, int], vol, inp: int, batch: int) -> int:
    """HBM bytes of the input's conversion into the plane layout of the
    first segment: the read, and the write padded by its halo."""
    window = math.prod(p + 2 * seg.halo for p in padded) * lanes(vol[2])
    return batch * (math.prod(vol) * seg.cin + window * rows(seg.cin)) * inp


def _out_bytes(seg: Segment, padded: tuple[int, int], vol, act: int, batch: int) -> int:
    """HBM bytes of the logits' conversion out of the plane layout of the
    last segment: the padded read and the write."""
    plane = math.prod(padded) * lanes(vol[2]) * rows(seg.cout)
    return batch * (plane + math.prod(vol) * seg.cout) * act


@dataclasses.dataclass(frozen=True)
class MegakernelPlan:
    """Static execution plan: segments + geometry for one (cfg, volume).

    ``widths`` carries the precision policy's per-role byte widths the
    plan was optimized for (None = the legacy uniform-``dtype_bytes``
    fp32 model); ``hbm_bytes`` prices with them, so the planner's DP
    objective and the reported model stay one formula per precision."""

    segments: tuple[Segment, ...]
    vol: tuple[int, int, int]  # true volume dims (pre-padding)
    vmem_budget: int
    widths: Optional[Widths] = None

    @property
    def xp(self) -> int:
        return lanes(self.vol[2])

    def padded(self, seg: Segment) -> tuple[int, int]:
        """Tile-multiple (Z, Y) dims of the region this segment computes."""
        return tuple(_ceil_to(v, t) for v, t in zip(self.vol[:2], seg.tile))

    def out_dims(self, i: int) -> tuple[int, int]:
        """(Z, Y) dims of segment i's HBM output array. Sized for the
        *next* segment's haloed DMA windows: max of both segments' padded
        extents plus the next halo per side (the halo border is never
        written — it is masked out as the next segment reads it)."""
        cur = self.padded(self.segments[i])
        if i + 1 == len(self.segments):
            return cur
        nxt = self.segments[i + 1]
        pad = self.padded(nxt)
        return tuple(max(c, p) + 2 * nxt.halo for c, p in zip(cur, pad))

    def hbm_bytes(self, batch: int = 1, dtype_bytes: int = 4) -> int:
        """Modeled HBM traffic of one batched forward: the conversion of
        the input into the padded plane layout (read + padded write), then
        per segment the haloed tile reads, the weight streams and the
        central-region writes (staging halo borders are allocated but
        never written, so they cost nothing), then the conversion of the
        logits back out of the plane layout. Data terms scale with
        ``batch``; the per-segment weight stream is charged once per
        launch. A plan optimized for a precision policy prices with its
        own per-role widths (``dtype_bytes`` is the legacy uniform knob
        and is ignored when ``widths`` is set)."""
        widths = self.widths or (dtype_bytes,) * 4
        first, last = self.segments[0], self.segments[-1]
        total = _in_bytes(first, self.padded(first), self.vol, widths[2], batch)
        for seg in self.segments:
            total += _segment_hbm_bytes(
                seg, self.padded(seg), self.vol[2], dtype_bytes, widths, batch=batch
            )
        return total + _out_bytes(last, self.padded(last), self.vol, widths[0], batch)

    def seconds(self, batch: int = 1) -> float:
        """Modeled device seconds of one batched forward: every segment's
        computed positions at ``T_POS``, and ``hbm_bytes`` at ``HBM_BW``."""
        work = sum(
            _segment_seconds(seg, self.padded(seg), self.vol[2], 0, batch)
            for seg in self.segments
        )
        return work + self.hbm_bytes(batch) / HBM_BW

    @property
    def recompute(self) -> float:
        """Conv positions the plan computes over those of the lane-padded
        volume (layers x Z x Y x ``xp`` x ``rows(cin)``): 1.0 computes each
        voxel once. X's lane padding, which no plan removes, is not
        counted; neither is the fused head."""
        done = need = 0
        for seg in self.segments:
            done += _ntiles(seg, self.padded(seg)) * _tile_positions(seg, self.vol[2])[0]
            cins = (seg.cin,) + (seg.channels,) * (len(seg.dilations) - 1)
            need += sum(self.vol[0] * self.vol[1] * self.xp * rows(c) for c in cins)
        return done / need


def plan(
    dilations: Sequence[int],
    in_channels: int,
    channels: int,
    num_classes: int,
    vol: tuple[int, int, int],
    *,
    vmem_budget: int = VMEM_BUDGET,
    dtype_bytes: int = 4,
    precision: Optional[str] = None,
    int8_staging: Optional[bool] = None,
    batch: int = 1,
    per_layer: bool = False,
    widths: Optional[Widths] = None,
) -> MegakernelPlan:
    """Choose segment boundaries and (Z, Y) tiles by DP over modeled
    device time (``MegakernelPlan.seconds``: computed positions at
    ``T_POS``, HBM bytes at ``HBM_BW``), subject to each segment's
    working set fitting VMEM.

    ``precision`` prices every tensor role at its policy width
    (``plan_widths``): a bf16/int8 working set is smaller, so the DP
    affords larger tiles. ``precision=None`` keeps the legacy uniform-
    ``dtype_bytes`` model. Under int8 staging the DP minimizes the HBM
    bytes alone, with the staging streams priced at the compute width:
    every int8 boundary is a lossy requantization, so the plan keeps the
    boundaries of the lossless byte-optimal schedule and only narrows
    them (the plan reports its true bytes); a time objective would buy
    more boundaries, and with them more rounding.

    ``batch`` co-optimizes the tile shape against the batch size: the DP
    objective scales the data terms and the computed positions by N
    while charging the weight stream once per launch. The VMEM constraint
    is unchanged — the grid iterates one (tile, batch element) at a time,
    so a plan feasible at batch 1 stays feasible at any batch.

    ``per_layer`` restricts every segment to one layer: the per-layer
    fused schedule (``pallas_fused``) is this plan. ``num_classes=0``
    plans a stack without the 1x1x1 head, and ``widths`` gives the
    per-role byte widths outright: one conv (kernels/dilated_conv3d.py)
    is the one-layer plan of its own array dtypes.

    Raises with an actionable message when even a single layer at the
    smallest tile exceeds the budget (channel width is the only lever
    left at that point). Memoized: the serving path replans the same
    (model, volume, precision) on every request.
    """
    return _plan_cached(
        tuple(int(d) for d in dilations),
        int(in_channels),
        int(channels),
        int(num_classes),
        tuple(int(v) for v in vol),
        int(vmem_budget),
        widths or plan_widths(precision, dtype_bytes, int8_staging),
        int(batch),
        bool(per_layer),
    )


@functools.lru_cache(maxsize=256)
def _plan_cached(
    dils: tuple[int, ...],
    in_channels: int,
    channels: int,
    num_classes: int,
    vol: tuple[int, int, int],
    vmem_budget: int,
    widths: Widths,
    batch: int = 1,
    per_layer: bool = False,
) -> MegakernelPlan:
    n = len(dils)
    act, _, inp, stg = widths
    # An int8 staging boundary requantizes the activations (lossy, unlike
    # a bf16 one), so int8 may narrow the streams of the boundaries the
    # lossless byte-optimal schedule takes but never buys an extra one:
    # such plans minimize bytes, staging priced at the compute width.
    # Every other plan minimizes modeled time. Feasibility uses the real
    # widths.
    by_bytes = stg < act
    cost_widths = widths[:3] + (max(stg, act),)
    # Oversize tiles only waste padding: cap candidates near the volume.
    cands = [
        [t for t in TILE_CANDIDATES if t <= _ceil_to(v, 8)] or [8]
        for v in vol[:2]
    ]

    def seg_for(i: int, j: int, tile) -> Segment:
        return Segment(
            start=i,
            dilations=dils[i:j],
            cin=in_channels if i == 0 else channels,
            channels=channels,
            tile=tile,
            fuse_head=(j == n and num_classes > 0),
            num_classes=num_classes,
        )

    # DP over segment boundaries; each (i, j) picks its cheapest feasible
    # tile. Segments are short (<= n layers) and the tile grid is ~14^2,
    # so each candidate is priced with the exact per-segment formulas the
    # plan reports.
    INF = float("inf")
    best: list[float] = [INF] * (n + 1)
    best[n] = 0.0
    choice: list[tuple[int, tuple[int, int]] | None] = [None] * (n + 1)
    for i in range(n - 1, -1, -1):
        for j in range(i + 1, (i + 2 if per_layer else n + 1)):
            top = None
            for tz in cands[0]:
                for ty in cands[1]:
                    seg = seg_for(i, j, (tz, ty))
                    if _segment_vmem_bytes(seg, widths=widths, width=vol[2]) > vmem_budget:
                        continue
                    padded = (_ceil_to(vol[0], tz), _ceil_to(vol[1], ty))
                    nbytes = _segment_hbm_bytes(seg, padded, vol[2], 4, cost_widths, batch)
                    if i == 0:
                        nbytes += _in_bytes(seg, padded, vol, inp, batch)
                    if j == n:
                        nbytes += _out_bytes(seg, padded, vol, act, batch)
                    cost = nbytes if by_bytes else _segment_seconds(
                        seg, padded, vol[2], nbytes, batch)
                    if top is None or cost < top[0]:
                        top = (cost, (tz, ty))
            if top is not None and top[0] + best[j] < best[i]:
                best[i] = top[0] + best[j]
                choice[i] = (j, top[1])
    if best[0] == INF:
        need, gi = max(
            (_segment_vmem_bytes(seg_for(i, i + 1, (8, 8)), widths=widths,
                                 width=vol[2]), i)
            for i in range(n)
        )
        raise ValueError(
            f"megakernel plan infeasible: layer {gi} (dilation {dils[gi]}) "
            f"alone at tile (8, 8) needs "
            f"{need / 2**20:.1f} MiB of VMEM, "
            f"over the {vmem_budget / 2**20:.0f} MiB budget — reduce channel "
            f"width ({channels}) or raise vmem_budget"
        )
    segments = []
    i = 0
    while i < n:
        j, tile = choice[i]  # type: ignore[misc]
        segments.append(seg_for(i, j, tile))
        i = j
    return MegakernelPlan(
        segments=tuple(segments),
        vol=vol,
        vmem_budget=vmem_budget,
        widths=None if widths == (4, 4, 4, 4) else widths,
    )


def plan_for_config(
    cfg,
    vol: tuple[int, int, int],
    *,
    vmem_budget: int = VMEM_BUDGET,
    dtype_bytes: int = 4,
    precision: Optional[str] = None,
    int8_staging: Optional[bool] = None,
    batch: int = 1,
    per_layer: bool = False,
) -> MegakernelPlan:
    """``plan`` from a MeshNetConfig-shaped object. With a ``precision``,
    int8 staging defaults to whether the config has BatchNorm statistics
    to bound the staging scales with (kernels/quantize.py)."""
    if precision is not None and int8_staging is None:
        int8_staging = bool(cfg.use_batchnorm)
    return plan(
        cfg.dilations,
        cfg.in_channels,
        cfg.channels,
        cfg.num_classes,
        vol,
        vmem_budget=vmem_budget,
        dtype_bytes=dtype_bytes,
        precision=precision,
        int8_staging=int8_staging,
        batch=batch,
        per_layer=per_layer,
    )


# ------------------------------------------------------------ layout ---


def to_planes(x: jax.Array, pad_z, pad_y) -> jax.Array:
    """(B, D, H, W, C) -> plane layout (B, Z, rows(C), Y * lanes(W)),
    zero-padding Z and Y by ``pad_z``/``pad_y`` (before, after) pairs."""
    B, D, H, W, C = x.shape
    x = jnp.pad(x, [(0, 0), pad_z, pad_y, (0, lanes(W) - W), (0, rows(C) - C)])
    _, Z, Y, X, Cp = x.shape
    return x.transpose(0, 1, 4, 2, 3).reshape(B, Z, Cp, Y * X)


def from_planes(a: jax.Array, shape: tuple[int, int, int, int]) -> jax.Array:
    """Plane layout -> (B, D, H, W, C) for true dims ``shape = (D, H, W, C)``."""
    D, H, W, C = shape
    B, Z, Cp, L = a.shape
    xp = lanes(W)
    a = a.reshape(B, Z, Cp, L // xp, xp).transpose(0, 1, 3, 4, 2)
    return a[:, :D, :H, :W, :C]


def _tap_matrix(w: jax.Array, ci: int, co: int) -> jax.Array:
    """(3, 3, 3, Cin, Cout) taps -> (co, 27 * ci): column t * ci + i holds
    tap t = 9 tz + 3 ty + tx of input channel i, the row order in which
    the kernel stacks its shifted slices."""
    w = jnp.pad(w, [(0, 0)] * 3 + [(0, ci - w.shape[3]), (0, co - w.shape[4])])
    return w.transpose(4, 0, 1, 2, 3).reshape(co, 27 * ci)


def _column(v: jax.Array, c: int, fill: float = 0.0) -> jax.Array:
    """(C,) per-channel vector -> (c, 1) f32 column (padded rows ``fill``)."""
    v = v.astype(jnp.float32)
    return jnp.pad(v, (0, c - v.shape[0]), constant_values=fill).reshape(c, 1)


# ------------------------------------------------------------ kernel ---


def _segment_kernel(
    *refs,
    seg: Segment,
    vol: tuple[int, int, int],
    out_halo: int,
    use_affine: bool,
    relu: bool,
    blocks: tuple[int, ...],
    mxu_dtype,
    has_z_bounds: bool = False,
    deq_in: bool = False,
    quant_out: bool = False,
):
    """Kernel body: DMA the haloed input window, run ``seg``'s layers
    back-to-back in VMEM (masking out-of-volume positions after every
    layer so per-layer 'same' zero padding is reproduced exactly), then
    DMA the finished tile (or fused-head logits) back out.

    Each layer loops over (output plane, block of ``blocks[li]`` rows);
    an iteration stacks the 27 shifted (Cin_p, rows * Xp) tap slices
    along the sublanes and contracts them with the layer's
    (Cout_p, 27 * Cin_p) tap matrix in one MXU matmul, accumulating in
    f32. The last row block of a plane is clamped to end at the plane's
    edge: it recomputes a few rows of its neighbour, which is idempotent.

    ``has_z_bounds`` adds a dynamic (2,)-int32 SMEM input narrowing the
    valid Z interval below ``[0, vol[0])`` — the sharded executor
    (core/spatial_shard.py) uses it to place the *true* volume boundary
    inside a slab+halo window.

    int8w staging (kernels/quantize.py): ``deq_in`` adds a per-channel
    column that dequantizes the DMA'd int8 staging window as the first
    layer reads it; ``quant_out`` adds the per-channel scale the last
    layer's output is quantized with before the output DMA, so what
    crosses HBM between segments is int8.
    """
    k = len(seg.dilations)
    per_layer = 4 if use_affine else 2
    n_head = 2 if seg.fuse_head else 0
    x_ref, xcol_ref, yrow_ref = refs[0], refs[1], refs[2]
    pos = 3
    layer_refs = [refs[pos + i * per_layer: pos + (i + 1) * per_layer] for i in range(k)]
    pos += k * per_layer
    head_refs = refs[pos: pos + n_head]
    pos += n_head
    deq_ref = refs[pos] if deq_in else None
    pos += int(deq_in)
    qscale_ref = refs[pos] if quant_out else None
    pos += int(quant_out)
    zb_ref = refs[pos] if has_z_bounds else None
    pos += int(has_z_bounds)
    out_ref = refs[pos]
    scratch = list(refs[pos + 1:])
    sem = scratch.pop()
    buf_in = scratch.pop(0)
    final = scratch.pop() if (seg.fuse_head or quant_out) else None
    ping = scratch[0] if scratch else None
    pong = scratch[1] if len(scratch) > 1 else None

    # batch is the INNERMOST grid axis: the weight/bias/affine blocks use
    # constant index maps, so between consecutive batch steps no input
    # block index changes and the segment's weights stay VMEM-resident.
    zi, yi, bi = (pl.program_id(i) for i in range(3))
    tz_, ty_ = seg.tile
    h = seg.halo
    sizes = seg.buffer_sizes()
    xp = lanes(vol[2])

    dma = pltpu.make_async_copy(
        x_ref.at[
            bi,
            pl.ds(zi * tz_, sizes[0][0]),
            :,
            pl.ds(pl.multiple_of(yi * (ty_ * xp), LANE), sizes[0][1] * xp),
        ],
        buf_in,
        sem.at[0],
    )
    dma.start()
    dma.wait()

    def valid(z, y0, L, r):
        """(1, L) mask of the positions of plane ``z``, rows ``y0 ..``
        (local coords ``r`` before the tile origin) inside the volume."""
        gz = jnp.full((1, L), zi * tz_ - r + z, jnp.int32)
        gy = yrow_ref[:, :L] + (yi * ty_ - r + y0)
        ok = (gz >= 0) & (gz < vol[0]) & (gy >= 0) & (gy < vol[1])
        ok = ok & (xcol_ref[:, :L] < vol[2])
        if zb_ref is not None:
            ok = ok & (gz >= zb_ref[0]) & (gz < zb_ref[1])
        return ok

    prev = buf_in
    cum = 0
    for li, d in enumerate(seg.dilations):
        w_ref, b_ref = layer_refs[li][0], layer_refs[li][1]
        s_out = sizes[li + 1]
        r = blocks[li]
        L = r * xp
        nb = -(-s_out[1] // r)
        cum += d
        last = li + 1 == k
        if last and (seg.fuse_head or quant_out):
            dst = final
        else:
            dst = ping if li % 2 == 0 else pong
        xs = xcol_ref[:, :L]
        xmask = {dx: (xs + dx >= 0) & (xs + dx < vol[2]) for dx in (-d, d)}

        def body(it, carry, li=li, d=d, prev=prev, dst=dst, r=r, L=L, nb=nb,
                 s_out=s_out, last=last, xmask=xmask, w_ref=w_ref, b_ref=b_ref,
                 rr=h - cum):
            z = it // nb
            y0 = jnp.minimum((it % nb) * r, s_out[1] - r)
            pieces = []
            for tz in range(3):
                for ty in range(3):
                    off = pl.multiple_of((y0 + ty * d) * xp, LANE)
                    s = prev[z + tz * d, :, pl.ds(off, L)].astype(jnp.float32)
                    if li == 0:
                        if deq_ref is not None:
                            s = s * deq_ref[...]
                        s = jnp.where(valid(z + tz * d, y0 + ty * d, L, h), s, 0.0)
                    for tx in range(3):
                        dx = (tx - 1) * d
                        if dx == 0:
                            pieces.append(s)
                        else:
                            rolled = pltpu.roll(s, (-dx) % L, 1)
                            pieces.append(jnp.where(xmask[dx], rolled, 0.0))
            stack = jnp.concatenate(pieces, axis=0).astype(mxu_dtype)
            out = jnp.dot(
                w_ref[...].astype(mxu_dtype), stack,
                preferred_element_type=jnp.float32,
            )
            out = out + b_ref[...]
            if use_affine:
                s_ref, o_ref = layer_refs[li][2], layer_refs[li][3]
                out = out * s_ref[...] + o_ref[...]
            if relu:
                out = jnp.maximum(out, 0.0)
            out = jnp.where(valid(z, y0, L, rr), out, 0.0)
            if last and seg.fuse_head:
                hw_ref, hb_ref = head_refs
                out = jnp.dot(
                    hw_ref[...].astype(mxu_dtype), out.astype(mxu_dtype),
                    preferred_element_type=jnp.float32,
                ) + hb_ref[...]
            elif last and quant_out:
                out = jnp.clip(jnp.round(out / qscale_ref[...]), -127.0, 127.0)
                out = out.astype(jnp.int32)
            dst[z, :, pl.ds(pl.multiple_of(y0 * xp, LANE), L)] = out.astype(dst.dtype)
            return carry

        jax.lax.fori_loop(0, s_out[0] * nb, body, 0)
        prev = dst

    src = prev
    if src.shape[0] != tz_ or src.shape[2] != ty_ * xp:
        src = src.at[pl.ds(0, tz_), :, pl.ds(0, ty_ * xp)]
    odma = pltpu.make_async_copy(
        src,
        out_ref.at[
            bi,
            pl.ds(out_halo + zi * tz_, tz_),
            :,
            pl.ds(pl.multiple_of((out_halo + yi * ty_) * xp, LANE), ty_ * xp),
        ],
        sem.at[1],
    )
    odma.start()
    odma.wait()


def run_segment(
    act: jax.Array,
    seg: Segment,
    pln: MegakernelPlan,
    i: int,
    layers: Sequence[tuple],
    *,
    interpret: bool,
    head: Optional[tuple] = None,
    relu: bool = True,
    z_bounds: jax.Array | None = None,
    compute_dtype=None,
    deq: jax.Array | None = None,
    qscale: jax.Array | None = None,
) -> jax.Array:
    """Run segment ``i`` of ``pln`` over the plane-layout array ``act``.

    ``layers`` holds one ``(w, bias, scale, offset)`` per layer of the
    segment (``scale``/``offset`` None for a plain conv), ``head`` the
    fused head's ``(w (C, classes), b)``. ``compute_dtype`` is the width
    of the in-VMEM activations, the MXU operands and the output (default
    ``act``'s dtype); ``deq``/``qscale`` are the int8 staging columns.
    Returns the segment's plane-layout output, sized by ``pln.out_dims``.
    """
    B = act.shape[0]
    vol = pln.vol
    xp = pln.xp
    k = len(seg.dilations)
    cdt = act.dtype if compute_dtype is None else compute_dtype
    mxu = jnp.float32 if cdt == jnp.float32 else jnp.bfloat16
    quant_out = qscale is not None
    use_affine = layers[0][2] is not None
    ci, c = rows(seg.cin), rows(seg.channels)
    out_halo = pln.segments[i + 1].halo if i + 1 < len(pln.segments) else 0
    sizes = seg.buffer_sizes()
    blocks = tuple(
        _row_block(s[1], seg.cin if li == 0 else seg.channels, xp)
        for li, s in enumerate(sizes[1:])
    )
    lmax = max(blocks) * xp
    lane = np.arange(lmax, dtype=np.int32)

    args = [act]
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]

    def add_full(a):
        args.append(a)
        in_specs.append(pl.BlockSpec(a.shape, lambda *_, n=a.ndim: (0,) * n))

    add_full(jnp.asarray(lane % xp)[None])  # x coordinate of each lane
    add_full(jnp.asarray(lane // xp)[None])  # row offset of each lane
    for li, (w, bias, scale, offset) in enumerate(layers):
        add_full(_tap_matrix(w, ci if li == 0 else c, c))
        add_full(_column(bias, c))
        if use_affine:
            add_full(_column(scale, c))
            add_full(_column(offset, c))
    if seg.fuse_head:
        hw, hb = head
        nc = rows(seg.num_classes)
        add_full(jnp.pad(hw, [(0, c - hw.shape[0]), (0, nc - hw.shape[1])]).T)
        add_full(_column(hb, nc))
    if deq is not None:
        add_full(_column(deq, ci, 1.0))
    if quant_out:
        add_full(_column(qscale, c, 1.0))
    if z_bounds is not None:
        args.append(z_bounds)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    def plane(s):
        return (s[0], s[1] * xp)

    scratch = [pltpu.VMEM((sizes[0][0], ci, sizes[0][1] * xp), act.dtype)]
    writes = range(k - 1) if (seg.fuse_head or quant_out) else range(k)
    for parity in (0, 1):
        outs = [plane(sizes[li + 1]) for li in writes if li % 2 == parity]
        if outs:
            z = max(o[0] for o in outs)
            L = max(o[1] for o in outs)
            scratch.append(pltpu.VMEM((z, c, L), cdt))
    if seg.fuse_head:
        scratch.append(pltpu.VMEM((seg.tile[0], rows(seg.num_classes), seg.tile[1] * xp), cdt))
    elif quant_out:
        scratch.append(pltpu.VMEM((seg.tile[0], c, seg.tile[1] * xp), jnp.int8))
    scratch.append(pltpu.SemaphoreType.DMA((2,)))

    kernel = functools.partial(
        _segment_kernel,
        seg=seg,
        vol=vol,
        out_halo=out_halo,
        use_affine=use_affine,
        relu=relu,
        blocks=blocks,
        mxu_dtype=mxu,
        has_z_bounds=z_bounds is not None,
        deq_in=deq is not None,
        quant_out=quant_out,
    )
    out_dtype = jnp.int8 if quant_out else cdt
    oz, oy = pln.out_dims(i)
    padded = pln.padded(seg)
    grid = tuple(p // t for p, t in zip(padded, seg.tile)) + (B,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((B, oz, rows(seg.cout), oy * xp), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=pln.vmem_budget + VMEM_HEADROOM,
        ),
        interpret=interpret,
    )(*args)


@functools.partial(
    jax.jit,
    static_argnums=(2,),
    static_argnames=(
        "interpret", "pln", "vmem_budget", "fold_affine", "precision",
        "per_layer",
    ),
)
def meshnet_apply(
    params,
    x: jax.Array,
    cfg,
    *,
    interpret: bool = False,
    pln: MegakernelPlan | None = None,
    vmem_budget: int = VMEM_BUDGET,
    fold_affine=None,
    z_bounds: jax.Array | None = None,
    precision: str = "fp32",
    staging_scales: Sequence[jax.Array] | None = None,
    per_layer: bool = False,
) -> jax.Array:
    """Depth-first MeshNet forward (== meshnet.apply, eval mode).

    ``interpret`` runs the kernels in the Pallas interpreter — the CPU
    correctness path; it is never chosen on a TPU (kernels/ops.py).

    ``fold_affine`` maps a layer dict to the folded inference-BN
    (scale, offset); ops.meshnet_apply_megakernel supplies it (kept
    injectable so this module does not import ops).

    ``z_bounds`` (optional (2,)-int32) narrows the valid Z interval below
    ``[0, D)``: positions outside it are re-zeroed per layer exactly like
    positions outside the volume. The sharded executor passes the true
    volume's extent inside a slab+halo window (core/spatial_shard.py).

    ``precision`` selects the storage policy (kernels/quantize.py):
    "fp32" is the f32 path; "bf16" runs the same schedule with bf16
    buffers/weights and fp32 accumulate, rounding at every layer output;
    "int8w" additionally streams per-output-channel int8 weights (dequant
    folded into the affine epilogue), the int8-quantized conformed input,
    and — when BatchNorm statistics or the given ``staging_scales``
    (quantize.calibrate) bound the activations — int8 inter-segment
    staging, dequantized on the fly in VMEM. A given ``pln`` decides: a
    plan priced with bf16 staging (``int8_staging=False``) stages bf16.

    ``per_layer`` plans one segment per layer: the ``pallas_fused``
    schedule, which stages every layer through HBM (at the activation
    width — it never stages int8).
    """
    if x.ndim == 4:
        x = x[..., None]
    B, D, H, W, Cin = x.shape
    vol = (D, H, W)
    if precision == "fp32":
        if pln is None:
            pln = plan_for_config(
                cfg, vol, vmem_budget=vmem_budget, dtype_bytes=x.dtype.itemsize,
                per_layer=per_layer,
            )
        if cfg.use_batchnorm and fold_affine is None:
            raise ValueError("fold_affine is required when cfg.use_batchnorm")

        def epilogue(layer, gi):
            if not cfg.use_batchnorm:
                return layer["b"], None, None
            return (layer["b"],) + tuple(fold_affine(layer))

        compute_dtype = None
        staging_scales = None
    else:
        quantize.validate(precision)
        params = quantize.prepare_params(params, cfg, precision)
        compute_dtype = quantize.act_dtype(precision)
        if precision == "int8w":
            if x.dtype != jnp.int8:
                x = quantize.quantize_input(x)
            if staging_scales is None and not per_layer:
                staging_scales = quantize.staging_scales_from_bn(params, cfg)
        else:
            x = x.astype(compute_dtype)
        if per_layer:
            staging_scales = None
        if pln is None:
            pln = plan_for_config(
                cfg,
                vol,
                vmem_budget=vmem_budget,
                precision=precision,
                int8_staging=staging_scales is not None,
                per_layer=per_layer,
            )
        if pln.widths is None or pln.widths[3] >= pln.widths[0]:
            staging_scales = None  # the plan stages at the compute width

        def epilogue(layer, gi, _prec=precision):
            bias, scale, offset = quantize.fold_epilogue(layer, cfg.use_batchnorm)
            if gi == 0 and _prec == "int8w":
                # the conformed volume's fixed int8 dequant scale rides
                # the first layer's epilogue (conv is linear in its
                # input scale)
                scale = scale * quantize.INPUT_SCALE
            return bias, scale, offset

    first = pln.segments[0]
    h0 = first.halo
    p0 = pln.padded(first)
    act = to_planes(x, (h0, h0 + p0[0] - D), (h0, h0 + p0[1] - H))
    for i, seg in enumerate(pln.segments):
        layers = [
            (params["layers"][gi]["w"],) + tuple(epilogue(params["layers"][gi], gi))
            for gi in range(seg.start, seg.start + len(seg.dilations))
        ]
        head = None
        if seg.fuse_head:
            head = (params["head"]["w"][0, 0, 0], params["head"]["b"])
        deq = qscale = None
        if staging_scales is not None:
            if seg.start > 0:
                deq = staging_scales[seg.start - 1]
            if not seg.fuse_head:
                qscale = staging_scales[seg.start + len(seg.dilations) - 1]
        act = run_segment(
            act, seg, pln, i, layers,
            interpret=interpret,
            head=head,
            z_bounds=z_bounds,
            compute_dtype=compute_dtype,
            deq=deq,
            qscale=qscale,
        )
    return from_planes(act, (D, H, W, cfg.num_classes))
