"""Modeled HBM bytes per MeshNet forward, per executor backend and
precision policy.

The TPU analogue of Brainchop's texture-bandwidth cost model: every
executor's schedule implies a deterministic amount of HBM traffic, and —
because MeshNet's narrow models are memory-bound (DESIGN.md §2) — that
byte count *is* the performance model. These functions price it
analytically (bytes, not wall-clock), the same methodology as the memory
budget model in telemetry/budget.py: the numbers drive the DESIGN.md §2
traffic table and the per-run ``hbm_bytes_modeled`` telemetry field.

Modeling conventions (counted per forward):
  * every XLA op materialises its output: a pad is a read + padded write,
    an elementwise stage is a read + write round-trip;
  * a Pallas grid step re-fetches each of its input blocks — consecutive
    steps share nothing, so per-step window bytes multiply by the step
    count (this is what makes the 27-view schedule 27x and a haloed
    (Z, Y) window ((b+2d)/b)^2 x);
  * the Pallas paths move whole padded planes: channels padded to 8
    sublanes and rows to 128 lanes (kernels/megakernel.py's plane
    layout), and the conversion into and out of that layout is counted;
  * weights are streamed once per grid step (tiny for MeshNet, but
    counted — at 16^3 benchmark volumes they are not negligible);
  * a batched forward (``batch=N``) re-reads and re-writes every data
    tensor per element but streams each weight tensor ONCE per launch:
    the batch loop is innermost in every backend's schedule (an XLA
    fusion keeps weights resident across the leading dim; the megakernel
    grid iterates batch inside the spatial tile), so
    ``bytes(batch=N) < N * bytes(batch=1)`` whenever the weight term is
    nonzero, with ``batch=1`` byte-identical to the pre-batching model;
  * scratch/VMEM traffic is free; only HBM crossings count.

Precision (kernels/quantize.py): every model takes the storage policy
and prices each tensor role at its width — activations (fp32 4 B / bf16
& int8w compute 2 B), weights (4/2/1 B), and for the megakernel the
input volume and inter-segment staging (down to 1 B under int8w). The
XLA backends (xla / streaming) dequantize the input up-front, so their
volume crossings are priced at the activation width; pallas_fused reads
the int8 input but stages every layer at the activation width; the
megakernel is the backend whose schedule streams int8 end-to-end, which
is why the int8w gate (<= 0.4x fp32 at 256^3, EXPERIMENTS.md H11) is
stated on it. ``precision="fp32"`` prices every role at 4 bytes (the
regression gate compares like-for-
like precision keys).

The pluggable executor registry wires these to its specs
(``core/executors.py``), so ``pipeline.run`` records bytes for whichever
(backend, precision) served a request without knowing how it is
scheduled.
"""

from __future__ import annotations

import math
from typing import Sequence

from repro.kernels import megakernel, quantize

Shape3 = Sequence[int]


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def _vox(shape: Shape3) -> int:
    return math.prod(int(s) for s in shape)


def _widths(precision: str) -> tuple[int, int]:
    """(activation, weight) byte widths for the layer-wise schedules."""
    return quantize.act_bytes(precision), quantize.weight_bytes(precision)


def meshnet_xla_bytes(
    cfg, vol: Shape3, batch: int = 1, precision: str = "fp32"
) -> int:
    """Reference XLA graph: each layer is conv -> BN -> ReLU, three
    materialised stages (the "three HBM round-trips per layer" the fused
    path collapses, EXPERIMENTS.md §Perf H1). Conv itself is modeled at
    its traffic floor (read once, write once) — generous to XLA."""
    ab, wb = _widths(precision)
    v = _vox(vol)
    data = 0
    weights = 0
    cin = cfg.in_channels
    c = cfg.channels
    stages = 3 if cfg.use_batchnorm else 2  # conv, (bn,) relu
    for _ in cfg.dilations:
        data += v * (cin + c) * ab  # conv read + write
        data += (stages - 1) * 2 * v * c * ab  # bn/relu round-trips
        weights += 27 * cin * c * wb
        cin = c
    data += v * (c + cfg.num_classes) * ab  # 1x1x1 head
    return batch * data + weights


def meshnet_fused_bytes(
    cfg, vol: Shape3, batch: int = 1, precision: str = "fp32"
) -> int:
    """Per-layer fused Pallas path (ops.meshnet_apply): the megakernel's
    planner restricted to one layer per segment, priced by its plan —
    every layer's haloed window reads and its write at the activation
    width (this path never stages int8), the head fused into the last
    layer's call, the weight streams charged once per launch."""
    pln = megakernel.plan_for_config(
        cfg,
        tuple(int(s) for s in vol),
        precision=None if precision == "fp32" else precision,
        int8_staging=False if precision == "int8w" else None,
        batch=batch,
        per_layer=True,
    )
    return pln.hbm_bytes(batch=batch)


def meshnet_views_bytes(
    cfg, vol: Shape3, batch: int = 1, block: int = 16, precision: str = "fp32"
) -> int:
    """The pre-halo-load 27-view schedule (variant="views"): every grid
    step streams 27 full blocks regardless of dilation — the ~28x-off
    baseline the haloed load replaced (DESIGN.md §2)."""
    ab, wb = _widths(precision)
    data = 0
    weights = 0
    cin = cfg.in_channels
    c = cfg.channels
    for _ in cfg.dilations:
        p = [_ceil_to(v, block) for v in vol]
        ntiles = math.prod(pp // block for pp in p)
        data += _vox(vol) * cin * ab  # block-halo pad read
        data += math.prod(pp + 2 * block for pp in p) * cin * ab
        data += ntiles * 27 * block**3 * cin * ab
        weights += ntiles * 27 * cin * c * wb
        data += math.prod(p) * c * ab
        cin = c
    data += _vox(vol) * (c + cfg.num_classes) * ab
    return batch * data + weights


def meshnet_streaming_bytes(
    cfg, vol: Shape3, batch: int = 1, precision: str = "fp32"
) -> int:
    """Scan-over-layers schedule (core/streaming.py): a memory-floor
    path, not a traffic-optimal one — each scanned layer pads the carry
    by the max dilation and gathers 27 dynamic-slice taps, each tap a
    read + accumulator round-trip."""
    ab, wb = _widths(precision)
    v = _vox(vol)
    dmax = max(cfg.dilations)
    vp = math.prod(int(s) + 2 * dmax for s in vol)
    data = 0
    weights = 0
    cin = cfg.in_channels
    c = cfg.channels
    for i, _ in enumerate(cfg.dilations):
        if i == 0:
            # first layer runs unstacked, as the plain XLA block
            stages = 3 if cfg.use_batchnorm else 2
            data += v * (cin + c) * ab
            data += (stages - 1) * 2 * v * c * ab
        else:
            data += v * c * ab + vp * c * ab  # pad carry
            data += 27 * (vp + 2 * v) * c * ab  # taps + acc r/w
            data += 2 * v * c * ab  # bn+relu epilogue
        weights += 27 * cin * c * wb
        cin = c
    data += v * (c + cfg.num_classes) * ab
    return batch * data + weights


def meshnet_megakernel_bytes(
    cfg,
    vol: Shape3,
    batch: int = 1,
    precision: str = "fp32",
    vmem_budget: int | None = None,
) -> int:
    """Depth-first tiled megakernel: the planner's own traffic model
    (kernels/megakernel.py) — haloed tile reads per segment, one logits
    write, zero intra-segment activation traffic — priced on the plan the
    planner picks by modeled time. The plan is re-optimized per precision
    (smaller working sets buy larger tiles) AND per batch size (the DP
    scales data terms and computed positions by N while charging the
    weight stream once), and
    each tensor role is priced at its policy width, including the int8
    input and staging streams under "int8w"."""
    pln = megakernel.plan_for_config(
        cfg,
        tuple(int(s) for s in vol),
        vmem_budget=vmem_budget or megakernel.VMEM_BUDGET,
        precision=None if precision == "fp32" else precision,
        batch=batch,
    )
    return pln.hbm_bytes(batch=batch)


def meshnet_collective_bytes(
    cfg,
    vol: Shape3,
    num_devices: int,
    batch: int = 1,
    precision: str = "fp32",
) -> int:
    """Modeled inter-device (ICI) bytes of one Z-sharded forward
    (core/spatial_shard.py, DESIGN.md §2.2).

    Each of the ``num_devices - 1`` slab boundaries exchanges, summed over
    the layer-wise schedule, ``2 * sum(dilations)`` Z-slices of the hidden
    activation in each direction:

        per_boundary = 2 * sum(dilations) * H * W * C_hidden * act_bytes

    (the one-shot RF-radius fetch of the megakernel inner moves the same
    slice count once, at the input channel width — this single formula is
    the accounting convention for the whole family). Reduced precisions
    exchange bf16 slabs, so the halo bill halves with the activations.
    Zero at one device; monotone in device count
    (tests/test_properties.py)."""
    n = int(num_devices)
    if n <= 1:
        return 0
    ab = quantize.act_bytes(precision)
    _, h, w = (int(s) for s in vol)
    per_boundary = 2 * sum(cfg.dilations) * h * w * cfg.channels * ab
    return batch * (n - 1) * per_boundary


def meshnet_sharded_bytes(
    inner: str,
    cfg,
    vol: Shape3,
    num_devices: int,
    batch: int = 1,
    precision: str = "fp32",
) -> int:
    """Modeled HBM bytes of one Z-sharded forward: every device runs the
    inner schedule on its slab, so the total is ``n`` times the inner
    model priced at the per-device window. The megakernel inner plans on
    the slab plus its one-shot RF-radius halo (that window is what its
    tiles actually read); the layer-wise inners are priced at the bare
    slab — their halo traffic crosses ICI, not HBM, and is accounted by
    ``meshnet_collective_bytes``. Per-device HBM = this / n
    (EXPERIMENTS.md §Perf H10)."""
    n = int(num_devices)
    d, h, w = (int(s) for s in vol)
    if d % n:
        from repro.core.spatial_shard import ShardGeometryError

        raise ShardGeometryError(f"Z dim {d} not divisible by {n} slabs")
    dloc = d // n
    if inner == "pallas_megakernel":
        radius = sum(cfg.dilations)
        per_dev = meshnet_megakernel_bytes(
            cfg, (dloc + 2 * radius, h, w), batch=batch, precision=precision
        )
    else:
        fn = EXECUTOR_MODELS[inner]
        per_dev = fn(cfg, (dloc, h, w), batch=batch, precision=precision)
    return n * per_dev


#: executor name -> modeled-bytes fn, the mapping the registry wires up
#: (base backends; the sharded family prices itself via
#: ``meshnet_sharded_bytes`` with its inner name and slab count).
EXECUTOR_MODELS = {
    "xla": meshnet_xla_bytes,
    "pallas_fused": meshnet_fused_bytes,
    "streaming": meshnet_streaming_bytes,
    "pallas_megakernel": meshnet_megakernel_bytes,
}


def executor_hbm_bytes(
    name: str, cfg, vol: Shape3, batch: int = 1, precision: str = "fp32"
) -> int | None:
    """Modeled bytes for a registered executor, or None if unmodeled."""
    fn = EXECUTOR_MODELS.get(name)
    return None if fn is None else fn(cfg, vol, batch=batch, precision=precision)
