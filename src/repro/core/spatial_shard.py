"""Distributed full-volume inference: the paper's sub-volume patching mapped
onto a device mesh.

Brainchop splits the volume into sub-cubes *in time* (sequential WebGL jobs)
because a browser has one GPU. A TPU pod has hundreds of chips, so the same
decomposition becomes *spatial sharding*: each device owns a Z-slab of the
volume, and instead of re-reading overlapping context from HBM per cube, the
overlap ("halo") is exchanged between neighbouring devices with
``collective_permute`` before every dilated conv layer.

Exactness: with a halo of ``dilation`` voxels per side per layer, the slab
conv equals the full-volume conv — the distributed analogue of the
``overlap >= RF`` rule in core/patching.py, paid incrementally per layer
(total exchanged per side = sum(dilations) = RF radius). Pod-edge devices
receive *zeros* from the void, which is exactly the volume's per-layer
'same' zero padding, so — unlike sub-volume patching — sharding has **no
boundary-band accuracy loss** (EXPERIMENTS.md §Perf H6).

Slabs thinner than the halo (small volumes over many devices, or the
one-shot RF-radius fetch below) are handled by *multi-hop* exchange:
``halo_exchange_z`` chains ``ppermute`` fetches through as many neighbours
as the halo spans, so any (volume, device-count) geometry with
``D % num_devices == 0`` is exact.

This module also implements the **sharded executor family** of the
registry (core/executors.py, DESIGN.md §2.2): ``sharded_executor_apply``
wraps any single-device backend and runs it per-slab under ``shard_map``
over a 1-D Z mesh —

  * ``xla`` inner — per-layer halo exchange + valid-Z conv (the original
    layer-wise schedule of this module);
  * ``pallas_fused`` inner — per-layer halo exchange + the fused Pallas
    conv+BN+ReLU kernel run 'same' on the extended slab, cropped back;
  * ``pallas_megakernel`` inner — ONE multi-hop exchange of the full
    RF radius (sum(dilations) = 46), then the depth-first megakernel runs
    on the slab+halo window (its DP tile plan computed on that shape) with
    dynamic Z mask bounds so per-layer 'same' zero padding is reproduced
    at the true volume edges, not the window edges.

All three are numerically equal to their single-device inner executor
(tests/test_sharded_executor.py enforces <=1e-4 across PAPER_MODELS at
2/4/8 slabs, including slabs thinner than the RF radius).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import meshnet
from repro.core.meshnet import MeshNetConfig
from repro.kernels import ops, quantize

#: the Z-mesh axis name the sharded executors use.
SPATIAL_AXIS = "z"

#: the batch-mesh axis name used when device counts allow a second axis.
BATCH_AXIS = "b"


class ShardGeometryError(ValueError):
    """The requested slab geometry cannot run: the Z dim does not divide
    into the slab count, or the host lacks the devices. The pipeline maps
    this to a failed telemetry record (fail_type='shard_geometry') instead
    of letting it escape — unlike other ValueErrors, which indicate bugs
    or bad input and propagate."""


@functools.lru_cache(maxsize=32)
def mesh_for(num_devices: int | None = None, axis: str = SPATIAL_AXIS) -> Mesh:
    """A 1-D Z mesh over the first ``num_devices`` local devices, cached so
    every pipeline run / engine request with the same slab count shares one
    Mesh object (and one compiled executable via the registry's jit cache).
    """
    n = num_devices or jax.device_count()
    devs = jax.devices()
    if n > len(devs):
        raise ShardGeometryError(
            f"sharded executor wants {n} devices; host has {len(devs)}"
        )
    return Mesh(np.array(devs[:n]), (axis,))


@functools.lru_cache(maxsize=32)
def mesh_for_batched(
    batch_shards: int,
    num_devices: int,
    axis: str = SPATIAL_AXIS,
    batch_axis: str = BATCH_AXIS,
) -> Mesh:
    """A 2-D (batch, Z) mesh: ``batch_shards`` rows of ``num_devices``
    Z-slab columns. Each batch row runs the full slab pipeline on its
    share of the leading dim; halo ``ppermute``s stay within a row (the
    named Z axis), so the slab numerics are identical to the 1-D mesh.
    Cached like ``mesh_for`` so repeat (batch, slab) signatures share one
    Mesh object and one compiled executable."""
    total = batch_shards * num_devices
    devs = jax.devices()
    if total > len(devs):
        raise ShardGeometryError(
            f"batched sharded executor wants {batch_shards}x{num_devices} "
            f"devices; host has {len(devs)}"
        )
    return Mesh(
        np.array(devs[:total]).reshape(batch_shards, num_devices),
        (batch_axis, axis),
    )


def auto_batch_shards(batch: int, num_devices: int) -> int:
    """The largest batch-axis size a host can add on top of ``num_devices``
    Z slabs: the biggest divisor of ``batch`` with ``k * num_devices``
    devices available. 1 when the host has no spare devices (the 1-D
    mesh), so single-device containers and exactly-sized hosts keep the
    legacy layout."""
    spare = jax.device_count() // max(num_devices, 1)
    for k in range(min(int(batch), spare), 1, -1):
        if batch % k == 0:
            return k
    return 1


def _fetch_slab(x: jax.Array, offset: int, axis_name: str, n: int) -> jax.Array:
    """The slab of the device ``offset`` positions before me (offset > 0)
    or after me (offset < 0); zeros where no such device exists (the pod
    edge — exactly the volume's zero padding)."""
    off = abs(offset)
    if off >= n:
        return jnp.zeros_like(x)
    if offset > 0:  # from device i - offset: i - offset sends to i
        perm = [(i, i + off) for i in range(n - off)]
    else:  # from device i + offset
        perm = [(i, i - off) for i in range(off, n)]
    return jax.lax.ppermute(x, axis_name, perm)


def halo_exchange_z(x: jax.Array, halo: int, axis_name: str) -> jax.Array:
    """Concatenate ``halo`` Z-slices from both neighbour chains onto a slab.

    x: (B, Dz_local, H, W, C) -> (B, Dz_local + 2*halo, H, W, C).
    Pod edges receive zeros (the volume's zero 'same' padding). Halos wider
    than the local slab are fetched *multi-hop*: ceil(halo / Dz_local)
    chained ``ppermute`` steps per side, the farthest hop trimmed to the
    remainder — so one exchange of ``n*h`` provides exactly the context of
    ``n`` per-layer exchanges of ``h`` (tests/test_properties.py).
    """
    if halo == 0:
        return x
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        pad = [(0, 0), (halo, halo), (0, 0), (0, 0), (0, 0)]
        return jnp.pad(x, pad)
    dloc = x.shape[1]
    hops = -(-halo // dloc)  # ceil
    rem = halo - (hops - 1) * dloc  # slices needed from the farthest hop
    left = []  # farthest neighbour first, so axis-1 order is global order
    right = []
    for j in range(hops, 0, -1):
        src = x[:, -rem:] if j == hops and rem < dloc else x
        left.append(_fetch_slab(src, j, axis_name, n))
    for j in range(1, hops + 1):
        src = x[:, :rem] if j == hops and rem < dloc else x
        right.append(_fetch_slab(src, -j, axis_name, n))
    return jnp.concatenate(left + [x] + right, axis=1)


def _conv_layer_slab(
    layer, x, dilation: int, cfg: MeshNetConfig, axis_name: str,
    precision: str = "fp32",
):
    """One MeshNet block on a Z-slab: halo exchange + valid-Z conv. At
    reduced precision the exchanged halos ship in the activation storage
    dtype (bf16), the conv accumulates fp32 on the (possibly int8) taps,
    and the dequant/BN epilogue runs fp32 — the same rounding points as
    the single-device backends, so slab parity holds per policy."""
    x = halo_exchange_z(x, dilation, axis_name)
    pad = dilation  # 'same' padding in H, W; Z context comes from the halo
    if precision == "fp32":
        out = jax.lax.conv_general_dilated(
            x,
            layer["w"],
            (1, 1, 1),
            [(0, 0), (pad, pad), (pad, pad)],
            rhs_dilation=(dilation,) * 3,
            dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
        ) + layer["b"]
        if cfg.use_batchnorm:
            out = (out - layer["bn_mean"]) * jax.lax.rsqrt(layer["bn_var"] + 1e-5)
            out = out * layer["bn_scale"] + layer["bn_bias"]
        return jax.nn.relu(out)
    # the one shared reduced-precision block (z_same=False: Z context
    # came from the halo exchange above) — same rounding points as the
    # xla oracle and the streaming first layer, by construction
    return quantize.conv_block_reduced(
        x, layer, dilation, cfg.use_batchnorm,
        quantize.act_dtype(precision), z_same=False,
    )


def _head(params, x: jax.Array, precision: str = "fp32") -> jax.Array:
    head = params["head"]
    if precision == "fp32":
        return jnp.einsum("bdhwi,io->bdhwo", x, head["w"][0, 0, 0]) + head["b"]
    adt = quantize.act_dtype(precision)
    logits = (
        jnp.einsum(
            "bdhwi,io->bdhwo",
            x,
            head["w"][0, 0, 0].astype(adt),
            preferred_element_type=jnp.float32,
        )
        + head["b"].astype(jnp.float32)
    )
    return logits.astype(adt)


def _dequant_slab_input(x, precision: str):
    """Bring a slab into the policy's activation dtype before the layer-
    wise schedules: pre-quantized int8 input dequants by the fixed
    conformed-volume scale; float input is (for int8w) first snapped to
    the same int8 grid so slab parity with the single-device backends is
    exact rather than approximate."""
    if precision == "fp32":
        return x
    adt = quantize.act_dtype(precision)
    if precision == "int8w":
        if x.dtype != jnp.int8:
            x = quantize.quantize_input(x)
        return x.astype(adt) * jnp.asarray(quantize.INPUT_SCALE, adt)
    return x.astype(adt)


def _slab_xla(
    params, x, cfg: MeshNetConfig, axis_name: str, precision: str = "fp32"
) -> jax.Array:
    """Layer-wise schedule, XLA inner: exchange d, valid-Z conv, repeat."""
    x = _dequant_slab_input(x, precision)
    for i, d in enumerate(cfg.dilations):
        x = _conv_layer_slab(params["layers"][i], x, d, cfg, axis_name, precision)
    return _head(params, x, precision)


def _slab_fused(
    params, x, cfg: MeshNetConfig, axis_name: str, precision: str = "fp32"
) -> jax.Array:
    """Layer-wise schedule, fused Pallas inner: exchange d, run the fused
    conv+BN+ReLU kernel 'same' on the extended slab, crop the polluted
    d-band back off. 'Same' output at positions >= d from the extended
    edge only taps in-window data, so the crop is exact; pod edges hold
    zero halos == the volume's per-layer zero padding. Reduced precisions
    exchange bf16 halos and stream bf16/int8 weights into the kernel,
    whose dequant epilogue is the same as the unsharded fused path."""
    x = _dequant_slab_input(x, precision)
    # params arrive already prepared: sharded_executor_apply quantizes
    # once outside shard_map so the prep is not replicated per device
    use_quant = precision != "fp32"
    for i, d in enumerate(cfg.dilations):
        layer = params["layers"][i]
        if use_quant:
            bias, scale, offset = quantize.fold_epilogue(layer, cfg.use_batchnorm)
        elif cfg.use_batchnorm:
            bias = layer["b"]
            scale, offset = ops.fold_batchnorm(layer)
        else:
            bias = layer["b"]
            scale = offset = None
        xe = halo_exchange_z(x, d, axis_name)
        out = ops.dilated_conv3d(
            xe, layer["w"], bias,
            dilation=d, scale=scale, offset=offset, fuse_affine=True,
        )
        x = out[:, d:-d]
    return _head(params, x, precision)


def _slab_megakernel(
    params, x, cfg: MeshNetConfig, axis_name: str, precision: str = "fp32"
) -> jax.Array:
    """One-shot schedule, megakernel inner: a single multi-hop exchange of
    the full RF radius feeds the depth-first megakernel, whose tile plan is
    computed on the slab+halo window. Dynamic Z mask bounds tell the kernel
    where the *true* volume ends inside the window, so per-layer 'same'
    zero padding is reproduced at pod edges (bit-exact boundary), while
    interior window edges only pollute the halo band the final crop drops.
    For int8w the exchange ships the *quantized* slab (int8 halos — the
    cheapest collectives of the family) and the kernel dequants in VMEM.
    """
    n = jax.lax.axis_size(axis_name)
    dloc = x.shape[1]
    radius = sum(cfg.dilations)
    if precision == "int8w" and x.dtype != jnp.int8:
        # quantize before exchanging: pointwise, so quantize-then-exchange
        # equals exchange-then-quantize, and the halo bytes quarter
        x = quantize.quantize_input(x)
    elif precision == "bf16":
        x = x.astype(quantize.act_dtype(precision))
    xe = halo_exchange_z(x, radius, axis_name)
    g = jax.lax.axis_index(axis_name) * dloc  # my slab's global Z start
    # local coord z holds global z = g - radius + z; valid global range
    # [0, n * dloc) maps to local [radius - g, radius - g + n * dloc).
    z_bounds = jnp.stack(
        [radius - g, radius - g + n * dloc]
    ).astype(jnp.int32)
    out = ops.meshnet_apply_megakernel(
        params, xe, cfg, z_bounds=z_bounds, precision=precision
    )
    return out[:, radius : radius + dloc]


_SLAB_FNS = {
    "xla": _slab_xla,
    "pallas_fused": _slab_fused,
    "pallas_megakernel": _slab_megakernel,
}

#: single-device backends the sharded wrapper accepts as inners.
SHARDED_INNERS = tuple(_SLAB_FNS)


def sharded_executor_apply(
    inner: str,
    params,
    x: jax.Array,
    cfg: MeshNetConfig,
    *,
    num_devices: int | None = None,
    axis: str = SPATIAL_AXIS,
    precision: str = "fp32",
    batch_shards: int | None = None,
) -> jax.Array:
    """Z-sharded MeshNet forward through the named inner backend.

    x: (B, D, H, W) or (B, D, H, W, C); D must divide by the slab count.
    The registry's ``sharded_<inner>`` specs (core/executors.py) are thin
    closures over this function; parity with the single-device inner is
    the sharded family's contract (tests/test_sharded_executor.py),
    per precision policy: the layer-wise inners exchange bf16 halos, the
    megakernel inner's one-shot RF fetch ships the int8 input under
    "int8w" (tests/test_precision.py).

    ``batch_shards`` adds the batch as a second mesh axis where device
    counts allow: ``batch_shards * num_devices`` devices arranged as a
    (batch, Z) grid, each row serving ``B / batch_shards`` volumes.
    ``None`` picks ``auto_batch_shards`` (1 unless the host has spare
    devices beyond the slab count); pass 1 to force the legacy 1-D mesh.
    """
    if inner not in _SLAB_FNS:
        raise KeyError(
            f"unknown sharded inner {inner!r}; supported: {sorted(_SLAB_FNS)}"
        )
    n = num_devices or jax.device_count()
    if x.ndim == 4:
        x = x[..., None]
    if x.shape[1] % n:
        raise ShardGeometryError(
            f"Z dim {x.shape[1]} not divisible by {n} slabs — pick a device "
            "count that divides the volume depth"
        )
    bs = auto_batch_shards(x.shape[0], n) if batch_shards is None else int(batch_shards)
    if bs > 1:
        if x.shape[0] % bs:
            raise ShardGeometryError(
                f"batch {x.shape[0]} not divisible by {bs} batch shards"
            )
        mesh = mesh_for_batched(bs, n, axis)
        in_spec = P(BATCH_AXIS, axis, None, None, None)
    else:
        mesh = mesh_for(n, axis)
        in_spec = P(None, axis, None, None, None)
    slab_fn = _SLAB_FNS[inner]
    if precision != "fp32":
        # prepare once, outside shard_map, so every slab streams the same
        # quantized weights (and the prep is not replicated per device)
        params = quantize.prepare_params(params, cfg, precision)

    fn = jax.shard_map(
        lambda p, xs: slab_fn(p, xs, cfg, axis, precision),
        mesh=mesh,
        in_specs=(P(), in_spec),
        out_specs=in_spec,
        # pallas_call has no varying-manual-axes rule; all our outputs are
        # honestly P(None, "z", ...)-sharded, so skipping the check is sound.
        check_vma=False,
    )
    # Lay inputs out to match the specs (callers may pass single-device arrays).
    params = jax.device_put(params, NamedSharding(mesh, P()))
    x = jax.device_put(x, NamedSharding(mesh, in_spec))
    return fn(params, x)


def sharded_apply(
    params,
    x: jax.Array,
    cfg: MeshNetConfig,
    mesh: Mesh,
    *,
    spatial_axis: str = "model",
    batch_axis: str | None = "data",
) -> jax.Array:
    """Full-volume MeshNet inference with the volume Z-sharded over
    ``spatial_axis`` and the batch over ``batch_axis`` (the standalone
    2-D-mesh demo; the executor registry path is ``sharded_executor_apply``).

    x: (B, D, H, W) or (B, D, H, W, 1); D must divide the spatial axis size.
    """
    if x.ndim == 4:
        x = x[..., None]
    batch_spec = batch_axis if batch_axis else None
    in_spec = P(batch_spec, spatial_axis, None, None, None)

    def slab_fn(params, xs):
        for i, d in enumerate(cfg.dilations):
            xs = _conv_layer_slab(params["layers"][i], xs, d, cfg, spatial_axis)
        head = params["head"]
        return meshnet.dilated_conv3d(xs, head["w"], head["b"], dilation=1)

    fn = jax.shard_map(
        slab_fn,
        mesh=mesh,
        in_specs=(P(), in_spec),
        out_specs=in_spec,
    )
    # Lay inputs out to match the specs (callers may pass single-device arrays).
    params = jax.device_put(params, NamedSharding(mesh, P()))
    x = jax.device_put(x, NamedSharding(mesh, in_spec))
    return fn(params, x)


def make_sharded_infer(params, cfg: MeshNetConfig, mesh: Mesh, **kw):
    """jit-compiled sharded inference fn: (B, D, H, W) -> logits."""

    @functools.partial(jax.jit, donate_argnums=(0,))
    def infer(x):
        return sharded_apply(params, x, cfg, mesh, **kw)

    return infer


def replicate_params(params, mesh: Mesh):
    """MeshNet weights are ~kB-scale: replicate everywhere (the paper ships
    them to every client; we ship them to every chip)."""
    sharding = NamedSharding(mesh, P())
    return jax.device_put(params, sharding)
