"""Pluggable MeshNet inference executors — the registry behind the pipeline.

The pipeline (core/pipeline.py) separates two orthogonal choices:

  * **mode** — the spatial strategy: ``full`` (whole volume in one forward),
    ``subvolume`` (overlap-patched cubes, the paper's failsafe), or
    ``streaming`` (layer-by-layer schedule, the paper's progressive
    inference with disposal).
  * **executor** — the forward-pass *implementation* that runs on each
    block of work. Every executor exposes the same uniform interface
    ``apply(params, x, cfg) -> logits`` with ``x: (B, D, H, W[, C])`` and
    logits ``(B, D, H, W, num_classes)``, numerically equal to
    ``meshnet.apply`` in eval mode (tests/test_executors.py enforces this).
    The leading ``B`` is a true N-volume batch axis on every backend — a
    leading dim the XLA/fused kernels carry through, the innermost grid
    axis of the megakernel (per-segment weight DMA amortizes across the
    whole batch), and a second mesh axis for the sharded family when the
    host has spare devices beyond the slab count — and each batch member's
    logits equal its unbatched forward (tests/test_batched.py). The traffic
    models price the amortization: ``hbm_bytes(batch=N) < N *
    hbm_bytes(batch=1)`` whenever a weight-stream term exists.

Built-in executors (DESIGN.md §2):

  ``xla``          — the reference path: ``meshnet.apply``, one XLA op per
                     conv/BN/ReLU stage. Always available; the parity oracle.
  ``pallas_fused`` — per-layer fusion: ``ops.meshnet_apply``, each hidden
                     layer is ONE fused Pallas call (conv+BN+ReLU epilogue),
                     so activations make a single HBM round-trip per layer
                     (EXPERIMENTS.md §Perf H1). Compiled Mosaic on TPU;
                     interpret mode (slow, correctness-path) on CPU hosts.
  ``pallas_megakernel`` — depth-first tiling: ``ops.meshnet_apply_megakernel``
                     runs the *whole* hidden stack (and the head) per
                     VMEM-resident tile, so hidden activations never touch
                     HBM within a segment — the traffic floor and the
                     production TPU path (EXPERIMENTS.md §Perf H9).
  ``streaming``    — the memory-floor path: ``streaming.streaming_apply``,
                     a lax.scan over stacked layers keeping two live
                     activations regardless of depth (DESIGN.md §4).

  ``sharded_<inner>`` — the multi-device family (DESIGN.md §2.2): wraps a
                     single-device backend (``xla`` | ``pallas_fused`` |
                     ``pallas_megakernel``) and runs it per Z-slab under
                     ``shard_map`` over a 1-D mesh, halos exchanged with
                     ``spatial_shard.halo_exchange_z`` (layer-wise for the
                     XLA/fused inners; one RF-radius fetch feeding the
                     megakernel's haloed-tile planner for the Pallas
                     inner). ``sharded_<inner>@<n>`` pins the slab count;
                     without ``@n`` all local devices are used. Specs are
                     registered on demand — any such name resolves.

``executor="auto"`` (the PipelineConfig default) resolves per backend: on
TPU with more than one device it prefers ``sharded_pallas_megakernel``
when the *per-slab* (slab + RF halo) tile plan fits the VMEM budget; on a
single TPU device, ``pallas_megakernel`` when its plan fits, else
``pallas_fused``; on CPU hosts it resolves to ``xla``, where Pallas
interpret mode is a correctness tool, not a serving backend. Pass an
explicit name to force a path (benchmarks and parity tests do).

Each spec also carries ``hbm_bytes`` — the modeled HBM traffic of one
forward under that executor's schedule (telemetry/traffic.py) — and, for
the sharded family, ``collective_bytes`` — the modeled inter-device halo
bytes. The pipeline stamps both into every telemetry record
(``hbm_bytes_modeled`` / ``collective_bytes_modeled``) and the benchmarks
report them next to wall-clock.

Extending: ``register(ExecutorSpec(...))`` adds a backend (e.g. a
quantised or remote forward) without touching the pipeline, engine, or
benchmarks — they all dispatch through this registry.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax

from repro.core import meshnet, spatial_shard, streaming
from repro.core.meshnet import MeshNetConfig
from repro.kernels import megakernel, ops, quantize
from repro.telemetry import traffic

# (params, x, cfg, precision) -> logits; x (B, D, H, W[, C]) ->
# (B, D, H, W, classes). B is an arbitrary batch size (>= 1): backends
# MUST treat the leading dim as independent volumes whose per-member
# logits match the unbatched forward. ``precision`` is the storage policy
# (kernels/quantize.py: "fp32" | "bf16" | "int8w"); params may arrive raw
# fp32 or already prepared (quantize.prepare_params is idempotent).
ApplyFn = Callable[[Any, jax.Array, MeshNetConfig, str], jax.Array]

# (cfg, volume_shape, batch, precision) -> modeled HBM bytes, or None.
BytesFn = Callable[..., Optional[int]]


@dataclasses.dataclass(frozen=True)
class ExecutorSpec:
    """One inference backend.

    ``apply`` is the uniform whole-batch forward — every backend takes a
    ``precision`` keyword (the policy of kernels/quantize.py) and must
    hold the parity gates per policy: bf16 logits within 1e-2 of fp32,
    int8w segmentation-dice >= 0.99 of fp32 (tests/test_precision.py).
    ``streaming_apply`` is the schedule mode="streaming" uses — for the
    fused paths it is the same function, because per-layer/per-tile
    fusion already yields the two-live-buffer schedule (each layer's
    activation is consumed by exactly one next call).
    ``hbm_bytes(cfg, vol, batch=1, precision="fp32")`` prices the
    schedule's HBM traffic at the policy's byte widths
    (telemetry/traffic.py); None if unmodeled.
    ``collective_bytes(cfg, vol, batch=1, precision="fp32")`` prices
    inter-device halo traffic — None for single-device backends (modeled
    as zero); reduced precisions ship bf16/int8 halos.
    """

    name: str
    apply: ApplyFn
    streaming_apply: ApplyFn
    description: str = ""
    hbm_bytes: Optional[BytesFn] = None
    collective_bytes: Optional[BytesFn] = None


_REGISTRY: dict[str, ExecutorSpec] = {}

#: the name PipelineConfig defaults to; resolved per-backend at run time.
AUTO = "auto"


def register(spec: ExecutorSpec) -> ExecutorSpec:
    _REGISTRY[spec.name] = spec
    # Evict only this spec's compiled wrappers; other backends stay hot.
    for key in [k for k in _JIT_CACHE if k[0] == spec.name]:
        _JIT_CACHE.pop(key, None)
    return spec


def names() -> list[str]:
    """Registered executor names (stable order of registration)."""
    return list(_REGISTRY)


# --------------------------------------------------------- sharded family ---

#: name prefix of the Z-sharded wrapper family (core/spatial_shard.py).
SHARDED_PREFIX = "sharded_"


def sharded_name(inner: str, num_devices: Optional[int] = None) -> str:
    """Registry name of the sharded wrapper around ``inner``:
    ``sharded_<inner>`` (all local devices) or ``sharded_<inner>@<n>``."""
    base = SHARDED_PREFIX + inner
    return base if num_devices is None else f"{base}@{num_devices}"


def parse_sharded(name: str) -> Optional[tuple[str, Optional[int]]]:
    """(inner, num_devices) for a sharded-family name, else None.
    Raises KeyError for a sharded name whose inner backend is unknown or
    whose slab count is not a positive integer."""
    if not name.startswith(SHARDED_PREFIX):
        return None
    rest = name[len(SHARDED_PREFIX):]
    inner, _, n = rest.partition("@")
    if inner not in spatial_shard.SHARDED_INNERS:
        raise KeyError(
            f"unknown executor {name!r}: sharded inner must be one of "
            f"{sorted(spatial_shard.SHARDED_INNERS)}"
        )
    if n and (not n.isdigit() or int(n) < 1):
        raise KeyError(
            f"unknown executor {name!r}: slab count after '@' must be a "
            "positive integer"
        )
    return inner, (int(n) if n else None)


def inner_of(name: str) -> str:
    """The single-device backend behind a sharded name (identity for
    non-sharded names) — what a device-count override re-wraps."""
    parsed = parse_sharded(name)
    return parsed[0] if parsed else name


def shardable(name: str) -> bool:
    """Whether the (inner of the) named executor has a sharded form."""
    return inner_of(name) in spatial_shard.SHARDED_INNERS


def _make_sharded_spec(inner: str, num_devices: Optional[int]) -> ExecutorSpec:
    def _apply(params, x, cfg, precision: str = "fp32"):
        return spatial_shard.sharded_executor_apply(
            inner, params, x, cfg, num_devices=num_devices, precision=precision
        )

    def _hbm(cfg, vol, batch: int = 1, precision: str = "fp32"):
        n = num_devices or jax.device_count()
        return traffic.meshnet_sharded_bytes(
            inner, cfg, vol, n, batch=batch, precision=precision
        )

    def _collective(cfg, vol, batch: int = 1, precision: str = "fp32"):
        n = num_devices or jax.device_count()
        return traffic.meshnet_collective_bytes(
            cfg, vol, n, batch=batch, precision=precision
        )

    slabs = f"{num_devices} Z-slabs" if num_devices else "one Z-slab per device"
    return ExecutorSpec(
        name=sharded_name(inner, num_devices),
        apply=_apply,
        streaming_apply=_apply,
        description=f"shard_map halo-exchange wrapper over {inner!r} ({slabs})",
        hbm_bytes=_hbm,
        collective_bytes=_collective,
    )


def ensure_sharded(inner_or_name: str, num_devices: Optional[int] = None) -> str:
    """Register (idempotently) and return the sharded wrapper's name.

    Accepts a bare inner backend (``"pallas_fused"``) or an existing
    sharded name (``"sharded_pallas_fused"``, re-pinned to ``num_devices``
    when given). This is how the pipeline's ``shard_devices`` and the
    engine's per-request device-count overrides materialise specs.
    """
    inner = inner_of(inner_or_name)
    if inner not in spatial_shard.SHARDED_INNERS:
        raise KeyError(
            f"executor {inner!r} cannot be sharded; supported inners: "
            f"{sorted(spatial_shard.SHARDED_INNERS)}"
        )
    name = sharded_name(inner, num_devices)
    if name not in _REGISTRY:
        register(_make_sharded_spec(inner, num_devices))
    return name


def default_executor(
    model: Optional[MeshNetConfig] = None,
    volume_shape: Optional[tuple[int, int, int]] = None,
    *,
    backend: Optional[str] = None,
    num_devices: Optional[int] = None,
    precision: str = "fp32",
) -> str:
    """The production default. On TPU: the sharded depth-first megakernel
    when more than one device is attached, the volume's Z dim divides
    evenly, and the *per-slab* (slab + RF-radius halo) tile plan fits the
    VMEM budget; on a single device, the megakernel when its plan fits,
    else the per-layer fused path; without a model to plan for, the fused
    path. On CPU hosts: XLA (Pallas interpret mode is a correctness path,
    far too slow to serve). Plans are judged at the request's resolved
    ``precision`` — a bf16/int8 working set can fit where fp32 does not.
    ``backend``/``num_devices`` override the host introspection (tests
    pin them)."""
    backend = backend or jax.default_backend()
    if backend != "tpu":
        return "xla"
    if model is None:
        return "pallas_fused"
    vol = volume_shape or (256, 256, 256)
    n = jax.device_count() if num_devices is None else num_devices
    if n > 1 and vol[0] % n == 0:
        radius = sum(model.dilations)
        slab = (vol[0] // n + 2 * radius, vol[1], vol[2])
        try:
            megakernel.plan_for_config(model, slab, precision=precision)
            # an explicit device count pins the spec ("@n"), so the
            # geometry validated here is the geometry that executes; the
            # introspected count stays unpinned (same n at run time).
            return ensure_sharded("pallas_megakernel", num_devices)
        except ValueError:
            pass
    try:
        megakernel.plan_for_config(model, vol, precision=precision)
        return "pallas_megakernel"
    except ValueError:
        return "pallas_fused"


def resolve(
    name: Optional[str],
    model: Optional[MeshNetConfig] = None,
    volume_shape: Optional[tuple[int, int, int]] = None,
    precision: str = "fp32",
) -> str:
    """Map None/"auto" to the backend default (model/shape/precision aware
    when the caller can supply them); validate explicit names. Sharded-
    family names (``sharded_<inner>[@n]``) register their spec on first
    use."""
    if name is None or name == AUTO:
        return default_executor(model, volume_shape, precision=precision)
    if name not in _REGISTRY:
        parsed = parse_sharded(name)  # KeyError on a bad sharded inner
        if parsed is not None:
            return ensure_sharded(parsed[0], parsed[1])
        raise KeyError(
            f"unknown executor {name!r}; registered: {sorted(_REGISTRY)} (or 'auto')"
        )
    return name


def get(name: Optional[str]) -> ExecutorSpec:
    """Fetch an executor spec, resolving "auto"."""
    return _REGISTRY[resolve(name)]


def apply(
    name: Optional[str],
    params,
    x: jax.Array,
    cfg: MeshNetConfig,
    precision: str = "fp32",
) -> jax.Array:
    """One-shot dispatch: run ``x`` through the named executor (eager —
    composable under an outer jit; use ``jitted_apply`` on hot paths)."""
    return get(name).apply(params, x, cfg, precision=precision)


def modeled_hbm_bytes(
    name: Optional[str],
    cfg: MeshNetConfig,
    volume_shape: tuple[int, int, int],
    batch: int = 1,
    precision: str = "fp32",
) -> Optional[int]:
    """Modeled HBM bytes of one forward under the named executor's
    schedule at the given precision policy, or None if the backend has no
    traffic model."""
    spec = _REGISTRY[resolve(name, cfg, volume_shape, precision)]
    if spec.hbm_bytes is None:
        return None
    return spec.hbm_bytes(cfg, volume_shape, batch=batch, precision=precision)


def modeled_recompute(
    name: Optional[str],
    cfg: MeshNetConfig,
    volume_shape: tuple[int, int, int],
    precision: str = "fp32",
) -> Optional[float]:
    """How far the named executor's megakernel plan computes past the
    lane-padded volume (``MegakernelPlan.recompute``), or None for a
    backend that runs no such plan. Host only: the plan is memoized."""
    name = resolve(name, cfg, volume_shape, precision)
    if name not in ("pallas_megakernel", "pallas_fused"):
        return None
    per_layer = name == "pallas_fused"
    return megakernel.plan_for_config(
        cfg,
        volume_shape,
        precision=None if precision == "fp32" else precision,
        int8_staging=False if per_layer and precision == "int8w" else None,
        per_layer=per_layer,
    ).recompute


def modeled_collective_bytes(
    name: Optional[str],
    cfg: MeshNetConfig,
    volume_shape: tuple[int, int, int],
    batch: int = 1,
    precision: str = "fp32",
) -> int:
    """Modeled inter-device halo bytes of one forward under the named
    executor — 0 for single-device backends, the
    ``traffic.meshnet_collective_bytes`` model for the sharded family
    (reduced precisions ship narrower halos). Stamped on every pipeline
    run next to ``hbm_bytes_modeled``."""
    spec = _REGISTRY[resolve(name, cfg, volume_shape, precision)]
    if spec.collective_bytes is None:
        return 0
    return spec.collective_bytes(
        cfg, volume_shape, batch=batch, precision=precision
    )


_JIT_CACHE: dict[tuple[str, str, str], Callable] = {}


def _jitted(name: str, schedule: str, precision: str):
    key = (name, schedule, precision)
    if key not in _JIT_CACHE:
        spec = _REGISTRY[name]
        fn = spec.apply if schedule == "apply" else spec.streaming_apply

        def bound(params, x, cfg, _fn=fn, _p=precision):
            return _fn(params, x, cfg, precision=_p)

        # cfg is a frozen (hashable) dataclass -> static, so one executable
        # is compiled per (executor, schedule, precision, cfg, input shape)
        # and shared by every pipeline run and serving request that matches.
        _JIT_CACHE[key] = jax.jit(bound, static_argnums=(2,))
    return _JIT_CACHE[key]


def jitted_apply(
    name: Optional[str], schedule: str = "apply", precision: str = "fp32"
) -> Callable[[Any, jax.Array, MeshNetConfig], jax.Array]:
    """Jit-compiled executor forward, cached per (executor, schedule,
    precision) — the returned callable keeps the 3-arg ``(params, x,
    cfg)`` signature, with the precision policy bound in.

    This is the dispatch point for hot paths (pipeline.run, the engine,
    sub-volume closures): repeated calls — and batched serving requests in
    any order — reuse one compiled executable per input shape instead of
    re-tracing a fresh ``jax.jit(lambda ...)`` each run.
    ``schedule="streaming"`` selects the spec's layer-streamed variant.
    """
    if schedule not in ("apply", "streaming"):
        raise ValueError(f"schedule must be 'apply' or 'streaming', got {schedule!r}")
    quantize.validate(precision)
    return _jitted(resolve(name), schedule, precision)


def make_infer(
    name: Optional[str],
    params,
    cfg: MeshNetConfig,
    volume_shape: Optional[tuple[int, int, int]] = None,
    precision: str = "fp32",
) -> Callable[[jax.Array], jax.Array]:
    """Build the per-block closure used by sub-volume patching: maps
    (B, d, h, w[, C]) cubes -> (B, d, h, w, classes). Backed by the shared
    ``jitted_apply`` cache, and compiled once per cube shape because all
    cubes in a CubeDivider share a static shape. ``volume_shape`` is the
    *cube* shape the closure will serve — "auto" judges slab divisibility
    and VMEM plans on it, not on the full-volume default."""
    fn = jitted_apply(resolve(name, cfg, volume_shape, precision),
                      precision=precision)

    def infer(c: jax.Array) -> jax.Array:
        return fn(params, c, cfg)

    return infer


def _xla_apply(params, x, cfg, precision: str = "fp32"):
    if precision == "fp32":
        return meshnet.apply(params, x, cfg)
    return quantize.reference_apply(
        quantize.prepare_params(params, cfg, precision), x, cfg, precision
    )


register(
    ExecutorSpec(
        name="xla",
        apply=_xla_apply,
        streaming_apply=streaming.streaming_apply,
        description="reference XLA graph (meshnet.apply); parity oracle",
        hbm_bytes=traffic.meshnet_xla_bytes,
    )
)

register(
    ExecutorSpec(
        name="pallas_fused",
        apply=ops.meshnet_apply,
        streaming_apply=ops.meshnet_apply,
        description="fused Pallas conv+BN+ReLU per layer",
        hbm_bytes=traffic.meshnet_fused_bytes,
    )
)

register(
    ExecutorSpec(
        name="pallas_megakernel",
        apply=ops.meshnet_apply_megakernel,
        streaming_apply=ops.meshnet_apply_megakernel,
        description="depth-first tiled whole-stack Pallas megakernel; "
        "production TPU path when the tile plan fits VMEM",
        hbm_bytes=traffic.meshnet_megakernel_bytes,
    )
)

register(
    ExecutorSpec(
        name="streaming",
        apply=streaming.streaming_apply,
        streaming_apply=streaming.streaming_apply,
        description="lax.scan over stacked layers; memory-floor schedule",
        hbm_bytes=traffic.meshnet_streaming_bytes,
    )
)

# The sharded wrapper family (all-local-devices variants; pinned "@n"
# variants register on demand through resolve/ensure_sharded).
for _inner in spatial_shard.SHARDED_INNERS:
    ensure_sharded(_inner)
del _inner
