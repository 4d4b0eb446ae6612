"""Kernel micro-benchmarks: us_per_call for the Pallas kernels (interpret
mode on CPU — correctness-path timing) vs the XLA reference implementation,
the streaming-vs-plain executor comparison (the paper's layer-wise disposal
strategy, Fig. 4's inference column), and the registry head-to-head
(``bench_executors``): xla vs pallas_fused vs pallas_megakernel end-to-end
MeshNet forward per paper model. ``bench_traffic`` prints the modeled HBM
bytes per forward at the paper's 256^3 volume for every registered
executor (telemetry/traffic.py) — the measurement behind EXPERIMENTS.md
§Perf H1 (per-layer fusion) and §Perf H9 (depth-first tiling: megakernel
>= 1.3x under pallas_fused).

Every row is (name, us_per_call, hbm_bytes_modeled, note); bytes are None
where no traffic model applies (training-side oracles).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import executors, meshnet
from repro.core.meshnet import MeshNetConfig, PAPER_MODELS
from repro.core import streaming
from repro.kernels import ops, ref
from repro.telemetry import traffic

KEY = jax.random.PRNGKey(0)

# Registry head-to-head coverage: the headline full-volume model and the
# wide failsafe model (where Cin x Cout taps start to be MXU-shaped).
EXEC_BENCH_MODELS = ("gwm_light", "subvolume_gwm_failsafe")

# Every executor with a traffic model, timed head-to-head.
EXEC_BENCH_BACKENDS = ("xla", "pallas_fused", "pallas_megakernel")

# Storage policies priced (and spot-timed) per backend — "fp32" rows keep
# their legacy un-suffixed key names so the regression gate diffs
# like-for-like; reduced policies get "@<precision>" keys.
BENCH_PRECISIONS = ("bf16", "int8w")

Row = tuple[str, float, "int | None", str]


def _time(fn, *args, iters=3) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6  # us


def bench() -> list[Row]:
    rows: list[Row] = []
    x = jax.random.normal(KEY, (1, 32, 32, 32, 5))
    w = jax.random.normal(KEY, (3, 3, 3, 5, 5)) * 0.2
    b = jnp.zeros((5,))
    conv_b = traffic.dilated_conv_layer_bytes((32, 32, 32), 5, 5, dilation=8)

    ref_fn = jax.jit(lambda x, w, b: ref.dilated_conv3d(x, w, b, dilation=8))
    rows.append(("dilated_conv3d_xla_ref_32cube", _time(ref_fn, x, w, b), None, "oracle"))
    pal_fn = jax.jit(
        lambda x, w, b: ops.dilated_conv3d(x, w, b, dilation=8)
    )
    rows.append(("dilated_conv3d_pallas_interp_32cube", _time(pal_fn, x, w, b), conv_b, "interpret-mode (correctness path; compiled Mosaic on TPU)"))

    pred = jax.random.randint(KEY, (64, 64, 64), 0, 3)
    truth = jax.random.randint(jax.random.PRNGKey(1), (64, 64, 64), 0, 3)
    from repro.training import losses

    dice_b = 2 * 64**3 * 4  # pred + truth reads; counts are negligible
    rows.append(("dice_xla_ref_64cube", _time(jax.jit(lambda a, b: losses.dice_score(a, b, 3)), pred, truth), None, "oracle"))
    rows.append(("dice_pallas_interp_64cube", _time(lambda a, b: ops.dice(a, b, 3), pred, truth), dice_b, "interpret-mode"))

    cfg = MeshNetConfig()
    p = meshnet.init(KEY, cfg)
    vol = jax.random.normal(KEY, (1, 32, 32, 32))
    shape32 = (32, 32, 32)
    plain = jax.jit(lambda v: meshnet.apply(p, v, cfg))
    rows.append(("meshnet_plain_32cube", _time(plain, vol), traffic.meshnet_xla_bytes(cfg, shape32), "all-layers graph"))
    stream = jax.jit(lambda v: streaming.streaming_apply(p, v, cfg))
    rows.append(("meshnet_streaming_32cube", _time(stream, vol), traffic.meshnet_streaming_bytes(cfg, shape32), "scan-over-layers (paper's layer disposal)"))
    return rows


def bench_executors(
    models: tuple[str, ...] = EXEC_BENCH_MODELS,
    side: int = 16,
    iters: int = 2,
) -> list[Row]:
    """Head-to-head end-to-end MeshNet forward per executor backend.

    For each paper model, times the same (1, side^3) volume through every
    Pallas-capable registry entry. On a CPU host the Pallas paths run in
    interpret mode — orders of magnitude slower, correctness-path numbers
    only; on TPU they are compiled Mosaic kernels and the comparison is
    the one that justifies the production default. The bytes column is
    the modeled HBM traffic *at this benchmark shape* (at 16^3 the halo
    dominates; see ``bench_traffic`` for the paper-volume picture).
    """
    rows: list[Row] = []
    backend = jax.default_backend()
    vol = jax.random.normal(KEY, (1, side, side, side))
    for name in models:
        cfg = PAPER_MODELS[name]
        p = meshnet.init(KEY, cfg)
        for exec_name in EXEC_BENCH_BACKENDS:
            # the registry's cached jit wrapper — the exact callable the
            # pipeline and engine serve with, not a fresh per-loop trace
            jf = executors.jitted_apply(exec_name)
            fn = lambda v, jf=jf, p=p, cfg=cfg: jf(p, v, cfg)
            note = (
                "oracle"
                if exec_name == "xla"
                else f"interpret-mode on {backend} (compiled Mosaic on TPU)"
                if backend != "tpu"
                else "compiled Mosaic"
            )
            hbm = executors.modeled_hbm_bytes(exec_name, cfg, (side,) * 3)
            rows.append(
                (f"meshnet_{name}_{exec_name}_{side}cube", _time(fn, vol, iters=iters), hbm, note)
            )
    # precision spot-checks: the headline model through the megakernel at
    # each reduced policy (same volume, same cached-jit dispatch path)
    cfg = PAPER_MODELS[models[0]]
    p = meshnet.init(KEY, cfg)
    for prec in BENCH_PRECISIONS:
        jf = executors.jitted_apply("pallas_megakernel", precision=prec)
        fn = lambda v, jf=jf, p=p, cfg=cfg: jf(p, v, cfg)
        hbm = executors.modeled_hbm_bytes(
            "pallas_megakernel", cfg, (side,) * 3, precision=prec
        )
        rows.append(
            (
                f"meshnet_{models[0]}_pallas_megakernel_{side}cube@{prec}",
                _time(fn, vol, iters=iters),
                hbm,
                f"precision policy {prec} (kernels/quantize.py)",
            )
        )
    return rows


def bench_traffic(
    models: tuple[str, ...] = EXEC_BENCH_MODELS,
    vol: tuple[int, int, int] = (256, 256, 256),
) -> list[Row]:
    """Modeled HBM bytes per forward at the paper's full volume, for every
    registered executor (no wall-clock: the model is analytic, so this
    runs anywhere — EXPERIMENTS.md §Perf H9's measurement)."""
    rows: list[Row] = []
    side = vol[0]
    for name in models:
        cfg = PAPER_MODELS[name]
        # the retired 27-view conv schedule (variant="views"), kept as the
        # baseline row of the DESIGN.md §2.1 table — not a registered
        # executor, so priced directly from the traffic model
        rows.append(
            (
                f"hbm_{name}_{side}_views_legacy",
                0.0,
                traffic.meshnet_views_bytes(cfg, vol),
                f"modeled at {side}^3 (no timing); retired 27-view schedule",
            )
        )
        for exec_name in executors.names():
            if exec_name.startswith(executors.SHARDED_PREFIX):
                continue  # priced below at explicit slab counts
            hbm = executors.modeled_hbm_bytes(exec_name, cfg, vol)
            note = f"modeled at {side}^3 (no timing)"
            if exec_name == "pallas_megakernel" and hbm is not None:
                fused = executors.modeled_hbm_bytes("pallas_fused", cfg, vol)
                note += f"; {fused / hbm:.1f}x under pallas_fused"
            rows.append((f"hbm_{name}_{side}_{exec_name}", 0.0, hbm, note))
            # per-precision rows (EXPERIMENTS.md H11): the acceptance
            # gate reads the megakernel ratios off this table
            for prec in BENCH_PRECISIONS:
                hb = executors.modeled_hbm_bytes(
                    exec_name, cfg, vol, precision=prec
                )
                pn = f"modeled at {side}^3; precision {prec}"
                if hb is not None and hbm:
                    pn += f", {hb / hbm:.2f}x of fp32"
                rows.append(
                    (f"hbm_{name}_{side}_{exec_name}@{prec}", 0.0, hb, pn)
                )
        # the sharded family (DESIGN.md §2.2): per-device HBM shrinks with
        # the slab count while the ICI halo bill grows one boundary at a
        # time — both modeled, so this prices the paper volume anywhere.
        for n in (2, 4, 8):
            hbm = traffic.meshnet_sharded_bytes("pallas_megakernel", cfg, vol, n)
            coll = traffic.meshnet_collective_bytes(cfg, vol, n)
            rows.append(
                (
                    f"hbm_{name}_{side}_sharded_pallas_megakernel@{n}",
                    0.0,
                    hbm,
                    f"modeled at {side}^3; per-device {hbm // n} HBM bytes, "
                    f"{coll} ICI halo bytes total (EXPERIMENTS.md H10)",
                )
            )
        # the sharded megakernel under int8w: int8 one-shot input fetch +
        # per-slab int8 staging plans. The ICI bill keeps the family-wide
        # activation-width convention (conservative for the int8 fetch —
        # DESIGN.md §2.3).
        hbm = traffic.meshnet_sharded_bytes(
            "pallas_megakernel", cfg, vol, 8, precision="int8w"
        )
        coll = traffic.meshnet_collective_bytes(cfg, vol, 8, precision="int8w")
        rows.append(
            (
                f"hbm_{name}_{side}_sharded_pallas_megakernel@8@int8w",
                0.0,
                hbm,
                f"modeled at {side}^3; precision int8w, {coll} ICI halo "
                "bytes (activation-width convention)",
            )
        )
    return rows
