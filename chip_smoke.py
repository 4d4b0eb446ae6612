#!/usr/bin/env python3
"""Chip smoke test: serve MeshNet segmentations on a TPU through the engine.

    python chip_smoke.py              # one chip (the default)
    python chip_smoke.py --chips 4    # the four-chip sharded path only

One process drives the chip(s); it starts no child. Weights come from
``meshnet.init`` and volumes from ``data/mri.py``, both seeded
(``--seed``). The one-chip run serves, through ``SegmentationEngine`` ->
``RequestScheduler`` -> ``pipeline.run`` (``submit_async``/``drain``):

  * 4 ``gwm_light`` requests at 256^3 under ``executor="auto"`` and
    ``precision="auto"``, which must resolve to ``pallas_megakernel`` at
    bf16, with connected-components postprocessing on, as served;
  * 1 request forced to ``executor="pallas_fused"`` (bf16);
  * 1 ``subvolume_gwm_failsafe`` request in ``mode="subvolume"`` (cube
    64, overlap 46) with ``precision="int8w"`` named (``auto`` serves
    bf16), which must run ``pallas_megakernel``.

Every request must come back ``status == "ok"`` with the expected
executor, precision and mode, with nothing demoted, faulted or shed by
the scheduler. Each full-volume segmentation must agree with the plain
fp32 reference (``meshnet.apply`` under
``default_matmul_precision("highest")`` on the same chip) on >= 0.95 of
the voxels.

The sub-volume request is compared cube by cube, on cubes cut and
trimmed here with plain slicing, with three XLA forwards of each cube:
fp32, int8w as the ``xla`` executor serves it, and int8w with the
megakernel's int8 inter-segment staging applied at the served plan's
segment boundaries. The kernel is gated against the last, which has the
same rounding points; the other two agreements are printed: they measure
the int8w policy (weight and staging quantization), not the kernels.

``--chips 4`` segments ``gwm_light`` at 256^3 with
``sharded_pallas_megakernel@4``, checks that its output really spans four
devices, and compares it with the single-chip megakernel and the fp32
reference under the same bound.

The time of each request is printed as the pipeline stamps it, the first
request of each executable (which includes compilation) apart from the
steady ones. They are facts for a benchmark, not claims.

The script exits nonzero, printing no result, when JAX finds no TPU or
any phase fails. Its last line on success is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

AGREEMENT_BOUND = 0.95  # the CPU tests' argmax bound (tests/test_precision.py)
VOLUME = (256, 256, 256)
CUBE, OVERLAP = 64, 46  # the failsafe's sub-volume geometry


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def volumes(seed: int, n: int):
    import jax

    from repro.data import mri

    keys = jax.random.split(jax.random.PRNGKey(seed), n)
    return [
        mri.generate(k, mri.SyntheticMRIConfig(shape=VOLUME))[0] for k in keys
    ]


def seeded_params(cfg, seed: int):
    import jax

    from repro.core import meshnet

    return meshnet.init(jax.random.PRNGKey(seed), cfg)


#: Z planes one reference window computes; the window adds the receptive
#: field radius (46) on each side, clamped to the volume.
REF_CHUNK = 8


def reference_segmentation(params, cfg, vol):
    """fp32 ``meshnet.apply`` at highest matmul precision on the conformed
    volume, argmax'd as the pipeline does.

    At 256^3 the plain XLA forward does not fit one chip's 16 GB (XLA
    pads the 5-channel minor dim to 128 lanes: ~8.6 GB per activation),
    so it runs on Z windows of ``REF_CHUNK`` planes plus the receptive
    field radius on each side. The windows are clamped to the volume, so
    every window edge is either the volume's own edge (where 'same'
    padding is the truth) or at least one radius from the planes kept —
    the kept planes are exact."""
    import jax
    import jax.numpy as jnp

    from repro.core import meshnet
    from repro.core import conform as conform_mod

    x = conform_mod.conform(vol, VOLUME)[None]
    depth = x.shape[1]
    radius = sum(cfg.dilations)
    window = min(depth, REF_CHUNK + 2 * radius)
    forward = jax.jit(meshnet.apply, static_argnums=2)
    parts = []
    with jax.default_matmul_precision("highest"):
        for z0 in range(0, depth, REF_CHUNK):
            start = min(max(z0 - radius, 0), depth - window)
            logits = forward(params, x[:, start:start + window], cfg)
            keep = logits[0, z0 - start:z0 - start + REF_CHUNK]
            parts.append(jnp.argmax(keep, -1).astype(jnp.int32))
    return jnp.concatenate(parts, axis=0)


def staged_int8w_forward(params, x, cfg, boundaries):
    """int8w at the megakernel's rounding points, written with plain XLA
    convs: the conformed input as its int8 codes (the dequant scale rides
    the first layer's affine), int8 weights dequantized in the fp32
    epilogue, activations rounded to bf16 between layers, and after each
    hidden layer in ``boundaries`` (the last layer of a segment) the fp32
    epilogue output quantized to int8 with the BatchNorm-derived staging
    scales and dequantized, as the staging buffer between two segments
    holds it. Logits are rounded to bf16 as the kernel stores them."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import quantize

    q = quantize.prepare_params(params, cfg, "int8w")
    scales = quantize.staging_scales_from_bn(q, cfg)
    bf16 = jnp.bfloat16
    h = quantize.quantize_input(x[..., None]).astype(bf16)
    for i, d in enumerate(cfg.dilations):
        layer = q["layers"][i]
        bias, scale, offset = quantize.fold_epilogue(layer, cfg.use_batchnorm)
        if i == 0:
            scale = scale * quantize.INPUT_SCALE
        acc = jax.lax.conv_general_dilated(
            h, layer["w"].astype(bf16), (1, 1, 1), [(d, d)] * 3,
            rhs_dilation=(d,) * 3, dimension_numbers=("NDHWC", "DHWIO", "NDHWC"),
            preferred_element_type=jnp.float32,
        )
        out = jnp.maximum((acc + bias) * scale + offset, 0.0)
        if i in boundaries:
            codes = jnp.clip(jnp.round(out / scales[i]), -127.0, 127.0)
            out = codes * scales[i]
        h = out.astype(bf16)
    head = q["head"]
    logits = jnp.einsum(
        "bdhwi,io->bdhwo", h, head["w"][0, 0, 0].astype(bf16),
        preferred_element_type=jnp.float32,
    ) + head["b"].astype(jnp.float32)
    return logits.astype(bf16)


def per_cube_segmentation(forward, vol, cube: int, overlap: int):
    """The sub-volume mode's semantics written out plainly: zero-pad the
    conformed volume by ``overlap``, run ``forward`` on every
    (cube + 2 * overlap)^3 window at a ``cube`` stride, and keep the
    argmax of each window's central cube^3."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import conform as conform_mod

    check(all(v % cube == 0 for v in VOLUME), "volume not a whole number of cubes")
    xp = jnp.pad(conform_mod.conform(vol, VOLUME), overlap)
    side = cube + 2 * overlap
    out = np.zeros(VOLUME, np.int32)
    for z in range(0, VOLUME[0], cube):
        for y in range(0, VOLUME[1], cube):
            for x in range(0, VOLUME[2], cube):
                window = xp[z:z + side, y:y + side, x:x + side][None]
                core = forward(window)[0, overlap:overlap + cube,
                                       overlap:overlap + cube,
                                       overlap:overlap + cube]
                out[z:z + cube, y:y + cube, x:x + cube] = np.asarray(
                    jnp.argmax(core, -1))
    return jnp.asarray(out)


def agreement(a, b) -> float:
    import jax.numpy as jnp

    return float(jnp.mean(a == b))


def report_times(label: str, rec) -> None:
    t = rec.times
    log(
        f"  {label}: preprocessing {t.preprocessing:.6f}s inference "
        f"{t.inference:.6f}s postprocessing {t.postprocessing:.6f}s "
        f"total {t.total():.6f}s"
    )


def serve(engine, vols, expect: dict, **overrides):
    """submit_async every volume, drain, and hold each completion to the
    expected (executor, precision, mode) with nothing demoted or shed."""
    ids = [engine.submit_async(v, priority="batch", **overrides) for v in vols]
    done = {c.id: c for c in engine.drain()}
    sched = engine.scheduler()
    out = []
    for i in ids:
        c = done.get(i)
        check(c is not None, f"request {i} never completed")
        rec = c.record
        check(
            c.outcome == "completed",
            f"request {i}: outcome {c.outcome} (demoted or shed by the scheduler)",
        )
        check(
            rec.status == "ok",
            f"request {i}: status {rec.status} fail_type {rec.fail_type}",
        )
        for field, want in expect.items():
            got = getattr(rec, field)
            check(got == want, f"request {i}: {field} {got!r}, expected {want!r}")
        out.append(c)
    st = sched.stats
    check(
        st.demoted == 0 and st.rejected_total() == 0,
        f"scheduler demoted {st.demoted} / rejected {st.rejected}",
    )
    check(
        st.transient_faults == 0 and st.permanent_faults == 0 and st.retries == 0,
        f"scheduler saw faults: transient {st.transient_faults} permanent "
        f"{st.permanent_faults} retries {st.retries}",
    )
    check(sched.breaker is None or sched.breaker.trips == 0,
          "the breaker demoted a signature")
    return out


def one_chip(seed: int) -> None:
    import jax

    from repro.core import components, executors, meshnet
    from repro.core.meshnet import PAPER_MODELS
    from repro.core.pipeline import PipelineConfig
    from repro.kernels import megakernel
    from repro.serving.engine import SegmentationEngine

    light = PAPER_MODELS["gwm_light"]
    failsafe = PAPER_MODELS["subvolume_gwm_failsafe"]
    p_light = seeded_params(light, seed)
    p_fs = seeded_params(failsafe, seed + 1)
    vols = volumes(seed, 4)
    light_cfg = PipelineConfig(name="gwm_light", model=light, volume_shape=VOLUME,
                               executor="auto", precision="auto",
                               postprocess=False)

    log("phase 1: gwm_light x4, executor=auto precision=auto")
    eng = SegmentationEngine(p_light, light_cfg)
    log(f"  engine budget {eng.budget.name}: {eng.budget.bytes_limit} bytes")
    comps = serve(eng, vols, {"executor": "pallas_megakernel", "precision": "bf16"})
    report_times("first request (includes compile)", comps[0].record)
    for c in comps[1:]:
        report_times(f"steady request {c.id}", c.record)
    refs = [reference_segmentation(p_light, light, v) for v in vols]
    for c, ref in zip(comps, refs):
        a = agreement(c.result.segmentation, ref)
        log(f"  request {c.id} agreement with fp32 reference: {a:.6f}")
        check(a >= AGREEMENT_BOUND, f"request {c.id} agreement {a} < {AGREEMENT_BOUND}")

    log("phase 2: gwm_light, executor=pallas_fused")
    (c,) = serve(eng, vols[:1], {"executor": "pallas_fused", "precision": "bf16"},
                 executor="pallas_fused")
    report_times("first request (includes compile)", c.record)
    a = agreement(c.result.segmentation, refs[0])
    log(f"  agreement with fp32 reference: {a:.6f}")
    check(a >= AGREEMENT_BOUND, f"pallas_fused agreement {a} < {AGREEMENT_BOUND}")

    log("phase 3: gwm_light with connected-components postprocessing")
    pp = dataclasses.replace(light_cfg, postprocess=True)
    (c,) = serve(SegmentationEngine(p_light, pp), vols[:1],
                 {"executor": "pallas_megakernel", "precision": "bf16"})
    report_times("first request (includes compile)", c.record)
    ref = components.filter_segmentation(refs[0], light.num_classes,
                                         pp.min_component_size)
    log(f"  postprocessing changed {float((ref != refs[0]).mean()):.6f} of "
        f"the fp32 reference's voxels")
    a = agreement(c.result.segmentation, ref)
    log(f"  agreement with the postprocessed fp32 reference: {a:.6f}")
    check(a >= AGREEMENT_BOUND, f"postprocessed agreement {a} < {AGREEMENT_BOUND}")

    cube, overlap = CUBE, OVERLAP
    log(f"phase 4: subvolume_gwm_failsafe, mode=subvolume cube {cube} "
        f"overlap {overlap}")
    eng_fs = SegmentationEngine(
        p_fs,
        PipelineConfig(name="subvolume_gwm_failsafe", model=failsafe,
                       volume_shape=VOLUME, executor="auto", precision="int8w",
                       cube=cube, overlap=overlap, postprocess=False),
    )
    (c,) = serve(
        eng_fs, vols[:1],
        {"executor": "pallas_megakernel", "precision": "int8w", "mode": "subvolume"},
        mode="subvolume",
    )
    report_times("first request (includes compile)", c.record)
    served = c.result.segmentation
    pln = megakernel.plan_for_config(failsafe, (cube + 2 * overlap,) * 3,
                                     precision="int8w")
    boundaries = {s.start - 1 for s in pln.segments[1:]}
    log(f"  served plan: segments {[s.dilations for s in pln.segments]}, "
        f"int8 staging after hidden layers {sorted(boundaries)}")
    staged = jax.jit(lambda w: staged_int8w_forward(p_fs, w, failsafe, boundaries))
    xla_int8w = executors.jitted_apply("xla", precision="int8w")
    fp32 = jax.jit(lambda w: meshnet.apply(p_fs, w, failsafe))
    with jax.default_matmul_precision("highest"):
        ref_fp32 = per_cube_segmentation(fp32, vols[0], cube, overlap)
    ref_int8w = per_cube_segmentation(
        lambda w: xla_int8w(p_fs, w, failsafe), vols[0], cube, overlap)
    ref_staged = per_cube_segmentation(staged, vols[0], cube, overlap)
    for label, ref in (("fp32", ref_fp32), ("xla int8w", ref_int8w)):
        log(f"  agreement with the per-cube {label} reference (the int8w "
            f"policy; not gated): {agreement(served, ref):.6f}")
    log(f"  per-cube xla int8w vs fp32: {agreement(ref_int8w, ref_fp32):.6f}; "
        f"xla int8w with int8 staging vs fp32: "
        f"{agreement(ref_staged, ref_fp32):.6f}")
    a = agreement(served, ref_staged)
    log(f"  agreement with the per-cube xla int8w reference staged at the "
        f"plan's boundaries: {a:.6f}")
    check(a >= AGREEMENT_BOUND, f"failsafe agreement {a} < {AGREEMENT_BOUND}")


def four_chips(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import conform as conform_mod
    from repro.core import executors
    from repro.core.meshnet import PAPER_MODELS
    from repro.core.pipeline import PipelineConfig
    from repro.serving.engine import SegmentationEngine

    light = PAPER_MODELS["gwm_light"]
    params = seeded_params(light, seed)
    (vol,) = volumes(seed, 1)

    auto = executors.resolve("auto", light, VOLUME, "bf16")
    log(f"executor=auto on {jax.device_count()} devices resolves to {auto}")
    check(executors.inner_of(auto) == "pallas_megakernel" and auto != "pallas_megakernel",
          f"auto resolved to {auto}, not the sharded megakernel")

    log("phase 1: sharded_pallas_megakernel@4 vs the single-chip megakernel")
    x = conform_mod.conform(vol, VOLUME).astype(jnp.bfloat16)[None]
    sharded = executors.jitted_apply("sharded_pallas_megakernel@4", precision="bf16")
    single = executors.jitted_apply("pallas_megakernel", precision="bf16")
    for label, fn in (("sharded", sharded), ("single-chip", single)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(params, x, light))
        log(f"  {label} forward, first call (includes compile): "
            f"{time.perf_counter() - t0:.6f}s")
    t0 = time.perf_counter()
    logits4 = jax.block_until_ready(sharded(params, x, light))
    log(f"  sharded forward, steady: {time.perf_counter() - t0:.6f}s")
    t0 = time.perf_counter()
    logits1 = jax.block_until_ready(single(params, x, light))
    log(f"  single-chip forward, steady: {time.perf_counter() - t0:.6f}s")
    devs = {s.device for s in logits4.addressable_shards}
    zs = sorted(s.index[1].start or 0 for s in logits4.addressable_shards)
    log(f"  output spans {len(devs)} devices, Z slab starts {zs}")
    check(len(devs) == 4 and len(set(zs)) == 4, "slabs did not land on four devices")
    a41 = agreement(jnp.argmax(logits4, -1), jnp.argmax(logits1, -1))
    log(f"  argmax agreement sharded vs single-chip: {a41:.6f}")
    check(a41 >= AGREEMENT_BOUND, f"sharded/single agreement {a41} < {AGREEMENT_BOUND}")

    log("phase 2: one request served on sharded_pallas_megakernel@4")
    eng = SegmentationEngine(
        params,
        PipelineConfig(name="gwm_light", model=light, volume_shape=VOLUME,
                       executor="sharded_pallas_megakernel@4", precision="bf16",
                       postprocess=False),
    )
    (c4,) = serve(eng, [vol], {"executor": "sharded_pallas_megakernel@4",
                               "precision": "bf16"})
    report_times("first request (includes compile)", c4.record)
    ref = reference_segmentation(params, light, vol)
    a4r = agreement(c4.result.segmentation, ref)
    log(f"  agreement with fp32 reference: {a4r:.6f}")
    check(a4r >= AGREEMENT_BOUND, f"sharded/reference agreement {a4r} < {AGREEMENT_BOUND}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "src"))
    try:
        from repro.runtime import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script ({e})",
              file=sys.stderr)
        return 2

    log(f"compile cache: {enable_compile_cache()}")
    import jax

    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    log(f"devices: {device}")
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devices)} devices",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    try:
        (four_chips if args.chips == 4 else one_chip)(args.seed)
    except Exception as e:  # every phase failure ends the run nonzero
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
