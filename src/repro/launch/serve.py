"""Serving launcher: batched MRI segmentation (the paper's deployment) or
LM generation for any assigned arch.

  PYTHONPATH=src python -m repro.launch.serve --engine segmentation -n 4
  PYTHONPATH=src python -m repro.launch.serve --engine lm --arch rwkv6-3b -n 3
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def serve_segmentation(args):
    import dataclasses

    from repro.core import meshnet
    from repro.core.meshnet import MeshNetConfig
    from repro.core.pipeline import PipelineConfig
    from repro.data import mri
    from repro.serving.engine import SegmentationEngine
    shape = (args.volume,) * 3
    cfg_m = MeshNetConfig()
    params = meshnet.init(jax.random.PRNGKey(0), cfg_m)
    pc = PipelineConfig(model=cfg_m, volume_shape=shape, min_component_size=8)
    eng = SegmentationEngine(params, pc)
    key = jax.random.PRNGKey(1)
    for i in range(args.n):
        key, k = jax.random.split(key)
        vol, _ = mri.generate(k, mri.SyntheticMRIConfig(shape=shape))
        res = eng.submit(vol)
        t = res.record.times
        print(
            f"req {i}: {res.record.status} mode={res.record.mode} "
            f"pre {t.preprocessing:.2f}s inf {t.inference:.2f}s post {t.postprocessing:.2f}s"
        )
    print(f"success rate: {eng.log.success_rate()*100:.1f}%")


def serve_lm(args):
    import dataclasses

    from repro import configs
    from repro.models import model as MD
    from repro.serving.engine import LMEngine, Request

    cfg = configs.get_smoke(args.arch)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = MD.init(jax.random.PRNGKey(0), cfg)
    eng = LMEngine(params, cfg, slots=args.slots, max_seq=args.max_seq, prefill_chunk=8)
    key = jax.random.PRNGKey(1)
    reqs = []
    for i in range(args.n):
        key, k = jax.random.split(key)
        plen = int(jax.random.randint(k, (), 3, 12))
        prompt = jax.random.randint(k, (plen,), 0, cfg.vocab_size).tolist()
        reqs.append(Request(prompt=prompt, max_new_tokens=args.max_new, id=i))
    t0 = time.perf_counter()
    outs = eng.run(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(c.tokens) for c in outs)
    for c in outs:
        print(f"req {c.id}: {len(c.tokens)} tokens, prefill {c.prefill_s:.2f}s")
    print(f"{total} tokens in {dt:.2f}s = {total/dt:.1f} tok/s ({args.arch} reduced)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="segmentation", choices=["segmentation", "lm"])
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("-n", type=int, default=4)
    ap.add_argument("--volume", type=int, default=48)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    if args.engine == "segmentation":
        serve_segmentation(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
