"""Serving launcher: batched MRI segmentation (the paper's deployment).

  PYTHONPATH=src python -m repro.launch.serve -n 4
"""

from __future__ import annotations

import argparse

import jax


def serve_segmentation(args):
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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("-n", type=int, default=4)
    ap.add_argument("--volume", type=int, default=48)
    args = ap.parse_args()
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    serve_segmentation(args)


if __name__ == "__main__":
    main()
