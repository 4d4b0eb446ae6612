"""The main-path Pallas kernels, compiled for a TPU v5e chip at the sizes
the service runs — without one. The TPU compiler ships with jaxlib; it
compiles for a described (not attached) chip, so what Mosaic refuses
(unaligned DMA windows, more VMEM than the limit, a kernel it cannot
lower) fails here, at no chip time. Nothing runs: results are checked by
the interpret-mode parity suites.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist every worker imports this file.
"""

import os
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import conform, meshnet
from repro.core.meshnet import PAPER_MODELS
from repro.kernels import dice, megakernel, ops
from repro.kernels import dilated_conv3d as conv_kernel

PAPER_VOLUME = (256, 256, 256)
#: one sub-volume cube of the failsafe: 64 + 2 * 46 (the RF radius)
CUBE_WINDOW = (156, 156, 156)
#: a compile that took minutes (and ~14 GB of host memory) before the
#: tap loop became one matmul per row block takes about a second now
COMPILE_SECONDS_BOUND = 60.0


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree
    )


def _compile(fn, *args):
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _meshnet(v5e, name, vol, precision, per_layer=False):
    cfg = PAPER_MODELS[name]
    params = _shapes(
        jax.eval_shape(lambda: meshnet.init(jax.random.PRNGKey(0), cfg)), v5e
    )
    dtype = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8w": jnp.int8}[precision]
    x = jax.ShapeDtypeStruct((1,) + vol, dtype, sharding=v5e)

    def forward(p, x):
        return megakernel.meshnet_apply(
            p, x, cfg, interpret=False, precision=precision,
            fold_affine=ops.fold_batchnorm, per_layer=per_layer,
        )

    compiled, secs = _compile(forward, params, x)
    pln = megakernel.plan_for_config(
        cfg, vol, precision=None if precision == "fp32" else precision,
        per_layer=per_layer,
        int8_staging=False if per_layer and precision == "int8w" else None,
    )
    return compiled, secs, pln


@pytest.mark.parametrize(
    "name,vol,precision",
    [
        ("gwm_light", PAPER_VOLUME, "bf16"),
        ("subvolume_gwm_failsafe", CUBE_WINDOW, "int8w"),
    ],
)
def test_megakernel_compiles_for_v5e(v5e, name, vol, precision):
    compiled, secs, pln = _meshnet(v5e, name, vol, precision)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= len(pln.segments)
    assert secs < COMPILE_SECONDS_BOUND, secs


def test_fused_compiles_for_v5e(v5e):
    compiled, secs, pln = _meshnet(v5e, "gwm_light", PAPER_VOLUME, "bf16", per_layer=True)
    assert len(pln.segments) == len(PAPER_MODELS["gwm_light"].dilations)
    assert compiled.as_text().count("tpu_custom_call") >= len(pln.segments)
    assert secs < COMPILE_SECONDS_BOUND, secs


def test_single_conv_compiles_for_v5e(v5e):
    # the widest halo of the schedule, 5 channels on a 256^3 volume
    x = jax.ShapeDtypeStruct((1,) + PAPER_VOLUME + (5,), jnp.float32, sharding=v5e)
    w = jax.ShapeDtypeStruct((3, 3, 3, 5, 5), jnp.float32, sharding=v5e)
    b = jax.ShapeDtypeStruct((5,), jnp.float32, sharding=v5e)
    compiled, _ = _compile(
        lambda x, w, b: conv_kernel.dilated_conv3d(x, w, b, dilation=16), x, w, b
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_dice_compiles_for_v5e(v5e):
    v = jax.ShapeDtypeStruct(PAPER_VOLUME, jnp.int32, sharding=v5e)
    compiled, _ = _compile(lambda a, b: dice.dice_counts(a, b, 3), v, v)
    assert "tpu_custom_call" in compiled.as_text()


#: an HLO sort instruction, as ``%sort.9 = f32[16777216]{0} sort(...)``
SORT_INSTRUCTION = re.compile(r"\ssort\(")


def test_conform_rescale_compiles_without_a_sort_for_v5e(v5e):
    # conform's quantiles come from exact selection: a full sort of the
    # 256^3 volume (~43 ms a request on a v5e) must not come back
    x = jax.ShapeDtypeStruct(PAPER_VOLUME, jnp.float32, sharding=v5e)
    compiled, secs = _compile(conform.rescale_intensity, x)
    assert not SORT_INSTRUCTION.search(compiled.as_text())
    assert secs < COMPILE_SECONDS_BOUND, secs
    # the pattern finds the sort jnp.quantile lowers to
    small = jax.ShapeDtypeStruct((16, 16, 16), jnp.float32, sharding=v5e)
    sorted_q, _ = _compile(lambda v: jnp.quantile(v, 0.01), small)
    assert SORT_INSTRUCTION.search(sorted_q.as_text())
