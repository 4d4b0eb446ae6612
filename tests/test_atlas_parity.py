"""atlas_104 (21 channels, 104 classes, its whole 8-conv dilation
schedule) served through ``SegmentationEngine`` against the plain fp32
reference, on the CPU at a small non-cubic volume.

The served path is the one the chip runs: ``auto`` precision resolves
bf16 on a TPU at every width, and the megakernel serves it (here in the
Pallas interpreter). The comparison is the one that decides a benchmark
run's ``correct`` (``chipbench/reference.py:logit_gap``): for each voxel,
how far the served class's reference logit lies below the reference's
best, averaged over the volume. Logits are compared, not labels, because
with random weights the top two logits of many voxels lie within
rounding of each other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conform, meshnet, pipeline
from repro.core.meshnet import PAPER_MODELS
from repro.core.pipeline import PipelineConfig
from repro.data import mri
from repro.serving.engine import SegmentationEngine

CFG = PAPER_MODELS["atlas_104"]
SHAPE = (24, 32, 40)

#: Mean logit gap allowed of the served labels. bf16 rounds the input,
#: the weights and every staged activation to 8 mantissa bits, so voxels
#: whose top two reference logits lie within that rounding may flip; the
#: gap such a flip costs is below the rounding itself. Read on seeds 0-2
#: of this test's data: bf16 7.0e-6 to 1.1e-5, int8w 1.2e-3 to 1.5e-3.
#: The limit leaves about 9x above the first and 12x below the second, so
#: a policy one step below bf16 fails it.
MEAN_GAP_LIMIT = 1e-4


def mean_logit_gap(ref_logits, served):
    """``chipbench/reference.logit_gap``'s ``mean``, channels last."""
    picked = jnp.take_along_axis(ref_logits, served[..., None], axis=-1)[..., 0]
    return float(jnp.mean(jnp.max(ref_logits, axis=-1) - picked))


@pytest.fixture(scope="module")
def case():
    key = jax.random.PRNGKey(0)
    params = meshnet.init(key, CFG)
    vol, _ = mri.generate(jax.random.fold_in(key, 1), mri.SyntheticMRIConfig(shape=SHAPE))
    with jax.default_matmul_precision("highest"):
        ref = meshnet.apply(params, conform.conform(vol, SHAPE)[None], CFG)[0]
    return params, vol, ref


@pytest.mark.parametrize("precision,within", [("bf16", True), ("int8w", False)])
def test_served_labels_against_fp32_reference(case, precision, within):
    params, vol, ref = case
    engine = SegmentationEngine(params, PipelineConfig(
        name="atlas_104", model=CFG, volume_shape=SHAPE,
        executor="pallas_megakernel", precision=precision, postprocess=False))
    res = engine.submit(vol)
    assert res.record.status == "ok", res.record.fail_type
    assert (res.record.executor, res.record.precision) == ("pallas_megakernel", precision)
    seg = res.segmentation
    assert seg.shape == SHAPE and seg.dtype == jnp.int32
    gap = mean_logit_gap(ref, seg)
    # int8w is the counter-case: one step below bf16, it must fail the limit
    assert (gap <= MEAN_GAP_LIMIT) == within, (precision, gap)


def test_labels_take_the_first_index_on_a_tie():
    logits = jax.random.normal(jax.random.PRNGKey(3), (4, 5, 6, CFG.num_classes))
    logits = logits.at[..., 7].set(9.0).at[..., 40].set(9.0).astype(jnp.bfloat16)
    got = pipeline.labels(logits)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.full((4, 5, 6), 7))
    np.testing.assert_array_equal(
        np.asarray(pipeline.labels(logits[..., 8:])),
        np.argmax(np.asarray(logits[..., 8:], np.float32), axis=-1))
