"""Operations and bytes per forward come from the configuration alone."""

import pytest

from chipbench import peaks, work


def _model(cfg) -> dict:
    return {"in_channels": cfg.in_channels, "channels": cfg.channels,
            "num_classes": cfg.num_classes, "dilations": list(cfg.dilations),
            "kernel_size": cfg.kernel_size, "use_batchnorm": cfg.use_batchnorm}


@pytest.mark.parametrize("name,per_voxel", [("gwm_light", 11_100), ("gwm_large", 43_800)])
def test_flops_per_voxel(name, per_voxel):
    from repro.core.meshnet import PAPER_MODELS

    assert work.flops_per_voxel(_model(PAPER_MODELS[name])) == per_voxel


def test_every_zoo_model_counts_twice_its_conv_weights():
    from repro.core.meshnet import PAPER_MODELS

    for name, cfg in PAPER_MODELS.items():
        model = _model(cfg)
        assert work.flops_per_voxel(model) == 2 * (cfg.param_count() - work.biases(model)), name


def test_bytes_are_compulsory_traffic():
    from repro.core.meshnet import PAPER_MODELS

    model = _model(PAPER_MODELS["gwm_light"])
    vox = 256 ** 3
    assert work.forward_bytes(model, (256,) * 3, "bf16") == vox * 2 + vox * 3 * 2 + 5598 * 2
    assert work.forward_bytes(model, (256,) * 3, "fp32") == 2 * work.forward_bytes(model, (256,) * 3, "bf16")


def test_both_configurations_are_compute_bound_on_v5e():
    from repro.core.meshnet import PAPER_MODELS

    p = peaks.for_device("TPU v5 lite")
    for name in ("gwm_light", "gwm_large"):
        model = _model(PAPER_MODELS[name])
        t_flops = work.forward_flops(model, (256,) * 3) / p["bf16_flops_per_s"]
        t_bytes = work.forward_bytes(model, (256,) * 3, "bf16") / p["hbm_bytes_per_s"]
        assert t_flops > t_bytes, name


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.for_device("TPU v9 imaginary")

