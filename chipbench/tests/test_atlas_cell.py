"""atlas_104.cohort: the cell finds its files, its configuration is the
zoo's atlas_104 at published widths with its one cut listed, a run of it
cut down on the CPU is correct, and its int8w control is not."""

import json

from chipbench import harness
from chipbench.tests.conftest import ROOT, tiny_cell

CELL = "atlas_104.cohort"


def test_cell_loads_with_the_zoo_model():
    from repro.core.meshnet import PAPER_MODELS, MeshNetConfig

    cell = harness.load_cell(ROOT, CELL)
    cfg, m = cell.config, cell.config["model"]
    # the model serve_run builds, and the zoo entry it is held to
    built = MeshNetConfig(
        in_channels=m["in_channels"], channels=m["channels"],
        num_classes=m["num_classes"], dilations=tuple(m["dilations"]),
        kernel_size=m["kernel_size"], use_batchnorm=m["use_batchnorm"])
    assert cfg["zoo"] == "atlas_104" and built == PAPER_MODELS["atlas_104"]
    assert (built.channels, built.num_classes, len(built.dilations)) == (21, 104, 8)
    assert cfg["params"] == built.param_count() == 86372
    assert cfg["volume_shape"] == [192, 192, 192]
    assert cfg["expect"] == {"executor": "pallas_megakernel", "precision": "bf16"}
    assert cfg["pipeline"]["precision"] == "auto"
    assert cell.chips == 1
    assert cell.traffic == {"loop": "closed", "outstanding": 16, "priority": "batch",
                            "pool": 16, "check_sample": 8}
    assert {m["name"] for m in cell.end_to_end} == {"volumes_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == {
        "preprocess_ms", "inference_ms", "megakernel_roofline_pct", "device_idle_pct",
        "mfu_pct", "conform_sync_ms", "sched_overhead_ms", "labels_ms"}


def test_every_cut_is_listed_in_both_files(spec):
    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(c["reduced"]), c["name"]
        if "volume_shape" not in c["reduced"]:
            assert cfg["volume_shape"] == [256, 256, 256], c["name"]


def test_tiny_run_is_correct():
    out = harness.serve(tiny_cell("cohort", "atlas_104"), 2**31 + 21, 1.5, False,
                        require_chip=False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_control_is_not_correct():
    # as test_control.py: the program's int8w policy, one step below the
    # configuration's bf16, must fail the limit that the program meets; at
    # 48^3, full depth and width (CPU readings of this seed: bf16 3.1e-5,
    # int8w 9.8e-4; on the chip at 192^3, PERF.md §4)
    dilations = harness.load_cell(ROOT, CELL).config["model"]["dilations"]

    def cell():
        c = tiny_cell("cohort", "atlas_104")
        c.config["volume_shape"] = [48, 48, 48]
        c.config["model"]["dilations"] = dilations
        return c

    seed = 2**31 + 1
    sound = harness.serve(cell(), seed, 1.0, False, require_chip=False)
    assert sound["correct"], sound["checks"]
    control = harness.serve(cell(), seed, 1.0, False, require_chip=False,
                            precision="int8w")
    c = control["checks"]["mean_logit_gap"]
    assert not control["correct"] and c["value"] > c["limit"]
