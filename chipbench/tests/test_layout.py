"""BENCHMARK.json finds every piece of a cell by name, and each
configuration file holds its zoo model at published widths."""

import importlib
import json
import re

from chipbench.tests.conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_cell_finds_its_files(spec):
    from chipbench import harness

    for w in spec["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.end_to_end + cell.per_layer:
            importlib.import_module(f"chipbench.metrics.{m['name']}").read


def test_per_layer_metrics_move_a_metric_their_cells_report(spec):
    from chipbench import harness

    for m in spec["per_layer"]:
        for w in m["workloads"]:
            cell = harness.load_cell(ROOT, w)
            assert m["moves"] in {e["name"] for e in cell.end_to_end}, (m["name"], w)


def test_names_and_units(spec):
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    names += [w["name"] for w in spec["workloads"]] + [c["name"] for c in spec["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(e["bound"] <= 0.25 for e in spec["end_to_end"])


def test_config_files_hold_the_zoo_models(spec):
    from repro.core.meshnet import PAPER_MODELS

    for c in spec["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        zoo = PAPER_MODELS[cfg["zoo"]]
        m = cfg["model"]
        assert (m["in_channels"], m["channels"], m["num_classes"], tuple(m["dilations"]),
                m["kernel_size"], m["use_batchnorm"]) == (
            zoo.in_channels, zoo.channels, zoo.num_classes, zoo.dilations,
            zoo.kernel_size, zoo.use_batchnorm)
        assert cfg["params"] == zoo.param_count()
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["volume_shape"] == [256, 256, 256]
