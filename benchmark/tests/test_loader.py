"""The loader finds cells, configurations, traffic and readers by name and
refuses what it does not know."""
import json
import os

import pytest

from benchmark.harness import device, spec

SPEC = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_unknown_cell_is_refused():
    with pytest.raises(spec.SpecError, match="unknown workload"):
        spec.load_cell("no-such-cell")


def test_unknown_metric_is_refused():
    with pytest.raises(spec.SpecError, match="unknown metric"):
        spec.load_metric_reader("no_such_metric")


@pytest.mark.parametrize("kind", ["TPU v4", "NVIDIA H100", "cpu"])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(device.DeviceError, match="not in"):
        device.peaks_for_kind(kind)


def test_v5e_row():
    p = device.peaks_for_kind("TPU v5 lite")
    assert (p.flops, p.hbm_bytes_s) == (197e12, 819e9)


def test_a_cpu_is_not_a_chip():
    with pytest.raises(device.DeviceError, match="not a TPU"):
        device.require_chips(1)


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_loads_and_reports_enough(name):
    cell = spec.load_cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e
        assert callable(spec.load_metric_reader(m["name"]))
    assert cell.config["driver"] in ("train", "serve")
    assert "limits" in cell.config


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_a_reader_that_finds_nothing_returns_nothing(metric):
    read = spec.load_metric_reader(metric)
    assert read({"counters": {}, "trace": {}, "end_to_end": {}, "cell": None}) is None
