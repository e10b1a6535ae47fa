"""The benchmark finds its configurations, cells and readers by name, and
``BENCHMARK.json`` agrees with the files."""

import json

import pytest

from chipbench import registry
from chipbench.run import Context


def test_every_entry_has_its_files():
    b = registry.benchmark()
    for c in b["configs"]:
        cfg = registry.config(c["name"])
        assert registry.ROOT.joinpath(c["file"]).is_file()
        assert cfg["name"] == c["name"]
        assert registry.config_module(c["name"]).__doc__
    for w in b["workloads"]:
        wl = registry.workload(w["name"])
        assert wl["config"] == w["config"] and wl["chips"] == w["chips"]
    for m in b["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


def test_every_cell_reports_what_the_contract_asks():
    b = registry.benchmark()
    for w in b["workloads"]:
        e2e = [m["name"] for m in registry.metrics_for(w["name"],
                                                       "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.metrics_for(w["name"], "per_layer")
    layers = {}
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
        layers.setdefault(m["layer"], []).append(m["name"])
    assert set(layers) >= {"kernels", "device", "steps", "orchestrator"}


def test_unknown_names_raise():
    with pytest.raises(KeyError):
        registry.workload("no-such-cell")
    with pytest.raises(FileNotFoundError):
        registry.config("no-such-config")
    with pytest.raises(FileNotFoundError):
        registry.metric_reader("no.such_metric")


def test_peaks_are_keyed_by_device_kind():
    p = registry.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["int8_ops"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    with open(registry.HERE / "peaks.json") as f:
        assert "TPU v5e" in json.load(f)["source"]
    with pytest.raises(KeyError):
        registry.peaks("TPU v4")
    with pytest.raises(KeyError):
        registry.peaks("cpu")


@pytest.mark.parametrize("name", [m["name"] for m in
                                  registry.benchmark()["per_layer"]])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    ctx = Context(None, {}, {}, {}, None, registry.peaks("TPU v5 lite"))
    assert registry.metric_reader(name)(ctx) is None
