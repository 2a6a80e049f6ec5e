"""The benchmark's data files: every name resolves to a file, and the
peaks table refuses a device it does not list."""

import json
import os

import pytest

from bench import spec

HERE = os.path.dirname(__file__)


def _bench():
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_peaks_lookup():
    v5e = spec.peaks("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["int8_ops"], v5e["hbm_bytes"]) == (
        197e12, 393e12, 819e9)
    with pytest.raises(SystemExit, match="no peaks for device_kind"):
        spec.peaks("TPU v4")


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves(name):
    cell = spec.load_cell(name)
    assert cell.config["source"].startswith("https://")
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert os.path.exists(os.path.join(HERE, "metrics", m["name"] + ".py"))
    assert cell.limits["limits"]
    # the file's sizes are the program's preset ones (nothing is cut)
    from repro.configs.base import get_config

    m = spec.model(cell.config)
    assert spec.program_config(cell.config, m) == get_config(cell.config["model"])


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="unknown workload"):
        spec.load_cell("no-such-cell")


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_metrics_apply_where_listed(name):
    bench = _bench()
    got = {m["name"] for m in (lambda c: c.per_layer + c.end_to_end)(
        spec.load_cell(name))}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            assert (m["name"] in got) == (name in m["workloads"]), m["name"]
    # every per-layer metric moves an end-to-end metric the cell reports
    for m in bench["per_layer"]:
        if m["name"] in got:
            assert m["moves"] in got
