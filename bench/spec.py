"""Everything a cell is made of, found by name.

``BENCHMARK.json`` at the root names each cell's configuration and
traffic; the rest is data under ``bench/``:

- ``configs/<config>.json``: the deployment (model sizes as published,
  quantization, engine fields, what was assumed);
- ``traffic/<traffic>.json``: parameters for the one generator in
  ``bench/traffic.py``;
- ``metrics/<metric>.py``: one reader per metric, ``read(run)`` returning
  a number or ``None`` when the run holds nothing to read;
- ``limits/<config>.json``: the limits of the numbers that decide
  ``correct``;
- ``families/<family>.py``: one module per model family, named by the
  configuration's ``family``: its keys, weights, reference layer, cache
  comparison and work counts (``bench/test_families.py`` lists what each
  provides);
- ``peaks.json``: the chip's peaks by ``device_kind``.

A later cell, configuration, family or metric is a new file and a new
entry; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    limits: dict
    run_seconds: int


def _applies(metric: dict, cell: str, end_to_end: list[dict]) -> bool:
    """A metric is reported in the cells it lists; without a list, in
    every cell that reports the end-to-end metric it moves (or in every
    cell, for an end-to-end metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" not in metric:
        return True
    moved = [m for m in end_to_end if m["name"] == metric["moves"]]
    return bool(moved) and _applies(moved[0], cell, end_to_end)


def cell_of(config: str, traffic: str, *, name: str = "", chips: int = 1,
            end_to_end=(), per_layer=(), run_seconds: int = 0) -> Cell:
    """A cell from its files: ``configs/<config>.json``,
    ``traffic/<traffic>.json`` and ``limits/<config>.json``."""
    return Cell(
        name=name or f"{config}.{traffic}", config_name=config,
        traffic_name=traffic, chips=chips,
        config=_json(BENCH, "configs", config + ".json"),
        traffic=_json(BENCH, "traffic", traffic + ".json"),
        end_to_end=list(end_to_end), per_layer=list(per_layer),
        limits=_json(BENCH, "limits", config + ".json"),
        run_seconds=run_seconds)


def load_cell(name: str, root: str = ROOT) -> Cell:
    """A cell of ``BENCHMARK.json``, with the metrics it reports."""
    bench = _json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, bench["end_to_end"])]
    per = [m for m in bench["per_layer"] if _applies(m, name, bench["end_to_end"])]
    return cell_of(w["config"], w["traffic"], name=name, chips=w["chips"],
                   end_to_end=e2e, per_layer=per,
                   run_seconds=bench["run_seconds"])


def _load(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def family(name: str):
    """The module ``families/<name>.py``, loaded once."""
    return _load("families", name)


def model(config: dict) -> dict:
    """The architecture part of a configuration file, as the reference
    and the weight maker read it."""
    fam = config["family"]
    return {"family": fam, **{k: config[k] for k in family(fam).FIELDS}}


def program_config(config: dict, m: dict):
    """The program's ModelConfig for this configuration: the preset the
    file names, with every size the file states set on it, and the sizes
    the family derives from them (the same values for the benchmark's
    cells, which ``test_spec`` checks; smaller ones for the CPU tests)."""
    from repro.configs.base import get_config

    fam = family(m["family"])
    want = {f: m[k] for k, f in fam.FIELDS.items()}
    want.update(fam.program_fields(m))
    return dataclasses.replace(get_config(config["model"]), **want)


def read_metric(name: str, run) -> float | None:
    """Load ``metrics/<name>.py`` and apply its reader to the run."""
    return _load("metrics", name).read(run)


def peaks(device_kind: str) -> dict:
    table = _json(BENCH, "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                         f"bench/peaks.json (has {sorted(table['devices'])})")
    return table["devices"][device_kind]
