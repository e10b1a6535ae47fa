"""Find configurations, workloads, metric readers and drivers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under this package:

* ``configs/<config>.json``: the sizes as run, with ``configs/<config>.py``
  beside it (the plain reference and the operation and byte counts);
* ``workloads/<cell>.json``: the traffic parameters of one cell;
* ``metrics/<metric>.py``: one reader per per-layer metric, ``read(ctx)``;
* ``drivers/<kind>.py``: one driver per entry kind (``serve``, ``fl``);
* ``peaks.json``: the chips' peaks, keyed by ``device_kind``.

Adding a cell, a configuration or a metric adds files; nothing here changes.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


def _load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no {path.relative_to(ROOT)}")
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return _json(ROOT / "BENCHMARK.json")


def workload(name: str) -> dict:
    """A cell's traffic parameters, with its ``BENCHMARK.json`` entry."""
    entry = {w["name"]: w for w in benchmark()["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    wl = _json(HERE / "workloads" / f"{name}.json")
    if wl.get("config") != entry["config"]:
        raise ValueError(f"workloads/{name}.json names config "
                         f"{wl.get('config')!r}, BENCHMARK.json "
                         f"{entry['config']!r}")
    return {**wl, "name": name, "chips": entry["chips"]}


def config(name: str) -> dict:
    return _json(HERE / "configs" / f"{name}.json")


def config_module(name: str):
    return _load_module(HERE / "configs" / f"{name}.py",
                        f"chipbench_config_{name.replace('-', '_')}")


def metric_reader(name: str):
    mod = _load_module(HERE / "metrics" / f"{name}.py",
                       f"chipbench_metric_{name.replace('.', '_')}")
    return mod.read


def driver(kind: str):
    return importlib.import_module(f"chipbench.drivers.{kind}")


def metrics_for(cell: str, section: str) -> list:
    """The ``BENCHMARK.json`` metrics of ``section`` that ``cell`` reports."""
    out = []
    for m in benchmark()[section]:
        cells = m.get("workloads")
        if cells is None or cell in cells:
            out.append(m)
    return out


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")
    if device_kind not in table["chips"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"chipbench/peaks.json ({sorted(table['chips'])})")
    return table["chips"][device_kind]
