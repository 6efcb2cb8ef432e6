"""Find the benchmark's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; every
piece lives in a file of its own, found here by that name:

    bench/configs/<config>.json     deployment: sizes, index, guarantees
    bench/traffic/<traffic>.json    load: clients, filter, k
    bench/metrics/<metric>.py       per-layer reader: ``read(run) -> float|None``
    bench/limits/<workload>.json    the limits that decide ``correct``

A later change adds a deployment, a mix or a metric by adding a file and an
entry; nothing here names one.  An unknown or malformed name raises
``UnknownName`` before anything runs, and so does a key in a file that the
harness would not read (``known_keys``).
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownName(LookupError):
    """A cell, configuration, traffic mix, metric or limits file that the
    benchmark does not have."""


def _file(kind: str, name: str, suffix: str) -> Path:
    if not isinstance(name, str) or not NAME.match(name):
        raise UnknownName(f"{kind}: {name!r} is not a valid name")
    path = BENCH / kind / f"{name}{suffix}"
    if not path.is_file():
        raise UnknownName(f"{kind}: no {name!r} ({path.relative_to(ROOT)})")
    return path


def load_json(kind: str, name: str) -> Dict[str, Any]:
    with open(_file(kind, name, ".json")) as f:
        return json.load(f)


def known_keys(where: str, d: Dict[str, Any], keys) -> Dict[str, Any]:
    """``d`` itself, once each of its keys is in ``keys``: a file may state
    nothing that the run would silently leave out."""
    extra = sorted(set(d) - set(keys))
    if extra:
        raise UnknownName(f"{where}: the harness reads no {extra}; it reads "
                          f"{sorted(keys)}")
    return d


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise UnknownName(f"workloads: no {name!r} in BENCHMARK.json")


def metrics_for(bench: Dict[str, Any], cell: str, section: str
                ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports: a
    metric without a ``workloads`` list is reported by every cell."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


def metric_reader(name: str) -> Callable[[Any], Any]:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = _file("metrics", name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``; a kind that is not in
    ``peaks.json`` is an error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise UnknownName(f"peaks.json: no entry for device kind "
                          f"{device_kind!r}")
    return table[device_kind]
