"""Finding everything by name.  ``BENCHMARK.json`` names cells,
configurations and metrics; every file that belongs to one of them is
found from that name under the benchmark's own directory, so adding a
configuration, a traffic mix, a cell or a per-layer metric is new files
plus new entries and never an edit here."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def load_benchmark() -> Dict[str, Any]:
    return load_json(os.path.join(REPO, "BENCHMARK.json"))


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; it has "
                    f"{[c['name'] for c in bench['workloads']]}")


def load_config(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for entry in bench["configs"]:
        if entry["name"] == name:
            return load_json(os.path.join(REPO, entry["file"]))
    raise SpecError(f"no config {name!r} in BENCHMARK.json")


def load_traffic(name: str) -> Dict[str, Any]:
    return load_json(os.path.join(BENCH_DIR, "traffic", name + ".json"))


def load_peaks(device_kind: str) -> Dict[str, Any]:
    """Published peaks of the device; an unknown kind is an error, not a
    default."""
    table = load_json(os.path.join(BENCH_DIR, "peaks.json"))
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SpecError(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({sorted(table['devices'])}): add its published peaks with "
            "their source before measuring on it") from None


def load_module(group: str, name: str):
    """Import ``benchmark/<group>/<name>.py`` by file path (names may
    hold dots and dashes, which a package import could not spell)."""
    path = os.path.join(BENCH_DIR, group, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"{name!r} not found in {group}: {path} does not "
                        "exist")
    modname = "benchmark_%s_%s" % (
        group, "".join(c if c.isalnum() else "_" for c in name))
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The reader of a per-layer metric: ``benchmark/metrics/<metric>.py``
    or, where one quantity is split by the end-to-end metric it moves
    (``decode_step_ms.chat``, ``decode_step_ms.batch``), the file of the
    name before its last dot, which serves every such split."""
    for name in (metric, metric.rpartition(".")[0]):
        if name and os.path.isfile(
                os.path.join(BENCH_DIR, "metrics", name + ".py")):
            return load_module("metrics", name)
    raise SpecError(f"no reader for {metric!r} under "
                    f"{os.path.join(BENCH_DIR, 'metrics')}")


def metrics_for(bench: Dict[str, Any], cell: str, group: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports:
    those without a ``workloads`` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]
