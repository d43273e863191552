"""Finding a cell's pieces by name.

``BENCHMARK.json`` names the cells; everything that belongs to one
configuration, traffic mix or metric lives in a file of its own that this
module finds by that name, so a later change adds files and edits none:

* a configuration: the ``file`` its ``configs`` entry names;
* its data: the generator ``bench/gen/<generator>.py`` it names;
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a query: ``bench/queries/<query>.rq``, with its plain reference in
  ``bench/reference/<query>.py``;
* a metric: ``bench/metrics/<name>.py``, whose ``read(run)`` returns the
  number or ``None`` when the run has nothing to read for it; a metric
  ``<base>.<part>`` with no file of its own is read by ``<base>.py``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError("no workload %r in BENCHMARK.json (have: %s)"
                   % (name, ", ".join(w["name"] for w in spec["workloads"])))


def config(spec: dict, name: str, root: Path = ROOT) -> dict:
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError("no configuration %r in BENCHMARK.json" % name)


def traffic(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "traffic" / ("%s.json" % name))


def query_text(name: str, bench: Path = BENCH) -> str:
    return (bench / "queries" / ("%s.rq" % name)).read_text()


def reference(name: str):
    """The plain reference module of query ``name``."""
    return importlib.import_module("bench.reference.%s" % name)


def metrics(spec: dict, cell_name: str, per_layer: bool) -> List[dict]:
    """The metrics a cell reports: its end-to-end metrics in a plain run,
    its per-layer metrics in a traced one."""
    group = spec["per_layer" if per_layer else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def _load(path: Path, package: str, name: str):
    importlib.import_module(package)
    mod_spec = importlib.util.spec_from_file_location(
        "%s.%s" % (package, name.replace(".", "_")), path)
    if mod_spec is None or not path.is_file():
        raise KeyError("no file %s" % path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench: Path = BENCH) -> Callable[[object], Optional[float]]:
    """``read`` of ``bench/metrics/<name>.py``, or of ``<base>.py`` for a
    metric ``<base>.<part>`` that has no file of its own (the same
    quantity split by the end-to-end metric it moves)."""
    path = bench / "metrics" / ("%s.py" % name)
    if not path.is_file() and "." in name:
        path = bench / "metrics" / ("%s.py" % name.rsplit(".", 1)[0])
    return _load(path, "bench.metrics", name).read


def generator(name: str, bench: Path = BENCH):
    """The generator module ``bench/gen/<name>.py`` a configuration names
    (its interface: ``bench/gen/dbpedia.py``)."""
    return _load(bench / "gen" / ("%s.py" % name), "bench.gen", name)


def peaks(kind: str, bench: Path = BENCH) -> Dict[str, float]:
    table = load_json(bench / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError("device kind %r is not in bench/peaks.json (have: %s)"
                       % (kind, ", ".join(table["devices"])))
    return table["devices"][kind]
