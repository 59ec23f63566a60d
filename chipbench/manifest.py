"""Find a cell's files by the names in BENCHMARK.json.

A cell is `<config>.<traffic>`; its configuration is
`configs/<config>.json`, its traffic mix `traffic/<traffic>.json`, and each
per-layer metric `metrics/<metric>.json`, which names its reader module
under `readers/`. What a transaction is — the configuration's `workload`
— is the pair `workloads/<kind>.py` (the client's side) and
`workloads/<kind>_reference.py` (its plain reference). Nothing here lists
a name: a later PR adds a cell, a configuration, a traffic mix, a metric
or a transaction kind by adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# the kind of a configuration file that names none: the first four did not
DEFAULT_WORKLOAD = "dagtransfer"


class ManifestError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as exc:
        raise ManifestError(f"cannot read {path}: {exc}") from exc
    except ValueError as exc:
        raise ManifestError(f"{path} is not JSON: {exc}") from exc


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_KINDS: dict = {}  # path -> the module, loaded once a process


def workload(config: dict, side: str = "client", bench_dir: str = HERE):
    """The module of the configuration's transaction kind: `side` "client"
    is `workloads/<kind>.py`, which may import the program; "reference" is
    `workloads/<kind>_reference.py`, which imports nothing of it. What a
    kind reads of its configuration, and refuses there, is its own."""
    kind = config.get("workload", DEFAULT_WORKLOAD)
    if not isinstance(kind, str) or not NAME_RE.match(kind):
        raise ManifestError(f"bad workload name {kind!r}")
    stem = kind if side == "client" else f"{kind}_{side}"
    path = os.path.join(bench_dir, "workloads", f"{stem}.py")
    if path not in _KINDS:
        if not os.path.exists(path):
            raise ManifestError(f"no workload {kind!r}: {path} is missing")
        _KINDS[path] = _module(path, f"chipbench_workload_{stem}")
    return _KINDS[path]


class Manifest:
    """BENCHMARK.json plus the data files it names. `root` is the checkout,
    `bench_dir` the directory holding configs/, traffic/, metrics/ and
    readers/ (tests point both at a temporary copy)."""

    def __init__(self, root: str = ROOT, bench_dir: str | None = None):
        self.root = root
        self.bench_dir = bench_dir or os.path.join(root, "chipbench")
        self.doc = _load_json(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}

    # -- cells ---------------------------------------------------------------
    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{sorted(self.cells)}")
        return self.cells[name]

    def config(self, name: str) -> dict:
        entry = next((c for c in self.doc["configs"] if c["name"] == name),
                     None)
        if entry is None:
            raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")
        cfg = _load_json(os.path.join(self.root, entry["file"]))
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> dict:
        t = _load_json(os.path.join(self.bench_dir, "traffic",
                                    f"{name}.json"))
        t["name"] = name
        return t

    # -- metrics -------------------------------------------------------------
    def _reports(self, metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.doc["end_to_end"] if self._reports(m, cell)]

    def per_layer(self, cell: str) -> list[dict]:
        """The cell's per-layer metrics, each with its `spec` (the metric's
        data file) and its reader's `read` function."""
        out = []
        for m in self.doc["per_layer"]:
            if not self._reports(m, cell):
                continue
            spec = _load_json(os.path.join(self.bench_dir, "metrics",
                                           f"{m['name']}.json"))
            out.append({**m, "spec": spec,
                        "read": self.reader(spec["reader"])})
        return out

    def reader(self, module: str):
        if not NAME_RE.match(module):
            raise ManifestError(f"bad reader name {module!r}")
        path = os.path.join(self.bench_dir, "readers", f"{module}.py")
        if not os.path.exists(path):
            raise ManifestError(f"no reader {path}")
        return _module(path, f"chipbench_reader_{module}").read

    def workload(self, config: dict) -> None:
        """Both files of the configuration's kind are there and load: said
        before anything starts."""
        for side in ("client", "reference"):
            workload(config, side, self.bench_dir)


def peaks(device_kind: str, bench_dir: str | None = None) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = _load_json(os.path.join(bench_dir or HERE, "peaks.json"))
    if device_kind not in table["devices"]:
        raise ManifestError(
            f"device kind {device_kind!r} is not in peaks.json "
            f"({sorted(table['devices'])}): add it with its source")
    return table["devices"][device_kind]
