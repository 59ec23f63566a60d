"""BENCHMARK.json keeps to the contract's limits, and a cell, a
configuration, a traffic mix and a per-layer metric are added by new files
and new entries alone."""

import json
import os
import shutil

import pytest
from conftest import BENCH, KEPT, ROOT, doc_with_kept_cells
from manifest import NAME_RE, SOURCES, UNIT_RE, Manifest, ManifestError, peaks

DOC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_limits():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["chipbench"]
    assert DOC["command"][:2] == ["python3", "chipbench/run.py"]
    assert isinstance(DOC["run_seconds"], int) and 1 <= DOC["run_seconds"] <= 51
    cells = 24  # the limit has to fit with the full 24 cells
    assert (2 + 14 * cells) * (DOC["run_seconds"] + 60) + cells * 180 \
        + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10


def test_configs_and_cells():
    names = [c["name"] for c in DOC["configs"]]
    assert len(set(names)) == len(names)
    files = [c["file"] for c in DOC["configs"]]
    assert len(set(files)) == len(files)
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME_RE.match(c["name"]) and _line(c["source"]) \
            and _line(c["why"])
        assert c["file"].startswith("chipbench/") and len(c["reduced"]) <= 16
        body = json.load(open(os.path.join(ROOT, c["file"])))
        for key in c["reduced"]:
            assert NAME_RE.match(key) and key in body
        assert "guarantees" in body and "assumed" in body
    cells = [w["name"] for w in DOC["workloads"]]
    assert len(set(cells)) == len(cells) and 1 <= len(cells) <= 24
    pairs = {(w["config"], w["traffic"]) for w in DOC["workloads"]}
    assert len(pairs) == len(cells)
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME_RE.match(w["name"]) and NAME_RE.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    assert {w["config"] for w in DOC["workloads"]} == set(names)
    assert sum(w["chips"] == 4 for w in DOC["workloads"]) \
        <= max(1, len(cells) // 2)


def test_metrics():
    cells = {w["name"] for w in DOC["workloads"]}
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    names = list(e2e) + [m["name"] for m in DOC["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in DOC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in DOC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        layers.add(m["layer"])
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".json"))
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
    for f in os.listdir(os.path.join(BENCH, "metrics")):
        if "roofline" in f:  # kept ready for the cells that use the kernels
            assert f.endswith("_roofline.json")
    for m in DOC["end_to_end"] + DOC["per_layer"]:
        assert NAME_RE.match(m["name"]) and UNIT_RE.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    # every cell reports set-up, one more end-to-end and a per-layer metric
    man = Manifest()
    for c in cells:
        assert len(man.end_to_end(c)) >= 2
        assert man.per_layer(c)


def test_files_under_paths_are_named_from_name_characters():
    import re
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert ok.match(rel) and len(rel) <= 200, rel


def test_additions_are_new_files_and_entries_only(tmp_path):
    """A later PR's cell: a copy of the tree plus new files; no file that
    was there is edited (BENCHMARK.json only gains entries)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.loads(json.dumps(DOC))
    bench = root / "chipbench"
    cfg = json.load(open(bench / "configs" / "air4-transfer.json"))
    cfg["accounts"] = 5000
    (bench / "configs" / "air4-small.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "batch256-paced.json").write_text(json.dumps(
        {"kind": "closed-batch", "senders": 2, "batch": 256,
         "presign_tx_per_s": 1000, "warmup_batches_per_sender": 1,
         "request_timeout_s": 60}))
    (bench / "metrics" / "hash_device_share.json").write_text(json.dumps(
        {"reader": "status_ratio", "node": 0, "scale": 100,
         "numerator": ["crypto.ops.hash.deviceItems"],
         "denominator": ["crypto.ops.hash.deviceItems",
                         "crypto.ops.hash.hostItems"]}))
    (bench / "metrics" / "answer.json").write_text(
        json.dumps({"reader": "fortytwo"}))
    (bench / "readers" / "fortytwo.py").write_text(
        "def read(ev, spec):\n    return 42.0\n")
    doc["configs"].append({"name": "air4-small", "source": "x",
                           "file": "chipbench/configs/air4-small.json",
                           "reduced": ["hosts"], "why": "y"})
    doc["workloads"].append({"name": "air4-small.batch256-paced",
                             "config": "air4-small",
                             "traffic": "batch256-paced", "chips": 1,
                             "why": "z"})
    for name in ("hash_device_share", "answer"):
        doc["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "program_counter", "layer": "crypto seam",
            "moves": "committed_tps",
            "workloads": ["air4-small.batch256-paced"]})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))

    man = Manifest(str(root))
    cell = man.cell("air4-small.batch256-paced")
    assert man.config(cell["config"])["accounts"] == 5000
    assert man.traffic(cell["traffic"])["batch"] == 256
    mine = {m["name"]: m for m in man.per_layer(cell["name"])}
    assert "hash_device_share" in mine and "receipt_p95_ms" not in mine
    ev = {"status": {
        "before": {"0": {"crypto": {"ops": {"hash": {
            "deviceItems": 10, "hostItems": 10}}}}},
        "after": {"0": {"crypto": {"ops": {"hash": {
            "deviceItems": 40, "hostItems": 20}}}}}}}
    m = mine["hash_device_share"]
    assert m["read"](ev, m["spec"]) == pytest.approx(75.0)
    assert mine["answer"]["read"](ev, mine["answer"]["spec"]) == 42.0
    # the cells that were there still load
    for w in DOC["workloads"]:
        assert man.per_layer(w["name"])
    with pytest.raises(ManifestError):
        man.cell("no-such.cell")
    with pytest.raises(ManifestError):
        man.config("no-such-config")


@pytest.mark.parametrize("cell", sorted(KEPT))
def test_kept_files_load_once_listed(tmp_path, cell):
    """What chipbench/ keeps for cells that are not listed (PERF.md, Open
    questions) loads as soon as entries name it."""
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "BENCHMARK.json").write_text(
        json.dumps(doc_with_kept_cells()))
    man = Manifest(str(tmp_path))
    entry = man.cell(cell)
    config, traffic = man.config(entry["config"]), man.traffic(
        entry["traffic"])
    assert config["guarantees"]["commit_seals"] == 3
    assert traffic["kind"] in ("closed-batch", "open-singles")
    names = {m["name"] for m in man.per_layer(cell)}
    assert set(KEPT[cell].get("with", ())) <= names
    assert not set(KEPT[cell]["without"]) & names


def test_unknown_device_is_an_error():
    assert peaks("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(ManifestError):
        peaks("TPU v9 imaginary")
