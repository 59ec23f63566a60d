import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

# files kept under chipbench/ for cells that BENCHMARK.json does not list
# (PERF.md, Open questions): the tests reach them as a later PR would, by
# entries added to a copy of the manifest
KEPT = {
    "air4-transfer.batch1k-backlog": {"without": ("sm2_verify_roofline",)},
    "air4-sm.batch1k-backlog": {"without": ("recover_roofline",)},
    "air4-transfer.singles-halfknee": {
        "with": ("gen_late_p99_ms", "admit_p50_ms"),
        "without": ("recover_roofline", "sm2_verify_roofline",
                    "merkle_roofline")},
}


def doc_with_kept_cells() -> dict:
    doc = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {c["name"] for c in doc["configs"]}
    for cell, how in KEPT.items():
        config, _, traffic = cell.partition(".")
        if config not in listed:
            listed.add(config)
            doc["configs"].append({
                "name": config, "source": "see the file",
                "file": f"chipbench/configs/{config}.json",
                "reduced": ["hosts", "device_nodes"], "why": "kept ready"})
        doc["workloads"].append({"name": cell, "config": config,
                                 "traffic": traffic, "chips": 1,
                                 "why": "kept ready"})
        for m in doc["per_layer"]:
            if m["name"] not in how["without"]:
                m["workloads"].append(cell)
        for name in how.get("with", ()):
            entry = next((m for m in doc["per_layer"] if m["name"] == name),
                         None)
            if entry is None:
                entry = {"name": name, "unit": "ms", "better": "lower",
                         "source": "host_clock", "layer": "client",
                         "moves": "committed_tps", "workloads": []}
                doc["per_layer"].append(entry)
            entry["workloads"].append(cell)
    return doc


def checkout_with_kept_cells(tmp_path) -> str:
    """A checkout whose BENCHMARK.json also lists the KEPT cells: a copy of
    chipbench/, links to the program."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for name in ("fisco_bcos_tpu", "tools", "native"):
        os.symlink(os.path.join(ROOT, name), root / name)
    (root / "BENCHMARK.json").write_text(json.dumps(doc_with_kept_cells()))
    return str(root)
