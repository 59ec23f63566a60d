"""The per-layer metrics that read work against wait and node0's CPU by
thread role (`*_cpu_share`, `thread_cpu_ms_per_block.<role>`) and the
parts of `roots` (`*_root_ms_per_block`, `prewrite_ms_per_block`): their
entries and data files, read through the benchmark's own `status_ratio`
reader from a solo node's getSystemStatus around one committed cohort."""

import json
import os

import pytest

from fisco_bcos_tpu.analysis import profiler
from test_prime_metrics import ROOT, _spec, status_ratio  # noqa: F401
from test_rpc_batch import cohort_node, cohort_txs, send_cohort, wait_until

CELLS = ["air4-transfer.batch1k-serial", "air4-sm.batch1k-serial",
         "air4-transfer-blk10k.batch10k-serial",
         "air4-transfer-disk.batch1k-serial",
         "air4-parallelok.batch1k-serial"]
SHARES = {"execute": "committed_tps", "roots": "committed_tps",
          "admit": "committed_tps", "prime": "receipt_p50_ms",
          "rpc_respond": "receipt_p50_ms"}
ROLES = ("edge", "ingest", "pbft", "execute", "commit", "notify", "net",
         "crypto", "native", "compaction")
PARTS = ("txs_root", "receipts_root", "prewrite", "state_root")
# name -> (unit, better, source, layer, moves, cells)
METRICS = {
    **{f"{s}_cpu_share": ("%", "higher", "program_span", "host process", m,
                          CELLS) for s, m in SHARES.items()},
    **{f"thread_cpu_ms_per_block.{r}": (
        "ms", "lower", "program_counter", "host process", "committed_tps",
        CELLS[3:4] if r == "compaction" else CELLS) for r in ROLES},
    **{f"{p}_ms_per_block": ("ms", "lower", "program_span",
                             "scheduler / executor", "committed_tps", CELLS)
       for p in PARTS},
}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_is_listed_for_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"] if m["name"] == name)
    unit, better, source, layer, moves, cells = METRICS[name]
    # a later cell is appended to the list, never put before these
    assert entry["workloads"][:len(cells)] == cells
    assert dict(entry, workloads=cells) == {
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves, "workloads": cells}
    spec = _spec(name)
    assert spec["reader"] == "status_ratio" and spec["node"] == 0
    assert name.split(".")[-1] in profiler.ROLES or "." not in name


def test_the_map_has_every_role_the_metrics_name():
    assert set(ROLES) <= set(profiler.ROLES)


@pytest.fixture(scope="module")
def window():
    """getSystemStatus before and after one committed cohort."""
    node, kp, _impl = cohort_node(False)
    try:
        primes = lambda: node.system_status()[  # noqa: E731
            "trace"]["stages"]["prime"]["count"]
        assert wait_until(lambda: primes() >= 1)  # the funding block's
        before = node.rpc.impl.get_system_status("group0")
        send_cohort(node, cohort_txs(node, kp, "cpu"))
        assert wait_until(
            lambda: primes() > before["trace"]["stages"]["prime"]["count"])
        after = node.rpc.impl.get_system_status("group0")
    finally:
        node.stop()
    return {"status": {"before": {"0": before}, "after": {"0": after}}}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_spec_reads_a_number_from_one_committed_block(name, window,
                                                      status_ratio):
    value = status_ratio(window, _spec(name))
    assert isinstance(value, float), (name, value)
    if name.endswith("_cpu_share"):
        # a clock tick of slack: the two clocks are read one after another
        assert 0.0 < value <= 100.0 + 1e-6, value
    elif name.startswith("thread_cpu_ms_per_block."):
        # `native` is what is left of the process's clock once the
        # threads' are read one after another: at each end it may hold
        # what the others burned during that read, well under a ms
        assert value >= -1.0, value
    else:
        assert 0.0 < value < 5000.0


def test_the_parts_lie_inside_roots(window, status_ratio):
    per_block = {p: status_ratio(window, _spec(f"{p}_ms_per_block"))
                 for p in PARTS + ("roots",)}
    assert sum(per_block[p] for p in PARTS) <= per_block["roots"]


def test_the_roles_sum_to_the_process(window, status_ratio):
    """Every role, the small ones too, adds up to the process's CPU over
    the window: nothing is counted twice or lost."""
    before, after = (window["status"][k]["0"]["trace"]["threads"]
                     for k in ("before", "after"))
    assert set(before) == set(after) == set(profiler.ROLES)
    assert all(after[r] >= before[r] - 1e-3 for r in profiler.ROLES
               if r != "native"), (before, after)
    assert sum(after.values()) > sum(before.values())


def test_a_program_without_the_fields_reads_nothing(window, status_ratio):
    """The parent's status has no thread roles, CPU fields or parts: each
    spec reads as nothing, and does not raise."""
    bare = json.loads(json.dumps(window))
    for side in ("before", "after"):
        trace = bare["status"][side]["0"]["trace"]
        del trace["threads"]
        for p in PARTS:
            del trace["stages"][p]
        for row in trace["stages"].values():
            del row["cpu_seconds"], row["cpu_wall_seconds"]
    for name in METRICS:
        assert status_ratio(bare, _spec(name)) is None, name
