"""The plain reference and the comparison that decides `correct`.

Imports nothing of the program and takes nothing it computed as true: the
operations are known from the seed, the semantics of the configuration's
transaction kind are its `workloads/<kind>_reference.py` (a replay over a
dict, what a receipt has to say), and the guarantees come from the
configuration file. `judge` compares what the cluster said (the client's
receipts and what `answers.gather` read back from the nodes after the
window) with both. Every number is a count of answers that say the wrong
thing, so every limit is 0.

The controls are the reference put in the program's place with one stated
guarantee broken: four of the chain (`FRAME_CONTROLS`) and the kind's own;
each has to fail at least one number (tests, and `--controls 1` on the
chip).
"""

from __future__ import annotations

import copy
import importlib.util
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def _beside(name: str):
    """A module of this directory, by its path: tier-1's tests load this
    file by path into a process whose `sys.path`, which is left alone,
    does not hold the harness."""
    path = os.path.join(HERE, f"{name}.py")
    for key in (name, f"chipbench_{name}"):
        mod = sys.modules.get(key)
        if mod is not None and getattr(mod, "__file__", None) == path:
            return mod
    spec = importlib.util.spec_from_file_location(f"chipbench_{name}", path)
    mod = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _kind(config: dict):
    return _beside("manifest").workload(config, "reference")


def _read(config: dict, sent: list[dict], answers: dict) -> dict:
    """node0's chain against what was sent, the kind's sequential replay
    of the committed order, and the hashes of the receipts that say what
    they have to: sent under this hash, in a block that exists, and of the
    operation done — or refused, where the replay refuses it (the kind's
    `receipt_says`)."""
    kind = _kind(config)
    by_hash = {s["hash"]: s for s in sent}
    where: dict = {}
    twice = foreign = 0
    order = []
    for blk in answers["blocks"]:
        for h in blk["tx_hashes"]:
            if h in where:
                twice += 1
            elif h not in by_hash:
                foreign += 1
            else:
                where[h] = blk["number"]
                order.append(h)
    want, untouched, refused = kind.expected(
        [by_hash[h]["move"] for h in order], config)
    refused = {order[i] for i in refused}
    right = {s["hash"] for s in sent if (rc := s["receipt"]) is not None
             and rc.get("transactionHash") == s["hash"]
             and isinstance(rc.get("blockNumber"), int)
             and 1 <= rc["blockNumber"] <= answers["height"]
             and kind.receipt_says(rc, s["move"], s["hash"] in refused)}
    return {"where": where, "twice": twice, "foreign": foreign,
            "want": want, "untouched": untouched, "right": right}


def receipts_right(config: dict, sent: list[dict], answers: dict) -> set:
    """Hashes of the transactions whose receipt says what it has to: the
    work a rate may count (a refusal the semantics demand is an answer,
    not a failure)."""
    return _read(config, sent, answers)["right"]


def judge(config: dict, sent: list[dict], answers: dict) -> list[dict]:
    """-> [{"name", "value", "limit"}], each a count that has to be 0.

    sent: every transaction the client sent, in any order:
        {"hash", "move" (the operation), "receipt" (the client's, or None)}
    answers: see answers.gather.
    """
    g = config["guarantees"]
    height = answers["height"]
    nodes = sorted(answers["header_hashes"])
    by_hash = {s["hash"]: s for s in sent}
    seen = _read(config, sent, answers)

    never = sum(1 for s in sent if s["receipt"] is None)
    wrong = sum(1 for s in sent if s["receipt"] is not None
                and s["hash"] not in seen["right"])

    # the chain as node0 holds it
    over = sum(1 for blk in answers["blocks"]
               if len(blk["tx_hashes"]) > config["block_tx_count_limit"])
    short = sum(1 for blk in answers["blocks"]
                if blk["seals"] < g["commit_seals"])
    misplaced = sum(1 for s in sent if s["receipt"] is not None
                    and seen["where"].get(s["hash"]) != s["receipt"].get(
                        "blockNumber"))

    # every replica holds the same chain
    ref = answers["header_hashes"][nodes[0]]
    differ = sum(1 for n in range(height + 1)
                 if not ref[n] or any(answers["header_hashes"][k][n] != ref[n]
                                      for k in nodes))

    # an acknowledged transaction reads back, equal, from 2f+1 nodes
    under = 0
    for h, per_node in answers["receipts"].items():
        mine = by_hash[h]["receipt"]
        same = sum(1 for k in nodes if mine is not None
                   and per_node.get(k) == mine)
        if same < g["replicas_readable"]:
            under += 1

    # state of the sampled touched keys: the sequential replay of the
    # committed order (the number keeps the name the ledger knows it by)
    bad_bal = sum(1 for per_key in answers["balances"].values()
                  for key, got in per_key.items()
                  if got != seen["want"].get(key, seen["untouched"]))

    counts = [
        ("never_answered", never),
        ("receipts_wrong", wrong),
        ("acked_not_in_chain", misplaced),
        ("chain_txs_twice_or_foreign", seen["twice"] + seen["foreign"]),
        ("blocks_over_tx_limit", over),
        ("blocks_under_quorum_seals", short),
        ("heights_replicas_differ", differ),
        ("receipts_under_quorum_reads", under),
        ("balances_off_replay", bad_bal),
    ]
    return [{"name": n, "value": v, "limit": 0} for n, v in counts]


def is_correct(numbers: list[dict]) -> bool:
    return all(x["value"] <= x["limit"] for x in numbers)


# -- controls: one guarantee broken each --------------------------------------

def lost_acknowledged_write(sent, answers):
    """Durability: a transaction was acknowledged and is not in the chain."""
    h = next(s for s in reversed(sent) if s["receipt"] is not None)["hash"]
    for blk in answers["blocks"]:
        if h in blk["tx_hashes"]:
            blk["tx_hashes"].remove(h)


def replica_diverged(sent, answers):
    """Agreement: one replica holds another block at the last height."""
    k = sorted(answers["header_hashes"])[-1]
    answers["header_hashes"][k][-1] = "0x" + "00" * 32


def read_from_two_only(sent, answers):
    """Quorum reads: an acknowledged receipt is on two nodes, not three."""
    h = next(iter(answers["receipts"]))
    for k in sorted(answers["receipts"][h])[-2:]:
        answers["receipts"][h][k] = None


def one_seal_short(sent, answers):
    """2f+1 commit seals: a block committed with two."""
    answers["blocks"][-1]["seals"] = 2


FRAME_CONTROLS = {f.__name__: f for f in (
    lost_acknowledged_write, replica_diverged, read_from_two_only,
    one_seal_short)}


def controls(config: dict) -> dict:
    """The chain's controls, then those of the configuration's kind."""
    return {**FRAME_CONTROLS, **_kind(config).CONTROLS}


def run_controls(config: dict, sent: list[dict], answers: dict) -> dict:
    """-> {control: the numbers it failed}; an empty list is a control that
    passed, which is a fault of the comparison."""
    out = {}
    for name, breaker in controls(config).items():
        s, a = copy.deepcopy(sent), copy.deepcopy(answers)
        breaker(s, a)
        out[name] = [x["name"] for x in judge(config, s, a)
                     if x["value"] > x["limit"]]
    return out


# the names today's callers know: those of the kind that a configuration
# naming none has
def replay(moves: list, start: int) -> tuple[dict, int]:
    return _kind({}).replay(moves, start)


def transfer_log(move) -> str:
    return _kind({}).transfer_log(move)


def __getattr__(name: str):
    if name == "CONTROLS":
        return controls({})
    raise AttributeError(name)
