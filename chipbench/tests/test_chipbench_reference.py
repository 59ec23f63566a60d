"""The comparison on answers made by hand: sound answers pass, every
control fails a number, and the schedule gives each seed the same work."""

import copy

import reference
from traffic import poisson_schedule

CONFIG = {"block_tx_count_limit": 3, "prefund_balance": 100,
          "guarantees": {"commit_seals": 3, "replicas_readable": 3}}


def _world():
    moves = [(b"a", b"b", 5), (b"b", b"c", 7), (b"c", b"a", 1),
             (b"a", b"c", 2)]
    sent = []
    for i, mv in enumerate(moves):
        h = "0x%064x" % (i + 1)
        sent.append({"hash": h, "move": mv, "receipt": {
            "status": 0, "transactionHash": h, "blockNumber": 1 + i // 3,
            "logEntries": [{"data": reference.transfer_log(mv)}]}})
    blocks = [{"number": 1, "hash": "0xb1", "seals": 3,
               "tx_hashes": [s["hash"] for s in sent[:3]]},
              {"number": 2, "hash": "0xb2", "seals": 4,
               "tx_hashes": [sent[3]["hash"]]}]
    hashes = ["0xb0", "0xb1", "0xb2"]
    answers = {
        "height": 2,
        "header_hashes": {k: list(hashes) for k in range(4)},
        "blocks": blocks,
        "receipts": {s["hash"]: {k: copy.deepcopy(s["receipt"])
                                 for k in range(4)} for s in sent[:2]},
        "balances": {0: {b"a": 94, b"b": 98, b"c": 108},
                     2: {b"a": 94, b"c": 108}},
    }
    return sent, answers


def test_replay():
    bal, over = reference.replay([(b"a", b"b", 60), (b"a", b"b", 60)], 100)
    assert bal == {b"a": 40, b"b": 160} and over == 1


def test_sound_answers_are_correct():
    sent, answers = _world()
    numbers = reference.judge(CONFIG, sent, answers)
    assert reference.is_correct(numbers), numbers
    assert all(x["limit"] == 0 for x in numbers)


def test_every_control_fails_a_number():
    sent, answers = _world()
    failed = reference.run_controls(CONFIG, sent, answers)
    assert set(failed) == set(reference.CONTROLS)
    for name, numbers in failed.items():
        assert numbers, f"control {name} passed"
    assert failed["replica_diverged"] == ["heights_replicas_differ"]
    assert failed["one_seal_short"] == ["blocks_under_quorum_seals"]
    assert failed["read_from_two_only"] == ["receipts_under_quorum_reads"]
    assert "balances_off_replay" in failed["lost_update"]
    assert "acked_not_in_chain" in failed["lost_acknowledged_write"]
    assert "receipts_wrong" in failed["wrong_receipt"]


def test_an_answer_that_never_came_and_a_lagging_replica():
    sent, answers = _world()
    sent[3]["receipt"] = None
    names = {x["name"] for x in reference.judge(CONFIG, sent, answers)
             if x["value"] > x["limit"]}
    assert names == {"never_answered"}
    sent, answers = _world()
    answers["header_hashes"][3][2] = None
    names = {x["name"] for x in reference.judge(CONFIG, sent, answers)
             if x["value"] > x["limit"]}
    assert names == {"heights_replicas_differ"}


def test_schedule_same_work_for_every_seed():
    a = poisson_schedule(140, 45, 1)
    b = poisson_schedule(140, 45, 2**31 + 7)
    assert len(a) == len(b) == 6300 and a != b
    gaps = lambda s: sorted(round(y - x, 9) for x, y in zip(s, s[1:] + [45.0]))
    assert gaps(a) == gaps(b)
    assert a[0] == 0.0 and max(a) < 45.0
    # exponential gaps: the mean is 1/rate, the median ln 2 of it
    g = gaps(a)
    assert abs(sum(g) / len(g) - 1 / 140) < 1e-9
    assert abs(g[len(g) // 2] * 140 - 0.693) < 0.01
