"""A four-node chain on the disk engine behind key pages (what
`build_chain.py --storage disk` deploys), stopped and opened again: the
nodes answer what they answered before, agree with each other, and go on."""

from test_block_limit import GROUP, Chain

ACCOUNTS = 300


def _chain(tmp_path, first_nonce: int = 0) -> Chain:
    c = Chain(limit=60, pool=120, accounts=ACCOUNTS,
              storage_backend="disk", storage_memtable_mb=1,
              storage_key_page_size=512,
              per_node=lambda i: {"storage_path": str(tmp_path / f"node{i}")})
    c.sent = [None] * first_nonce  # send_cohort numbers its transfers on
    return c


def _answers(c: Chain) -> dict:
    height = c.nodes[0].ledger.current_number()
    c.settle(height)
    out = {"height": height, "headers": [], "balances": []}
    for n in c.nodes:
        out["headers"].append([n.ledger.header_by_number(k).hash(n.suite)
                               for k in range(height + 1)])
        out["balances"].append([n.storage.get("c_balance", b"acct-%07d" % a)
                                for a in range(ACCOUNTS)])
    return out


def _close(c: Chain) -> None:
    c.stop()
    for n in c.nodes:
        n.storage.close()


def test_disk_chain_answers_the_same_after_a_restart(tmp_path):
    c = _chain(tmp_path)
    try:
        for _ in range(5):
            sent = c.send_cohort(60)
            assert all(s["receipt"] and s["receipt"]["status"] == 0
                       for s in sent)
        c.nodes[0].storage.flush()   # node0 serves from a segment, the
        c.nodes[1].storage.compact()  # others from memtable, log and L1
        sent = c.send_cohort(60)
        assert c.judged() == {}
        before = _answers(c)
        assert before["height"] >= 6
        assert all(h == before["headers"][0] for h in before["headers"])
        assert all(b == before["balances"][0] for b in before["balances"])
        st = c.nodes[0].system_status()
        assert st["storage"]["key_page_size"] == 512
        assert st["storage"]["backend_stats"]["backend"] == "disk"
        stages = st["trace"]["stages"]
        assert stages["storage_prepare"]["count"] == \
            stages["storage_commit"]["count"] == stages["commit"]["count"]
        assert 0.0 < stages["storage_prepare"]["seconds"] \
            + stages["storage_commit"]["seconds"] <= stages["commit"]["seconds"]
        n_sent = len(c.sent)
    finally:
        _close(c)

    c = _chain(tmp_path, first_nonce=n_sent)
    try:
        assert _answers(c) == before     # every node agrees with itself
        # ... and the chain goes on from there, still equal to the replay
        # from the balances it reopened with
        more = c.send_cohort(60)
        assert all(s["receipt"] and s["receipt"]["status"] == 0 for s in more)
        after = _answers(c)
        assert after["height"] > before["height"]
        assert all(b == after["balances"][0] for b in after["balances"])
        assert all(h == after["headers"][0] for h in after["headers"])
        assert after["headers"][0][:before["height"] + 1] == \
            before["headers"][0]
        moved = {}
        for s in more:
            src, dst, amt = s["move"]
            moved[src] = moved.get(src, 0) - amt
            moved[dst] = moved.get(dst, 0) + amt
        for a in range(ACCOUNTS):
            was = int.from_bytes(before["balances"][0][a], "big")
            now = int.from_bytes(after["balances"][0][a], "big")
            assert now - was == moved.get(b"acct-%07d" % a, 0)
        assert GROUP == c.group
    finally:
        _close(c)
