"""The page layer over the disk engine, against a dict of dicts.

`KeyPageStorage(DiskStorage)` is what a `--storage disk` node runs
(chipbench's air4-transfer-disk). The plain reference of its semantics is
`MemoryStorage`: the same operations give the same rows, whatever the cache
holds, whatever the engine has flushed or merged, across a reopen."""

import random

import pytest

from fisco_bcos_tpu.storage import make_storage
from fisco_bcos_tpu.storage.engine import DiskStorage
from fisco_bcos_tpu.storage.interface import Entry, EntryStatus
from fisco_bcos_tpu.storage.keypage import (META_KEY, PAGE_PREFIX,
                                            UNPAGED_TABLES, KeyPageStorage,
                                            _Page)
from fisco_bcos_tpu.storage.memory import MemoryStorage
from fisco_bcos_tpu.testing.scenario import (ScenarioSpec, prefund_rows,
                                             prefund_storage)

PAGE = 256
TABLES = ("c_balance", "u_wide", "s_hash_2_tx")  # the last stays unpaged
PREFIXES = (b"", b"k0", b"k01", b"k1", b"zz")


def _disk(path, **kw):
    kw.setdefault("memtable_bytes", 4 << 10)
    kw.setdefault("max_segments", 2)
    kw.setdefault("level_base_bytes", 8 << 10)
    kw.setdefault("auto_compact", False)
    return DiskStorage(str(path), **kw)


def _paged(backend, cache_pages=3):
    return KeyPageStorage(backend, page_size=PAGE,
                          cache_bytes=cache_pages * PAGE)


def _changeset(rng, keys):
    cs = {}
    for _ in range(rng.randrange(1, 40)):
        table = rng.choice(TABLES)
        key = rng.choice(keys)
        if rng.random() < 0.3:
            cs[(table, key)] = Entry(b"", EntryStatus.DELETED)
        else:
            cs[(table, key)] = Entry(rng.randbytes(rng.randrange(1, 60)))
    return cs


def _backend_rows(mem: MemoryStorage):
    return sorted((t, k, v) for t, rows in mem._tables.items()
                  for k, v in rows.items())


def _same(kp, ref, twin, keys):
    for table in TABLES:
        for k in keys:
            assert kp.get(table, k) == ref.get(table, k), (table, k)
        for p in PREFIXES:
            assert list(kp.keys(table, p)) == list(ref.keys(table, p))
    # the layout itself: the engine under a cache of three pages holds the
    # bytes a dict under an unbounded cache holds
    assert sorted(kp.capture_rows()) == _backend_rows(twin.backend)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_random_changesets_match_a_dict(tmp_path, seed):
    rng = random.Random(seed)
    keys = [b"k%03d" % i for i in range(150)]
    kp = _paged(_disk(tmp_path / "db"))
    ref = MemoryStorage()                               # rows, plainly
    twin = KeyPageStorage(MemoryStorage(), page_size=PAGE)  # pages, no bound
    everyone = (kp, ref, twin)
    number = 0
    for step in range(60):
        op = rng.choice(("commit", "commit", "commit", "rollback", "twice",
                         "batch", "reopen", "engine"))
        if op in ("commit", "rollback", "twice"):
            number += 1
            cs = _changeset(rng, keys)
            for st in everyone:
                st.prepare(number, cs)
            if op == "rollback":
                for st in everyone:
                    st.rollback(number)
            else:
                if op == "twice":  # a second prepare replaces the first
                    cs = _changeset(rng, keys)
                    for st in everyone:
                        st.prepare(number, cs)
                for st in everyone:
                    st.commit(number)
        elif op == "batch":
            table = rng.choice(TABLES)
            rows = [(rng.choice(keys), rng.randbytes(20))
                    for _ in range(rng.randrange(1, 30))]
            gone = rng.sample(keys, rng.randrange(0, 10))
            for st in everyone:
                st.set_batch(table, rows)
                st.remove_batch(table, gone)
        elif op == "reopen":
            kp.close()
            kp = _paged(_disk(tmp_path / "db"))
            everyone = (kp, ref, twin)
        else:
            kp.backend.flush()
            kp.backend.compact_once(force=rng.random() < 0.5)
        if step % 6 == 5:
            _same(kp, ref, twin, keys)
    _same(kp, ref, twin, keys)
    s = kp.stats()
    assert s["evictions"] > 0, "a cache of three pages never evicted"
    assert s["cached_bytes"] <= 3 * PAGE + max(
        len(v) for _, k, v in kp.capture_rows() if k.startswith(PAGE_PREFIX))
    assert kp.backend.stats()["segment_count"] > 0  # served from SSTables
    kp.close()


def test_prepare_stages_only_what_the_changeset_touches(tmp_path):
    kp = KeyPageStorage(_disk(tmp_path / "db", memtable_bytes=1 << 20),
                        page_size=PAGE, cache_bytes=1 << 30)
    keys = [b"a%05d" % i for i in range(4000)]
    kp.set_batch("c_balance", [(k, b"v" * 16) for k in keys])
    kp.set_batch("u_other", [(k, b"w" * 16) for k in keys[:500]])
    for k in keys:                       # every page parsed and cached
        kp.get("c_balance", k)
    cached = kp.stats()["cached_pages"]
    assert cached > 400
    rng = random.Random(9)
    staged_per_block = []
    for number in range(1, 51):
        hot = rng.sample(keys, 5)
        cs = {("c_balance", k): Entry(b"x" * 16) for k in hot}
        cs[("s_hash_2_tx", b"h%d" % number)] = Entry(b"tx")
        kp.prepare(number, cs)
        st = kp._staged[number]
        # the staged state: the pages of the five rows and one table's
        # page index, nothing of the other table, nothing of the cache
        assert set(st.meta) == {"c_balance"}
        assert len(st.pages) <= 5 and not st.dropped
        for (table, start), page in st.pages.items():
            assert table == "c_balance" and any(
                page.get(k) is not None for k in hot)
        assert st.rows == {("s_hash_2_tx", b"h%d" % number): Entry(b"tx")}
        staged_per_block.append(len(st.pages))
        kp.commit(number)
        for k in rng.sample(keys, 40):   # the cache goes on growing warm
            kp.get("c_balance", k)
    # pages copied a block: bounded by the block, flat over 50 blocks
    assert max(staged_per_block) <= 5
    assert sum(staged_per_block[-10:]) <= sum(staged_per_block[:10]) + 5
    assert kp.stats()["cached_pages"] >= cached
    kp.close()


def _raw(kp):
    return sorted(kp.capture_rows())


@pytest.mark.parametrize("accounts", [1, 141, 285, 286, 3000])
def test_batches_give_the_pages_the_row_by_row_path_gives(
        tmp_path, accounts, monkeypatch):
    from fisco_bcos_tpu.testing import scenario
    monkeypatch.setattr(scenario, "PREFUND_BATCH", 700)  # streamed in five
    spec = ScenarioSpec("hot-key", accounts=accounts)
    rows = prefund_rows(spec)["c_balance"]
    one = make_storage("disk", str(tmp_path / "one"))
    for k, v in rows:                    # the row-by-row path
        one.set("c_balance", k, v)
    batch = make_storage("disk", str(tmp_path / "batch"))
    batch.set_batch("c_balance", rows)
    streamed = make_storage("disk", str(tmp_path / "streamed"))
    assert prefund_storage(streamed, spec) == accounts
    want = _raw(one)
    assert _raw(batch) == want and _raw(streamed) == want
    pages = [v for _, k, v in want if k.startswith(PAGE_PREFIX)]
    assert sum(len(_Page.load(p)) for p in pages) == accounts
    assert all(len(p) <= one.page_size for p in pages)
    assert one.page_size == 10240        # upstream's key_page_size
    # deletes and overwrites in one batch, any order
    rng = random.Random(accounts)
    mixed = [(k, rng.randbytes(16)) for k, _ in rng.sample(rows, min(
        len(rows), 200))]
    gone = [k for k, _ in rng.sample(rows, min(len(rows), 50))]
    for k, v in mixed:
        one.set("c_balance", k, v)
    for k in gone:
        one.remove("c_balance", k)
    batch.set_batch("c_balance", mixed)
    batch.remove_batch("c_balance", gone)
    assert _raw(batch) == _raw(one)
    for st in (one, batch, streamed):
        st.close()


def test_unpaged_tables_keep_their_rows(tmp_path):
    kp = make_storage("disk", str(tmp_path / "db"))
    cs = {(t, b"key-%d" % i): Entry(b"v%d" % i)
          for t in sorted(UNPAGED_TABLES) + ["g/group1/s_hash_2_tx"]
          for i in range(3)}
    cs[("c_balance", b"acct")] = Entry(b"1")
    kp.prepare(1, cs)
    kp.commit(1)
    raw = {(t, k) for t, k, _ in kp.capture_rows()}
    for (t, k) in cs:
        if t == "c_balance":
            assert (t, META_KEY) in raw and (t, k) not in raw
        else:
            assert (t, k) in raw and (t, META_KEY) not in raw
            assert kp.get(t, k) == cs[(t, k)].value
    assert list(kp.keys("s_hash_2_tx", b"key-")) == [
        b"key-0", b"key-1", b"key-2"]
    kp.remove("s_hash_2_tx", b"key-1")
    assert kp.get("s_hash_2_tx", b"key-1") is None
    kp.close()


def test_engine_stamps_its_work(tmp_path):
    st = _disk(tmp_path / "db", memtable_bytes=2 << 10)
    s0 = st.stats()
    assert (s0["flushes"], s0["merges"], s0["stall_seconds"]) == (0, 0, 0.0)
    for number in range(1, 30):
        st.prepare(number, {("t", b"k%04d" % (number * 7 + i)): Entry(
            b"v" * 100) for i in range(10)})
        st.commit(number)
    while st.compact_once(force=False):
        pass
    s = st.stats()
    assert s["flushes"] >= 5 and s["flush_seconds"] > 0.0
    assert s["merges"] >= 1 and s["merge_seconds"] > 0.0
    # a commit that crosses the watermark runs the flush itself
    assert s["stall_seconds"] >= s["flush_seconds"] * 0.5
    assert s["open_seconds"] >= s["flush_seconds"]
    st.close()
