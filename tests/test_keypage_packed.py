"""The page type's two forms (`storage/keypage.py` `_Page`).

A page of fixed-width rows stays packed in memory and is read and written
at byte offsets; any other page is a dict. The reference kept here is the
dict form as the page layer had it before the packed one: a plain dict of
rows and the pack routine, written out again so that it shares no code
with what it checks. Whatever the form, the answers and the bytes handed
to the backend are the reference's."""

import hashlib
import random
import struct

import pytest

from fisco_bcos_tpu.storage.interface import Entry, EntryStatus
from fisco_bcos_tpu.storage.keypage import (PAGE_PREFIX, KeyPageStorage,
                                            _fixed_widths, _Page)
from fisco_bcos_tpu.storage.memory import MemoryStorage

_U32 = struct.Struct("<I").pack


def ref_pack(rows: dict) -> bytes:
    out = [_U32(len(rows))]
    for k in sorted(rows):
        out += [_U32(len(k)), k, _U32(len(rows[k])), rows[k]]
    return b"".join(out)


def ref_size(rows: dict) -> int:
    return 4 + sum(8 + len(k) + len(v) for k, v in rows.items())


# kind -> (rows to start from and their value widths, the key widths and
# the value widths a put draws from)
KINDS = {
    "fixed": (120, (16,), (12,), (16,)),
    "mixed": (60, (0, 7, 16), (3, 12), (0, 7, 16)),
    "breaks-width": (100, (16,), (12,), (16,) * 40 + (5,)),
    "empty-values": (90, (0,), (8,), (0,)),
    "one-row": (1, (16,), (12,), (16,)),
    "empty-page": (0, (16,), (12,), (16,)),
    "at-the-split": (285, (16,), (12,), (16,)),
}
PAGE_SIZE = 10240


def _draw_key(rng, widths):
    w = rng.choice(widths)
    return (b"%0*d" % (w, rng.randrange(400)))[:w] if w else b""


def _check(page, rows, rng, widths):
    assert len(page) == len(rows)
    assert page.size == ref_size(rows)
    assert sorted(page.keys()) == sorted(rows)
    assert all(type(k) is bytes for k in page.keys())
    for k in list(rows)[:5] + [_draw_key(rng, widths) for _ in range(5)]:
        got = page.get(k)
        assert got == rows.get(k) and (got is None or type(got) is bytes)
    packed = page.copy().packed()
    assert type(packed) is bytes and packed == ref_pack(rows)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_operation_gives_the_dict_forms_bytes(kind, seed):
    start, start_widths, key_widths, value_widths = KINDS[kind]
    rng = random.Random(f"{kind}-{seed}")
    rows = {}
    while len(rows) < start:
        k = (b"%0*d" % (key_widths[-1], len(rows) * 3))[:key_widths[-1]]
        rows[k] = rng.randbytes(rng.choice(start_widths))
    page = _Page.load(ref_pack(rows)).copy()
    fixed = len(key_widths) == 1 and len(value_widths) == 1
    assert (page.rows is None) == (len(start_widths) == 1)
    _check(page, rows, rng, key_widths)
    for _ in range(400):
        op = rng.random()
        key = rng.choice(sorted(rows)) if rows and op < 0.45 \
            else _draw_key(rng, key_widths)
        if op < 0.25 or 0.45 <= op < 0.55:       # delete: there or not
            page.put(key, None)
            rows.pop(key, None)
        else:                                    # overwrite or insert
            value = rng.randbytes(rng.choice(value_widths))
            page.put(key, value)
            rows[key] = value
        if page.size > PAGE_SIZE and len(page) > 1:
            ks = sorted(rows)
            lo, hi_start, hi = page.split()
            assert hi_start == ks[len(ks) // 2] and type(hi_start) is bytes
            halves = ({k: rows[k] for k in ks[:len(ks) // 2]},
                      {k: rows[k] for k in ks[len(ks) // 2:]})
            _check(lo, halves[0], rng, key_widths)
            _check(hi, halves[1], rng, key_widths)
            page, rows = rng.choice(((lo, halves[0]), (hi, halves[1])))
        _check(page, rows, rng, key_widths)
    assert (page.rows is None) == fixed, "one width: packed, else a dict"
    if kind == "at-the-split":
        assert len(rows) < 285, "285 rows of 36 bytes never split"


def _trade(rows, a, b):
    """Row `a` a byte longer in the key, row `b` a byte shorter: the total
    length stands."""
    out = dict(rows)
    ks = sorted(rows)
    out[ks[a] + b"x"] = out.pop(ks[a])
    out[ks[b][:-1]] = out.pop(ks[b])
    return out


def _key_for_value(rows, a):
    """Row `a` gives a byte of its value to its key."""
    out = dict(rows)
    k = sorted(rows)[a]
    v = out.pop(k)
    out[k + b"x"] = v[:-1]
    return out


ACCOUNTS = {b"acct%08d" % i: b"%016d" % i for i in range(142)}


@pytest.mark.parametrize("case,rows,packed", [
    ("accounts", ACCOUNTS, True),
    ("one row", {b"k": b"v"}, True),
    ("empty values", {b"a%d" % i: b"" for i in range(9)}, True),
    ("empty key", {b"": b"v"}, True),
    ("two rows trade a key byte", _trade(ACCOUNTS, 3, 100), False),
    ("the first row trades", _trade(ACCOUNTS, 0, 141), False),
    ("a value byte goes to its key", _key_for_value(ACCOUNTS, 77), False),
    ("the last row's too", _key_for_value(ACCOUNTS, 141), False),
    ("two widths", {b"a": b"1", b"bb": b"22"}, False),
])
def test_a_page_loads_in_the_form_its_bytes_show(case, rows, packed):
    raw = ref_pack(rows)
    assert len(raw) == ref_size(rows)
    if not packed and len(rows) == 142:
        assert len(raw) == len(ref_pack(ACCOUNTS)), "the case proves nothing"
    page = _Page.load(raw)
    assert (page.rows is None) == packed
    assert (_fixed_widths(raw) is not None) == packed
    assert page.size == len(raw) and len(page) == len(rows)
    assert sorted(page.keys()) == sorted(rows)
    for k, v in rows.items():
        assert page.get(k) == v
    assert page.get(b"acct") is None
    assert page.copy().packed() == raw


@pytest.mark.parametrize("raw", [
    b"", b"\x01", _U32(0) + b"x", _U32(1), _U32(1) + _U32(3) + b"ab",
    _U32(2) + _U32(1) + b"a" + _U32(0)])
def test_bytes_that_are_no_whole_page_are_not_fixed_width(raw):
    assert _fixed_widths(raw) is None


def _paged(page_size=4096, cache_bytes=1 << 20):
    return KeyPageStorage(MemoryStorage(), page_size=page_size,
                          cache_bytes=cache_bytes)


@pytest.mark.parametrize("key,value", [
    (b"acct00000003", b"short"),              # a value of another width
    (b"acct3", b"%016d" % 3),                 # a key of another width
])
def test_a_put_of_another_width_moves_the_page_once(key, value):
    kp = _paged()
    rows = {b"acct%08d" % i: b"%016d" % i for i in range(50)}
    kp.set_batch("c_balance", sorted(rows.items()))
    s0 = kp.stats()
    assert s0["pages_rowwise"] == 0 and s0["pages_packed"] >= 1
    (ck, page), = kp._pages.items()
    assert page.rows is None
    kp.prepare(1, {("c_balance", key): Entry(value)})
    s1 = kp.stats()
    assert s1["pages_rowwise"] == 1                  # the staged copy
    assert s1["pages_packed"] == s0["pages_packed"]
    assert kp._pages[ck] is page and page.rows is None   # not the cached one
    kp.commit(1)
    rows[key] = value
    assert kp._pages[ck].rows == rows                # and it stays so
    kp.prepare(2, {("c_balance", b"acct%08d" % 9): Entry(b"%016d" % 99)})
    kp.commit(2)
    rows[b"acct%08d" % 9] = b"%016d" % 99
    s2 = kp.stats()
    assert (s2["pages_rowwise"], s2["pages_packed"]) == (
        2, s0["pages_packed"])
    for k, v in rows.items():
        assert kp.get("c_balance", k) == v
    assert kp.backend.get("c_balance", PAGE_PREFIX + ck[1]) == ref_pack(rows)
    kp.flush_caches()                                # read back: still mixed
    assert kp.get("c_balance", key) == value
    assert kp.stats()["pages_rowwise"] == 3


@pytest.mark.parametrize("ending", ["rollback", "left staged", "replaced"])
def test_a_translation_never_writes_through_the_cached_page(ending):
    kp = _paged()
    rows = {b"acct%08d" % i: b"%016d" % i for i in range(100)}
    kp.set_batch("c_balance", sorted(rows.items()))
    for k in rows:
        kp.get("c_balance", k)
    before = {ck: (page, bytes(page.buf), page.size)
              for ck, page in kp._pages.items()}
    assert before and all(type(p.buf) is bytes for p, _, _ in before.values())
    cs = {("c_balance", b"acct%08d" % 5): Entry(b"%016d" % 555),
          ("c_balance", b"acct%08d" % 6): Entry(b"", EntryStatus.DELETED),
          ("c_balance", b"acct%08d" % 500): Entry(b"%016d" % 500)}
    kp.prepare(7, cs)
    if ending == "rollback":
        kp.rollback(7)
    elif ending == "replaced":
        kp.prepare(7, {("c_balance", b"acct%08d" % 7): Entry(b"%016d" % 1)})
    for ck, (page, raw, size) in before.items():
        assert kp._pages[ck] is page
        assert page.buf == raw and page.size == size and page.rows is None
        assert kp.backend.get("c_balance", PAGE_PREFIX + ck[1]) == raw
    for k, v in rows.items():
        assert kp.get("c_balance", k) == v
    assert kp.get("c_balance", b"acct%08d" % 500) is None
    if ending != "rollback":
        kp.commit(7)
        assert kp.get("c_balance", b"acct%08d" % 7) == (
            b"%016d" % (1 if ending == "replaced" else 7))
        assert kp.get("c_balance", b"acct%08d" % 5) == (
            b"%016d" % (5 if ending == "replaced" else 555))


def _scripted_run(widths):
    """A fixed script of batches, blocks, rollbacks, deletes and reads
    over a cache of eight pages -> the counters and the backend's bytes."""
    rng = random.Random(35)
    kp = _paged(page_size=1024, cache_bytes=8 * 1024)
    keys = [b"acct%08d" % i for i in range(0, 3000, 3)]

    def value():
        return rng.randbytes(rng.choice(widths))

    kp.set_batch("c_balance", [(k, value()) for k in keys])
    kp.set_batch("u_kv", [(k[4:], value()) for k in keys[:200]])
    for number in range(1, 41):
        for k in rng.sample(keys, 30):
            kp.get("c_balance", k)
        cs = {("c_balance", k): Entry(value()) for k in rng.sample(keys, 20)}
        for k in rng.sample(keys, 3):
            cs[("c_balance", k)] = Entry(b"", EntryStatus.DELETED)
        for i in range(4):
            cs[("c_balance", b"acct%08d" % (rng.randrange(1000) * 3 + 1))] = \
                Entry(value())
        cs[("s_hash_2_tx", b"h%d" % number)] = Entry(b"tx")
        kp.prepare(number, cs)
        if number % 7 == 0:
            kp.rollback(number)
        else:
            kp.commit(number)
        list(kp.keys("c_balance", b"acct0000%d" % (number % 3)))
    kp.remove_batch("c_balance", keys[100:400])
    s = kp.stats()
    digest = hashlib.sha256()
    for t, k, v in sorted((t, k, v) for t, rows in kp.backend._tables.items()
                          for k, v in rows.items()):
        digest.update(b"%s|%d|%s|%d|%s" % (t.encode(), len(k), k, len(v), v))
    return {"cached_bytes": s["cached_bytes"], "evictions": s["evictions"],
            "page_bytes_written": s["page_bytes_written"],
            "backend_reads": s["backend_reads"],
            "cache_hits": s["cache_hits"],
            "backend_sha256": digest.hexdigest()[:16]}


# What the tree before the packed form (commit 530c552) reads on the same
# script: the LRU holds the same pages and the backend the same bytes.
PARENT_READS = {
    (16,): {"cached_bytes": 1768, "evictions": 3576,
            "page_bytes_written": 524204, "backend_reads": 2714,
            "cache_hits": 319, "backend_sha256": "59c05b78d6423591"},
    (0, 7, 16): {"cached_bytes": 1611, "evictions": 3130,
                 "page_bytes_written": 502917, "backend_reads": 2325,
                 "cache_hits": 411, "backend_sha256": "c60f3107eebb44bc"},
}


@pytest.mark.parametrize("widths", sorted(PARENT_READS))
def test_the_cache_and_the_backend_read_what_the_parent_read(widths):
    assert _scripted_run(widths) == PARENT_READS[widths]
