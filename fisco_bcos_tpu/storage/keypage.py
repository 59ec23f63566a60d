"""KeyPageStorage — packs table rows into pages to cut KV round-trips.

Reference counterpart: /root/reference/bcos-table/src/KeyPageStorage.h:87-99
(rows bucketed into ~10KB pages keyed by their first row; configured by
`storage.key_page_size`, bcos-tool/bcos-tool/NodeConfig.cpp:620). Small
contract-state rows dominate a block's working set; paging them turns N tiny
backend reads into a handful of page reads — the same motivation as the
reference, and on this framework it also batches nicely ahead of device
hashing (fewer, larger host->storage ops).

Layout in the backend:
  * per table, a meta row ``_kp_/meta`` holds the sorted list of page-start
    keys (u32 count, then length-prefixed keys);
  * each page lives at ``_kp_/p/<start-key>`` and holds its rows sorted
    (u32 count, then (u32 klen, key, u32 vlen, val)*);
  * the tables of `UNPAGED_TABLES` keep their rows as they are.

Row-level 2PC changesets are translated into page-level changesets at
`prepare`, so the wrapped TransactionalStorage (WalStorage / NativeStorage /
DiskStorage) commits pages atomically with everything else. A translation
stages only the pages and page indexes its rows touch (`_Staged`): its cost
follows the block, not what the node has read or written before.

Pages are held in an LRU bounded by `PAGE_CACHE_BYTES` of packed page
bytes (the reference keeps an LRU `CacheStorage` over RocksDB); a state
larger than that is served from the backend, page by page. A page whose
rows all have one key width and one value width (an account ledger) stays
packed in memory, the bytes the backend holds, and is read and written at
byte offsets; any other page is parsed into a dict (`_Page`).

As the disk engine's value layout (`[storage] key_page_size > 0`,
storage/__init__.py make_storage) this is what makes wide tables cheap:
a `keys(prefix)` range scan touches the pages covering the prefix range —
typically ONE backend read — instead of a per-row walk, and the engine
sees few large values (better block packing, fewer bloom probes).
`stats()` exposes the counters the unit tests and the benchmark read.
"""

from __future__ import annotations

import bisect
import struct
import threading
import time
from collections import OrderedDict
from typing import Iterable, Iterator, Optional

from .interface import ChangeSet, Entry, EntryStatus, TransactionalStorage

META_KEY = b"_kp_/meta"
PAGE_PREFIX = b"_kp_/p/"

# Packed bytes of parsed pages a node keeps (LRU). Not an ini key: the
# reference sizes its CacheStorage in code too. 16 MiB is ~3,000 pages of
# accounts: a block of 1,000 transfers touches ~2,000 pages at most, so one
# block's working set stays resident between `execute` and `prepare`,
# while a state of millions of accounts (chipbench's air4-transfer-disk:
# 72 MB of rows) does not fit and is read from the engine.
PAGE_CACHE_BYTES = 16 << 20

# Tables that stay row by row under the page layer: keyed by a hash or a
# block number, read by point lookups, never range-scanned by prefix over
# neighbouring rows. Paging them would cost a page read and a ~10 KB page
# rewrite for every random key of a block. The reference's Initializer
# hands KeyPageStorage such an ignore list of ledger tables (written from
# memory of it: s_hash_2_tx, s_hash_2_receipt, s_number_2_txs, the
# number/hash/header/nonce tables, s_config, s_consensus; no reference
# tree is on this machine). The names are ledger/ledger.py's, the zk
# plane's and consensus/pbft/storage.py's; storage imports none of them.
UNPAGED_TABLES = frozenset({
    "s_number_2_header", "s_hash_2_number", "s_number_2_txs", "s_hash_2_tx",
    "s_hash_2_receipt", "s_number_2_nonces", "s_number_2_statehash",
    "s_current_state", "s_config", "s_consensus", "s_snapshot_state",
    "c_pbft_log"})

_U32 = struct.Struct("<I")
_EMPTY_PAGE_BYTES = 4  # the row count alone


def _pack_page(rows: dict[bytes, bytes]) -> bytes:
    pack = _U32.pack
    parts = [pack(len(rows))]
    for k in sorted(rows):
        v = rows[k]
        parts += (pack(len(k)), k, pack(len(v)), v)
    return b"".join(parts)


def _unpack_page(data: bytes) -> dict[bytes, bytes]:
    unpack = _U32.unpack_from
    (n,) = unpack(data, 0)
    off = 4
    rows: dict[bytes, bytes] = {}
    for _ in range(n):
        (kl,) = unpack(data, off)
        off += 4
        k = data[off:off + kl]
        off += kl
        (vl,) = unpack(data, off)
        off += 4
        rows[k] = data[off:off + vl]
        off += vl
    return rows


def _pack_meta(starts: list[bytes]) -> bytes:
    pack = _U32.pack
    parts = [pack(len(starts))]
    for s in starts:
        parts += (pack(len(s)), s)
    return b"".join(parts)


def _unpack_meta(data: bytes) -> list[bytes]:
    (n,) = _U32.unpack_from(data, 0)
    off = 4
    out = []
    for _ in range(n):
        (sl,) = _U32.unpack_from(data, off)
        off += 4
        out.append(data[off:off + sl])
        off += sl
    return out


def _fixed_widths(data: bytes) -> Optional[tuple[int, int, int]]:
    """-> (rows, key width, value width) where every row of the packed
    page `data` has the first row's two widths, else None. The total
    length alone proves nothing (two rows may trade a byte): each of the
    eight bytes of the two length fields is compared down the stride."""
    size = len(data)
    if size < 4:
        return None
    (n,) = _U32.unpack_from(data, 0)
    if n == 0:
        return (0, 0, 0) if size == 4 else None
    if size < 12:
        return None
    (kl,) = _U32.unpack_from(data, 4)
    if size < 12 + kl:
        return None
    (vl,) = _U32.unpack_from(data, 8 + kl)
    stride = 8 + kl + vl
    if size != 4 + n * stride:
        return None
    if n > 1:
        for at in (4, 5, 6, 7, 8 + kl, 9 + kl, 10 + kl, 11 + kl):
            if data[at::stride] != data[at:at + 1] * n:
                return None
    return n, kl, vl


class _Page:
    """One page in memory, in the form its bytes show.

    Packed: `buf` holds the page as it lies in the backend and every row
    has one key width `kl` and one value width `vl`, so row `i` starts at
    `4 + i * (8 + kl + vl)`: a row is found by a bisect over the bytes and
    no row is ever made an object. Row-wise: `rows` is the parsed dict
    (`buf` is None): a page of mixed widths, or one that received a key or
    a value of another width, takes that form and stays in it. `size` is
    the packed byte count in both.

    `buf` is `bytes` on a page read from the backend and on one handed to
    it (`packed()`), a `bytearray` on a staged copy in between: a cached
    page cannot be written through."""

    __slots__ = ("rows", "size", "buf", "n", "kl", "vl")

    def __init__(self, rows: Optional[dict[bytes, bytes]], buf=None,
                 widths: tuple[int, int, int] = (0, 0, 0),
                 size: Optional[int] = None):
        self.rows = rows
        self.buf = buf
        self.n, self.kl, self.vl = widths  # of the packed form alone
        if size is None:
            size = len(buf) if rows is None else _EMPTY_PAGE_BYTES + sum(
                8 + len(k) + len(v) for k, v in rows.items())
        self.size = size

    @classmethod
    def load(cls, raw: Optional[bytes] = None) -> "_Page":
        """The page of the backend's bytes `raw`; of none, an empty one."""
        if not raw:
            return cls(None, bytearray(_U32.pack(0)))
        widths = _fixed_widths(raw)
        if widths is None:
            return cls(_unpack_page(raw), size=len(raw))
        return cls(None, raw, widths)

    def __len__(self) -> int:
        return self.n if self.rows is None else len(self.rows)

    def copy(self) -> "_Page":
        """A page of its own to write to."""
        if self.rows is not None:
            return _Page(dict(self.rows), size=self.size)
        return _Page(None, bytearray(self.buf), (self.n, self.kl, self.vl))

    def _find(self, key: bytes) -> tuple[int, bool]:
        """Packed form -> (offset of the row `key` has or would take,
        whether it is there)."""
        buf, kl = self.buf, self.kl
        stride = 8 + kl + self.vl
        lo, hi = 0, self.n
        while lo < hi:
            mid = (lo + hi) >> 1
            at = 8 + mid * stride
            if buf[at:at + kl] < key:
                lo = mid + 1
            else:
                hi = mid
        at = 4 + lo * stride
        return at, lo < self.n and buf[at + 4:at + 4 + kl] == key

    def get(self, key: bytes) -> Optional[bytes]:
        if self.rows is not None:
            return self.rows.get(key)
        at, found = self._find(key)
        if not found:
            return None
        at += 8 + self.kl
        return bytes(self.buf[at:at + self.vl])

    def keys(self) -> list[bytes]:
        if self.rows is not None:
            return list(self.rows)
        buf, kl = self.buf, self.kl
        return [bytes(buf[at:at + kl])
                for at in range(8, len(buf), 8 + kl + self.vl)]

    def put(self, key: bytes, value: Optional[bytes]) -> None:
        """Set a row, or drop it where `value` is None."""
        if self.rows is not None:
            return self._put_row(key, value)
        buf, kl, vl = self.buf, self.kl, self.vl
        if value is None:
            at, found = self._find(key)
            if found:
                del buf[at:at + 8 + kl + vl]
                self.n -= 1
                _U32.pack_into(buf, 0, self.n)
                self.size -= 8 + kl + vl
            return
        if not self.n:
            kl, vl = self.kl, self.vl = len(key), len(value)
        elif len(key) != kl or len(value) != vl:
            # a row of other widths: the dict form from here on
            self.rows = _unpack_page(bytes(buf))
            self.buf = None
            return self._put_row(key, value)
        pack = _U32.pack
        if self.n and buf[-4 - vl - kl:-4 - vl] < key:
            # past the last row, as rows loaded in key order all are
            buf += pack(kl) + key + pack(vl) + value
        else:
            at, found = self._find(key)
            if found:
                buf[at + 8 + kl:at + 8 + kl + vl] = value
                return
            buf[at:at] = pack(kl) + key + pack(vl) + value
        self.n += 1
        _U32.pack_into(buf, 0, self.n)
        self.size += 8 + kl + vl

    def _put_row(self, key: bytes, value: Optional[bytes]) -> None:
        rows = self.rows
        old = rows.get(key)
        if value is None:
            if old is not None:
                del rows[key]
                self.size -= 8 + len(key) + len(old)
            return
        rows[key] = value
        self.size += len(value) - len(old) if old is not None \
            else 8 + len(key) + len(value)

    def split(self) -> tuple["_Page", bytes, "_Page"]:
        """-> (the lower half of the rows, the upper half's first key, the
        upper half), cut at the middle row."""
        if self.rows is not None:
            rows, ks = self.rows, sorted(self.rows)
            mid = len(ks) // 2
            return (_Page({k: rows[k] for k in ks[:mid]}), ks[mid],
                    _Page({k: rows[k] for k in ks[mid:]}))
        mid = self.n // 2
        kl, vl = self.kl, self.vl
        cut = 4 + mid * (8 + kl + vl)
        lo = bytearray(self.buf[:cut])
        lo[0:4] = _U32.pack(mid)
        hi = bytearray(_U32.pack(self.n - mid)) + self.buf[cut:]
        return (_Page(None, lo, (mid, kl, vl)), bytes(hi[8:8 + kl]),
                _Page(None, hi, (self.n - mid, kl, vl)))

    def packed(self) -> bytes:
        """The page as the backend holds it. A packed page's buffer is
        handed over as it is, sealed against further writes."""
        if self.rows is not None:
            return _pack_page(self.rows)
        if type(self.buf) is not bytes:
            self.buf = bytes(self.buf)
        return self.buf


class _Staged:
    """What one translation touched, over the committed state: private
    copies of the touched tables' page indexes and of the touched pages,
    the pages it dropped, and the rows of unpaged tables it passes on."""

    __slots__ = ("meta", "pages", "dropped", "dirty_meta", "rows")

    def __init__(self):
        self.meta: dict[str, list[bytes]] = {}
        self.pages: dict[tuple[str, bytes], _Page] = {}
        self.dropped: set[tuple[str, bytes]] = set()
        self.dirty_meta: set[str] = set()
        self.rows: ChangeSet = {}


class KeyPageStorage(TransactionalStorage):
    """Row-level TransactionalStorage over a page-level backend."""

    def __init__(self, backend: TransactionalStorage,
                 page_size: int = 10 * 1024,
                 cache_bytes: int = PAGE_CACHE_BYTES):
        self.backend = backend
        self.page_size = page_size
        self.cache_bytes = cache_bytes
        self._lock = threading.RLock()
        self._meta: dict[str, list[bytes]] = {}  # table -> page starts
        # committed pages, least recently used first
        self._pages: OrderedDict[tuple[str, bytes], _Page] = OrderedDict()
        self._cached_bytes = 0
        self._staged: dict[int, _Staged] = {}  # block -> its translation
        # read-amplification accounting: backend reads vs rows served —
        # the property the page layout exists for, pinned by unit tests
        self._backend_reads = 0
        self._cache_hits = 0
        self._read_seconds = 0.0   # backend page reads, parse included
        self._evictions = 0
        self._page_bytes_written = 0  # pages + page indexes to the backend
        # pages loaded or staged, by the form they took (`_Page`)
        self._pages_packed = 0
        self._pages_rowwise = 0

    def _paged(self, table: str) -> bool:
        # a group's view prefixes its tables `g/<group>/` (namespace.py)
        return table.rpartition("/")[2] not in UNPAGED_TABLES

    # -- page plumbing -----------------------------------------------------
    def _meta_for(self, table: str) -> list[bytes]:
        m = self._meta.get(table)
        if m is None:
            raw = self.backend.get(table, META_KEY)
            self._backend_reads += 1
            m = _unpack_meta(raw) if raw else []
            self._meta[table] = m
        return m

    def _page(self, table: str, start: bytes, reading: bool = True) -> _Page:
        """The committed page, from the cache or the backend. `cache_hits`
        counts row reads (`get`, `keys`) a cached page answered; a
        translation's look at the page it is about to rewrite (`reading`
        False) is no read, and counts only where it has to go to the
        backend."""
        ck = (table, start)
        page = self._pages.get(ck)
        if page is not None:
            self._cache_hits += reading
            self._pages.move_to_end(ck)
            return page
        t0 = time.perf_counter()
        raw = self.backend.get(table, PAGE_PREFIX + start)
        page = _Page.load(raw)
        self._read_seconds += time.perf_counter() - t0
        self._backend_reads += 1
        self._count_form(page)
        self._cache_put(ck, page)
        return page

    def _count_form(self, page: _Page) -> None:
        if page.rows is None:
            self._pages_packed += 1
        else:
            self._pages_rowwise += 1

    def _cache_put(self, ck: tuple[str, bytes], page: _Page) -> None:
        """Hold `page` as the most recently used, then drop the least
        recently used ones down to the budget (never the one just put)."""
        self._cache_drop(ck)
        self._pages[ck] = page
        self._cached_bytes += page.size
        while self._cached_bytes > self.cache_bytes and len(self._pages) > 1:
            _, gone = self._pages.popitem(last=False)
            self._cached_bytes -= gone.size
            self._evictions += 1

    def _cache_drop(self, ck: tuple[str, bytes]) -> None:
        gone = self._pages.pop(ck, None)
        if gone is not None:
            self._cached_bytes -= gone.size

    # -- row-level ops (direct, non-transactional path) --------------------
    def get(self, table: str, key: bytes) -> Optional[bytes]:
        if not self._paged(table):
            return self.backend.get(table, key)
        with self._lock:
            meta = self._meta_for(table)
            i = bisect.bisect_right(meta, key) - 1
            if i < 0:
                return None
            return self._page(table, meta[i]).get(key)

    def set(self, table: str, key: bytes, value: bytes) -> None:
        self.set_batch(table, ((key, value),))

    def remove(self, table: str, key: bytes) -> None:
        self.remove_batch(table, (key,))

    def set_batch(self, table: str,
                  items: Iterable[tuple[bytes, bytes]]) -> None:
        if not self._paged(table):
            self.backend.set_batch(table, items)
            return
        self._write_rows(table, items)

    def remove_batch(self, table: str, ks: Iterable[bytes]) -> None:
        if not self._paged(table):
            self.backend.remove_batch(table, ks)
            return
        self._write_rows(table, ((k, None) for k in ks))

    def _write_rows(self, table: str, items) -> None:
        """Apply rows in the order given, each as the row-by-row path would
        (a page splits the moment it overflows), translated once: the
        backend gets one batch of the pages as they stand at the end."""
        with self._lock:
            st = _Staged()
            for key, value in items:
                start = self._apply(st, table, key, value)
                if start is not None:
                    self._settle(st, table, start)
            cs = self._emit(st)
            if not cs:
                return
            # the new pages and the index before the dropped pages go: a
            # crash between the two leaves unreferenced pages, never a
            # reference to a page that is gone
            self.backend.set_batch(
                table, [(k, e.value) for (_, k), e in cs.items()
                        if not e.deleted])
            gone = [k for (_, k), e in cs.items() if e.deleted]
            if gone:
                self.backend.remove_batch(table, gone)
            self._absorb(st)

    def keys(self, table: str, prefix: bytes = b"") -> Iterator[bytes]:
        if not self._paged(table):
            return self.backend.keys(table, prefix)
        with self._lock:
            meta = self._meta_for(table)
            out = []
            start_i = max(0, bisect.bisect_right(meta, prefix) - 1)
            for s in meta[start_i:]:
                # a page whose start is already past the prefix range can
                # hold no matching row (its rows are >= start) — stop
                # BEFORE paying the read, so a range scan touches exactly
                # the pages covering the prefix
                if prefix and s > prefix and not s.startswith(prefix):
                    break
                out += [k for k in self._page(table, s).keys()
                        if k.startswith(prefix)]
            return iter(sorted(out))

    def tables(self) -> list[str]:
        """Row-level table names == backend table names (pages live inside
        the same table under the `_kp_/` key prefix); snapshot export and
        operator tooling need this passthrough."""
        base_tables = getattr(self.backend, "tables", None)
        return [] if base_tables is None else base_tables()

    def stats(self) -> dict:
        """The page layer's counters (unit tests; the benchmark reads them
        as window deltas), merged with the wrapped backend's stats under
        `backend_stats` so the ops surface (getSystemStatus, storage_tool)
        still sees the engine's level/debt/segment detail when keypage is
        the default layout."""
        with self._lock:
            out = {"backend_reads": self._backend_reads,
                   "cache_hits": self._cache_hits,
                   "read_seconds": self._read_seconds,
                   "evictions": self._evictions,
                   "cached_pages": len(self._pages),
                   "cached_bytes": self._cached_bytes,
                   "cache_budget_bytes": self.cache_bytes,
                   "page_bytes_written": self._page_bytes_written,
                   "pages_packed": self._pages_packed,
                   "pages_rowwise": self._pages_rowwise,
                   "tables_cached": len(self._meta),
                   "key_page_size": self.page_size}
        backend_stats = getattr(self.backend, "stats", None)
        if backend_stats is not None:
            out["backend_stats"] = backend_stats()
        return out

    # -- engine passthroughs ----------------------------------------------
    # KeyPageStorage is a LAYOUT, not a lifecycle owner: every operational
    # seam the node discovers by feature detection (ops/audit.py, snapshot
    # export/install, the overload debt signal, storage_tool) must keep
    # working when the disk engine sits behind a page layer — these appear
    # only when the backend provides them, preserving the getattr contract.
    def __getattr__(self, name):
        if name in ("audit", "compaction_debt_bytes", "disk_bytes",
                    "flush", "needs_compaction", "probe_space"):
            return getattr(self.backend, name)
        raise AttributeError(name)

    def compact(self) -> None:
        backend_compact = getattr(self.backend, "compact", None)
        if backend_compact is not None:
            backend_compact()

    def capture_rows(self):
        """Snapshot export passthrough: rows stream in the PAGE layout
        (meta + `_kp_/` pages are ordinary rows to the backend), which is
        deterministic for identical logical state — so cross-node
        `c_balance` byte-comparisons and snapshot install both stay
        exact."""
        return self.backend.capture_rows()

    def install_rows(self, by_table: dict) -> None:
        self.backend.install_rows(by_table)
        # the swapped-in state invalidates every cached page wholesale
        self.flush_caches()

    # -- changeset translation ---------------------------------------------
    def _stage_page(self, st: _Staged, table: str, start: bytes) -> _Page:
        ck = (table, start)
        page = st.pages.get(ck)
        if page is None:
            page = st.pages[ck] = self._page(table, start, False).copy()
        return page

    def _apply(self, st: _Staged, table: str, key: bytes,
               value: Optional[bytes]) -> Optional[bytes]:
        """Put one row (None: delete it) into its page in `st` -> the
        page's start, or None where there was nothing to do."""
        meta = st.meta.get(table)
        if meta is None:
            meta = st.meta[table] = list(self._meta_for(table))
        i = bisect.bisect_right(meta, key) - 1
        if i < 0:
            if not meta:
                if value is None:
                    return None
                meta.append(key)
                st.pages[(table, key)] = _Page.load()
            else:
                # key sorts before the first page: extend page 0 downward
                old0 = meta[0]
                page = self._stage_page(st, table, old0)
                del st.pages[(table, old0)]
                st.dropped.add((table, old0))
                st.pages[(table, key)] = page
                meta[0] = key
            st.dropped.discard((table, key))
            st.dirty_meta.add(table)
            i = 0
        start = meta[i]
        self._stage_page(st, table, start).put(key, value)
        return start

    def _settle(self, st: _Staged, table: str, start: bytes) -> None:
        """Drop a touched page that is left empty, split one that outgrew
        the page size (once, in halves)."""
        ck = (table, start)
        page = st.pages[ck]
        meta = st.meta[table]
        if page.size == _EMPTY_PAGE_BYTES:  # no row left
            if len(meta) > 1:
                del meta[bisect.bisect_left(meta, start)]
                del st.pages[ck]
                st.dropped.add(ck)
                st.dirty_meta.add(table)
        elif page.size > self.page_size and len(page) > 1:
            st.pages[ck], hi_start, hi = page.split()
            st.pages[(table, hi_start)] = hi
            st.dropped.discard((table, hi_start))
            bisect.insort(meta, hi_start)
            st.dirty_meta.add(table)

    def _emit(self, st: _Staged) -> ChangeSet:
        """-> the page-level backend changeset of `st`."""
        out: ChangeSet = dict(st.rows)
        written = 0
        for table, start in st.dropped:
            out[(table, PAGE_PREFIX + start)] = Entry(
                b"", EntryStatus.DELETED)
        for (table, start), page in st.pages.items():
            packed = page.packed()
            self._count_form(page)
            written += len(packed)
            out[(table, PAGE_PREFIX + start)] = Entry(packed)
        for table in st.dirty_meta:
            packed = _pack_meta(st.meta[table])
            written += len(packed)
            out[(table, META_KEY)] = Entry(packed)
        self._page_bytes_written += written
        return out

    def _absorb(self, st: _Staged) -> None:
        """The backend holds `st`: make it the committed state."""
        self._meta.update(st.meta)
        for ck in st.dropped:
            self._cache_drop(ck)
        for ck, page in st.pages.items():
            self._cache_put(ck, page)

    # -- 2PC ---------------------------------------------------------------
    def prepare(self, block_number: int, changes: ChangeSet) -> None:
        with self._lock:
            st = _Staged()
            touched = []
            for (table, key), e in sorted(changes.items()):
                if not self._paged(table):
                    st.rows[(table, key)] = e
                    continue
                start = self._apply(st, table, key,
                                    None if e.deleted else e.value)
                if start is not None:
                    touched.append((table, start))
            # in key order, so that every node that drops or splits pages
            # of one table ends with the same ones
            for table, start in sorted(set(touched)):
                self._settle(st, table, start)
            self._staged[block_number] = st
            self.backend.prepare(block_number, self._emit(st))

    def commit(self, block_number: int) -> None:
        with self._lock:
            self.backend.commit(block_number)
            self._absorb(self._staged.pop(block_number))

    def rollback(self, block_number: int) -> None:
        with self._lock:
            self._staged.pop(block_number, None)
            self.backend.rollback(block_number)

    def close(self) -> None:
        self.backend.close()

    def flush_caches(self) -> None:
        with self._lock:
            self._meta.clear()
            self._pages.clear()
            self._cached_bytes = 0
