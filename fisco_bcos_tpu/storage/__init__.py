"""Storage stack: transactional KV with 2PC + state overlays.

Reference counterpart: TransactionalStorageInterface with asyncPrepare/
asyncCommit/asyncRollback (/root/reference/bcos-framework/bcos-framework/
storage/StorageInterface.h:126-141), RocksDBStorage (bcos-storage/
bcos-storage/RocksDBStorage.h:64-68) and the StateStorage/KeyPageStorage
overlays (bcos-table/src/). The persistent slot has two fills: WalStorage
(snapshot + full-log replay, small states) and DiskStorage (log-structured
segments + manifest, storage/engine.py — restart flat in chain length,
datasets beyond RAM: the page layer over it, storage/keypage.py, holds a
bounded LRU of pages), selected by the `[storage] backend` ini knob.
"""

from typing import Optional

from .interface import Entry, StorageInterface, TransactionalStorage
from .memory import MemoryStorage
from .namespace import NamespacedStorage
from .state import StateStorage
from .wal import WalStorage


def __getattr__(name):  # lazy: engine pulls in sstable/compact machinery
    if name == "DiskStorage":
        from .engine import DiskStorage
        return DiskStorage
    if name == "KeyPageStorage":
        from .keypage import KeyPageStorage
        return KeyPageStorage
    raise AttributeError(name)


# auto page size for the disk backend: what upstream's build_chain.sh
# writes into every config.ini (`[storage] key_page_size=10240`)
DEFAULT_KEY_PAGE_SIZE = 10240


def make_storage(backend: str, path: Optional[str],
                 memtable_mb: int = 64, compact_segments: int = 8,
                 key_page_size: int = -1, registry=None, health=None,
                 level_base_mb: int = 16, level_fanout: int = 8
                 ) -> TransactionalStorage:
    """Build the node's backing store from the `[storage]` config surface.

    backend: `auto` keeps the historical selection (WAL-backed when a path
    is configured, in-memory otherwise); `memory`/`wal`/`disk` force one.
    `key_page_size` wraps the persistent backend in KeyPageStorage so
    wide-table rows are page-packed (reference KeyPageStorage layout):
    > 0 sets an explicit page size, 0 disables paging, and < 0 (the
    default, ini `key_page_size = auto`) turns paging ON for the disk
    backend — wide tables are the norm at production scale, and the page
    layout is what keeps their range scans at O(pages) backend reads.
    The page layer caches at most `keypage.PAGE_CACHE_BYTES` of pages and
    leaves `keypage.UNPAGED_TABLES` row by row.
    `level_base_mb`/`level_fanout` shape the disk engine's leveled
    compaction (L1 byte target and per-level growth factor).
    `health` (utils/health.py) receives the persistent backends' ENOSPC /
    flush-failure degradation signals.
    """
    if backend in ("", "auto", None):
        backend = "wal" if path else "memory"
    if backend == "memory":
        return MemoryStorage()
    if path is None:
        raise ValueError(f"[storage] backend={backend} needs a data path")
    if backend == "wal":
        st: TransactionalStorage = WalStorage(path, health=health)
    elif backend == "disk":
        from .engine import DiskStorage
        st = DiskStorage(path, memtable_bytes=memtable_mb << 20,
                         max_segments=compact_segments, registry=registry,
                         health=health,
                         level_base_bytes=level_base_mb << 20,
                         level_fanout=level_fanout)
    else:
        raise ValueError(f"unknown [storage] backend {backend!r}")
    if key_page_size < 0:
        key_page_size = DEFAULT_KEY_PAGE_SIZE if backend == "disk" else 0
    if key_page_size > 0:
        from .keypage import KeyPageStorage
        st = KeyPageStorage(st, page_size=key_page_size)
    return st


__all__ = [
    "Entry",
    "StorageInterface",
    "TransactionalStorage",
    "MemoryStorage",
    "NamespacedStorage",
    "StateStorage",
    "WalStorage",
    "DiskStorage",
    "KeyPageStorage",
    "make_storage",
]
