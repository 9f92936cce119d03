"""Persisted tuning database: winners survive restarts, fleet-wide.

One JSONL file — by default ``tune.jsonl`` inside the XLA persistent
compile cache directory (``$JAX_COMPILATION_CACHE_DIR``, else
``<checkout>/.jax_cache``), the same shared home that lets a fleet of
processes reuse each other's compiled programs — holds every tuned
winner, keyed by ``surface | signature | device
kind``. The durability model mirrors the compile cache's:

- **atomic rename writes**: a put re-reads the current file, merges the
  new winner, writes the whole merged state to a uniquely-named temp
  file, fsyncs, and ``os.replace``\\ s it over the target. Concurrent
  writers race at the rename and the last COMPLETE write wins — a
  reader can never observe a torn file, and a writer killed mid-write
  (even ``kill -9``) leaves only a stale temp file behind, never a
  corrupt store;
- **schema versioning**: every record carries ``"v"``; records from a
  different schema version are ignored on read (the consumer simply
  re-tunes), so a binary upgrade never misreads an old store;
- **corrupt-line tolerance**: unparseable lines (a partial write from a
  pre-rename implementation, disk corruption) are skipped with a
  warning, never fatal;
- **cross-process staleness by mtime re-read**: reads go through an
  in-process cache invalidated on ``(mtime_ns, size)`` change, so a
  winner tuned by process A is visible to a long-running process B at
  its next lookup for the cost of one ``stat``.

The store knows nothing about what a config means — it maps key
strings to JSON dicts. :mod:`tensorframes_tpu.tune.search` owns the
semantics (grids, trials, installation).
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Any, Dict, Optional, Tuple

from ..utils.logging import get_logger

__all__ = [
    "SCHEMA_VERSION",
    "TuneStore",
    "device_kind",
    "store_path",
]

logger = get_logger("tune.store")

#: bump when the record layout changes incompatibly: old-version records
#: are IGNORED on read (ignore-and-retune), never misinterpreted
SCHEMA_VERSION = 1


_device_kind_cache: Optional[str] = None


def device_kind() -> str:
    """The accelerator kind winners are keyed under — a winner measured
    on one chip generation must not serve another. Cached for the
    process lifetime (the device cannot change under a live runtime,
    and this sits on per-transfer lookup paths). Initializes the
    backend if nothing has yet: whoever asks for a tuned winner is
    about to compute on the device, and a backend that fails to come up
    must say so rather than tune under ``"unknown"``."""
    global _device_kind_cache
    if _device_kind_cache is None:
        import jax

        _device_kind_cache = str(jax.devices()[0].device_kind)
    return _device_kind_cache


def store_path() -> str:
    """Where the tuning store lives: ``Config.tune_file``, else
    ``$TFT_TUNE_FILE``, else ``tune.jsonl`` inside the compile-cache
    directory (:func:`~tensorframes_tpu.utils.config.compilation_cache_dir`
    — ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), so
    whoever places the cache places the winners with it."""
    from ..utils.config import compilation_cache_dir, get_config

    explicit = get_config().tune_file or os.environ.get("TFT_TUNE_FILE", "")
    if explicit:
        return explicit
    return os.path.join(compilation_cache_dir(), "tune.jsonl")


def make_key(surface: str, signature: str, device: Optional[str] = None) -> str:
    return f"{surface}|{signature}|{device if device is not None else device_kind()}"


class TuneStore:
    """The persisted winner map. Thread-safe; see the module docstring
    for the cross-process contract."""

    def __init__(self, path: Optional[str] = None):
        self._explicit_path = path
        self._lock = threading.Lock()
        #: read cache: (resolved path, (mtime_ns, size)) -> entries.
        #: Invalidation is by stat change, so process B sees process A's
        #: winners at its next get() without re-parsing on every lookup.
        self._cache_path: Optional[str] = None
        self._cache_stat: Optional[Tuple[int, int]] = None
        self._entries: Dict[str, Dict[str, Any]] = {}
        self._corrupt_seen = 0

    # -- path / load -------------------------------------------------------

    def path(self) -> str:
        return self._explicit_path or store_path()

    def _stat(self, path: str) -> Optional[Tuple[int, int]]:
        try:
            st = os.stat(path)
            return (st.st_mtime_ns, st.st_size)
        except OSError:
            return None

    def _parse(
        self, path: str
    ) -> Tuple[Dict[str, Dict[str, Any]], list]:
        """``(entries, foreign_lines)``: current-schema records by key
        (later lines win), plus the RAW lines of valid records from
        OTHER schema versions — invisible to this process
        (ignore-and-retune) but carried verbatim through rewrites so a
        mixed-version fleet sharing one store never erases each other's
        winners."""
        entries: Dict[str, Dict[str, Any]] = {}
        foreign: list = []
        corrupt = 0
        try:
            with open(path) as f:
                for line in f:
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        rec = json.loads(line)
                    except ValueError:
                        corrupt += 1
                        continue
                    if not isinstance(rec, dict):
                        corrupt += 1
                        continue
                    if rec.get("v") != SCHEMA_VERSION:
                        # a different schema version is not corruption —
                        # it is simply not for us (ignore-and-retune);
                        # preserved verbatim on rewrite
                        foreign.append(line)
                        continue
                    key = rec.get("key")
                    cfg = rec.get("config")
                    if not isinstance(key, str) or not isinstance(cfg, dict):
                        corrupt += 1
                        continue
                    # later lines win: last-complete-wins per key
                    entries[key] = rec
        except OSError:
            return {}, []
        if corrupt and corrupt != self._corrupt_seen:
            self._corrupt_seen = corrupt
            logger.warning(
                "tuning store %s: %d unparseable line(s) skipped", path,
                corrupt,
            )
        return entries, foreign

    def _load(self) -> Dict[str, Dict[str, Any]]:
        """Entries under the lock-free read path: re-parse only when the
        file's (mtime_ns, size) moved or the resolved path changed."""
        path = self.path()
        st = self._stat(path)
        with self._lock:
            if path == self._cache_path and st == self._cache_stat:
                return self._entries
            self._entries = (
                self._parse(path)[0] if st is not None else {}
            )
            self._cache_path, self._cache_stat = path, st
            return self._entries

    # -- reads -------------------------------------------------------------

    def get(self, key: str) -> Optional[Dict[str, Any]]:
        """The stored record for ``key`` (``None`` when absent). The
        returned dict is the raw record; callers read ``record["config"]``."""
        return self._load().get(key)

    def entries(self) -> Dict[str, Dict[str, Any]]:
        """A snapshot of every stored record, by key."""
        return dict(self._load())

    # -- writes ------------------------------------------------------------

    def put(
        self,
        key: str,
        config: Dict[str, Any],
        *,
        wall_s: Optional[float] = None,
        meta: Optional[Dict[str, Any]] = None,
    ) -> Dict[str, Any]:
        """Record a winner: read-merge-rewrite with an atomic rename.

        The merge re-reads the file immediately before writing so a
        concurrent writer's winners for OTHER keys are carried forward
        whenever the interleaving allows; two simultaneous writers to
        the SAME key race at the rename and the last complete write
        wins. Either way the file always parses."""
        # key = surface | signature | device, where the SIGNATURE may
        # itself contain "|" separators — the device is always the last
        # segment, so split it off from the right
        surface, _, rest = key.partition("|")
        signature, _, device = rest.rpartition("|")
        rec = {
            "v": SCHEMA_VERSION,
            "key": key,
            "surface": surface,
            "signature": signature,
            "device": device,
            "config": dict(config),
            "wall_s": None if wall_s is None else round(float(wall_s), 6),
            "meta": dict(meta or {}),
            "ts": round(time.time(), 3),
            "host": socket.gethostname(),
            "pid": os.getpid(),
        }
        path = self.path()
        with self._lock:
            entries, foreign = self._parse(path)
            entries = dict(entries)
            entries[key] = rec
            self._write(path, entries, foreign)
            self._entries = entries
            self._cache_path = path
            self._cache_stat = self._stat(path)
        return rec

    def clear(self, surface: Optional[str] = None) -> int:
        """Drop every stored winner (or only one surface's); returns the
        number removed. The pin/clear cookbook entry in docs/tuning.md."""
        path = self.path()
        with self._lock:
            entries, foreign = self._parse(path)
            entries = dict(entries)
            if surface is None:
                removed, entries = len(entries), {}
            else:
                victims = [
                    k for k, r in entries.items()
                    if r.get("surface") == surface
                ]
                for k in victims:
                    del entries[k]
                removed = len(victims)
            if removed:
                self._write(path, entries, foreign)
            self._entries = entries
            self._cache_path = path
            self._cache_stat = self._stat(path)
        return removed

    def _write(
        self,
        path: str,
        entries: Dict[str, Dict[str, Any]],
        foreign: list = (),
    ) -> None:
        d = os.path.dirname(path) or "."
        os.makedirs(d, exist_ok=True)
        # unique temp name per writer: two processes must never share a
        # temp file (the dist-jobs _atomic_write lesson); the rename is
        # the single atomic commit point
        tmp = os.path.join(
            d,
            f".{os.path.basename(path)}.{os.getpid()}."
            f"{threading.get_ident()}.tmp",
        )
        body = "".join(ln + "\n" for ln in foreign) + "".join(
            json.dumps(entries[k], default=str) + "\n"
            for k in sorted(entries)
        )
        try:
            with open(tmp, "w") as f:
                f.write(body)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        finally:
            try:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            except OSError:
                pass
