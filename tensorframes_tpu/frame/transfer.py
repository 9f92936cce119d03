"""Streaming host↔device transfers: chunked, concurrent, retried.

A column that crosses the host↔device link as ONE blocking
``jax.device_put`` leaves the chip idle for the whole upload and has no
retry. The reference pays the same per-session marshaling
(``TFDataOps.scala``); the TPU-performance literature (Kaufman et al.,
arXiv:2008.01040) makes the general point that end-to-end throughput is
gated by *feeding* the chip, not the MXU. Here every column-sized
transfer is split into row chunks that move concurrently on a small
thread pool (link rates: not re-measured on the current machine), so

- multiple chunks are in flight at once (a single stream cannot fill a
  high-latency link; N streams pipeline against each other),
- consumers can start computing on chunk *i* while chunk *i+1* is still
  in the air (:class:`StreamingUpload` hands out per-chunk device
  arrays; ``engine/ops.py`` feeds block loops from them),
- each chunk crosses inside its own ``run_with_retries`` window with a
  ``frame.h2d`` / ``frame.d2h`` chaos site, so a transient link error
  retries one chunk instead of killing the whole ingest (the monolithic
  path had **no** retry at all).

Knobs (:class:`~tensorframes_tpu.utils.config.Config`):
``transfer_chunk_bytes`` (chunk size; ``<= 0`` restores the monolithic
path — still retried and counted), ``transfer_streams`` (pool width),
and ``transfer_dtype="bf16"`` — a WIRE cast: float32 payloads cross the
link as bfloat16 (half the link bytes) and are upcast back to float32
on device, so schemas, programs, and device dtypes are untouched; the
values are bf16-rounded, the same precision loss the bf16 bench mode
measures (≥98% argmax agreement on the scoring workload). An accuracy
trade the caller opts into.

Byte-identity is the hard contract: with no wire cast configured, a
chunked transfer produces exactly the bytes the monolithic one would,
in both directions (tests/test_transfer.py holds the greedy matrix).

Telemetry: ``frame.h2d_bytes_total`` / ``frame.d2h_bytes_total``
(moved here from ``frame/table.py`` — still real link bytes, now
including the engine's per-block feed uploads), per-chunk
``frame.h2d_seconds`` / ``frame.d2h_seconds`` histograms, and an
``ingest.inflight_chunks`` gauge. See docs/ingest.md for tuning
guidance and docs/observability.md for the catalog.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, List, Optional, Tuple

import numpy as np

from ..obs import span as _span
from ..obs.metrics import counter as _counter
from ..obs.metrics import gauge as _gauge
from ..obs.metrics import histogram as _histogram
from ..utils import get_logger

__all__ = [
    "StreamingUpload",
    "d2h",
    "d2h_async",
    "h2d",
    "wire_dtype",
]

logger = get_logger("transfer")

#: link-traffic accounting (moved from ``frame/table.py``): bytes that
#: actually cross the host↔device link — memoized column transfers AND
#: the engine's per-block feed uploads, each counted once where the
#: transfer happens
_m_h2d = _counter(
    "frame.h2d_bytes_total", "Host-to-device transfer bytes over the link"
)
_m_d2h = _counter(
    "frame.d2h_bytes_total", "Device-to-host transfer bytes over the link"
)
#: per-CHUNK transfer latency: throughput is visible as bytes/seconds
#: per scrape window; a fat tail here is the link stalling
_h_h2d = _histogram(
    "frame.h2d_seconds", "Per-chunk host-to-device transfer seconds"
)
_h_d2h = _histogram(
    "frame.d2h_seconds", "Per-chunk device-to-host transfer seconds"
)
_g_inflight = _gauge(
    "ingest.inflight_chunks",
    "Transfer chunks currently in flight (both directions)",
)
#: the live transfer knobs as gauges: a /varz reader (or the future
#: autotuner) correlating a throughput dip with a retune needs the knob
#: values IN the series, not in a config file somewhere else
_g_chunk_bytes = _gauge(
    "ingest.chunk_bytes",
    "Configured transfer chunk size in bytes (<= 0 = monolithic)",
)
_g_streams = _gauge(
    "ingest.streams", "Configured transfer pool width (chunks in flight)"
)


def _refresh_knob_gauges() -> None:
    from ..utils.config import get_config

    cfg = get_config()
    _g_chunk_bytes.set(float(cfg.transfer_chunk_bytes))
    _g_streams.set(float(max(1, int(cfg.transfer_streams))))


from ..utils.config import register_on_change as _register_on_change  # noqa: E402

_register_on_change(_refresh_knob_gauges)

#: hard cap on chunks per transfer: a pathological chunk-bytes setting
#: (1 byte) must not mint a million thread-pool tasks
_MAX_CHUNKS = 1024


# ---------------------------------------------------------------------------
# tuned link knobs
# ---------------------------------------------------------------------------

#: cap on the autotuner's trial payload for this link (bytes;
#: ``TFT_TUNE_TRIAL_BYTES`` overrides — tests shrink it, operators on a
#: fast link may grow it for higher-fidelity trials)
_TRIAL_BYTES_DEFAULT = 64 << 20


def _link_knobs() -> Tuple[int, int]:
    """The effective ``(chunk_bytes, streams)`` for this link: the
    Config statics, overridden by the autotuner's winner for the
    ``transfer.link`` surface when one is installed (re-read per pool
    retune — winners key on device kind, and ``tune.mode()`` gates
    everything). Chunking
    disabled by config (``transfer_chunk_bytes <= 0``) is an operator
    opt-out the tuner respects."""
    from ..utils import get_config

    cfg = get_config()
    default_cb = int(cfg.transfer_chunk_bytes)
    default_st = max(1, int(cfg.transfer_streams))
    if default_cb <= 0:
        return default_cb, default_st
    try:
        from .. import tune

        if tune.mode() == "off":
            return default_cb, default_st
        grid, feats, trial = _link_search(default_cb, default_st)
        win = tune.lookup(
            "transfer.link", "link",
            {"chunk_bytes": default_cb, "streams": default_st},
            grid=grid, feats=feats, trial=trial,
        )
        cb = int(win.get("chunk_bytes", default_cb))
        st = int(win.get("streams", default_st))
        return (cb if cb > 0 else default_cb), max(1, min(st, 64))
    except Exception:
        logger.warning(
            "transfer knob tuning lookup failed; using Config statics",
            exc_info=True,
        )
        return default_cb, default_st


def _link_search(default_cb: int, default_st: int):
    """(grid, feats, trial) for the transfer-knob search. The trial
    moves a seeded payload host→device as concurrent row chunks on a
    PRIVATE pool (raw ``device_put`` — no recursion into this layer,
    and the re-entrancy guard covers stray lookups). Payload is capped
    (``TFT_TUNE_TRIAL_BYTES``), and chunk candidates are capped at half
    the payload so every candidate genuinely exercises chunking at
    trial scale — a fidelity trade documented in docs/tuning.md."""
    import os as _os

    cap = int(
        _os.environ.get("TFT_TUNE_TRIAL_BYTES", "")
        or _TRIAL_BYTES_DEFAULT
    )
    payload = max(4096, min(2 * default_cb, cap))
    chunk_cands = sorted(
        {
            c
            for c in (
                payload // 8, payload // 4, payload // 2, default_cb,
            )
            if 0 < c <= payload // 2
        }
    )
    if not chunk_cands:
        chunk_cands = [max(1, payload // 2)]
    stream_cands = sorted({2, default_st, 8})
    grid = [
        {"chunk_bytes": int(c), "streams": int(s)}
        for c in chunk_cands
        for s in stream_cands
    ]
    state: dict = {}

    def _payload() -> np.ndarray:
        buf = state.get("buf")
        if buf is None:
            rows = max(1, payload // 4096)
            buf = state["buf"] = (
                np.random.default_rng(0)
                .integers(0, 255, size=(rows, 1024), dtype=np.int64)
                .astype(np.float32)
            )
        return buf

    def feats(cand):
        chunks = max(1, -(-payload // max(1, int(cand["chunk_bytes"]))))
        waves = -(-chunks // max(1, int(cand["streams"])))
        # flops 0 (pure data movement); the bytes term prices the link,
        # the dispatch term prices per-chunk submission/latency waves
        return 0.0, float(payload), float(chunks + waves)

    def trial(cand):
        import jax

        buf = _payload()
        row_bytes = buf.itemsize * buf.shape[1]
        rows = max(1, int(cand["chunk_bytes"]) // row_bytes)
        bounds = [
            (lo, min(lo + rows, buf.shape[0]))
            for lo in range(0, buf.shape[0], rows)
        ]
        with ThreadPoolExecutor(
            max_workers=max(1, int(cand["streams"])),
            thread_name_prefix="tft-tune-link",
        ) as pool:
            futs = [
                pool.submit(jax.device_put, buf[lo:hi])
                for lo, hi in bounds
            ]
            for f in futs:
                jax.block_until_ready(f.result())

    return grid, feats, trial


# ---------------------------------------------------------------------------
# pool + plan
# ---------------------------------------------------------------------------

_pool_lock = threading.Lock()
_pool: Optional[ThreadPoolExecutor] = None
_pool_width = 0


def _get_pool(width: Optional[int] = None) -> ThreadPoolExecutor:
    """The shared transfer pool, sized to ``Config.transfer_streams``
    (or the autotuner's winner for this link — ``_link_knobs``; rebuilt
    when the effective width changes; in-flight work on the old pool
    drains, it is never cancelled). Callers that already resolved the
    link knobs pass ``width`` so one transfer op sees ONE consistent
    (chunk, streams) pair instead of re-resolving per helper."""
    global _pool, _pool_width
    if width is None:
        _, width = _link_knobs()
    width = max(1, int(width))
    with _pool_lock:
        if _pool is None or _pool_width != width:
            # the old pool is NOT shut down: an in-flight transfer that
            # grabbed its reference may still submit chunks to it, and
            # submit-after-shutdown raises. Its idle workers linger until
            # process exit — retunes are rare operator actions, and a few
            # parked threads beat crashing a 3 GB upload mid-flight.
            _pool = ThreadPoolExecutor(
                max_workers=width, thread_name_prefix="tft-transfer"
            )
            _pool_width = width
        return _pool


def wire_dtype(host_dtype) -> np.dtype:
    """The dtype a payload crosses the link with: the host dtype, or
    bfloat16 when ``Config.transfer_dtype="bf16"`` and the payload is
    float32 (the halve-the-link-bytes cast; upcast back to float32 on
    device, so only the *values* round — dtypes never change)."""
    from ..utils import get_config

    host_dtype = np.dtype(host_dtype)
    td = get_config().transfer_dtype
    if not td:
        return host_dtype
    if td != "bf16":
        raise ValueError(
            f"unknown Config.transfer_dtype {td!r}; expected '' or 'bf16'"
        )
    if host_dtype == np.float32:
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return host_dtype


def _chunk_bounds(
    n_rows: int, row_bytes: int, chunk_bytes: Optional[int] = None
) -> List[Tuple[int, int]]:
    """Row-range chunks for an ``[n_rows, ...]`` transfer. One chunk when
    chunking is off (``transfer_chunk_bytes <= 0``), the payload fits a
    single chunk, or the array is empty/rowless. Chunk size is the
    tuned link value when the autotuner has a winner (``_link_knobs``;
    pass ``chunk_bytes`` when the caller already resolved it)."""
    if chunk_bytes is None:
        chunk_bytes, _ = _link_knobs()
    if n_rows <= 1 or chunk_bytes <= 0 or row_bytes <= 0:
        return [(0, n_rows)]
    rows = max(1, int(chunk_bytes // row_bytes))
    n_chunks = -(-n_rows // rows)
    if n_chunks > _MAX_CHUNKS:
        rows = -(-n_rows // _MAX_CHUNKS)
    if rows >= n_rows:
        return [(0, n_rows)]
    return [(lo, min(lo + rows, n_rows)) for lo in range(0, n_rows, rows)]


def chunk_rows(row_bytes: int) -> int:
    """Rows per transfer chunk for a payload of ``row_bytes`` per row —
    the alignment quantum for consumers that plan their own block loops
    (``engine/ops.py``'s journaled ``map_rows`` caps its block plan at
    this so a journal block never spans transfer chunks and a resumed
    job re-uploads only its own unfinished blocks' bytes). Effectively
    unbounded when chunking is off."""
    chunk_bytes, _ = _link_knobs()
    if chunk_bytes <= 0 or row_bytes <= 0:
        return 1 << 62
    return max(1, int(chunk_bytes // row_bytes))


def _submit(pool, fn, *args):
    """Submit a transfer task carrying the submitting thread's retry
    deadline into the pool thread. The distributed-job worker clips
    each block's retry budget below its lease TTL via a thread-local
    ``retry_deadline`` window (``utils/failures.py``); chunk transfers
    run their ``run_with_retries`` windows on pool threads, where that
    thread-local would otherwise be unset — i.e. unbounded, letting a
    transient burst on the link retry past the TTL while the worker is
    alive and mid-block (presumed dead, fenced)."""
    from ..utils.failures import (
        adopt_retry_deadline,
        current_retry_deadline,
    )

    deadline = current_retry_deadline()
    if deadline is None:
        return pool.submit(fn, *args)

    def run(*a):
        with adopt_retry_deadline(deadline):
            return fn(*a)

    return pool.submit(run, *args)


def _observed(direction: str, fn, what: str):
    """Run one chunk transfer inside its retry window with the chaos
    site, inflight gauge, latency histogram, and byte counter applied.
    ``fn`` must SYNCHRONIZE (return only once the bytes have crossed)
    so retries see transfer failures and the histogram is honest."""
    from ..utils import run_with_retries
    from ..utils.chaos import site as _chaos_site

    site = "frame." + direction
    hist = _h_h2d if direction == "h2d" else _h_d2h
    ctr = _m_h2d if direction == "h2d" else _m_d2h

    def attempt():
        _chaos_site(site)
        return fn()

    _g_inflight.inc()
    try:
        t0 = time.perf_counter()
        out, nbytes = run_with_retries(attempt, what=what)
        hist.observe(time.perf_counter() - t0)
        ctr.inc(nbytes)
        return out
    finally:
        _g_inflight.dec()


# ---------------------------------------------------------------------------
# host -> device
# ---------------------------------------------------------------------------


def _put_chunk(piece: np.ndarray, wire: np.dtype, what: str):
    import jax

    host_dtype = piece.dtype
    if host_dtype != wire:
        # host-side cast BEFORE the link: this is the whole point of
        # transfer_dtype — half the f32 bytes ever cross the link;
        # the upcast back to the host dtype runs on DEVICE below
        piece = piece.astype(wire)

    def go():
        dev = jax.device_put(piece)
        if dev.dtype != host_dtype:
            dev = dev.astype(host_dtype)
        # sync inside the retry window: device_put is async on real
        # runtimes, and an un-synced failure would surface far away
        return jax.block_until_ready(dev), piece.nbytes

    return _observed("h2d", go, what)


class _Resident:
    """Stream interface over an already-device-resident array (the
    degenerate :class:`StreamingUpload`): everything has 'landed'."""

    __slots__ = ("arr",)

    def __init__(self, arr):
        self.arr = arr

    def slice(self, lo: int, hi: int):
        a = self.arr
        return a if lo == 0 and hi == a.shape[0] else a[lo:hi]

    def assembled(self):
        return self.arr


class StreamingUpload:
    """One host column crossing the link as concurrent row chunks.

    Construction submits every chunk to the transfer pool immediately
    (``Config.transfer_streams`` in flight at once). Consumers pull
    results at whatever granularity they need:

    - :meth:`slice` ``(lo, hi)`` waits only for the chunks covering that
      row range — a block loop computing on rows [0, c) runs while rows
      [c, 2c) are still in the air (upload/compute overlap);
    - :meth:`assembled` waits for everything and returns the full column
      as one device array (a jit-cached on-device concat), memoized — the
      drop-in replacement for the old monolithic ``device_put``.

    Byte-identity with the monolithic path holds whenever no
    ``transfer_dtype`` wire cast applies (device_put of row slices
    followed by an on-device concat moves exactly the same bytes).
    """

    __slots__ = ("arr", "wire", "bounds", "what", "_futs", "_chunks",
                 "_assembled", "_lock")

    def __init__(self, arr: np.ndarray, what: str = "column"):
        self.arr = arr
        self.wire = wire_dtype(arr.dtype)
        # resolve the link knobs ONCE per upload: bounds and pool width
        # must come from the same (chunk, streams) pair even if a tuned
        # winner lands mid-transfer
        chunk_bytes, streams = _link_knobs()
        if arr.ndim == 0:
            # scalars cross whole (they cannot be row-sliced); d2h has
            # the symmetric case
            self.bounds = [(0, 1)]
        else:
            row_bytes = self.wire.itemsize * int(
                np.prod(arr.shape[1:], initial=1)
            )
            self.bounds = _chunk_bounds(
                int(arr.shape[0]), row_bytes, chunk_bytes
            )
        self.what = what
        self._chunks: List[Any] = [None] * len(self.bounds)
        self._assembled = None
        self._lock = threading.Lock()
        pool = _get_pool(streams)
        self._futs = [
            _submit(
                pool,
                _put_chunk,
                arr[lo:hi] if arr.ndim else arr,
                self.wire,
                f"frame.h2d {what} chunk {i}/{len(self.bounds)}",
            )
            for i, (lo, hi) in enumerate(self.bounds)
        ]

    @property
    def num_chunks(self) -> int:
        return len(self.bounds)

    def chunk(self, i: int):
        """Device array for chunk ``i`` (blocks until it has landed), or
        ``None`` once :meth:`assembled` has collapsed the chunks (the
        caller falls back to slicing the assembled column). Future waits
        happen OUTSIDE the lock so concurrent consumers overlap."""
        with self._lock:
            if self._assembled is not None:
                return None
            c = self._chunks[i]
            fut = self._futs[i]
        if c is not None:
            return c
        c = fut.result()
        with self._lock:
            if self._assembled is None:
                self._chunks[i] = c
        return c

    def slice(self, lo: int, hi: int):
        """Device array for rows ``[lo, hi)``, waiting only on the
        chunks that cover the range. Matches the ``_block_feeder``
        slicer contract: the full range returns the assembled column
        itself (no extra on-device copy)."""
        if self.arr.ndim == 0:
            return self.assembled()
        n = int(self.arr.shape[0])
        if lo == 0 and hi == n:
            return self.assembled()
        with self._lock:
            asm = self._assembled
            futs = self._futs
        if asm is None and futs and all(f.done() for f in futs):
            # everything has landed: assemble once so chained passes
            # slice one array instead of re-concatenating chunks
            asm = self.assembled()
        if asm is not None:
            return asm[lo:hi]
        pieces = []
        for i, (clo, chi) in enumerate(self.bounds):
            if chi <= lo or clo >= hi:
                continue
            dev = self.chunk(i)
            if dev is None:  # a concurrent assembled() collapsed chunks
                return self.assembled()[lo:hi]
            a, b = max(lo - clo, 0), min(hi, chi) - clo
            pieces.append(
                dev if (a == 0 and b == chi - clo) else dev[a:b]
            )
        if len(pieces) == 1:
            return pieces[0]
        import jax.numpy as jnp

        return jnp.concatenate(pieces, axis=0)

    def assembled(self):
        """The whole column on device (memoized). Waits for every chunk;
        multi-chunk uploads concatenate once on device."""
        with self._lock:
            if self._assembled is not None:
                return self._assembled
        # collect OUTSIDE the lock (future waits can be long); chunks are
        # still present because only the winner below drops them
        chunks = [self.chunk(i) for i in range(len(self.bounds))]
        with self._lock:
            if self._assembled is None:
                if None in chunks:  # another thread won and collapsed
                    raise AssertionError("assembled state torn")
                if len(chunks) == 1:
                    self._assembled = chunks[0]
                else:
                    import jax.numpy as jnp

                    self._assembled = jnp.concatenate(chunks, axis=0)
                # drop per-chunk refs (futures included — a future pins
                # its result): once assembled exists the chunk buffers
                # would otherwise hold 2x the column in HBM
                self._chunks = [None] * len(self.bounds)
                self._futs = []
            return self._assembled


def h2d(arr: np.ndarray, what: str = "feed"):
    """Move one host array to device: chunked + concurrent when it
    exceeds ``transfer_chunk_bytes``, monolithic otherwise — either way
    retried per chunk, chaos-injectable at ``frame.h2d``, and counted.
    Synchronous (returns once every byte has crossed)."""
    with _span("frame.h2d", bytes=int(arr.nbytes)):
        return StreamingUpload(arr, what=what).assembled()


# ---------------------------------------------------------------------------
# device -> host
# ---------------------------------------------------------------------------


class _PendingFetch:
    """Handle for an in-flight chunked d2h: ``result()`` waits for every
    chunk and returns the assembled host array."""

    __slots__ = ("_out", "_futs")

    def __init__(self, out, futs):
        self._out = out
        self._futs = futs

    def result(self) -> np.ndarray:
        for f in self._futs:
            f.result()
        return self._out


class _WholeFetch:
    """Handle for an un-chunked d2h (scalar / single-chunk / sharded)."""

    __slots__ = ("_fut",)

    def __init__(self, fut):
        self._fut = fut

    def result(self) -> np.ndarray:
        return self._fut.result()


def d2h_async(dev, what: str = "column"):
    """Start fetching a device array to host as concurrent chunks;
    returns immediately with a handle whose ``result()`` blocks. The
    caller can keep dispatching compute while the fetch drains — the
    streaming replacement for ``copy_to_host_async`` double-buffering
    (which the round-5 bench measured costing more than it overlapped)."""
    import jax

    dtype = np.dtype(dev.dtype)
    shape = tuple(dev.shape)
    multi_device = False
    try:
        multi_device = len(dev.devices()) > 1
    except Exception:
        pass
    # one knob resolution per fetch (bounds + pool width stay a
    # consistent pair; see StreamingUpload)
    chunk_bytes, streams = _link_knobs()
    bounds = (
        [(0, 0)]
        if not shape
        else _chunk_bounds(
            shape[0],
            dtype.itemsize * int(np.prod(shape[1:], initial=1)),
            chunk_bytes,
        )
    )
    if not shape or multi_device or len(bounds) == 1:
        # scalars and single-chunk payloads fetch whole; sharded arrays
        # (virtual meshes, multihost) keep the single gather — per-chunk
        # slicing of a distributed array would route every chunk through
        # a cross-device gather
        def fetch_whole():
            arr = np.asarray(dev)
            return arr, arr.nbytes

        return _WholeFetch(
            _submit(
                _get_pool(streams),
                _observed, "d2h", fetch_whole, f"frame.d2h {what}",
            )
        )
    out = np.empty(shape, dtype)

    def fetch(i, lo, hi):
        def go():
            piece = np.asarray(jax.block_until_ready(dev[lo:hi]))
            return piece, piece.nbytes

        out[lo:hi] = _observed(
            "d2h", go, f"frame.d2h {what} chunk {i}/{len(bounds)}"
        )

    pool = _get_pool(streams)
    futs = [
        _submit(pool, fetch, i, lo, hi)
        for i, (lo, hi) in enumerate(bounds)
    ]
    return _PendingFetch(out, futs)


def d2h(dev, what: str = "column") -> np.ndarray:
    """Fetch a device array to host (chunked + concurrent + retried);
    blocks until complete. Byte-identical to ``np.asarray(dev)``."""
    with _span("frame.d2h", bytes=int(np.dtype(dev.dtype).itemsize
                                      * int(np.prod(dev.shape, initial=1)))):
        return d2h_async(dev, what=what).result()
