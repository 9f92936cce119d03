"""Telemetry export: periodic per-process snapshots for fleet federation.

Every observability surface so far is per-process, but the fleet
(:mod:`tensorframes_tpu.serve.fleet`), the distributed job workers
(:mod:`tensorframes_tpu.engine.dist_jobs`), and any driver each run in
their OWN process — one pane of glass needs their registries in one
place. This module is the write side of that plane: each process with a
live sampler periodically serializes its metric registry
(:func:`~.metrics.snapshot`) and the raw tier of its time-series store
into ``<telemetry_dir>/<proc-id>.json``. The read side
(:mod:`.aggregate`) merges whatever snapshot files it finds.

Design points, all borrowed from the repo's existing durable surfaces:

- **atomic rename** — a snapshot is written to a ``.tmp-<pid>`` sibling
  and ``os.replace``'d into place, so readers only ever see whole files
  (the tune store and job journal write the same way);
- **schema version** — the payload carries ``schema``; the aggregator
  skips files from a different schema instead of guessing;
- **mtime staleness** — liveness is the FILE's mtime, not anything in
  the payload: a kill -9'd process stops refreshing its file, and the
  aggregator flags it ``stale`` after ``Config.telemetry_stale_after_s``
  while keeping its last counters visible (crashed workers' totals
  still count);
- **rides the sampler tick** — :func:`autoexport` is called from
  ``timeseries.sample_once`` exactly like ``programs.autopersist``,
  throttled to ``Config.obs_export_interval_s``; no extra thread;
- **kill-switch parity** — under ``TFT_OBS=0`` /
  ``Config(observability=False)`` nothing touches the disk.

The module also owns process **identity**: a ``build.info``-style gauge
(proc id, pid, role, package version, device kind — value 1.0, the
Prometheus ``build_info`` idiom) that federation uses to label merged
series and ``/statusz`` shows. Roles: ``serve-replica`` (a
``ScoringServer`` with an engine), ``job-worker``
(``dist_jobs.run_worker``), ``driver`` (everything else, the default).
"""

from __future__ import annotations

import json
import os
import re
import socket
import threading
import time
from typing import Any, Dict, List, Optional

from ..utils.logging import get_logger
from .metrics import counter as _counter, enabled, gauge as _gauge, registry

__all__ = [
    "SCHEMA_VERSION",
    "autoexport",
    "export_snapshot",
    "identity",
    "proc_id",
    "set_identity",
    "telemetry_dir",
]

logger = get_logger("obs.export")

#: bump on any incompatible snapshot-layout change; the aggregator
#: skips files whose schema differs (never guesses)
SCHEMA_VERSION = 1

#: newest tier-0 points exported per series — bounds snapshot size; the
#: fleet view is an operational window, not an archive (each process
#: keeps its own full tiered history locally)
_EXPORT_POINTS = 256

_m_exports = _counter(
    "obs.telemetry_exports_total",
    "Telemetry snapshots written to the fleet telemetry directory",
)
_g_identity = _gauge(
    "build.info",
    "Process identity (value is always 1 for the current role): proc "
    "id, pid, role serve-replica|job-worker|driver, package version, "
    "device kind — what federation labels merged series with",
    labels=("proc", "pid", "role", "version", "device"),
)

_lock = threading.Lock()
_role = "driver"
#: label set the identity gauge currently publishes at 1.0 (None before
#: the first set_identity)
_published: Optional[Dict[str, str]] = None
_last_export = 0.0  # monotonic, throttles autoexport


def proc_id() -> str:
    """Stable-ish process identity for the snapshot filename and the
    identity gauge: ``$TFT_PROC_ID`` when set (fleet replicas and job
    workers get deterministic ids that way), else ``<host>-<pid>``."""
    explicit = os.environ.get("TFT_PROC_ID", "")
    if explicit:
        return explicit
    return f"{socket.gethostname()}-{os.getpid()}"


def _package_version() -> str:
    try:
        from .. import __version__

        return str(__version__)
    except Exception:
        return "unknown"


def _device_kind() -> str:
    """Device kind of the default backend when this process has
    initialized one, else ``"unknown"`` — a process that holds no engine
    (a router, a score-only driver) must not initialize a backend, and
    with it take the chip, just to label itself. A serve replica's
    engine has placed its weights before the server starts, so its
    label is the real kind."""
    from .programs import initialized_device

    dev = initialized_device()
    return "unknown" if dev is None else str(dev.device_kind)


def set_identity(role: str) -> Dict[str, Any]:
    """Declare this process's role and (re)publish the identity gauge.

    Idempotent; when any label changed (the role, or the device kind
    once a backend came up) the former series is zeroed first (the
    gauge has no per-series removal, and two series at 1.0 would
    double-count the process in any fleet sum)."""
    global _role, _published
    with _lock:
        _role = str(role)
        labels = dict(
            proc=proc_id(), pid=str(os.getpid()), role=_role,
            version=_package_version(), device=_device_kind(),
        )
        if _published is not None and _published != labels:
            _g_identity.set(0.0, **_published)
        _g_identity.set(1.0, **labels)
        _published = labels
    return identity()


def identity() -> Dict[str, Any]:
    """This process's identity labels — the ``/statusz`` ``identity``
    block and the per-proc header federation attaches to merged data."""
    return {
        "proc": proc_id(),
        "pid": os.getpid(),
        "role": _role,
        "version": _package_version(),
        "device": _device_kind(),
        "host": socket.gethostname(),
    }


def telemetry_dir() -> str:
    """The shared snapshot directory: ``Config.telemetry_dir``, else
    ``$TFT_TELEMETRY_DIR``, else ``""`` (export disabled)."""
    from ..utils.config import get_config

    return get_config().telemetry_dir or os.environ.get(
        "TFT_TELEMETRY_DIR", ""
    )


def _snapshot_payload(now: float) -> Dict[str, Any]:
    from . import timeseries as _ts

    series: Dict[str, List[List[float]]] = {}
    st = _ts.store()
    for name in st.names():
        pts = st.points(name, 0)[-_EXPORT_POINTS:]
        if pts:
            series[name] = [[round(ts, 3), v] for ts, v in pts]
    return {
        "schema": SCHEMA_VERSION,
        "proc": proc_id(),
        "pid": os.getpid(),
        "ts_unix": round(now, 3),
        "identity": identity(),
        "metrics": registry().snapshot(),
        "series": series,
        "last_tick_ts": _ts.last_tick_ts(),
    }


def export_snapshot(
    dir: Optional[str] = None, now: Optional[float] = None
) -> Optional[str]:
    """Write this process's snapshot; returns the path, or ``None``
    when export is disabled (no directory / kill switch) or the write
    failed (logged — telemetry must never take down what it observes)."""
    if not enabled():
        return None
    target_dir = dir or telemetry_dir()
    if not target_dir:
        return None
    ts = time.time() if now is None else now
    try:
        payload = _snapshot_payload(ts)
        os.makedirs(target_dir, exist_ok=True)
        fname = re.sub(r"[^A-Za-z0-9_.-]", "_", payload["proc"]) + ".json"
        path = os.path.join(target_dir, fname)
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(payload, f, default=str)
        os.replace(tmp, path)
        _m_exports.inc()
        return path
    except Exception:
        logger.warning("telemetry export failed", exc_info=True)
        return None


def autoexport(now: Optional[float] = None) -> Optional[str]:
    """Throttled :func:`export_snapshot` for the sampler tick: at most
    one write per ``Config.obs_export_interval_s`` (re-read each call,
    so retunes apply live). No-op when export is disabled."""
    global _last_export
    if not enabled() or not telemetry_dir():
        return None
    from ..utils.config import get_config

    interval = get_config().obs_export_interval_s
    mono = time.monotonic()
    if mono - _last_export < max(0.0, interval):
        return None
    _last_export = mono
    return export_snapshot(now=now)
