"""Drift detection: EWMA baselines + tolerance bands over stored series.

The SLO monitors (:mod:`.slo`) answer "is this value ACCEPTABLE?"
against a bound an operator declared. Drift asks a different question:
"is this value still what it USED to be?" — no absolute bound, just a
learned baseline and a tolerance band around it. That is the trigger
feed ROADMAP item 4's re-tuning loop consumes ("the observatory records
drift nobody acts on"): an autotuner winner measured under last week's
traffic is stale exactly when the series it was tuned against drifts.

A :class:`Detector` watches one stored series (or every series under a
prefix) in the time-series store (:mod:`.timeseries`):

- the **baseline** is a deterministic EWMA over in-band samples,
  seeded by the first ``min_samples`` points (warmup: no banding);
- a sample is **out-of-band** when it falls outside
  ``baseline ± max(tolerance * |baseline|, min_band)``;
- ``trigger`` CONSECUTIVE out-of-band samples flip the series to
  **drifted** (``obs.drift_active{series}=1``, a
  ``drift``-ring flight event, a ``logger.warning``); ``trigger``
  consecutive in-band samples flip it back (recovery event, gauge 0);
- while any sample is out-of-band the baseline is FROZEN — a detector
  that kept averaging the shifted values would quietly adopt the drift
  as the new normal and report recovery without any recovery happening.
  The baseline resumes adapting only from in-band samples.

Everything is deterministic: same points in, same transitions out (the
drift e2e test replays a synthetic shift through ``sample_once`` ticks).
Evaluation rides the sampler tick next to SLO evaluation; the canned
default detectors cover the serving signals whose shifts most often
mean "re-tune or investigate": host→device p50, speculative acceptance
rate, inter-token p99, and the preemption rate. Cookbook:
``docs/observability.md`` ("Drift detection") and ``docs/tuning.md``.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..utils.logging import get_logger
from . import flight as _flight
from .metrics import counter as _counter, enabled, gauge as _gauge

__all__ = [
    "Detector",
    "DriftMonitor",
    "default_detectors",
    "drift_report",
    "h2d_p50",
    "inter_token_p99",
    "monitor",
    "preemption_rate",
    "spec_acceptance",
]

logger = get_logger("obs.drift")

_m_shifts = _counter(
    "obs.drift_shifts_total",
    "Drift transitions (in-band -> drifted), by stored series",
    labels=("series",),
)
_g_active = _gauge(
    "obs.drift_active",
    "Whether the stored series is currently outside its EWMA baseline "
    "tolerance band (1) or tracking it (0)",
    labels=("series",),
)


@dataclasses.dataclass(frozen=True)
class Detector:
    """One drift rule over one stored series (or a name prefix).

    ``tolerance`` is RELATIVE (0.5 = ±50% of the baseline);
    ``min_band`` is the absolute band floor — essential for series that
    idle near zero (a preemption rate of 0.0 would otherwise make ANY
    preemption "drift"). ``match="prefix"`` resolves every stored
    series starting with ``series`` each tick, so labeled series
    (``failures.preemptions_total{op=serve}.rate``) are covered without
    naming each label combination."""

    name: str
    series: str
    tolerance: float = 0.5
    alpha: float = 0.1
    min_samples: int = 5
    trigger: int = 3
    min_band: float = 0.0
    match: str = "exact"

    def __post_init__(self):
        if self.match not in ("exact", "prefix"):
            raise ValueError(
                f"detector match must be 'exact' or 'prefix'; got "
                f"{self.match!r}"
            )
        if not 0.0 < self.alpha <= 1.0:
            raise ValueError(
                f"detector alpha must be in (0, 1]; got {self.alpha}"
            )
        if self.tolerance <= 0.0:
            raise ValueError(
                f"detector tolerance must be > 0; got {self.tolerance}"
            )
        if self.min_samples < 1 or self.trigger < 1:
            raise ValueError(
                "detector min_samples and trigger must be >= 1"
            )

    def band(self, baseline: float) -> float:
        return max(self.tolerance * abs(baseline), self.min_band)


class _State:
    """Per resolved-series detector state."""

    __slots__ = ("baseline", "n", "out_streak", "in_streak", "active",
                 "last_ts", "last_value", "since")

    def __init__(self):
        self.baseline: Optional[float] = None
        self.n = 0  # in-band samples folded into the baseline
        self.out_streak = 0
        self.in_streak = 0
        self.active = False
        self.last_ts = float("-inf")
        self.last_value: Optional[float] = None
        self.since: Optional[float] = None


class DriftMonitor:
    """Detector set + per-series drift state machine, evaluated per
    sampler tick. ``monitor()`` is the process-wide default (canned
    detectors preinstalled)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._detectors: Dict[str, Detector] = {}
        #: (detector name, resolved series) -> state
        self._states: Dict[Tuple[str, str], _State] = {}

    def add(self, detector: Detector) -> Detector:
        with self._lock:
            self._detectors[detector.name] = detector
        return detector

    def remove(self, name: str) -> None:
        with self._lock:
            self._detectors.pop(name, None)
            gone = [k for k in self._states if k[0] == name]
            for k in gone:
                self._states.pop(k)
        for _, series in gone:
            _g_active.set(0.0, series=series)

    def detectors(self) -> List[Detector]:
        with self._lock:
            return list(self._detectors.values())

    def _resolve(self, det: Detector, store) -> List[str]:
        if det.match == "exact":
            return [det.series]
        return [n for n in store.names() if n.startswith(det.series)]

    # -- evaluation --------------------------------------------------------

    def evaluate(self, store, now: Optional[float] = None) -> None:
        """One pass: feed every detector the points that landed since
        its last evaluation (by timestamp — deterministic under replay).
        Called by ``timeseries.sample_once`` after the tick's points
        land."""
        if not enabled():
            return
        ts_now = time.time() if now is None else now
        for det in self.detectors():
            for series in self._resolve(det, store):
                with self._lock:
                    st = self._states.setdefault(
                        (det.name, series), _State()
                    )
                for pt_ts, value in store.points(series, 0):
                    if pt_ts <= st.last_ts:
                        continue
                    st.last_ts = pt_ts
                    self._feed(det, series, st, pt_ts, value)

    def _feed(
        self, det: Detector, series: str, st: _State,
        ts: float, value: float,
    ) -> None:
        st.last_value = value
        if st.baseline is None:
            st.baseline = value
            st.n = 1
            return
        if st.n < det.min_samples:
            # warmup: the baseline absorbs everything, no banding yet
            st.baseline += det.alpha * (value - st.baseline)
            st.n += 1
            return
        out = abs(value - st.baseline) > det.band(st.baseline)
        if out:
            st.out_streak += 1
            st.in_streak = 0
            # baseline frozen: adapting to out-of-band samples would
            # adopt the shift as the new normal (see module doc)
        else:
            st.in_streak += 1
            st.out_streak = 0
            st.baseline += det.alpha * (value - st.baseline)
        if out and not st.active and st.out_streak >= det.trigger:
            st.active = True
            st.since = ts
            _m_shifts.inc(series=series)
            _g_active.set(1.0, series=series)
            delta = value - st.baseline
            logger.warning(
                "drift %r: series %s shifted to %g (baseline %g, "
                "band ±%g)",
                det.name, series, value, st.baseline,
                det.band(st.baseline),
            )
            _flight.record(
                "drift", "shift",
                detector=det.name, series=series, value=value,
                baseline=round(st.baseline, 6),
                band=round(det.band(st.baseline), 6),
                delta=round(delta, 6),
            )
        elif not out and st.active and st.in_streak >= det.trigger:
            st.active = False
            dur = ts - st.since if st.since is not None else None
            st.since = None
            _g_active.set(0.0, series=series)
            logger.warning(
                "drift %r: series %s recovered (drifted %.1fs)",
                det.name, series, dur or 0.0,
            )
            _flight.record(
                "drift", "recovered",
                detector=det.name, series=series, value=value,
                baseline=round(st.baseline, 6),
                drifted_s=None if dur is None else round(dur, 3),
            )

    # -- reporting ---------------------------------------------------------

    def report(self) -> List[Dict[str, Any]]:
        """One row per (detector, resolved series) that has seen data —
        what ``drift_report()``, the ``/statusz`` ``drift`` table, and
        the re-tune loop read. ``delta`` is last value minus baseline
        (signed: which WAY it drifted)."""
        out = []
        with self._lock:
            dets = dict(self._detectors)
            items = list(self._states.items())
        for (dname, series), st in items:
            det = dets.get(dname)
            if det is None:
                continue
            delta = (
                None
                if st.last_value is None or st.baseline is None
                else st.last_value - st.baseline
            )
            out.append({
                "detector": dname,
                "series": series,
                "active": st.active,
                "since": st.since,
                "baseline": st.baseline,
                "last_value": st.last_value,
                "delta": delta,
                "band": (
                    None if st.baseline is None
                    else det.band(st.baseline)
                ),
                "samples": st.n,
            })
        out.sort(key=lambda r: (not r["active"], r["series"]))
        return out

    def any_active(self) -> bool:
        with self._lock:
            return any(s.active for s in self._states.values())

    def reset(self) -> None:
        with self._lock:
            keys = list(self._states)
            self._states.clear()
        for _, series in keys:
            _g_active.set(0.0, series=series)


# -- canned detectors ---------------------------------------------------------


def h2d_p50(**kw) -> Detector:
    """Host→device transfer p50 — a shifted link (another host, a congested
    fabric) invalidates the transfer chunk/stream tuning."""
    return Detector(
        name="h2d_p50", series="frame.h2d_seconds.p50", **kw,
    )


def spec_acceptance(**kw) -> Detector:
    """Speculative-decoding acceptance rate, any engine — the draft
    length was tuned against THIS rate; a drifted workload wants a new
    ``spec_k``."""
    kw.setdefault("match", "prefix")
    kw.setdefault("tolerance", 0.25)
    return Detector(
        name="spec_acceptance", series="serve.spec_acceptance_rate",
        **kw,
    )


def inter_token_p99(**kw) -> Detector:
    """Decode-cadence p99 — the serving latency signal users feel."""
    return Detector(
        name="inter_token_p99", series="serve.inter_token_seconds.p99",
        **kw,
    )


def preemption_rate(**kw) -> Detector:
    """Preemptions/second, any op label. ``min_band`` floors the band:
    the healthy baseline is ~0/s, and a relative band around zero would
    flag the first preemption ever as drift."""
    kw.setdefault("match", "prefix")
    kw.setdefault("min_band", 0.5)
    return Detector(
        name="preemption_rate",
        series="failures.preemptions_total", **kw,
    )


def default_detectors() -> List[Detector]:
    return [h2d_p50(), spec_acceptance(), inter_token_p99(),
            preemption_rate()]


_monitor = DriftMonitor()
for _det in default_detectors():
    _monitor.add(_det)
del _det


def monitor() -> DriftMonitor:
    """The process-wide default monitor (what the sampler tick
    evaluates and ``/statusz`` reports)."""
    return _monitor


def drift_report() -> List[Dict[str, Any]]:
    """Convenience: :meth:`DriftMonitor.report` on the default
    monitor — the queryable answer to "what drifted, and by how
    much?"."""
    return _monitor.report()
