"""Profiling & timing hooks.

The reference ships none (SURVEY §5: timing is manual prints in ``ignore``d
suites). Here the jax profiler is first-class: ``trace()`` captures a
Perfetto/TensorBoard-compatible device trace; ``Timer`` wraps wall-clock
sections with device synchronization so numbers mean what they say.
"""

from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

__all__ = ["trace", "Timer", "block_until_ready", "device_stamp"]


def device_stamp() -> Dict[str, object]:
    """``platform`` / ``device_kind`` / ``device_count`` as jax reports
    them: stamped on every line a benchmark prints, so that no number
    can be read without the device it was taken on."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def block_until_ready(tree) -> None:
    """Synchronize: wait for every array in a pytree (async dispatch means
    wall-clock without this measures dispatch, not compute)."""
    import jax

    jax.block_until_ready(tree)


@contextlib.contextmanager
def trace(log_dir: str, host_tracer_level: int = 2):
    """Capture a device+host trace viewable in Perfetto / TensorBoard::

        with tft.utils.profiling.trace("/tmp/trace"):
            df2.collect()

    While the capture is open, observability spans
    (:func:`tensorframes_tpu.obs.span`) forward to
    ``jax.profiler.TraceAnnotation`` and appear as named slices in the
    resulting trace; outside a capture that forwarding is skipped (it
    costs real microseconds per span with nobody listening). Direct
    ``jax.profiler.start_trace`` users can opt in with
    ``tft.obs.set_annotations(True)``.
    """
    import jax

    from ..obs.tracing import set_annotations

    try:
        jax.profiler.start_trace(log_dir, host_tracer_level=host_tracer_level)
    except TypeError:
        # newer jax moved tracer options off the start_trace signature
        jax.profiler.start_trace(log_dir)
    set_annotations(True)
    try:
        yield
    finally:
        set_annotations(False)
        jax.profiler.stop_trace()


class Timer:
    """Accumulating section timer with device sync.

    >>> t = Timer()
    >>> with t.section("score"):
    ...     out = engine_call()
    >>> t.report()

    ``publish=True`` additionally streams every section duration into the
    observability registry (``profiling.timer_seconds{section=...}``
    histogram, :mod:`tensorframes_tpu.obs`), so ad-hoc Timer numbers show
    up on the same scrape as the engine/serving metrics. The default
    stays registry-free — existing callers are unaffected.
    """

    def __init__(self, publish: bool = False):
        self.totals: Dict[str, float] = {}
        self.counts: Dict[str, int] = {}
        self.mins: Dict[str, float] = {}
        self.maxs: Dict[str, float] = {}
        self._publish = publish

    @contextlib.contextmanager
    def section(self, name: str, sync=None):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if sync is not None:
                block_until_ready(sync)
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.counts[name] = self.counts.get(name, 0) + 1
            if name not in self.mins or dt < self.mins[name]:
                self.mins[name] = dt
            if name not in self.maxs or dt > self.maxs[name]:
                self.maxs[name] = dt
            if self._publish:
                _timer_seconds().observe(dt, section=name)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        """Per-section stats as a plain (JSON-able) dict:
        ``{section: {"total_s", "count", "min_s", "max_s", "mean_s"}}``."""
        return {
            name: {
                "total_s": self.totals[name],
                "count": self.counts[name],
                "min_s": self.mins[name],
                "max_s": self.maxs[name],
                "mean_s": self.totals[name] / self.counts[name],
            }
            for name in self.totals
        }

    def report(self) -> str:
        lines = []
        for name in sorted(self.totals, key=self.totals.get, reverse=True):
            n = self.counts[name]
            tot = self.totals[name]
            lines.append(
                f"{name}: {tot * 1e3:.2f} ms total, {n} calls, "
                f"{tot / n * 1e3:.3f} ms/call"
            )
        return "\n".join(lines)


def _timer_seconds():
    """The shared ``Timer`` histogram (created on first publishing Timer —
    importing this module must not touch the registry)."""
    global _timer_hist
    if _timer_hist is None:
        from ..obs.metrics import histogram

        _timer_hist = histogram(
            "profiling.timer_seconds",
            "Timer section durations (seconds), by section",
            labels=("section",),
        )
    return _timer_hist


_timer_hist = None
