"""Online autotuner: measure the candidate grid once, serve the winner
forever.

On first sight of a ``(surface, signature, device-kind)`` the tuner —
in ``online`` mode — runs a short **seeded micro-benchmark** over the
surface's candidate grid (flash tile sizes, transfer chunk bytes ×
streams, serve page size / prefill chunk tokens, map-rows block-row
budgets), picks the winner by **median wall**, installs it for every
subsequent dispatch of that signature, and persists it to the shared
:class:`~tensorframes_tpu.tune.store.TuneStore` so other processes —
and future ones — serve it from cache with zero trials.

Search is budgeted and model-pruned: the learned cost predictor
(:mod:`.model`) ranks the grid and only the top-K predicted candidates
are measured (never more than half the full grid), each inside
``Config.tune_budget_s`` wall-clock for the whole signature. The static
default is ALWAYS measured first, so an exhausted budget or a flaky
grid degrades to "keep the default", never to a blind winner.

Trials run inside the same envelopes as every other dispatch: each
timed attempt passes the ``tune.trial`` chaos site and runs under
``run_with_retries`` (a transient fault retries the trial; a fatal one
skips the candidate). While a tuning pass is live, every lookup —
from the trial's own thread or any other (trials may push work onto
engine threads) — is READ-ONLY: installed winners still apply, so the
trial measures the configuration steady state will run with, but no
nested search can start, so a transfer trial can upload bytes without
recursively tuning the transfer layer.

The hard contract, enforced by tests/test_tune.py: **tuning changes
which config runs, never what it computes** — consumer grids only offer
candidates whose results are byte-identical to the static default's
(see docs/tuning.md for what that constrains per surface).
"""

from __future__ import annotations

import os
import statistics
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..obs.metrics import counter as _counter
from ..obs.metrics import histogram as _histogram
from ..utils.logging import get_logger
from .model import CostModel, default_model
from .store import TuneStore, device_kind, make_key

__all__ = [
    "Tuner",
    "clear",
    "jobs_signature",
    "lookup",
    "mode",
    "pin",
    "rank_tp_layouts",
    "render_table",
    "reset",
    "snapshot",
    "tune_serve_knobs",
    "tuner",
]

logger = get_logger("tune")

_m_trials = _counter(
    "tune.trials_total",
    "Autotuner micro-benchmark candidates measured, by surface and "
    "signature",
    labels=("surface", "signature"),
)
_m_winners = _counter(
    "tune.winners_total",
    "Tuned winners installed and persisted by this process, by surface",
    labels=("surface",),
)
_m_hits = _counter(
    "tune.cache_hits_total",
    "Tuner lookups served from the persisted store or the in-process "
    "memo without running a trial, by surface",
    labels=("surface",),
)
_h_err = _histogram(
    "tune.predicted_error_ratio",
    "Cost-model honesty per measured trial: |predicted - measured| / "
    "measured wall",
)

#: re-entrancy guard: lookups made from inside a trial body must never
#: START a tuning pass (a transfer trial must not recursively tune
#: transfer). Thread-local for the common same-thread case, PLUS a
#: process-global depth for trials that spawn work onto other threads
#: (a serve-knob trial's engine steps on its own daemon thread) —
#: while ANY tuning pass is live, every lookup is read-only.
_tls = threading.local()
_tuning_depth = 0
_tuning_lock = threading.Lock()

_MODES = ("off", "cached", "online")
_warned_mode = set()


def mode() -> str:
    """The active tuning mode: ``"off"`` | ``"cached"`` | ``"online"``.
    ``TFT_TUNE=0`` in the environment is the kill switch (checked live,
    so a harness can pin it per subprocess without a restart); then
    ``Config.autotune`` (master switch) and ``Config.tune_mode``."""
    if os.environ.get("TFT_TUNE", "") == "0":
        return "off"
    from ..utils.config import get_config

    cfg = get_config()
    if not cfg.autotune:
        return "off"
    m = cfg.tune_mode
    if m not in _MODES:
        if m not in _warned_mode:
            _warned_mode.add(m)
            logger.warning(
                "unknown Config.tune_mode %r (expected one of %s); "
                "tuning disabled", m, _MODES,
            )
        return "off"
    return m


def in_trial() -> bool:
    """True while a tuning pass is live anywhere in the process: this
    thread is inside a trial body, OR any tuner is mid-search (trials
    may run work on other threads — the engine's stepping thread —
    which must not nest a second search inside the one being timed)."""
    if getattr(_tls, "in_trial", False):
        return True
    return _tuning_depth > 0


class Tuner:
    """One store-backed tuner. The module singleton (:func:`tuner`) is
    what the consumers use; tests may build private instances against
    their own store paths."""

    def __init__(
        self,
        store: Optional[TuneStore] = None,
        model: Optional[CostModel] = None,
    ):
        self.store = store if store is not None else TuneStore()
        self._model = model
        #: an explicitly-injected model (tests, operators) is
        #: authoritative for EVERY view, per-chip included
        self._model_injected = model is not None
        self._model_per_chip: Optional[CostModel] = None
        self._lock = threading.Lock()
        #: resolved winners, keyed (store path, surface, signature,
        #: device) -> (config, source). "Installed for all subsequent
        #: dispatches": once resolved, a signature is stable for this
        #: process's lifetime (path in the key keeps tests that repoint
        #: TFT_TUNE_FILE isolated without a reset)
        self._installed: Dict[tuple, tuple] = {}

    # -- model -------------------------------------------------------------

    def model(self, per_chip: bool = False) -> CostModel:
        """The tuner's cached cost model — fit once per Tuner lifetime
        (``programs.jsonl`` read + ridge fit are not per-call work).
        ``per_chip=True`` serves the multi-device-normalized fit
        (:func:`~tensorframes_tpu.tune.model.per_chip_records`) the
        tensor-parallel layout ranker uses; a model injected at
        construction is authoritative for both views."""
        with self._lock:
            if per_chip and not self._model_injected:
                if self._model_per_chip is None:
                    self._model_per_chip = default_model(per_chip=True)
                return self._model_per_chip
            if self._model is None:
                self._model = default_model()
            return self._model

    # -- resolution --------------------------------------------------------

    def lookup(
        self,
        surface: str,
        signature: str,
        default: Dict[str, Any],
        *,
        grid: Optional[Sequence[Dict[str, Any]]] = None,
        feats: Optional[Callable[[Dict[str, Any]], tuple]] = None,
        trial: Optional[Callable[[Dict[str, Any]], None]] = None,
        budget_s: Optional[float] = None,
        repeats: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Resolve the active config for ``(surface, signature)``.

        Returns ``default`` merged under the winner (winner keys win),
        so consumers always see every knob they asked about. ``off``
        mode and ``cached`` misses return ``default`` as-is; lookups
        made while a tuning pass is live are read-only (winners serve,
        no nested search starts). ``online`` misses run the measured
        search when ``trial`` is supplied and ``grid`` offers at least
        one non-default candidate;
        surfaces with no safe in-process trial (the serving knobs at
        engine init) pass ``trial=None`` and stay cache-only — their
        winners come from :func:`tune_serve_knobs` or an operator
        pin (:meth:`Tuner.pin`)."""
        m = mode()
        if m == "off":
            return dict(default)
        # a lookup made from INSIDE a trial body must never tune (that
        # would recurse), but it SHOULD see already-installed winners —
        # trials must measure the configuration steady state will run
        # with, not a defaults-only world that biases winner selection
        trialing = in_trial()
        dev = device_kind()
        key = make_key(surface, signature, dev)
        memo_key = (self.store.path(), surface, signature, dev)
        with self._lock:
            hit = self._installed.get(memo_key)
        if hit is not None:
            if not trialing:
                _m_hits.inc(surface=surface)
            return {**default, **hit[0]}
        rec = self.store.get(key)
        if rec is not None:
            cfg = dict(rec.get("config") or {})
            with self._lock:
                self._installed[memo_key] = (cfg, "store")
            if not trialing:
                _m_hits.inc(surface=surface)
            return {**default, **cfg}
        if m != "online" or trial is None or trialing:
            return dict(default)
        rest = [c for c in (grid or []) if c != default]
        if not rest:
            # nothing to choose between: measuring the lone default and
            # fsync'ing a store write on the request path buys nothing
            return dict(default)
        winner = self._tune(
            surface, signature, key, memo_key, default,
            rest, feats, trial, budget_s, repeats,
        )
        return {**default, **winner}

    # -- the measured search ----------------------------------------------

    def _tune(
        self,
        surface: str,
        signature: str,
        key: str,
        memo_key: tuple,
        default: Dict[str, Any],
        rest: List[Dict[str, Any]],
        feats,
        trial,
        budget_s: Optional[float],
        repeats: Optional[int],
    ) -> Dict[str, Any]:
        global _tuning_depth
        with _tuning_lock:
            _tuning_depth += 1
        try:
            return self._tune_locked(
                surface, signature, key, memo_key, default, rest,
                feats, trial, budget_s, repeats,
            )
        finally:
            with _tuning_lock:
                _tuning_depth -= 1

    def _tune_locked(
        self,
        surface: str,
        signature: str,
        key: str,
        memo_key: tuple,
        default: Dict[str, Any],
        rest: List[Dict[str, Any]],
        feats,
        trial,
        budget_s: Optional[float],
        repeats: Optional[int],
    ) -> Dict[str, Any]:
        from ..utils.config import get_config

        cfg = get_config()
        budget = cfg.tune_budget_s if budget_s is None else budget_s
        n_rep = max(1, cfg.tune_trials if repeats is None else repeats)
        # the static default is ALWAYS candidate 0 — the winner can
        # never be a config that measured worse than what we had
        candidates: List[Dict[str, Any]] = [dict(default)]
        predicted: Dict[int, float] = {}
        if rest:
            # the learned ranker prunes: measured trials cover only the
            # top-K predicted configs, and never more than half of the
            # full grid (default included in the count). Tiny grids
            # (<= 3 candidates) measure in full — halving a 2-entry
            # grid would mean never measuring the alternative at all
            full = len(rest) + 1
            if full <= 3:
                top_k = full
            else:
                top_k = max(1, min(int(cfg.tune_top_k), full // 2))
            if feats is not None:
                ranked = self.model().rank(rest, feats)
            else:
                ranked = [(c, float("inf")) for c in rest]
            import math

            for cand, pred in ranked[: max(0, top_k - 1)]:
                # feats-less searches (and candidates whose features
                # raised) rank at +inf — that is "no prediction", not a
                # prediction to hold the honesty histogram against
                # (observing inf would poison the scrape's _sum forever)
                if math.isfinite(pred):
                    predicted[len(candidates)] = pred
                candidates.append(cand)
            if feats is not None:
                try:
                    f, b, d = feats(dict(default))
                    predicted[0] = self.model().predict(f, b, d)
                except Exception:
                    pass
        deadline = time.monotonic() + max(0.0, float(budget))
        walls: List[Optional[float]] = []
        for i, cand in enumerate(candidates):
            if i > 0 and time.monotonic() > deadline:
                logger.info(
                    "tune %s[%s]: budget %.2fs exhausted after %d/%d "
                    "candidates", surface, signature, budget, i,
                    len(candidates),
                )
                break
            try:
                wall = self._measure(
                    surface, signature, cand, trial, n_rep, deadline
                )
            except Exception as e:
                logger.warning(
                    "tune %s[%s]: candidate %r failed (%s: %s); skipped",
                    surface, signature, cand, type(e).__name__, e,
                )
                walls.append(None)
                continue
            walls.append(wall)
            pred = predicted.get(i)
            if pred is not None and wall > 0:
                _h_err.observe(abs(pred - wall) / wall)
        measured = [
            (w, i) for i, w in enumerate(walls) if w is not None
        ]
        if not measured or walls[0] is None:
            # nothing measured cleanly — or the DEFAULT's own trial
            # failed: a candidate that was never compared against the
            # default must not become a fleet-wide winner ("degrades to
            # keep the default, never a blind winner"). Store nothing;
            # a healthier pass may tune this signature later.
            return dict(default)
        best_wall, best_i = min(measured)
        winner = dict(candidates[best_i])
        with self._lock:
            self._installed[memo_key] = (winner, "tuned")
        self.store.put(
            key, winner,
            wall_s=best_wall,
            meta={
                "trials": len(measured),
                "grid": len(candidates),
                "default_wall_s": round(walls[0], 6)
                if walls and walls[0] is not None
                else None,
                "model": self.model().source if feats is not None else None,
            },
        )
        _m_winners.inc(surface=surface)
        logger.info(
            "tune %s[%s]: winner %r at %.4fs median over %d candidate(s)",
            surface, signature, winner, best_wall, len(measured),
        )
        return winner

    def _measure(
        self,
        surface: str,
        signature: str,
        cand: Dict[str, Any],
        trial,
        repeats: int,
        deadline: float,
    ) -> float:
        """Median wall of up to ``repeats`` timed trial runs (plus one
        untimed warmup that pays any compile), each attempt behind the
        ``tune.trial`` chaos site inside its own retry window. The
        budget deadline binds BETWEEN repeats too — one slow candidate
        must not overshoot the signature budget by repeats × wall — but
        every started candidate completes at least one timed run, so a
        measurement always exists."""
        from ..utils import run_with_retries
        from ..utils.chaos import site as _chaos_site

        def attempt() -> float:
            _chaos_site("tune.trial")
            _tls.in_trial = True
            t0 = time.perf_counter()
            try:
                trial(cand)
            finally:
                _tls.in_trial = False
            return time.perf_counter() - t0

        what = f"tune.trial {surface}[{signature}]"
        run_with_retries(attempt, what=f"{what} warmup")
        walls = []
        for _ in range(repeats):
            walls.append(run_with_retries(attempt, what=what))
            if time.monotonic() > deadline:
                break
        # one trial == one measured candidate (the acceptance criterion
        # "trials-per-signature <= half of full-grid" counts candidates,
        # not repeats)
        _m_trials.inc(surface=surface, signature=signature)
        return float(statistics.median(walls))

    # -- operator verbs ----------------------------------------------------

    def pin(
        self,
        surface: str,
        signature: str,
        config: Dict[str, Any],
        device: Optional[str] = None,
    ) -> None:
        """Force a winner (no measurement): installed in-process and
        persisted, exactly as if it had been tuned. The cookbook verb
        for carrying a winner from a bench box to a fleet, and what the
        byte-identity tests use to exercise tuned paths
        deterministically."""
        dev = device if device is not None else device_kind()
        key = make_key(surface, signature, dev)
        self.store.put(key, dict(config), meta={"pinned": True})
        with self._lock:
            self._installed[
                (self.store.path(), surface, signature, dev)
            ] = (dict(config), "pinned")

    def clear(self, surface: Optional[str] = None) -> int:
        """Forget winners (one surface's, or all): cleared from the
        store AND the in-process memo, so the next lookup re-tunes."""
        removed = self.store.clear(surface)
        with self._lock:
            if surface is None:
                self._installed.clear()
            else:
                for k in [
                    k for k in self._installed if k[1] == surface
                ]:
                    del self._installed[k]
        return removed

    def snapshot(self) -> List[Dict[str, Any]]:
        """Every resolved-or-stored winner, for ``/statusz`` and
        ``explain(analyze=True)``: in-process installations first
        (source ``tuned``/``pinned``/``store``), then store entries not
        yet consulted by this process (source ``persisted``)."""
        out: List[Dict[str, Any]] = []
        with self._lock:
            installed = dict(self._installed)
        seen = set()
        for (path, surface, signature, dev), (cfg, src) in sorted(
            installed.items()
        ):
            out.append(
                {
                    "surface": surface,
                    "signature": signature,
                    "device": dev,
                    "config": dict(cfg),
                    "source": src,
                }
            )
            seen.add((surface, signature, dev))
        try:
            for key, rec in sorted(self.store.entries().items()):
                ident = (
                    rec.get("surface"), rec.get("signature"),
                    rec.get("device"),
                )
                if ident in seen:
                    continue
                out.append(
                    {
                        "surface": rec.get("surface"),
                        "signature": rec.get("signature"),
                        "device": rec.get("device"),
                        "config": dict(rec.get("config") or {}),
                        "source": "persisted",
                        "wall_s": rec.get("wall_s"),
                    }
                )
        except Exception:
            pass
        return out


# ---------------------------------------------------------------------------
# module singleton + convenience verbs
# ---------------------------------------------------------------------------

_singleton_lock = threading.Lock()
_singleton: Optional[Tuner] = None


def tuner() -> Tuner:
    global _singleton
    with _singleton_lock:
        if _singleton is None:
            _singleton = Tuner()
        return _singleton


def reset() -> None:
    """Drop the singleton (test isolation: fresh memo, fresh model,
    store path re-resolved)."""
    global _singleton
    with _singleton_lock:
        _singleton = None


def lookup(surface, signature, default, **kw) -> Dict[str, Any]:
    return tuner().lookup(surface, signature, default, **kw)


def pin(surface, signature, config, device=None) -> None:
    tuner().pin(surface, signature, config, device)


def clear(surface: Optional[str] = None) -> int:
    return tuner().clear(surface)


def snapshot() -> List[Dict[str, Any]]:
    return tuner().snapshot()


def render_table() -> str:
    """Plain-text tuned-config table for ``explain(analyze=True)``."""
    rows = snapshot()
    lines = [f"== Tuned configs == (mode={mode()})"]
    if not rows:
        lines.append(" (no tuned winners installed or stored)")
        return "\n".join(lines)
    for r in rows:
        cfg = " ".join(f"{k}={v}" for k, v in sorted(r["config"].items()))
        lines.append(
            f" {r['surface']}[{r['signature']}] @{r['device']} "
            f"{cfg} ({r['source']})"
        )
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# the serving-knob search
# ---------------------------------------------------------------------------


def jobs_signature() -> str:
    """The distributed-job knob signature. Lease TTL trades liveness
    (how fast a dead worker's blocks reclaim) against safety margin for
    slow-but-alive workers — a property of the HOST (filesystem
    latency, scheduler jitter), not of any workload shape, so one row
    per device kind (the store keys on device separately) is the right
    granularity."""
    return "host=v1"


def rank_tp_layouts(
    model,
    *,
    max_seq_len: int,
    max_slots: int = 8,
    degrees: Sequence[int] = (1, 2, 4, 8),
    page_size: Optional[int] = None,
    persist: bool = True,
) -> List[Dict[str, Any]]:
    """Rank tensor-parallel serving layouts for one model shape with
    the learned cost model — the ``serve.tp_layout`` surface.

    No engines are built: per candidate TP degree the decode step's
    PER-CHIP features are derived analytically from the sharding plan
    (``serve/tp.py``) — the paged attention read's bytes and FLOPs
    scale 1/N (KV pool sharded on heads), dense projections stay
    replicated, and the per-step weight + context gathers add their
    ``(N-1)/N`` bytes — and
    :meth:`~tensorframes_tpu.tune.model.CostModel.predict` turns them
    into a predicted step wall. The model is ridge-fit from the
    observatory's persisted ``programs.jsonl`` FLOP/byte/wall records
    when enough exist — INCLUDING multi-device rows: per-replica
    TP-named programs carry ``meta.tp_degree``, and
    :func:`~tensorframes_tpu.tune.model.per_chip_records` normalizes
    their global estimates to the per-chip unit the candidate features
    use, so mixed-degree serving history fits one coherent model —
    with the analytic roofline prior as the thin-data fallback.

    Returns ``[{"tp": N, "predicted_step_s": ..., "flops": ...,
    "bytes": ...}, ...]`` cheapest-predicted first, and (with
    ``persist`` and tuning not ``off``) pins the winner under
    ``serve.tp_layout`` so benches, ``/statusz``, and operators read
    one store row instead of re-deriving it."""
    import numpy as np

    from ..models.transformer import _kv_heads
    from ..ops.attention import paged_page_size_hint

    params = getattr(model, "params", model)
    n_heads = params["n_heads"]
    d_model = int(np.shape(params["embed"])[1])
    vocab = int(np.shape(params["embed"])[0])
    hd = d_model // n_heads
    n_kv = _kv_heads(params["blocks"][0], d_model, n_heads)
    n_layers = len(params["blocks"])
    blk0 = params["blocks"][0]
    d_ff = int(np.shape(blk0["up"])[1]) if "up" in blk0 else 0
    kv_d = n_kv * hd
    dtype = np.dtype(getattr(params["embed"], "dtype", np.float32))
    itemsize = dtype.itemsize
    ps = page_size or max(
        1, min(int(paged_page_size_hint(dtype, hd)), max_seq_len)
    )
    t = -(-int(max_seq_len) // ps) * ps  # gather span per slot
    s = int(max_slots)
    w_layer = (
        d_model * (d_model + 2 * kv_d)  # qkv
        + d_model * d_model             # proj
        + 2 * d_model * d_ff            # up + down
    ) * itemsize

    def feats(cand: Dict[str, Any]):
        n = int(cand["tp"])
        if n < 1 or n_kv % n or n_heads % n or (d_ff and d_ff % n):
            raise ValueError(f"tp={n} does not divide the model")
        kloc = n_kv // n
        group = n_heads // n_kv
        # paged read per chip: both gathered copies cross HBM, local
        # heads only; scores + weighted sum per local head
        att_bytes = 2.0 * n_layers * s * t * kloc * hd * itemsize
        att_flops = 4.0 * n_layers * s * t * kloc * group * hd
        # dense walk replicated at full shape (weights re-read per step)
        dense_flops = 2.0 * s * (
            n_layers * (
                d_model * (d_model + 2 * kv_d)
                + d_model * d_model
                + 2 * d_model * d_ff
            )
            + d_model * vocab
        )
        dense_bytes = float(
            n_layers * w_layer + vocab * d_model * itemsize
        )
        # the byte-identity plan's collectives: weight shards gathered
        # to full + one per-layer context gather, (n-1)/n received
        frac = (n - 1) / n
        gather_bytes = frac * (
            n_layers * w_layer + n_layers * s * d_model * itemsize
        )
        return (
            att_flops + dense_flops,
            att_bytes + dense_bytes + gather_bytes,
            1.0,
        )

    # the layout winner depends on MODEL SIZE, not just the serving
    # signature (a shallow toy model and a deep production model with
    # the same dtype/head_dim/seq bucket want different degrees) —
    # extend the key with every feature the prediction reads so they
    # never overwrite each other's store row
    sig = (
        serve_signature(dtype, hd, max_seq_len)
        + f"|layers={n_layers}|dff={d_ff}|kv={n_kv}|V={vocab}"
        + f"|slots={s}"
    )
    t_ = tuner()
    cands = [{"tp": int(n)} for n in degrees]
    # fit over the FULL programs.jsonl history including multi-device
    # records: per-replica TP-named step programs carry meta.tp_degree,
    # and per_chip_records folds their global FLOP/byte estimates down
    # to the per-chip unit these candidate features are computed in —
    # multi-device serving rounds sharpen the ranking instead of
    # skewing the fitted rates. Served through the tuner's model cache
    # (one fit per Tuner lifetime; an injected model stays honored).
    ranked = t_.model(per_chip=True).rank(cands, feats)
    out = []
    for cand, pred in ranked:
        f, b, _ = (
            feats(cand) if np.isfinite(pred) else (None, None, None)
        )
        out.append(
            {
                "tp": cand["tp"],
                "predicted_step_s": pred,
                "flops": f,
                "bytes": b,
            }
        )
    if persist and mode() != "off" and out and np.isfinite(
        out[0]["predicted_step_s"]
    ):
        t_.pin("serve.tp_layout", sig, {"tp": out[0]["tp"]})
    return out


def serve_signature(dtype, head_dim: int, max_seq_len: int) -> str:
    """The serving-knob signature: pool dtype kind, head dim, and the
    pow2 sequence bucket — what the page-size/prefill winners key on
    (shared by engine init and :func:`tune_serve_knobs` so they resolve
    the same store rows)."""
    import numpy as np

    kind = np.dtype(dtype).name
    bucket = 1 << max(4, int(max_seq_len - 1).bit_length())
    return f"dtype={kind}|hd={head_dim}|L={bucket}"


def tune_serve_knobs(
    model,
    *,
    max_seq_len: int,
    prompt_len: Optional[int] = None,
    max_new_tokens: int = 16,
    max_slots: int = 4,
    page_sizes: Optional[Sequence[int]] = None,
    prefill_chunks: Optional[Sequence[int]] = None,
    page_slots: Optional[Sequence[Dict[str, int]]] = None,
    draft_params=None,
    draft_lens: Optional[Sequence[int]] = None,
    seed: int = 0,
    repeats: int = 1,
    budget_s: Optional[float] = None,
) -> Dict[str, Dict[str, Any]]:
    """Measure and persist the serving knobs — page size, prefill
    chunk tokens, the pool geometry (``serve.page_slots``: decode
    slots × pages per slot), and (with ``draft_params``) the
    speculative draft length (``serve.draft_len``) — for one model
    shape.

    Engine init consults the store only (building engines inside an
    engine's own constructor is not a sane trial), so the measured
    search for these surfaces lives here: each candidate runs a seeded
    prompt batch through a throwaway
    :class:`~tensorframes_tpu.serve.GenerationEngine`'s prefill +
    decode, and the median-wall winner is persisted for every later
    engine with this signature (operators and warm-up scripts
    call this; byte-identity of the streams across every candidate is
    a serve-suite invariant — page size, chunking, slot count, pool
    size, and draft length never change emitted tokens, only
    scheduling). Throwaway engines are MEMOIZED per engine-level
    config within each surface's grid — candidates that differ only in
    scheduler-side knobs (and repeat trials of one candidate) reuse
    one engine instead of rebuilding per trial, which keeps the
    measured search inside ``tune_budget_s`` on the larger
    speculation-enabled grid and keeps construction wall out of the
    measured steady state; the memo is released between surfaces so at
    most one grid's device pools are ever resident.

    ``page_slots`` candidates are ``{"slots": S, "pages_per_slot": P}``
    dicts (default: the full-coverage geometry plus a half-pool
    oversubscription and a double-slot batch). ``draft_lens``
    candidates (default ``2, 4, 8``) each serve the trial batch
    speculatively; the median verify-inclusive wall — which is exactly
    where the measured acceptance rate and per-dispatch verify cost
    land (the ``serve.spec_acceptance_rate`` gauge and
    ``serve.verify_seconds`` histogram export the series live) —
    decides k. Engines built with the DEFAULT knobs pick winners up
    from the store; explicit arguments always win (docs/tuning.md).

    Returns ``{"serve.page_size": winner, "serve.prefill_chunk":
    winner, "serve.page_slots": winner[, "serve.draft_len": winner]}``.
    """
    import numpy as np

    from ..ops.attention import paged_page_size_hint

    if mode() != "online":
        # lookups below would be read-only: nothing gets measured or
        # persisted, and a defaults-shaped return would masquerade as a
        # tuned result — say so loudly instead of no-op'ing silently
        logger.warning(
            "tune_serve_knobs called with tuning mode %r — the measured "
            "search needs set_config(tune_mode=\"online\") (or "
            "autotune=True / TFT_TUNE unset); returning store/default "
            "resolutions without measuring", mode(),
        )
    if max_new_tokens >= max_seq_len:
        raise ValueError(
            f"max_seq_len ({max_seq_len}) must exceed max_new_tokens "
            f"({max_new_tokens}) — the trial prompts need at least one "
            f"token of room"
        )
    params = getattr(model, "params", model)
    n_heads = params["n_heads"]
    d_model = int(np.shape(params["embed"])[1])
    hd = d_model // n_heads
    dtype = np.dtype(getattr(params["embed"], "dtype", np.float32))
    sig = serve_signature(dtype, hd, max_seq_len)
    plen = prompt_len or max(8, max_seq_len // 2)
    plen = max(1, min(plen, max_seq_len - max_new_tokens))
    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(1, 32, size=plen).astype(np.int32).tolist()
        for _ in range(max_slots)
    ]

    # ONE throwaway engine at a time, keyed by its engine-level config
    # (the satellite fix: a candidate's warmup + repeat trials used to
    # rebuild the engine — pool, weight copy, jit wrappers — per call,
    # blowing the budget on construction wall). A trial whose config
    # matches the resident engine's reuses it; a config change drops
    # the old engine FIRST, so peak device residency stays one pool's
    # footprint — exactly the old per-trial teardown's — instead of a
    # whole grid's pools pinned at once.
    resident: Dict[str, Any] = {"key": None, "eng": None}

    def run_engine(
        page_size: int,
        chunk: int,
        slots: Optional[int] = None,
        pages_per_slot: Optional[int] = None,
        draft_k: int = 0,
    ) -> None:
        from ..serve import GenerationEngine, pages_needed

        slots = int(slots or max_slots)
        num_pages = None
        if pages_per_slot is not None:
            # the feasibility floor: the pool must hold one full-length
            # request even when the candidate oversubscribes
            num_pages = max(
                pages_needed(max_seq_len, int(page_size)),
                slots * int(pages_per_slot),
            )
        key = (int(page_size), int(chunk), slots, num_pages, int(draft_k))
        if resident["key"] != key:
            resident["key"] = resident["eng"] = None  # release first
            kw: Dict[str, Any] = {}
            if draft_k:
                kw = dict(
                    draft_params=draft_params, draft_len=int(draft_k)
                )
            resident["eng"] = GenerationEngine(
                model,
                max_slots=slots,
                page_size=int(page_size),
                num_pages=num_pages,
                max_seq_len=max_seq_len,
                queue_capacity=max(slots, max_slots),
                prefill_chunk_tokens=int(chunk),
                **kw,
            )
            resident["key"] = key
        eng = resident["eng"]
        # drive synchronously (no thread start/stop per trial); the
        # batch drains fully, so the reused engine is idle between
        # trials
        handles = [eng.submit(p, max_new_tokens) for p in prompts]
        eng.run_until_idle()
        for h in handles:
            h.result(timeout=300)

    hint = max(1, min(int(paged_page_size_hint(dtype, hd)), max_seq_len))
    if page_sizes is None:
        page_sizes = sorted({16, max(1, hint // 2), hint})
    if prefill_chunks is None:
        prefill_chunks = sorted({0, max(8, plen // 2)})
    t = tuner()
    ps_winner = t.lookup(
        "serve.page_size", sig, {"page_size": hint},
        grid=[{"page_size": int(p)} for p in page_sizes],
        trial=lambda cand: run_engine(
            cand["page_size"], 0
        ),
        budget_s=budget_s, repeats=repeats,
    )
    pc_winner = t.lookup(
        "serve.prefill_chunk", sig, {"tokens": 0},
        grid=[{"tokens": int(c)} for c in prefill_chunks],
        trial=lambda cand: run_engine(
            int(ps_winner.get("page_size", hint)), cand["tokens"]
        ),
        budget_s=budget_s, repeats=repeats,
    )
    best_ps = int(ps_winner.get("page_size", hint))
    best_pc = int(pc_winner.get("tokens", 0))
    from ..serve import pages_needed as _pages_needed

    full_pps = _pages_needed(max_seq_len, best_ps)
    geo_default = {"slots": int(max_slots), "pages_per_slot": full_pps}
    if page_slots is None:
        page_slots = [
            geo_default,
            # oversubscribe the pool: half the pages, lean on
            # preempt-and-requeue (wins when live tokens << max length)
            {
                "slots": int(max_slots),
                "pages_per_slot": max(1, full_pps // 2),
            },
            # widen the decode batch instead
            {"slots": int(max_slots) * 2, "pages_per_slot": full_pps},
        ]
    geo_winner = t.lookup(
        "serve.page_slots", sig, geo_default,
        grid=[
            {"slots": int(c["slots"]),
             "pages_per_slot": int(c["pages_per_slot"])}
            for c in page_slots
        ],
        trial=lambda cand: run_engine(
            best_ps, best_pc,
            slots=cand["slots"], pages_per_slot=cand["pages_per_slot"],
        ),
        budget_s=budget_s, repeats=repeats,
    )
    out = {
        "serve.page_size": ps_winner,
        "serve.prefill_chunk": pc_winner,
        "serve.page_slots": geo_winner,
    }
    if draft_params is not None:
        # the speculative draft-length search: each candidate k serves
        # the same batch through draft + batched verify; the measured
        # wall folds the acceptance rate and per-dispatch verify cost
        # together, which is the trade k exists to balance
        if draft_lens is None:
            draft_lens = (2, 4, 8)
        cands = sorted(
            {
                max(1, min(int(k), max_seq_len - 1))
                for k in draft_lens
            }
        )
        out["serve.draft_len"] = t.lookup(
            "serve.draft_len", sig, {"k": 4},
            grid=[{"k": k} for k in cands],
            trial=lambda cand: run_engine(
                best_ps, best_pc, draft_k=cand["k"]
            ),
            budget_s=budget_s, repeats=repeats,
        )
    resident["key"] = resident["eng"] = None  # release the last engine
    return out
