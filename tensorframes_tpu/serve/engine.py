"""GenerationEngine: compiled prefill/decode steps over the paged cache.

The serving counterpart of
:func:`~tensorframes_tpu.models.transformer_generate`: where that
function compiles one scan program per (batch shape, decode structure),
this engine compiles at most THREE programs for a whole serving
lifetime —

- **prefill** ``[1, max_seq_len]``: one right-padded prompt through the
  batched causal pass (:func:`~tensorframes_tpu.models.transformer_prefill`),
  its per-layer k/v scattered into the sequence's pages, the first token
  sampled from the last real position's logits;
- **decode** ``[max_slots]``: one token per occupied slot through the
  shared per-token step (:func:`~tensorframes_tpu.models.transformer_step`)
  with attention delegated to the paged read,
  :func:`~tensorframes_tpu.ops.ragged_paged_attention` (one Pallas
  kernel a layer that copies each slot's live pages);
- **prefill-chunk** ``[1, chunk]`` (dispatched only when chunked
  prefill or a shared-prefix cache hit needs it): one mid-prompt span
  through :func:`~tensorframes_tpu.models.transformer_prefill_chunk`,
  attending to the pages already written — long prompts prefill one
  chunk per step, interleaved with decode, and prefix-cache hits resume
  after the cached span;
- with SPECULATIVE DECODING on (``draft_params=``), two more — a
  **draft** program proposing up to k tokens per slot from the draft
  model's own KV page group, and a **verify** ``[max_slots, k + 1]``
  program (the mid-sequence sibling of the prefill chunk,
  :func:`~tensorframes_tpu.models.transformer_verify_chunk`) scoring
  every proposal against the target's paged KV in one dispatch, with
  exact-match acceptance keeping streams byte-identical to solo decode
  (the plain decode program stops dispatching; the budget becomes
  <= 5). See docs/serving_llm.md "Speculative decoding".

Every input shape is static (page tables are fixed-width, idle slots
point at the trash page), so slot turnover, ragged lengths, and
greedy/sampled mixes all reuse the same two executables — the
no-recompile property the ROADMAP's heavy-traffic target needs. Sampling
parameters (temperature / seed / top_p) are per-request TRACED inputs;
``top_k`` is engine-level static structure, as in ``generate``.

Requests stream through :class:`~.scheduler.Scheduler` (bounded
admission, continuous batching, preempt-and-requeue on page-pool
exhaustion); each :meth:`submit` returns a
:class:`~.scheduler.GenerationHandle` whose iterator yields tokens as
steps complete. Observability: queue depth / batch occupancy /
pages-in-use gauges, time-to-first-token and inter-token latency
histograms, all on the PR-1 registry (``docs/observability.md``).

**Deferred delivery.** Between two step programs the stepping thread
does only what the next program's arguments need: the token joins
``generated``, a finished slot and its pages go back. What else a step
owes — the token and the end mark handed to the handle (which wakes the
stream's thread), counters, histograms, ``timings``, the cost record,
the gauges — is queued in order (:meth:`GenerationEngine._owe`) and run
once the next program is issued, while the device works and before the
thread waits for it (``after_issue`` of ``obs/programs.py``). When no
program follows — the loop goes idle, ``step()`` / ``run_until_idle()``
return, a request is failed, preempted, expired or moved — it is
delivered then and there, in a ``serve.deliver`` span, so no end mark
overtakes a token and nothing waits while the thread sleeps.

**Supervision** (``docs/fault_tolerance.md``): step failures are
classified against the ``utils/failures.py`` taxonomy — transient
dispatch errors retry with bounded backoff inside the step, device OOM
recovers by ``defragment()`` + preempt-youngest (recompute-style, so
streams never replay or lose tokens), and anything fatal fails every
in-flight handle promptly with the real error and marks the engine
unhealthy (``submit`` sheds with :class:`EngineUnhealthyError`;
``GET /healthz`` reports it). :meth:`restart` rebuilds device state
from host-side scheduler progress — emitted bytes stay identical and
no step program recompiles. Per-request deadlines
(``submit(deadline=...)``) are swept every step; expired requests fail
with :class:`~tensorframes_tpu.utils.failures.DeadlineExceededError`.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..models.transformer import (
    _kv_heads,
    device_tree,
    filter_logits,
    model_spec,
    static_entries,
    transformer_prefill,
    transformer_prefill_chunk,
    transformer_step,
    transformer_verify_chunk,
)
from ..obs import (
    SpanChain as _SpanChain,
    current_trace as _current_trace,
    flight as _flight,
    programs as _programs,
    requests as _obs_requests,
    span as _span,
    use_trace as _use_trace,
)
from ..obs.metrics import (
    counter as _counter,
    gauge as _gauge,
    histogram as _histogram,
)
from ..ops.attention import decode_read_positions, ragged_paged_attention
from ..utils import chaos as _chaos
from ..utils.config import get_config
from ..utils.failures import (
    DeadlineExceededError,
    PagePoolExhausted,
    TenantThrottledError,
    first_line as _first_line,
    is_oom,
    is_transient,
    run_with_retries,
)
from ..utils.logging import get_logger
from . import tenancy as _tenancy
from .kv_pages import (
    CacheLayout,
    PagePool,
    PrefixCache,
    SequencePages,
    pages_needed,
    read_pages,
    split_heads,
    write_prompt,
    write_rows,
)
from .scheduler import (
    GenerationHandle,
    GenRequest,
    QueueFullError,
    Scheduler,
    _Active,
)

__all__ = ["EngineUnhealthyError", "GenerationEngine"]

logger = get_logger("serve.engine")

_m_queue_depth = _gauge(
    "serve.queue_depth", "Generation requests waiting for a decode slot"
)
_m_active_slots = _gauge(
    "serve.active_slots",
    "Decode-batch occupancy (sequences currently holding a slot)",
)
_m_pages_in_use = _gauge(
    "serve.pages_in_use", "KV pages currently owned by live sequences"
)
_m_pages_capacity = _gauge(
    "serve.pages_capacity", "Total KV pages in the pool"
)
_m_ttft = _histogram(
    "serve.ttft_seconds",
    "Time to first token: submit to first emission (seconds)",
)
_m_itl = _histogram(
    "serve.inter_token_seconds",
    "Inter-token latency per stream: gap between emissions (seconds)",
)
_m_tokens = _counter(
    "serve.tokens_total", "Tokens emitted across all generation streams"
)
_m_delivered = _counter(
    "serve.tokens_delivered_total",
    "Tokens handed to their streams' handles (delivery is deferred: "
    "serve/engine.py)",
)
_m_delivered_under_program = _counter(
    "serve.tokens_delivered_under_program_total",
    "Tokens handed to their handles while the next step program was "
    "in flight, the host's delivery hidden behind the device's work; "
    "over serve.tokens_delivered_total, how often deferral engages",
)
_m_requests = _counter(
    "serve.requests_total",
    "Generation requests by terminal status",
    labels=("status",),
)
_m_restarts = _counter(
    "serve.engine_restarts_total",
    "GenerationEngine.restart() recoveries (device state rebuilt from "
    "host-side scheduler progress)",
)
_m_deadline_expired = _counter(
    "serve.deadline_expired_total",
    "Requests evicted because their deadline passed (queued or "
    "mid-generation)",
)
_m_handles_failed = _counter(
    "serve.handles_failed_total",
    "Generation handles closed with an error, by classified reason",
    labels=("reason",),
)
_m_prefix_lookups = _counter(
    "serve.prefix_cache_lookups_total",
    "Admissions that consulted the shared-prefix KV cache",
)
_m_prefix_hits = _counter(
    "serve.prefix_cache_hits_total",
    "Admissions whose prompt prefix was served from cached KV pages "
    "(the prefill skipped the shared span)",
)
_m_prefix_tokens_saved = _counter(
    "serve.prefix_cache_tokens_saved_total",
    "Prompt positions whose prefill was skipped via cached KV pages",
)
_m_pages_shared = _gauge(
    "serve.kv_pages_shared",
    "KV pages currently named by more than one reference (prefix-cache "
    "dedup across sequences)",
)
_m_prefill_chunks = _counter(
    "serve.prefill_chunks_total",
    "Prefill chunks dispatched (chunked prefill and prefix-cache "
    "resume both count)",
)
_m_tp_degree = _gauge(
    "serve.tp_degree",
    "Tensor-parallel degree of the engine's step programs (chips per "
    "replica; 1 = solo single-chip serving), per engine",
    labels=("engine",),
)
_m_spec_proposed = _counter(
    "serve.spec_proposed_total",
    "Speculative draft tokens proposed to the verify pass "
    "(docs/serving_llm.md 'Speculative decoding')",
)
_m_spec_accepted = _counter(
    "serve.spec_accepted_total",
    "Speculative draft tokens accepted by exact match against the "
    "target's own sampled token (the byte-identity contract)",
)
_m_spec_accept_rate = _gauge(
    "serve.spec_acceptance_rate",
    "Cumulative speculative acceptance per engine: accepted / proposed "
    "draft tokens (the draft-length controller's signal; absent until "
    "the first proposal). Labeled like serve.tp_degree — fleets run "
    "several speculative engines in one process, and an unlabeled "
    "gauge would flap between replicas last-writer-wins",
    labels=("engine",),
)
_m_verify_s = _histogram(
    "serve.verify_seconds",
    "Wall seconds per batched multi-token verify dispatch (the "
    "[max_slots, k+1] step program)",
)
_m_recomputed = _counter(
    "serve.recomputed_tokens_total",
    "Prompt tokens put through a prefill program a second time because "
    "a preemption had released the pages that held them (the cost of "
    "failures.preemptions_total's events)",
)
_m_kind_pages = _gauge(
    "serve.pages_in_use_by_kind",
    "KV pool pages the live sequences' cache kinds hold (\"full\": layers "
    "that keep every position; \"window\": layers that keep the last "
    "window's)",
    labels=("kind",),
)
_m_window_released = _counter(
    "serve.window_pages_released_total",
    "Pool pages window layers gave back before their sequence ended: "
    "every position in them was more than the window behind the next "
    "query",
)
_m_window_allocated = _counter(
    "serve.window_pages_allocated_total",
    "Pool pages taken for window layers (what "
    "serve.window_pages_released_total is a share of)",
)
_m_tokens_routed = _counter(
    "moe.tokens_routed_total",
    "Token-expert pairs the serving step programs put through an expert "
    "layer (tokens x experts per token x expert layers; padding rows of "
    "a prefill chunk not counted)",
)
_m_collective_s = _counter(
    "serve.collective_seconds",
    "ESTIMATED wall seconds spent in cross-chip collectives by the "
    "tensor-parallel step programs (per-step estimate from a one-time "
    "micro-measurement of the step's gather pattern at engine init — "
    "the real gathers overlap compute inside the compiled step)",
)


#: a model whose sequences can be longer than this is prefilled in
#: chunks of ``_AUTO_CHUNK_TOKENS`` unless the caller, the Config or the
#: tune store chose otherwise: the one-pass program is as wide as the
#: longest sequence and scores it densely
_AUTO_CHUNK_ABOVE = 2048
_AUTO_CHUNK_TOKENS = 1024

#: the decode programs' paged read of a layer and its account of what it
#: copies: ``(read, positions)``, ``read`` called as
#: :func:`~tensorframes_tpu.ops.ragged_paged_attention` is, and
#: ``positions(lengths, rows, page_size, page_bytes)`` the key positions
#: it copies a layer for a batch's ``lengths`` (idle slots at one key)
#: over tables of ``rows`` pages. The tests put the gather oracle here.
_DECODE_READ = (ragged_paged_attention, decode_read_positions)

_engine_seq_lock = threading.Lock()
_engine_seq = 0


def _next_engine_seq() -> int:
    global _engine_seq
    with _engine_seq_lock:
        _engine_seq += 1
        return _engine_seq


class EngineUnhealthyError(RuntimeError):
    """The engine is shedding load: a terminal stepping failure (or a
    wedged stop) marked it unhealthy, and submissions fail fast until
    :meth:`GenerationEngine.restart`. The HTTP endpoint maps this to
    503 + ``Retry-After`` (``interop/serving.py``)."""


def _fail_reason(e: BaseException) -> str:
    """Bounded reason label for ``serve.handles_failed_total``."""
    if isinstance(e, DeadlineExceededError):
        return "deadline"
    if is_oom(e):
        return "oom"
    if is_transient(e):
        return "transient_exhausted"
    return "fatal"


def _span_attend(state, ptabs, pos, pos_c, counts, ps, trash, mp,
                 max_len):
    """The shared ``[S, C]`` paged scatter+read attend of the
    speculative programs — the verify step and the draft's phase-1
    chunk use this ONE builder (the TP verify keeps its own body: head
    slicing and the context gather differ materially): scatter the
    whole span's k/v (positions past ``counts`` or the sequence bound
    land in the trash page), then read each position's visible history
    through the page table under the chunk family's mask. One
    implementation so the mask/scatter the byte-identity contract
    rides on cannot drift between the two programs. ``state`` is the
    caller's two-element ``[k_pool, v_pool]`` list, threaded through
    layer by layer."""
    import jax
    import jax.numpy as jnp

    from ..ops.attention import _NEG_BIG

    slots, c = pos.shape
    offs = jnp.arange(c)

    def attend(li, q, k, v):
        valid = (offs[None, :] < counts[:, None]) & (pos < max_len)
        page = jnp.where(
            valid,
            jnp.take_along_axis(ptabs, pos_c // ps, axis=1),
            trash,
        )
        off = pos_c % ps
        with jax.named_scope("kv_write"):
            state[0] = write_rows(state[0], li, page, off, k)
            state[1] = write_rows(state[1], li, page, off, v)
        n_kv, hd = k.shape[2], k.shape[3]
        t = mp * ps
        with jax.named_scope("kv_read"):
            kg = split_heads(read_pages(state[0], li, ptabs), hd)
            vg = split_heads(read_pages(state[1], li, ptabs), hd)
        scale = 1.0 / float(np.sqrt(hd))
        s = jnp.einsum("sckgd,stkd->sckgt", q, kg) * scale
        visible = jnp.arange(t)[None, None, :] <= pos_c[:, :, None]
        s = jnp.where(visible[:, :, None, None, :], s, _NEG_BIG)
        att = jnp.einsum(
            "sckgt,stkd->sckgd", jax.nn.softmax(s, axis=-1), vg
        )
        return att.reshape(slots, c, n_kv * q.shape[3] * hd)

    return attend


def _sample_slot_tokens(logits, positions, temps, seeds, top_ps, top_k):
    """THE per-row token rule, shared by the speculative draft and
    verify programs: greedy argmax, or seeded categorical after
    temperature + top-k/top-p filtering with the per-step key folded at
    the row's ABSOLUTE position — line-for-line the decode program's
    sampling (:meth:`GenerationEngine._decode_impl`), which is the
    byte-identity contract: a verify row at position ``p`` draws
    exactly the token solo decode would draw at ``p``. Traced inside
    the compiled steps. ``logits`` [N, V]; everything else [N]."""
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(logits, axis=-1)
    keys = jax.vmap(
        lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
    )(seeds, positions)
    scaled = logits / jnp.maximum(temps[:, None], 1e-6)
    filt = filter_logits(scaled, top_k=top_k, top_p=top_ps[:, None])
    sampled = jax.vmap(jax.random.categorical)(keys, filt)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


class GenerationEngine:
    """Continuous-batching generation over a :class:`PagePool`.

    >>> eng = GenerationEngine(lm, max_slots=8, page_size=16)
    >>> h = eng.submit(prompt_ids, max_new_tokens=64)
    >>> eng.start()              # background stepping (or drive .step())
    >>> for tok in h: ...        # stream
    >>> eng.stop()

    ``model`` is a :class:`~tensorframes_tpu.models.TransformerLM` or its
    params dict. ``max_seq_len`` bounds prompt + generation per request
    (default: the model's positional table). ``num_pages`` defaults to
    full-length pages for every slot (no preemption pressure); size it
    SMALLER to oversubscribe memory and lean on preempt-and-requeue.
    ``top_k`` is engine-static; temperature / ``top_p`` / seed are
    per-request.

    Perf knobs (``None`` falls back to the matching ``Config`` field;
    docs/serving_llm.md):

    - ``page_size``: KV page granularity. Default (``None``) is the
      flash sweep's best key tile ``ops.paged_page_size_hint`` clamped
      to ``max_seq_len``, or the
      autotuner's ``serve.page_size`` winner when one is stored
      (docs/tuning.md); an explicit argument always wins, and
      ``/healthz`` reports the chosen size;

    - ``prefill_chunk_tokens``: > 0 prefills prompts longer than this in
      chunks of this size, one per step, interleaved with decode — a
      long prompt no longer stalls the whole batch for its full prefill;
    - ``prefix_cache``: share identical page-aligned prompt prefixes
      (system prompts, few-shot templates) as refcounted KV pages with
      copy-on-write on in-page divergence; repeat prefixes skip their
      prefill entirely;
    - ``draft_params``: a small DRAFT model of the same transformer
      family (``TransformerLM`` or params dict; same vocabulary and a
      positional table covering ``max_seq_len`` —
      :func:`~tensorframes_tpu.models.init_draft_transformer` derives
      one) turns on SPECULATIVE DECODING: each step the draft proposes
      up to ``draft_len`` tokens from its own KV page group in the
      pool, and ONE batched ``[max_slots, draft_len + 1]`` verify
      program scores every proposal against the target's paged KV.
      Acceptance is EXACT-MATCH against the target's own sampled token
      (greedy or seeded), so emitted streams stay byte-identical to
      non-speculative decode; rejected speculative KV rolls back by
      length bookkeeping alone. Adds two compiled step programs
      (draft + verify; the plain decode program never dispatches while
      speculation is on, so ``num_step_programs`` stays <= 5 — <= 3
      with speculation off). See docs/serving_llm.md "Speculative
      decoding";
    - ``draft_len``: the compiled STATIC draft length k (default:
      the autotuner's ``serve.draft_len`` winner, else 4). A per-slot
      adaptive controller shrinks the effective k on cold
      (low-acceptance) slots and grows it back on hot ones, bounded by
      this static k;
    - ``mesh``: a 1-D :class:`jax.sharding.Mesh` makes THIS replica
      span its chips (tensor parallelism, ``serve/tp.py``): the same
      three step programs compile as ``jit(shard_map(...))`` — weights
      sharded at rest and gathered bit-exactly inside the step, the KV
      pool and the paged attention walk sharded along KV heads — so
      decode streams stay byte-identical to solo at every TP degree
      while per-chip weight/KV memory scales ~1/N. ``num_pages``
      becomes the PER-CHIP page budget (the pool holds
      ``num_pages × N`` total — aggregate KV capacity scales with the
      mesh). Requires ``n_heads``/``n_kv_heads``/``d_ff`` divisible by
      the mesh size; dense (non-MoE) blocks only
      (docs/serving_llm.md "Tensor parallelism"). A ONE-device mesh
      shards nothing: it pins an ordinary solo replica to that chip
      (weights, pool and programs), which is how a
      :class:`~tensorframes_tpu.serve.Fleet` spreads its replicas over
      the chips of a host.

    A third compiled program (the ``[1, chunk]`` prefill-chunk step)
    exists only when chunked prefill or the prefix cache dispatches it:
    ``num_step_programs`` stays <= 2 with both off, <= 3 otherwise.
    Speculative decoding (``draft_params=``) adds the draft and verify
    programs — and retires the plain decode dispatch while it is on —
    so the budget becomes <= 5."""

    def __init__(
        self,
        model,
        *,
        max_slots: Optional[int] = None,
        page_size: Optional[int] = None,
        num_pages: Optional[int] = None,
        max_seq_len: Optional[int] = None,
        queue_capacity: int = 64,
        top_k: int = 0,
        eos_id: Optional[int] = None,
        moe_top_k: Optional[int] = None,
        prefill_chunk_tokens: Optional[int] = None,
        prefix_cache: Optional[bool] = None,
        draft_params=None,
        draft_len: Optional[int] = None,
        name: Optional[str] = None,
        mesh=None,
    ):
        import jax

        params = getattr(model, "params", model)
        #: the model description (``models.transformer.ModelSpec``): the
        #: tree's own where it carries one, else what a GPT-2-style
        #: tree's weights imply
        spec = self.spec = model_spec(params)
        n_heads, n_kv, hd = spec.n_heads, spec.n_kv_heads, spec.head_dim
        d_model = int(np.shape(params["embed"])[1])
        model_max = spec.max_len
        if moe_top_k is None:
            moe_top_k = spec.experts_per_token or 1
        windowed = "window" in spec.kinds
        if windowed and (mesh is not None or draft_params is not None):
            raise ValueError(
                "a model with window layers runs on one chip without "
                "speculation: the tensor-parallel and the draft/verify "
                "programs have no window mask and no second cache kind"
            )
        self.max_seq_len = int(max_seq_len or model_max)
        if self.max_seq_len > model_max:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"longest sequence ({model_max})"
            )
        # dtype only — never np.asarray the embed table (that would
        # d2h-copy the whole embedding just to read one attribute)
        kv_dtype = np.dtype(getattr(params["embed"], "dtype", np.float32))
        #: the ``serve.page_slots`` winner for this model signature when
        #: one is stored (None otherwise) — pool GEOMETRY: decode slots
        #: × pages per slot. Cached-mode-safe like every init-time knob:
        #: consulted only where the caller passed no explicit value
        #: (slot count and pool size change scheduling, never streams —
        #: the serve-suite byte-identity).
        self._tuned_geometry = self._tuned_page_slots(kv_dtype, hd)
        if max_slots is None:
            max_slots = 8
            if self._tuned_geometry is not None:
                max_slots = max(
                    1, int(self._tuned_geometry.get("slots", 8))
                )
        self.max_slots = int(max_slots)
        #: the chip a solo replica is pinned to: weights, KV pool and
        #: step programs live there. None = jax's default device. A
        #: one-device mesh pins; there is nothing to shard, so such a
        #: replica runs the plain programs, not the shard_map ones —
        #: this is how a Fleet puts replica i on chip i mod n.
        self._device = None
        if mesh is not None and mesh.devices.size == 1:
            self._device = mesh.devices.flat[0]
            mesh = None
        #: tensor parallelism (docs/serving_llm.md "Tensor parallelism",
        #: serve/tp.py): a 1-D jax Mesh makes THIS replica span its
        #: chips — weights sharded at rest, the KV pool and paged
        #: attention sharded along KV heads, decode streams
        #: byte-identical to solo at every degree
        self.mesh = mesh
        self.tp_degree = 1
        self._tp_axis: Optional[str] = None
        if mesh is not None:
            from .tp import validate_tp_mesh

            blk0 = params["blocks"][0]
            d_ff = (
                int(np.shape(blk0["up"])[1]) if "up" in blk0 else 0
            )
            self._tp_axis = validate_tp_mesh(mesh, n_heads, n_kv, d_ff)
            self.tp_degree = int(mesh.devices.size)
        if page_size is None:
            # the measured-best default (ISSUE 13 satellite): one page IS
            # the fused read's key tile, so the flash sweep's block_k —
            # ``paged_page_size_hint`` — is the default, clamped to the
            # sequence bound; the autotuner's ``serve.page_size`` winner
            # (the measured search is tune.tune_serve_knobs) overrides
            # the hint. An EXPLICIT argument wins over both and is
            # taken verbatim (no clamp — callers pinning a page size
            # keep exactly the pool layout they asked for).
            # /healthz reports whatever was chosen.
            page_size = self._default_page_size(kv_dtype, hd)
        self.page_size = max(1, int(page_size))
        self._max_pages = pages_needed(self.max_seq_len, self.page_size)
        if num_pages is None:
            pps = self._max_pages
            if self._tuned_geometry is not None:
                # the tuned pool geometry may oversubscribe (fewer pages
                # per slot than full coverage — preempt-and-requeue is
                # the relief valve), never undercut feasibility: the
                # pool always holds at least one full-length request.
                # Like an explicit ``num_pages``, a tuned budget is a
                # PER-CHIP quantity, so it scales by the TP degree —
                # only the untuned full-coverage default (which can
                # never preempt) skips the multiply.
                pps = max(
                    1,
                    min(
                        int(
                            self._tuned_geometry.get(
                                "pages_per_slot", pps
                            )
                        ),
                        self._max_pages,
                    ),
                )
                num_pages = max(
                    self._max_pages,
                    self.max_slots * pps * self.tp_degree,
                )
            else:
                num_pages = self.max_slots * pps
        elif self.tp_degree > 1:
            # ``num_pages`` is the PER-CHIP page budget: a page spans
            # the mesh's shards (1/N of its solo bytes per chip), so a
            # fixed per-chip HBM budget holds N× the pages — aggregate
            # KV capacity scales with the TP degree, which is what lets
            # a workload that exhausts TP=1 admission serve
            # preemption-free at TP=2 (``serve.pages_capacity`` reports
            # the scaled total)
            num_pages = int(num_pages) * self.tp_degree
        kv_sharding = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            from .tp import tp_kv_specs

            kv_sharding = NamedSharding(mesh, tp_kv_specs(self._tp_axis))
        elif self._device is not None:
            kv_sharding = jax.sharding.SingleDeviceSharding(self._device)
        cfg = get_config()
        if prefill_chunk_tokens is None:
            prefill_chunk_tokens = cfg.serve_prefill_chunk_tokens
            if prefill_chunk_tokens == 0:
                # neither argument nor config asked for chunking: take
                # the autotuner's winner when one is stored (cache-only
                # at init — the measured search for the serving knobs
                # lives in tune.tune_serve_knobs; chunking never changes
                # emitted tokens, the serve-suite byte-identity)
                prefill_chunk_tokens = self._tuned_prefill_chunk(
                    kv_dtype, hd
                )
        if prefill_chunk_tokens < 0:
            raise ValueError(
                f"prefill_chunk_tokens must be >= 0; got "
                f"{prefill_chunk_tokens}"
            )
        #: the engine chose chunking itself, from the model's longest
        #: sequence: EVERY prompt then goes through the chunk program
        #: (the one-pass program would be ``max_seq_len`` wide and is
        #: never built), the chunk and decode programs read the pool
        #: bounded by what is live, and :meth:`start` builds both
        #: before any traffic
        self._long = prefill_chunk_tokens == 0 and (
            self.max_seq_len > _AUTO_CHUNK_ABOVE
        )
        if self._long:
            prefill_chunk_tokens = _AUTO_CHUNK_TOKENS
        if windowed:
            # window layers are prefilled chunk by chunk, whole pages
            # at a time, releasing pages behind the window as they go
            self._long = True
            ps = self.page_size
            prefill_chunk_tokens = ps * max(
                1,
                min(
                    prefill_chunk_tokens or _AUTO_CHUNK_TOKENS,
                    self.max_seq_len,
                ) // ps,
            )
        self.prefill_chunk_tokens = int(prefill_chunk_tokens)
        #: the chunk program's STATIC width: the chunk size when chunked
        #: prefill is on, else the full prompt row (the prefix-cache
        #: resume path then runs as one "chunk" mid-sequence)
        self._chunk_c = self.prefill_chunk_tokens or self.max_seq_len
        n_layers = len(params["blocks"])
        #: cache kinds (``serve/kv_pages.py``): None for a model whose
        #: layers all keep every position — one kind, a pool page as
        #: deep as the model, today's pool to the letter
        self.layout: Optional[CacheLayout] = None
        if windowed:
            self.layout = CacheLayout.of(
                spec.layer_types, spec.window, self.page_size,
                lookahead=self._chunk_c,
            )
        #: how the live-read programs find a layer in the pool: the
        #: cache kinds, or the one kind of a model whose layers are alike
        self._pool_layout = self.layout or CacheLayout.of(
            ("full",) * n_layers, 0, self.page_size
        )
        self.pool = PagePool(
            n_layers=self._pool_layout.depth,
            n_kv_heads=n_kv,
            head_dim=hd,
            num_pages=num_pages,
            page_size=self.page_size,
            # a GPT-2-style tree keeps the float32 pool it always had; a
            # tree with a description states its cache with its weights
            dtype=kv_dtype if "spec" in params else None,
            sharding=kv_sharding,
        )
        if prefix_cache is None:
            prefix_cache = cfg.serve_prefix_cache and not windowed
        if prefix_cache and windowed:
            raise ValueError(
                "the prefix cache shares pages of one cache kind; a model "
                "with window layers runs without it"
            )
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.pool) if prefix_cache else None
        )
        self.scheduler = Scheduler(
            self.pool, self.max_slots, queue_capacity, self.max_seq_len,
            prefix_cache=self.prefix_cache, layout=self.layout,
        )
        self.top_k = int(top_k)
        self.eos_id = eos_id
        self._d_model = d_model
        # -- speculative decoding: the draft model's config + KV page
        # group (docs/serving_llm.md "Speculative decoding") -----------
        #: compiled static draft length k (0 = speculation off)
        self.draft_len = 0
        self._draft_dev = None
        self._draft_group = None
        self._draft_d_model = 0
        #: cumulative host-side speculation stats (health()/statusz)
        self._spec_proposed = 0
        self._spec_accepted = 0
        if draft_params is not None:
            dp = getattr(draft_params, "params", draft_params)
            d_vocab = int(np.shape(dp["embed"])[0])
            vocab = int(np.shape(params["embed"])[0])
            if d_vocab != vocab:
                raise ValueError(
                    f"draft model vocabulary ({d_vocab}) must match the "
                    f"target's ({vocab}): proposals are target token ids"
                )
            if int(np.shape(dp["pos"])[0]) < self.max_seq_len:
                raise ValueError(
                    f"draft model's positional table "
                    f"({int(np.shape(dp['pos'])[0])}) is shorter than "
                    f"max_seq_len ({self.max_seq_len})"
                )
            if draft_len is None:
                draft_len = self._tuned_draft_len(kv_dtype, hd)
            if int(draft_len) < 1:
                raise ValueError(
                    f"draft_len must be >= 1 with a draft model; got "
                    f"{draft_len} (omit draft_params to disable "
                    f"speculation)"
                )
            self.draft_len = min(int(draft_len), self.max_seq_len - 1)
            d_heads = dp["n_heads"]
            self._draft_d_model = int(np.shape(dp["embed"])[1])
            d_hd = self._draft_d_model // d_heads
            d_n_kv = _kv_heads(
                dp["blocks"][0], self._draft_d_model, d_heads
            )
            # the draft's own KV page group: parallel page arrays in the
            # SAME pool index space (one page list covers both models —
            # alloc/free/defrag/prefix-sharing stay single-sourced).
            # Replicated even under a TP mesh: the draft is small and
            # its proposals never touch emitted bytes, so sharding it
            # buys nothing the verify contract needs.
            self._draft_group = self.pool.add_group(
                "draft",
                n_layers=len(dp["blocks"]),
                n_kv_heads=d_n_kv,
                head_dim=d_hd,
                dtype=np.dtype(
                    getattr(dp["embed"], "dtype", np.float32)
                ),
                sharding=kv_sharding if mesh is None else None,
            )
            self._draft_host = {
                k: v for k, v in dp.items() if k != "n_heads"
            }
            self._draft_n_heads = d_heads
        # weights enter the compiled steps as an ARGUMENT (swap-safe, like
        # TransformerLM.generate); one device copy held for the lifetime.
        # Under tensor parallelism the copy is SHARDED AT REST per
        # transformer_tp_specs (qkv/up on output columns, proj/down on
        # hidden rows — per-chip weight HBM scales ~1/N); the step
        # programs gather shards back to bit-exact full weights inside
        # the mesh (serve/tp.py).
        #: token-expert pairs one token makes through the whole model
        self._pairs_per_token = (
            spec.experts_per_token
            * sum("moe" in b for b in params["blocks"])
            if spec.mlp == "gated_experts" else 0
        )
        #: window pages counted into the counters so far
        self._window_counted = [0, 0]
        self._host_params = params
        host = device_tree(params)
        #: the tree's non-array entries, merged back into the weight
        #: argument inside every step program
        self._static = static_entries(params)
        self._tp_param_specs = None
        if mesh is not None:
            from jax.sharding import NamedSharding

            from ..models.transformer import transformer_tp_specs

            self._tp_param_specs = transformer_tp_specs(
                host, self._tp_axis
            )
            self._params_dev = jax.device_put(
                host,
                jax.tree.map(
                    lambda s: NamedSharding(mesh, s),
                    self._tp_param_specs,
                    is_leaf=lambda x: not isinstance(x, (dict, list)),
                ),
            )
        else:
            self._params_dev = jax.device_put(host, self._device)
        #: display name for telemetry — the fleet passes its replica
        #: names so the cost registry and /statusz attribute each step
        #: program to its replica; the sequence keeps registry KEYS
        #: unique even when two fleets reuse a replica name
        seq = _next_engine_seq()
        self.name = name if name is not None else f"eng{seq}"
        # the step programs update the KV pool in place: pool.k / pool.v
        # are donated on every backend, so whoever holds a pre-step
        # reference to them holds a deleted array
        donate = (1, 2)
        # each step program registers in the per-program cost registry
        # (obs/programs.py): compile wall-time + FLOP/byte estimates at
        # first dispatch, invocation count + cumulative dispatch time
        # after. sync=True is semantics-neutral here — every dispatch
        # site already block_until_ready()s inside its retry window, so
        # the wrapper's sync just moves the wait inside the timing.
        # after_issue: between the issue and that wait the stepping
        # thread delivers what the steps so far owe (_deliver_hosted).
        mmeta = dict(
            max_slots=self.max_slots, page_size=self.page_size,
            max_seq_len=self.max_seq_len, d_model=d_model,
            tp_degree=self.tp_degree,
        )
        if mesh is not None:
            # the SAME three step programs, as jit(shard_map(...)) over
            # the mesh (serve/tp.py): identical call signatures, shapes,
            # and — the serving contract — identical emitted bytes
            from . import tp as _tp

            ax = self._tp_axis
            prefill_fn = _tp.tp_prefill_impl(
                self, mesh, ax, n_heads, moe_top_k
            )
            decode_fn = _tp.tp_decode_impl(
                self, mesh, ax, n_heads, moe_top_k
            )
            chunk_fn = _tp.tp_prefill_chunk_impl(
                self, mesh, ax, n_heads, moe_top_k
            )
            verify_fn = (
                _tp.tp_verify_impl(self, mesh, ax, n_heads, moe_top_k)
                if self.draft_len
                else None
            )
        else:
            prefill_fn = self._prefill_impl(n_heads, moe_top_k)
            if self._long:
                decode_fn = self._decode_live_impl(moe_top_k)
                chunk_fn = self._chunk_live_impl(moe_top_k)
            else:
                decode_fn = self._decode_impl(n_heads, moe_top_k)
                chunk_fn = self._prefill_chunk_impl(n_heads, moe_top_k)
            verify_fn = (
                self._verify_impl(n_heads, moe_top_k)
                if self.draft_len
                else None
            )
        self._prefill_jit = _programs.instrument(
            jax.jit(prefill_fn, donate_argnums=donate),
            key=f"serve.{seq}:prefill",
            name=f"serve.prefill[{self.name}]",
            kind="serve.step", sync=True,
            after_issue=self._deliver_hosted, **mmeta,
        )
        self._decode_jit = _programs.instrument(
            jax.jit(decode_fn, donate_argnums=donate),
            key=f"serve.{seq}:decode",
            name=f"serve.decode[{self.name}]",
            kind="serve.step", sync=True,
            after_issue=self._deliver_hosted, **mmeta,
        )
        # built unconditionally (a jit wrapper is free until dispatched);
        # it only dispatches — and only then counts a program — when
        # chunked prefill or a prefix-cache resume needs it
        self._prefill_chunk_jit = _programs.instrument(
            jax.jit(chunk_fn, donate_argnums=donate),
            key=f"serve.{seq}:prefill_chunk",
            name=f"serve.prefill_chunk[{self.name}]",
            kind="serve.step", sync=True,
            after_issue=self._deliver_hosted, **mmeta,
        )
        self._verify_jit = self._draft_jit = None
        if self.draft_len:
            # the two speculative programs (draft + verify). The DRAFT
            # model runs replicated (plain jit) even under a mesh — its
            # proposals steer how many positions the verify covers,
            # never their values — while the VERIFY program shards on
            # KV heads exactly like decode (serve/tp.py).
            self._draft_dev = jax.device_put(
                self._draft_host, self._device
            )
            del self._draft_host
            self._verify_jit = _programs.instrument(
                jax.jit(verify_fn, donate_argnums=donate),
                key=f"serve.{seq}:verify",
                name=f"serve.verify[{self.name}]",
                kind="serve.step", sync=True,
                after_issue=self._deliver_hosted,
                draft_len=self.draft_len, **mmeta,
            )
            self._draft_jit = _programs.instrument(
                jax.jit(
                    self._draft_impl(self._draft_n_heads, moe_top_k),
                    donate_argnums=donate,
                ),
                key=f"serve.{seq}:draft",
                name=f"serve.draft[{self.name}]",
                kind="serve.step", sync=True,
                after_issue=self._deliver_hosted,
                draft_len=self.draft_len, **mmeta,
            )
        #: distinct (name, abstract input signature) pairs dispatched —
        #: jit keys compiles on exactly this, so its length IS the number
        #: of compiled step programs
        self.program_signatures: set = set()
        #: the step in progress dispatches a program for the first time
        #: (set by _record_program, cleared when the step completes)
        self._compiling = False
        self._req_counter = 0
        self._submit_lock = threading.Lock()
        self._step_lock = threading.RLock()
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        #: False after a terminal stepping failure (supervisor fail-fast)
        #: or a wedged stop; submit sheds until restart()
        self.healthy = True
        #: stop() observed the stepping thread outliving its join window
        self._stop_wedged = False
        #: consecutive decode steps lost to device OOM — bounds the
        #: defragment + preempt-youngest recovery loop
        self._consecutive_ooms = 0
        #: monotonic time the last step COMPLETED (the /healthz watchdog:
        #: a large age with work queued means the stepping path is wedged)
        self._last_step_t = time.monotonic()
        #: a fault queued by :meth:`inject_fault` — consumed (and raised)
        #: at the START of the next step, so an externally-injected
        #: replica kill lands at a step boundary instead of racing a
        #: step in progress
        self._poison: Optional[BaseException] = None
        #: when the last step program's wait returned: perf_counter, and
        #: (thread, thread_time) if its span was live (_note_hosted)
        self._wait_returned_t: Optional[float] = None
        self._wait_returned_cpu: Optional[Tuple[int, float]] = None
        #: the step loop's phase spans run back to back on this chain
        #: (each begins where the last ended), so their walls add up to
        #: the host time between two dispatches with nothing in between
        self._phases = _SpanChain()
        #: what the steps so far owe and no program's arguments need,
        #: oldest first: ``(fn, args)`` (:meth:`_owe`). Appended and
        #: drained under the step lock only
        self._owed: Deque[Tuple] = deque()
        #: tokens and end marks handed over so far (:meth:`_drain`
        #: reads the difference one delivery made)
        self._delivered = [0, 0]
        #: tokens handed over and seconds taken under the program now in
        #: flight, for its span's ``delivered_tokens`` / ``deliver_s``
        self._hosted = [0, 0.0]
        _m_pages_capacity.set(float(num_pages))
        _m_tp_degree.set(float(self.tp_degree), engine=self.name)
        #: estimated collective wall per dispatched step (0 solo): a
        #: one-time micro-measurement of the step's gather pattern,
        #: charged to serve.collective_seconds per dispatch
        self._collective_step_s = 0.0
        self._collective_bytes_per_step = 0.0
        if mesh is not None and self.tp_degree > 1:
            from .tp import estimate_collective_seconds

            (
                self._collective_step_s,
                self._collective_bytes_per_step,
            ) = estimate_collective_seconds(self, mesh, self._tp_axis)
        # per-request cost attribution (obs/requests.py): observe every
        # finishing slot while it still holds its pages
        self.scheduler.on_request_done = self._account_request
        self.scheduler.before_release = self._deliver_unless_wedged

    # -- tuned serving knobs ----------------------------------------------

    def _default_page_size(self, kv_dtype, head_dim: int) -> int:
        """Default page size when the caller passed none: the
        measured-best key-tile mapping (``paged_page_size_hint``, the
        flash sweep's block_k clamped to ``max_seq_len``), overridden by
        the autotuner's ``serve.page_size`` winner for this model
        signature when one is in the store."""
        from ..ops.attention import paged_page_size_hint

        hint = max(
            1,
            min(
                int(paged_page_size_hint(kv_dtype, head_dim)),
                self.max_seq_len,
            ),
        )
        try:
            from .. import tune

            if tune.mode() == "off":
                return hint
            win = tune.lookup(
                "serve.page_size",
                tune.serve_signature(kv_dtype, head_dim, self.max_seq_len),
                {"page_size": hint},
            )
            # defaulted path: clamp the winner like the hint — a store
            # row from a longer-sequence world must not oversize pages
            return max(
                1, min(int(win.get("page_size", hint)), self.max_seq_len)
            )
        except Exception:
            return hint

    def _tuned_page_slots(self, kv_dtype, head_dim: int):
        """The autotuner's ``serve.page_slots`` winner — pool geometry
        (decode slots × pages per slot) — for this model signature, or
        None when nothing is stored. Cache-only at init, like the other
        serving knobs (the measured search lives in
        ``tune.tune_serve_knobs``)."""
        try:
            from .. import tune

            if tune.mode() == "off":
                return None
            win = tune.lookup(
                "serve.page_slots",
                tune.serve_signature(
                    kv_dtype, head_dim, self.max_seq_len
                ),
                {},
            )
            return win or None
        except Exception:
            return None

    def _tuned_draft_len(self, kv_dtype, head_dim: int) -> int:
        """Default static draft length k when ``draft_params`` is given
        with no explicit ``draft_len``: the autotuner's
        ``serve.draft_len`` winner for this model signature (the
        measured search lives in ``tune.tune_serve_knobs``, driven by
        the acceptance-rate and verify-wall series), else 4 — cache-only
        at init like the other serving knobs."""
        try:
            from .. import tune

            if tune.mode() == "off":
                return 4
            win = tune.lookup(
                "serve.draft_len",
                tune.serve_signature(kv_dtype, head_dim, self.max_seq_len),
                {"k": 4},
            )
            return max(1, min(int(win.get("k", 4)), self.max_seq_len - 1))
        except Exception:
            return 4

    def _tuned_prefill_chunk(self, kv_dtype, head_dim: int) -> int:
        """The autotuner's ``serve.prefill_chunk`` winner (0 — whole
        prompts in one pass — when nothing is stored)."""
        try:
            from .. import tune

            if tune.mode() == "off":
                return 0
            win = tune.lookup(
                "serve.prefill_chunk",
                tune.serve_signature(kv_dtype, head_dim, self.max_seq_len),
                {"tokens": 0},
            )
            return max(0, min(int(win.get("tokens", 0)), self.max_seq_len))
        except Exception:
            return 0

    # -- compiled step builders -------------------------------------------

    def _prefill_impl(self, n_heads: int, moe_top_k: int):
        import jax
        import jax.numpy as jnp

        trash = self.pool.trash_page
        top_k = self.top_k

        def prefill(p, kp, vp, prompt, length, ptab, temp, seed, top_p):
            full = {**p, **self._static}
            state = [kp, vp]

            def store(li, k, v):
                # each layer's [1, Pmax, n_kv, hd] rows go into the pool
                # as the layer produces them; positions past the real
                # prompt land in the trash page
                with jax.named_scope("kv_write"):
                    state[0] = write_prompt(
                        state[0], li, ptab, length, k[0], trash
                    )
                    state[1] = write_prompt(
                        state[1], li, ptab, length, v[0], trash
                    )

            logits = transformer_prefill(
                full, prompt, store, moe_top_k, jnp.reshape(length, (1,))
            )
            kp, vp = state
            with jax.named_scope("sample"):
                last = logits[0, length - 1]
                greedy = jnp.argmax(last, axis=-1)
                # sampled path mirrors generate: per-step key folded at
                # the emitting position, filter_logits truncation,
                # categorical
                key = jax.random.fold_in(
                    jax.random.PRNGKey(seed), length - 1
                )
                scaled = last[None] / jnp.maximum(
                    jnp.asarray(temp, jnp.float32), 1e-6
                )
                filt = filter_logits(scaled, top_k=top_k, top_p=top_p)
                sampled = jax.random.categorical(key, filt, axis=-1)[0]
                tok = jnp.where(temp > 0, sampled, greedy).astype(
                    jnp.int32
                )
            return kp, vp, tok

        return prefill

    def _prefill_chunk_impl(self, n_heads: int, moe_top_k: int):
        """The third compiled step: one ``[1, C]`` span of a prompt at
        positions ``start .. start + C``, attending to the pages already
        written (earlier chunks, or a shared-prefix cache hit) plus
        itself causally. The per-position math is
        :func:`transformer_prefill_chunk`'s block walk — the one-pass
        prefill's to float associativity — and the sampled token
        mirrors the full program's (folded at the LAST prompt position),
        so only the final chunk's token is consumed."""
        import jax
        import jax.numpy as jnp

        from ..ops.attention import _NEG_BIG

        ps = self.page_size
        trash = self.pool.trash_page
        top_k = self.top_k
        mp = self._max_pages
        max_len = self.max_seq_len

        def chunk_step(
            p, kp, vp, chunk, start, valid, total_len, ptab, temp, seed,
            top_p,
        ):
            full = {**p, **self._static}
            c = chunk.shape[1]
            offs = jnp.arange(c)
            pos = start + offs  # absolute positions; tail is padding
            pos_clipped = jnp.minimum(pos, max_len - 1)
            state = [kp, vp]

            def attend(li, q, k, v):
                # scatter this chunk's k/v into its pages (padding rows
                # land in the trash page), then read the whole visible
                # history through the page table under the causal mask
                page = jnp.where(offs < valid, ptab[pos_clipped // ps], trash)
                off = pos_clipped % ps
                with jax.named_scope("kv_write"):
                    state[0] = write_rows(state[0], li, page, off, k[0])
                    state[1] = write_rows(state[1], li, page, off, v[0])
                n_kv, hd = k.shape[2], k.shape[3]
                t = mp * ps
                with jax.named_scope("kv_read"):
                    kg = split_heads(read_pages(state[0], li, ptab), hd)
                    vg = split_heads(read_pages(state[1], li, ptab), hd)
                scale = 1.0 / float(np.sqrt(hd))
                s = jnp.einsum("ckgd,tkd->ckgt", q[0], kg) * scale
                visible = jnp.arange(t)[None, :] <= pos[:, None]
                # the shared mask fill: byte-identity between chunked
                # and one-pass prefill depends on every paged/dense
                # read masking with the same value
                s = jnp.where(visible[:, None, None, :], s, _NEG_BIG)
                att = jnp.einsum(
                    "ckgt,tkd->ckgd", jax.nn.softmax(s, axis=-1), vg
                )
                return att.reshape(1, c, n_kv * q.shape[3] * hd)

            logits = transformer_prefill_chunk(
                full, chunk, pos_clipped, attend, moe_top_k=moe_top_k
            )
            # the final chunk's last REAL position seeds generation,
            # exactly as the one-pass prefill samples it (key folded at
            # the absolute last prompt position)
            last = logits[0, valid - 1]
            greedy = jnp.argmax(last, axis=-1)
            key = jax.random.fold_in(
                jax.random.PRNGKey(seed), total_len - 1
            )
            scaled = last[None] / jnp.maximum(
                jnp.asarray(temp, jnp.float32), 1e-6
            )
            filt = filter_logits(scaled, top_k=top_k, top_p=top_p)
            sampled = jax.random.categorical(key, filt, axis=-1)[0]
            tok = jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)
            return state[0], state[1], tok

        return chunk_step

    def _decode_impl(self, n_heads: int, moe_top_k: int):
        import jax
        import jax.numpy as jnp

        ps = self.page_size
        top_k = self.top_k
        read = _DECODE_READ[0]

        def decode(p, kp, vp, toks, positions, ptabs, temps, seeds, top_ps):
            full = {**p, **self._static}
            slots = toks.shape[0]
            state = [kp, vp]
            page = ptabs[jnp.arange(slots), positions // ps]
            off = positions % ps

            def write(kp, vp, li, k, v):
                return (
                    write_rows(kp, li, page, off, k),
                    write_rows(vp, li, page, off, v),
                )

            # the layer index is data to the scatter and to the read (a
            # jitted callee of its own), so each is traced once, not
            # once per layer: 48 traced copies were 1.5 s of every
            # engine's set-up (PERF.md §6, PR 26)
            write = jax.jit(write)

            def attend(li, q, k, v):
                # write this token's k/v into its page, then read the
                # whole visible history through the page table
                with jax.named_scope("kv_write"):
                    state[0], state[1] = write(state[0], state[1], li, k, v)
                with jax.named_scope("kv_read"):
                    ctx = read(
                        q, state[0], state[1], ptabs, positions + 1,
                        layer=li,
                    )
                return ctx.reshape(slots, -1)

            logits = transformer_step(
                full, toks, positions, attend, moe_top_k=moe_top_k
            )
            with jax.named_scope("sample"):
                greedy = jnp.argmax(logits, axis=-1)
                keys = jax.vmap(
                    lambda s, t: jax.random.fold_in(
                        jax.random.PRNGKey(s), t
                    )
                )(seeds, positions)
                scaled = logits / jnp.maximum(temps[:, None], 1e-6)
                filt = filter_logits(
                    scaled, top_k=top_k, top_p=top_ps[:, None]
                )
                sampled = jax.vmap(jax.random.categorical)(keys, filt)
                nxt = jnp.where(temps > 0, sampled, greedy).astype(
                    jnp.int32
                )
            return state[0], state[1], nxt

        return decode

    def _kind_views(self):
        """``view(tabs, li) -> (pool row, [.., T] table, kind index)`` for
        the live-read programs: which row of a pool page holds layer
        ``li`` and which column of its kind's table names that page. A
        model of one kind has one table and ``li`` is the row."""
        layout = self._pool_layout

        def view(tabs, li):
            ki, sub, row = layout.locate(li)
            tab = tabs[layout.kinds[ki].name]
            if layout.kinds[ki].units > 1:
                tab = tab[..., sub]
            return row, tab, ki

        return view

    def _kind_widths(self, span: int) -> Dict[str, int]:
        """Rows of each kind's page table in a program whose queries
        span ``span`` positions: every page of the longest sequence for
        a kind that keeps them all, the window's and the span's for a
        window kind."""
        return {
            k.name: (
                min(
                    self._max_pages,
                    pages_needed(k.window + span, self.page_size) + 1,
                )
                if k.window
                else self._max_pages
            )
            for k in self._pool_layout.kinds
        }

    def _decode_live_impl(self, moe_top_k: int):
        """The decode program of a long-sequence model: the block walk
        and the sampling of :meth:`_decode_impl`, with the read bounded
        by what is live (``ops.paged_attention_live``: a window layer's
        table is the window's pages; the slots of a full layer's walk go
        in groups by length, each to its own longest's last page), the
        window mask in that read, one page table per cache kind, and the
        expert layers' routing counts packed behind the tokens in the
        one array the host reads back."""
        import jax
        import jax.numpy as jnp

        from ..ops.attention import paged_attention_live

        ps = self.page_size
        top_k = self.top_k
        spec = self.spec
        view = self._kind_views()

        def decode(p, kp, vp, toks, positions, tabs, temps, seeds, top_ps):
            full = {**p, **self._static}
            slots = toks.shape[0]
            state = [kp, vp]
            lane = jnp.arange(slots)
            off = positions % ps
            q_pos = positions[:, None]
            routed: List = []

            def attend(li, q, k, v):
                row, tab, ki = view(tabs, li)
                first = tabs["first"][:, ki]
                at = jnp.clip(positions // ps - first, 0, tab.shape[1] - 1)
                page = tab[lane, at]
                with jax.named_scope("kv_write"):
                    state[0] = write_rows(
                        state[0], row, page, off, k.astype(kp.dtype)
                    )
                    state[1] = write_rows(
                        state[1], row, page, off, v.astype(vp.dtype)
                    )
                with jax.named_scope("kv_read"):
                    return paged_attention_live(
                        q[:, None], state[0], state[1], tab, first, q_pos,
                        positions + 1, row, window=spec.layer_window(li),
                    )[:, 0]

            logits = transformer_step(
                full, toks, positions, attend, moe_top_k=moe_top_k,
                routed=routed,
            )
            with jax.named_scope("sample"):
                # the sampled rule sorts the whole vocabulary for every
                # slot; a batch of greedy requests takes the argmax
                nxt = jax.lax.cond(
                    jnp.any(temps > 0),
                    lambda: _sample_slot_tokens(
                        logits, positions, temps, seeds, top_ps, top_k
                    ),
                    lambda: jnp.argmax(logits, axis=-1).astype(jnp.int32),
                )
            if routed:  # [layers, experts] pair counts ride the readback
                nxt = jnp.concatenate(
                    [nxt, jnp.stack(routed).reshape(-1).astype(jnp.int32)]
                )
            return state[0], state[1], nxt

        return decode

    def _chunk_live_impl(self, moe_top_k: int):
        """The prefill-chunk program of a long-sequence model: one
        page-aligned ``[1, C]`` span at ``start``, its K and V written a
        whole page at a time (pages wholly past the real tokens go to
        the trash page; the page the prompt ends inside takes the
        padding rows too, which decode overwrites before any read
        unmasks them), its queries read against the pages written so
        far by ``ops.paged_attention_live`` — a window layer's the
        window's and the chunk's own, a full layer's up to the chunk's
        end — under the causal and the window mask."""
        import jax
        import jax.numpy as jnp

        from ..ops.attention import paged_attention_live

        ps = self.page_size
        trash = self.pool.trash_page
        top_k = self.top_k
        max_len = self.max_seq_len
        spec = self.spec
        view = self._kind_views()

        def chunk_step(
            p, kp, vp, chunk, start, valid, total_len, tabs, temp, seed,
            top_p,
        ):
            full = {**p, **self._static}
            c = chunk.shape[1]
            pos = start + jnp.arange(c)  # absolute; the tail is padding
            pos_c = jnp.minimum(pos, max_len - 1)
            slab = jnp.arange(c // ps)
            state = [kp, vp]
            end = (start + valid)[None]

            def attend(li, q, k, v):
                row, tab, ki = view(tabs, li)
                first = tabs["first"][:, ki]
                at = jnp.clip(
                    start // ps + slab - first[0], 0, tab.shape[1] - 1
                )
                page = jnp.where(slab * ps < valid, tab[0, at], trash)
                width = state[0].shape[-1]
                with jax.named_scope("kv_write"):
                    for i, rows in enumerate((k, v)):
                        state[i] = state[i].at[row, page].set(
                            rows.astype(state[i].dtype).reshape(
                                c // ps, ps, width
                            )
                        )
                with jax.named_scope("kv_read"):
                    return paged_attention_live(
                        q, state[0], state[1], tab, first, pos[None],
                        end, row, window=spec.layer_window(li),
                    )

            last = transformer_prefill_chunk(
                full, chunk, pos_c, attend, moe_top_k=moe_top_k,
                head_at=valid - 1,
            )[0]
            tok = _sample_slot_tokens(
                last[None], (total_len - 1)[None], temp[None], seed[None],
                top_p[None], top_k,
            )[0]
            return state[0], state[1], tok

        return chunk_step

    def _warm_programs(self) -> None:
        """Build the chunk and the decode program before any traffic
        (:meth:`start` of a long-sequence engine): one dispatch of each
        with every table naming the trash page, so nothing a request
        will read is written. At these widths a program takes from tens
        of seconds to minutes to build, and a build inside traffic
        stalls every stream for that long."""
        import jax

        pool = self.pool
        chunk = self._chunk_args(
            np.zeros(self._chunk_c, np.int32), 0, 1, 1,
            SequencePages(pool, self.layout), 0.0, 0, 1.0,
        )
        self._record_program(
            "prefill_chunk", self._params_dev, pool.k, *chunk
        )
        pool.k, pool.v, _ = jax.block_until_ready(
            self._prefill_chunk_jit(self._params_dev, pool.k, pool.v, *chunk)
        )
        args = self._decode_args([])
        self._record_program("decode", self._params_dev, pool.k, *args)
        pool.k, pool.v, _ = jax.block_until_ready(
            self._decode_jit(self._params_dev, pool.k, pool.v, *args)
        )

    def _tables(self, seqs, span: int):
        """The page tables of ``seqs`` (None = an idle slot, every row
        the trash page) for a live-read program whose queries span
        ``span`` positions: ``{kind: [S, T] or [S, T, units] int32,
        "first": [S, kinds] int32}``."""
        kinds = self._pool_layout.kinds
        widths = self._kind_widths(span)
        out = {"first": np.zeros((len(seqs), len(kinds)), np.int32)}
        for ki, kind in enumerate(kinds):
            width = widths[kind.name]
            shape = (len(seqs), width) + (
                (kind.units,) if kind.units > 1 else ()
            )
            tab = np.full(shape, self.pool.trash_page, np.int32)
            for i, seq in enumerate(seqs):
                if seq is not None:
                    tab[i] = seq.table(width, ki)
                    out["first"][i, ki] = seq.first[ki]
            out[kind.name] = tab
        return out

    def _verify_impl(self, n_heads: int, moe_top_k: int):
        """The VERIFY step — the engine's fourth compiled program, the
        speculative-decoding tentpole: ``[max_slots, k + 1]`` tokens
        (each slot's pending token followed by its draft proposals) run
        the TARGET model's mid-sequence chunk walk
        (:func:`transformer_verify_chunk`) in ONE dispatch, scattering
        target k/v for every position and sampling the target's token
        at each with the per-step key folded at that ABSOLUTE position
        — exactly the decode program's rule, which is what keeps
        speculative streams byte-identical to solo decode (greedy and
        seeded). Positions past a slot's ``n_valid`` (adaptive k < the
        static k, idle slots) scatter into the trash page and their
        samples are ignored."""
        import jax.numpy as jnp

        ps = self.page_size
        trash = self.pool.trash_page
        top_k = self.top_k
        mp = self._max_pages
        max_len = self.max_seq_len
        c = self.draft_len + 1

        def verify(
            p, kp, vp, toks, starts, n_valid, ptabs, temps, seeds, top_ps
        ):
            full = {**p, **self._static}
            slots = toks.shape[0]
            pos = starts[:, None] + jnp.arange(c)[None, :]  # [S, C]
            pos_c = jnp.clip(pos, 0, max_len - 1)
            state = [kp, vp]
            # the shared span attend: scatter the whole verify span's
            # k/v (padding and out-of-range positions land in the trash
            # page), then read each position's visible history through
            # the page table — the prefill-chunk read, batched over
            # slots
            attend = _span_attend(
                state, ptabs, pos, pos_c, n_valid, ps, trash, mp,
                max_len,
            )
            logits = transformer_verify_chunk(
                full, toks, pos_c, attend, moe_top_k=moe_top_k
            )  # [S, C, V]
            vocab = logits.shape[-1]
            u = _sample_slot_tokens(
                logits.reshape(slots * c, vocab),
                pos_c.reshape(-1),
                jnp.repeat(temps, c),
                jnp.repeat(seeds, c),
                jnp.repeat(top_ps, c),
                top_k,
            ).reshape(slots, c)
            return state[0], state[1], u

        return verify

    def _draft_impl(self, n_heads: int, moe_top_k: int):
        """The DRAFT step — one dispatch per engine step proposes up to
        k tokens per slot from the draft model's own KV page group:

        - phase 1 (chunk): the ``[max_slots, k + 1]`` context window —
          tokens the draft has not ingested yet, teacher-forced —
          runs the draft's chunk walk, writing draft k/v; the LAST
          context token's logits seed proposal 1 (sampled with the
          target's exact rule at that absolute position, so a correct
          draft's proposal matches the target's token bit-for-bit);
        - phase 2 (scan, k - 1 iterations): single-token draft steps
          extend the proposals, each writing its draft k/v and sampling
          the next.

        The same program also serves CATCH-UP (a freshly prefilled
        prompt, a preemption replay): the host feeds ONE lag window per
        engine step through phase 1 — the slot decodes plainly until
        the backlog drains, bounding the stall like chunked prefill —
        and uses proposals only once the window reaches the newest
        token. Proposals never touch emitted bytes — the verify
        program's target tokens do — so the draft runs replicated even
        under a TP mesh."""
        import jax
        import jax.numpy as jnp

        ps = self.page_size
        trash = self.pool.trash_page
        top_k = self.top_k
        mp = self._max_pages
        max_len = self.max_seq_len
        k_static = self.draft_len
        w = k_static + 1
        d_model = self._draft_d_model

        def draft(
            p, kp, vp, ctx, starts, n_ctx, ptabs, temps, seeds, top_ps
        ):
            full = {**p, "n_heads": n_heads}
            slots = ctx.shape[0]
            pos = starts[:, None] + jnp.arange(w)[None, :]
            pos_c = jnp.clip(pos, 0, max_len - 1)
            state = [kp, vp]
            attend = _span_attend(
                state, ptabs, pos, pos_c, n_ctx, ps, trash, mp, max_len
            )
            logits = transformer_verify_chunk(
                full, ctx, pos_c, attend, moe_top_k=moe_top_k
            )  # [S, W, V]
            last_pos = starts + n_ctx - 1
            last = jnp.take_along_axis(
                logits, (n_ctx - 1)[:, None, None], axis=1
            )[:, 0]  # [S, V]
            t1 = _sample_slot_tokens(
                last,
                jnp.clip(last_pos, 0, max_len - 1),
                temps, seeds, top_ps, top_k,
            )
            if k_static == 1:
                return state[0], state[1], t1[:, None]

            def scan_body(carry, _):
                dk, dv, tok, posn = carry
                posn_c = jnp.clip(posn, 0, max_len - 1)
                inner = [dk, dv]

                def attend_step(li, q, k, v):
                    page = jnp.where(
                        posn < max_len,
                        ptabs[jnp.arange(slots), posn_c // ps],
                        trash,
                    )
                    off = posn_c % ps
                    inner[0] = write_rows(inner[0], li, page, off, k)
                    inner[1] = write_rows(inner[1], li, page, off, v)
                    read = _DECODE_READ[0](
                        q, inner[0], inner[1], ptabs, posn_c + 1,
                        layer=li,
                    )
                    return read.reshape(slots, d_model)

                step_logits = transformer_step(
                    full, tok, posn_c, attend_step, moe_top_k=moe_top_k
                )
                nxt = _sample_slot_tokens(
                    step_logits, posn_c, temps, seeds, top_ps, top_k
                )
                return (inner[0], inner[1], nxt, posn + 1), nxt

            # proposal t_i sits at absolute position last_pos + i; the
            # scan walks t_1 .. t_{k-1} through the draft (writing their
            # draft k/v — correct whenever the proposal is accepted) and
            # emits t_2 .. t_k
            (dk, dv, _, _), rest = jax.lax.scan(
                scan_body,
                (state[0], state[1], t1, last_pos + 1),
                None,
                length=k_static - 1,
            )
            props = jnp.concatenate([t1[:, None], rest.T], axis=1)
            return dk, dv, props

        return draft

    def _charge_collectives(self) -> None:
        """One step program dispatched: charge its estimated collective
        wall (no-op solo)."""
        if self._collective_step_s:
            _m_collective_s.inc(self._collective_step_s)

    def _record_program(self, name: str, *args) -> None:
        sig: List = [name]
        for a in args:
            if isinstance(a, dict):
                sig.append("params")
            else:
                arr = np.asarray(a) if np.isscalar(a) else a
                sig.append((tuple(arr.shape), str(arr.dtype)))
        key = tuple(sig)
        if key not in self.program_signatures:
            # jit keys compiles on exactly this signature, so the
            # dispatch that follows compiles — tens of seconds at real
            # model widths. health() says so until the step completes:
            # a watchdog must not read a compile as a wedge.
            self._compiling = True
            self.program_signatures.add(key)

    @property
    def num_step_programs(self) -> int:
        """Distinct compiled step programs dispatched so far (jit keys on
        the abstract input signature; static shapes keep this at <= 3:
        one prefill + one decode, plus the prefill-chunk program when
        chunked prefill / prefix-cache resume dispatches it — and <= 5
        with speculative decoding on, which adds the draft and verify
        programs while the plain decode program stops dispatching)."""
        return len(self.program_signatures)

    # -- submission --------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int,
        temperature: float = 0.0,
        top_p: float = 1.0,
        seed: int = 0,
        eos_id: Optional[int] = None,
        block: bool = True,
        timeout: Optional[float] = None,
        deadline: Optional[float] = None,
        trace=None,
        tenant: str = "",
        _handle_factory=None,
    ) -> GenerationHandle:
        """Queue one generation request; returns its streaming handle.
        Raises ``ValueError`` for requests that could never be scheduled,
        :class:`~.scheduler.QueueFullError` when the bounded queue is
        full and ``block=False``, and :class:`EngineUnhealthyError` when
        the engine is shedding after a terminal failure (restart() to
        recover). ``deadline`` is a per-request budget in SECONDS from
        now: the step sweep evicts the request — queued or
        mid-generation — once it passes, and the handle raises
        :class:`~tensorframes_tpu.utils.failures.DeadlineExceededError`.

        ``trace`` attaches a
        :class:`~tensorframes_tpu.obs.TraceContext` the request's
        engine-side spans join (default: the submitting thread's
        current trace, so an HTTP ``traceparent`` flows through without
        every caller threading it explicitly).

        ``tenant`` keys the request's cost-attribution record
        (``obs/requests.py``; empty = unattributed) — the fleet fills
        it from the session id when the client names no tenant.

        ``_handle_factory`` (private) lets the fleet router
        (``serve/fleet.py``) substitute its relay handle —
        ``factory(request_id) -> GenerationHandle`` — so emissions and
        the terminal close forward to the fleet-level stream."""
        prompt = np.asarray(prompt, np.int32).ravel()
        if prompt.size < 1:
            _m_requests.inc(status="rejected")
            raise ValueError("prompt needs at least one token")
        if max_new_tokens < 1:
            _m_requests.inc(status="rejected")
            raise ValueError(
                f"max_new_tokens must be >= 1; got {max_new_tokens}"
            )
        if deadline is not None and deadline <= 0:
            _m_requests.inc(status="rejected")
            raise ValueError(
                f"deadline must be positive seconds from now; got {deadline}"
            )
        if not self.healthy or self._stop_wedged:
            # shed instead of queueing work a broken engine will never
            # run — the caller gets the fast 503, not a hung handle
            _m_requests.inc(status="rejected")
            raise EngineUnhealthyError(
                "engine is unhealthy after a terminal stepping failure "
                "or a wedged stop; restart() it (or recycle the process) "
                "before submitting"
            )
        if _handle_factory is None and _tenancy.enabled():
            # the QoS admission gate (quota / rate / SLO shed → 429).
            # Only at the FRONT door: the fleet router charged its
            # fleet-wide check already, so the relay path
            # (_handle_factory set) must not bill the tenant twice —
            # and preemption requeues / failover replays never come
            # back through submit at all
            active, queued = self.scheduler.tenant_counts()
            key = str(tenant or "")
            try:
                _tenancy.admit_request(
                    key, int(max_new_tokens),
                    active.get(key, 0), queued.get(key, 0),
                )
            except TenantThrottledError:
                _m_requests.inc(status="rejected")
                raise
        with self._submit_lock:
            self._req_counter += 1
            rid = self._req_counter
        handle = (
            GenerationHandle if _handle_factory is None else _handle_factory
        )(rid)
        req = GenRequest(
            request_id=rid,
            prompt=prompt,
            max_new_tokens=int(max_new_tokens),
            temperature=float(temperature),
            top_p=float(top_p),
            seed=int(seed),
            eos_id=self.eos_id if eos_id is None else eos_id,
            handle=handle,
            deadline_t=(
                None if deadline is None else time.monotonic() + deadline
            ),
            trace=trace if trace is not None else _current_trace(),
            tenant=str(tenant or ""),
            priority=_tenancy.priority_of(str(tenant or "")),
        )
        try:
            self.scheduler.submit(req, block=block, timeout=timeout)
        except (ValueError, QueueFullError):
            # both are terminal rejections from the caller's view —
            # infeasible shape and queue backpressure alike must keep
            # completed + failed + rejected == submissions
            _m_requests.inc(status="rejected")
            raise
        _m_queue_depth.set(float(self.scheduler.queue_depth))
        return handle

    # -- stepping ----------------------------------------------------------

    def step(self) -> bool:
        """One scheduler iteration: sweep expired deadlines, admit +
        prefill newcomers, grow pages (preempting on exhaustion), one
        decode step for the batch. Returns whether work remains. When
        it returns, every token the step produced is on its handle
        (and every finished handle is closed): with no next program in
        sight the delivery is not deferred past the return.

        Failure classification (the supervisor's contract,
        ``docs/fault_tolerance.md``): transient dispatch errors retry
        with bounded backoff INSIDE the step (``run_with_retries`` on
        the compiled-step calls); device OOM mid-decode recovers by
        ``defragment()`` + preempt-youngest without failing anyone;
        whatever still escapes fails the affected requests' handles and
        re-raises for the caller (the background loop then fails the
        rest and marks the engine unhealthy)."""
        # whatever the caller did since its last step() is no phase of
        # this one; the engine's own loops keep the chain (_step_once)
        self._phases.reset()
        try:
            return self._step_once()
        finally:
            self._deliver_idle()

    def _step_once(self) -> bool:
        with self._step_lock:
            try:
                return self._step_locked()
            finally:
                # the /healthz watchdog: age of the last step COMPLETION
                # (normal, recovered, or failed — a wedged device call is
                # the thing this must expose, and that never reaches here)
                self._last_step_t = time.monotonic()
                self._compiling = False

    def _step_locked(self) -> bool:
        poison = self._poison
        if poison is not None:
            # an injected hard fault (inject_fault): raise BEFORE touching
            # the batch so every token already emitted stays consistent —
            # the supervisor then fails all in-flight handles promptly
            self._poison = None
            raise poison
        # the host phases of a step are LEAF spans, none enclosing
        # another and none around the whole step: a profiler capture
        # names each device-idle gap after the span that covers most of
        # it, and a parent would win every gap
        with _span("serve.admit", chain=self._phases) as sp:
            expired = self.scheduler.expire(time.monotonic())
            if expired:
                _m_deadline_expired.inc(expired)
                _m_handles_failed.inc(expired, reason="deadline")
                _m_requests.inc(expired, status="failed")
            admitted = self.scheduler.admit()
            if sp is not None:
                sp.attrs.update(
                    admitted=len(admitted),
                    queued=self.scheduler.queue_depth,
                    blocked_on=self.scheduler.blocked_on or "none",
                )
        prefill_err: Optional[BaseException] = None
        stepped: set = set()
        for idx, act in admitted:
            stepped.add(idx)
            err = self._try_prefill(idx, act, first=True)
            if err is not None and prefill_err is None:
                prefill_err = err
        # slots admitted in EARLIER steps still mid-prompt (chunked
        # prefill) advance one chunk per step, interleaved with the
        # decode batch below — the bounded-stall property
        for idx, act in self.scheduler.active:
            if (
                idx in stepped
                or act.generated
                or self.scheduler.slots[idx] is not act
            ):
                continue
            err = self._try_prefill(idx, act, first=False)
            if err is not None and prefill_err is None:
                prefill_err = err
        if prefill_err is not None:
            # every surviving slot is prefilled; propagate now, before
            # decode, so synchronous drivers see the device error
            self._refresh_gauges()
            raise prefill_err
        ready: List[Tuple[int, _Active]] = []
        with _span("serve.grow", chain=self._phases) as sp:
            preempted = self.scheduler.preemptions
            for idx, act in self.scheduler.active:
                if self.scheduler.slots[idx] is not act:
                    continue  # preempted as a victim already
                if not act.generated:
                    continue  # still prefilling in chunks
                if self.scheduler.grow(idx):
                    ready.append((idx, act))
            # growth for a later slot may have evicted an earlier one
            ready = [
                (i, a) for i, a in ready if self.scheduler.slots[i] is a
            ]
            if sp is not None:
                sp.attrs.update(
                    ready=len(ready),
                    preempted=self.scheduler.preemptions - preempted,
                )
        if ready:
            try:
                if self.draft_len:
                    self._spec_batch(ready)
                else:
                    self._decode_batch(ready)
                self._consecutive_ooms = 0
            except Exception as e:
                if is_oom(e) and self._recover_oom():
                    self._refresh_gauges()
                    return True
                for i, _ in ready:
                    if self.scheduler.slots[i] is not None:
                        self.scheduler.finish(i, error=e)
                        _m_requests.inc(status="failed")
                        _m_handles_failed.inc(reason=_fail_reason(e))
                raise
        with _span("serve.bookkeeping", chain=self._phases):
            more = self.scheduler.has_work()
            if more:  # a program follows, and the gauges wait for it
                self._owe(self._refresh_gauges)
            else:
                self._refresh_gauges()
            return more

    def _note_oom(self) -> bool:
        """One more consecutive OOM recovery attempt; False once the
        bounded budget (``max_slots + 1`` without a completed decode) is
        spent — shrinking cannot help, treat the OOM as fatal."""
        self._consecutive_ooms += 1
        return self._consecutive_ooms <= self.max_slots + 1

    def _recover_oom(self) -> bool:
        """Device OOM mid-decode: the batch died BEFORE its emission loop
        (no tokens were streamed), so the step is safe to redo. Compact
        the pool and shed the youngest sequence (recompute-style requeue
        — its stream never notices), then let the next step retry with a
        smaller batch. Bounded via :meth:`_note_oom`."""
        if not self._note_oom():
            return False
        logger.warning(
            "decode step hit device OOM (%d consecutive); defragmenting "
            "and preempting the youngest sequence",
            self._consecutive_ooms,
        )
        self._defragment_locked()
        victim = self.scheduler._victim_slot(exclude=-1)
        if victim is not None:
            self.scheduler.preempt(victim)
        return True

    def _try_prefill(
        self, idx: int, act: _Active, first: bool
    ) -> Optional[BaseException]:
        """One prefill advance (full prompt, or one chunk) under the
        step's failure contract: device OOM degrades to defragment +
        requeue-self (nothing emitted yet — recompute-style), anything
        else fails THIS request only so later slots still step (an
        abort mid-loop would leave them with no prefill, poisoning the
        decode batch). Returns the non-OOM error, if any, for the caller
        to re-raise once every slot has been serviced."""
        try:
            if first:
                self._prefill_one(idx, act)
            else:
                self._advance_prefill(idx, act)
            return None
        except Exception as e:
            if is_oom(e) and self._note_oom():
                logger.warning(
                    "prefill hit device OOM (%d consecutive); "
                    "defragmenting and requeueing request %d",
                    self._consecutive_ooms,
                    act.req.request_id,
                )
                self._defragment_locked()
                self.scheduler.preempt(idx)
                return None
            self.scheduler.finish(idx, error=e)
            _m_requests.inc(status="failed")
            _m_handles_failed.inc(reason=_fail_reason(e))
            return e

    def _defragment_locked(self) -> Dict[int, int]:
        """Pool compaction with every live page list renumbered — the
        sequences', the prefix cache's (cached prefixes survive), AND
        any slot's pending copy-on-write donor page. The cow reference
        is held as a bare index on ``_Active``, not a list the pool can
        rewrite in place, so it is wrapped here and written back: a
        defragment between admission and ``_apply_cow`` (an earlier
        slot's prefill OOM) would otherwise leave a stale donor index —
        the later clone would copy whatever page landed there (silent KV
        corruption) and free the wrong page's reference."""
        acts = [a for _, a in self.scheduler.active]
        cow_lists = [[a.cow_src] for a in acts if a.cow_src is not None]
        page_lists: List[List[int]] = list(cow_lists)
        if self.prefix_cache is not None:
            page_lists.extend(self.prefix_cache.entry_page_lists())
        remap = self.pool.defragment(
            [a.seq for a in acts], page_lists=page_lists
        )
        it = iter(cow_lists)
        for a in acts:
            if a.cow_src is not None:
                a.cow_src = next(it)[0]
        return remap

    def _prefill_one(self, idx: int, act: _Active) -> None:
        """First prefill service for a newly admitted slot: route to the
        one-pass program, or to the chunk program when the prompt
        exceeds the chunk size or a prefix-cache hit starts mid-prompt."""
        req = act.req
        plen = len(req.prompt)
        timings = req.handle.timings
        now = time.monotonic()
        # admit() charged the wait up to the admission; what passed
        # since (earlier newcomers' prefills in this step) is waiting too
        self.scheduler.charge_wait(req, now)
        if "queue_wait_s" not in timings:
            # first admission only (preemption/replay requeues keep the
            # original submitted_at, and setdefault keeps the first wait)
            timings["queue_wait_s"] = now - req.submitted_at
        if self.prefix_cache is not None:
            _m_prefix_lookups.inc()
            if act.cached_tokens > 0:
                _m_prefix_hits.inc()
                _m_prefix_tokens_saved.inc(act.cached_tokens)
                # cost attribution: tokens this request never prefilled
                # (accumulates across preemption re-admissions)
                timings["prefix_cached_tokens"] = (
                    timings.get("prefix_cached_tokens", 0)
                    + act.cached_tokens
                )
        if self.draft_len and act.cached_tokens > 0:
            # shared prefix pages carry the donor's DRAFT-KV rows too
            # (same page indices in the draft group), so the draft skips
            # the cached span exactly like the target prefill does; a
            # donor that never caught up leaves zeroed rows — proposals
            # degrade, the verify pass still decides every byte
            act.draft_pos = act.cached_tokens
        chunking = self.prefill_chunk_tokens > 0
        if act.cached_tokens > 0 or self._long or (
            chunking and plen > self.prefill_chunk_tokens
        ):
            self._apply_cow(act)
            act.prefill_pos = act.cached_tokens
            self._advance_prefill(idx, act)
            return
        self._prefill_full(idx, act)

    def _apply_cow(self, act: _Active) -> None:
        """Copy-on-write for a cached prefix that ends INSIDE a donor
        page: clone the donor's page row into this sequence's private
        page, then drop the temporary donor reference. Positions up to
        ``cached_tokens`` are then valid; the chunk prefill overwrites
        from the divergence point on. Plain device indexing, like
        ``defragment()`` — not a step program."""
        if act.cow_src is None:
            return
        src = act.cow_src
        dst = act.seq.pages[act.cached_tokens // self.page_size]
        pool = self.pool
        # the donor's draft-KV rows ride the same page indices: the
        # clone carries every group too, or the sharer's draft would
        # propose from a zeroed page (correctness is unaffected —
        # verify decides — but the acceptance rate would crater)
        pool.copy_page(src, dst)
        act.cow_src = None
        pool.free([src])

    def _register_prefix(self, act: _Active) -> None:
        """A finished prefill publishes its prompt's complete pages for
        future identical prefixes to share."""
        if self.prefix_cache is not None:
            self.prefix_cache.insert(
                act.req.prompt, act.seq.pages,
                priority=act.req.priority,
            )

    def _advance_prefill(self, idx: int, act: _Active) -> None:
        """Dispatch ONE prefill chunk (the third compiled program); on
        the final chunk, sample and emit the first token and register
        the prompt's pages in the prefix cache."""
        req = act.req
        plen = len(req.prompt)
        start = act.prefill_pos
        c = self._chunk_c
        valid = min(c, plen - start)
        if self._long:
            if start % self.page_size:
                # the live chunk program writes whole pages; a prefix
                # hit that ends inside one starts over at its first row
                start -= start % self.page_size
                valid = min(c, plen - start)
            try:
                # window kinds give back what fell behind this chunk's
                # first query, then take the chunk's own pages: as many
                # as were just freed, once the window is full
                act.seq.advance(start)
                act.seq.ensure(start + valid)
            except PagePoolExhausted:
                self.scheduler.preempt(idx)
                return
        args = self._chunk_args(
            req.prompt[start : start + valid], start, valid, plen,
            act.seq, req.temperature, req.seed, req.top_p,
        )
        pool = self.pool
        self._record_program(
            "prefill_chunk", self._params_dev, pool.k, *args
        )

        def dispatch():
            import jax

            _chaos.site("serve.prefill_chunk")
            return jax.block_until_ready(
                self._prefill_chunk_jit(
                    self._params_dev, pool.k, pool.v, *args
                )
            )

        recompute = max(0, min(start + valid, req.computed) - start)
        t0 = time.perf_counter()
        routed = valid * self._pairs_per_token
        with _use_trace(req.trace), _span(
            "serve.prefill_chunk",
            chain=self._phases,
            request=req.request_id,
            start=start,
            tokens=valid,
            recompute=recompute,
            pad_share=1.0 - valid / c,
            tokens_routed=routed,
        ) as sp:
            if sp is not None and self._long:
                sp.attrs["attn_blocks"] = self._chunk_attn_blocks(
                    act.seq, start + valid
                )
            pool.k, pool.v, tok = run_with_retries(
                dispatch,
                what=f"serve.prefill_chunk request {req.request_id}",
            )
            self._note_hosted(sp, self._prefill_chunk_jit)
        self._owe(
            self._charge_prefill, req.handle.timings,
            self._prefill_chunk_jit, valid, recompute,
            self._wait_returned_t - t0, routed,
        )
        act.prefill_pos = start + valid
        if act.prefill_pos >= plen:
            self._register_prefix(act)
            self._emit(idx, act, int(tok))

    def _chunk_attn_blocks(self, seq, end: int) -> int:
        """``attn_blocks`` of a live engine's ``serve.prefill_chunk``
        span: the blocks of keys the chunk program's attention walks
        visit for a chunk whose last real token sits before ``end``, all
        layers — per cache kind the trip count of
        ``ops.paged_attention_live``'s span walk (``live_span_trips``,
        the arithmetic the program's own count comes from) times the
        kind's layers."""
        from ..ops.attention import live_read_blocks, live_span_trips

        ps = self.page_size
        widths = self._kind_widths(self._chunk_c)
        blocks = 0
        for ki, kind in enumerate(self._pool_layout.kinds):
            n_blocks, bp = live_read_blocks(widths[kind.name])
            live = np.asarray([end - seq.first[ki] * ps])
            blocks += len(kind.layers) * int(
                live_span_trips(live, bp * ps, n_blocks)
            )
        return blocks

    def _chunk_args(
        self, tokens, start, valid, plen, seq, temperature, seed, top_p
    ):
        """The chunk program's arguments after the weights and the
        pool."""
        chunk_row = np.zeros((1, self._chunk_c), np.int32)
        chunk_row[0, :valid] = tokens[:valid]
        if self._long:
            ptab = self._tables([seq], self._chunk_c)
        else:
            ptab = seq.table(self._max_pages)
        return (
            chunk_row,
            np.int32(start),
            np.int32(valid),
            np.int32(plen),
            ptab,
            np.float32(temperature),
            np.int32(seed),
            np.float32(top_p),
        )

    def _decode_args(self, ready: List[Tuple[int, _Active]]):
        """The decode program's arguments after the weights and the
        pool: each ready slot's pending token, its write position, page
        tables and sampling parameters; idle slots name the trash page."""
        s = self.max_slots
        toks = np.zeros(s, np.int32)
        positions = np.zeros(s, np.int32)
        temps = np.zeros(s, np.float32)
        seeds = np.zeros(s, np.int32)
        top_ps = np.ones(s, np.float32)
        seqs: List = [None] * s
        for idx, act in ready:
            toks[idx] = act.generated[-1]
            # this token's write position
            positions[idx] = act.length - 1
            seqs[idx] = act.seq
            temps[idx] = act.req.temperature
            seeds[idx] = act.req.seed
            top_ps[idx] = act.req.top_p
        if self._long:
            ptabs = self._tables(seqs, 1)
        else:
            ptabs = np.full(
                (s, self._max_pages), self.pool.trash_page, np.int32
            )
            for idx, act in ready:
                ptabs[idx] = act.seq.table(self._max_pages)
        return (toks, positions, ptabs, temps, seeds, top_ps)

    def _prefill_full(self, idx: int, act: _Active) -> None:
        req = act.req
        plen = len(req.prompt)
        prompt_row = np.zeros((1, self.max_seq_len), np.int32)
        prompt_row[0, :plen] = req.prompt
        ptab = act.seq.table(self._max_pages)
        args = (
            prompt_row,
            np.int32(plen),
            ptab,
            np.float32(req.temperature),
            np.int32(req.seed),
            np.float32(req.top_p),
        )
        pool = self.pool
        self._record_program("prefill", self._params_dev, pool.k, *args)

        # dispatch inside a retry window, SYNCED inside it (jax dispatch
        # is async; failures.py's coverage rule): the compiled call is
        # functional and pool arrays are reassigned only on success, so a
        # transient failure retries with an identical result. The
        # step donates pool.k/v — a mid-execution failure there consumes
        # the donated buffers, the retry fails non-transiently, and the
        # supervisor escalates to fail-fast + restart() instead.
        def dispatch():
            import jax

            _chaos.site("serve.prefill")
            return jax.block_until_ready(
                self._prefill_jit(self._params_dev, pool.k, pool.v, *args)
            )

        recompute = min(plen, req.computed)
        t0 = time.perf_counter()
        with _use_trace(req.trace), _span(
            "serve.prefill",
            chain=self._phases,
            request=req.request_id,
            prompt_len=plen,
        ) as sp:
            if sp is not None:
                sp.attrs.update(
                    padded_len=self.max_seq_len,
                    pad_share=1.0 - plen / self.max_seq_len,
                    recompute=recompute,
                    **self._prompt_tile_attrs(plen),
                )
            pool.k, pool.v, tok = run_with_retries(
                dispatch, what=f"serve.prefill request {req.request_id}"
            )
            self._note_hosted(sp, self._prefill_jit)
        with _span("serve.readback", chain=self._phases):
            tok = int(tok)
        with _span("serve.emit", chain=self._phases, tokens=1) as sp:
            self._owe(
                self._charge_prefill, req.handle.timings, self._prefill_jit,
                plen, recompute, self._wait_returned_t - t0,
            )
            act.prefill_pos = plen
            self._register_prefix(act)
            self._emit(idx, act, tok)
            if sp is not None:
                sp.attrs["finished"] = int(
                    self.scheduler.slots[idx] is not act
                )

    def _prompt_tile_attrs(self, plen: int) -> dict:
        """What a ``serve.prefill`` span says of its attention:
        ``attn_tiles``, the pairs of a query and a key tile that the
        one-pass prefill's kernel computes for a prompt of ``plen``
        tokens in its row, all layers, and ``attn_tiles_skipped``, those
        it skips (above the diagonal, past the prompt, outside a window)
        — ``ops.prompt_attention_tiles``, the arithmetic of the kernel's
        own regimes."""
        from ..ops.attention import prompt_attention_tiles

        computed, skipped = prompt_attention_tiles(
            plen, self.max_seq_len,
            [k.window for k in self._pool_layout.kinds for _ in k.layers],
        )
        return {"attn_tiles": computed, "attn_tiles_skipped": skipped}

    def _decode_batch(self, ready: List[Tuple[int, _Active]]) -> None:
        s = self.max_slots
        pool = self.pool
        with _span("serve.decode_args", chain=self._phases):
            args = self._decode_args(ready)
            self._record_program("decode", self._params_dev, pool.k, *args)

        # synced inside the retry window, like prefill (the host loop
        # needs ``nxt`` before the next step's arguments; what is done
        # with the step before's tokens needs no more than the host, and
        # runs between this call's issue and its wait: _deliver_hosted);
        # same donation caveat as prefill
        def dispatch():
            import jax

            _chaos.site("serve.decode_step")
            return jax.block_until_ready(
                self._decode_jit(self._params_dev, pool.k, pool.v, *args)
            )

        with _span(
            "serve.decode_step", chain=self._phases, occupancy=len(ready)
        ) as sp:
            if sp is not None:
                self._decode_step_attrs(sp.attrs, ready)
            pool.k, pool.v, nxt = run_with_retries(
                dispatch, what="serve.decode_step"
            )
            self._note_hosted(sp, self._decode_jit)
            if self._pairs_per_token:
                # the expert layers' pair counts came back behind the
                # tokens, in the one array the host reads anyway
                nxt = np.asarray(nxt)
                self._note_routing(sp, nxt[s:], len(ready))
                nxt = nxt[:s]
        with _span("serve.readback", chain=self._phases):
            nxt = np.asarray(nxt)
        with _span(
            "serve.emit", chain=self._phases, tokens=len(ready)
        ) as sp:
            self._owe(self._charge_collectives)
            share = 1.0 / max(1, len(ready))
            for idx, act in ready:
                self._owe(
                    self._charge_flops,
                    act.req.handle.timings, self._decode_jit, share,
                )
                self._emit(idx, act, int(nxt[idx]))
            if sp is not None:
                sp.attrs["finished"] = sum(
                    self.scheduler.slots[i] is not a for i, a in ready
                )

    def _decode_step_attrs(
        self, attrs: dict, ready: List[Tuple[int, _Active]]
    ) -> None:
        """What a live ``serve.decode_step`` span says of its batch: who
        is in it, how long the host took since the last step program's
        wait returned — up to where this span began where that
        program's span was live too, and then how much of it the thread
        spent off the CPU — and how many KV positions the read copies
        per layer against how many are live: each slot's pages up to
        its end (``ops.attention.decode_read_positions``, the
        arithmetic of the kernel's own schedule)."""
        attrs["requests"] = [a.req.request_id for _, a in ready]
        waited, mark = self._wait_returned_t, self._wait_returned_cpu
        chain = self._phases
        if (
            mark is not None
            and mark[0] == chain.thread == threading.get_ident()
            and chain.t is not None
            and chain.t >= waited
        ):
            # up to where this span began, the end of the phase before
            # it, whose span read both clocks already
            gap = attrs["host_gap_s"] = chain.t - waited
            attrs["host_gap_offcpu_s"] = gap - (chain.cpu - mark[1])
        elif waited is not None:
            attrs["host_gap_s"] = time.perf_counter() - waited
        live = sum(a.length for _, a in ready)
        if self._long:  # by cache kind, and their mean over the layers
            read, live = self._live_read_attrs(attrs, ready)
        else:  # the read's own account; an idle slot holds one key
            lengths = np.ones(self.max_slots, np.int64)
            for idx, act in ready:
                lengths[idx] = act.length
            pool = self.pool.k
            read = _DECODE_READ[1](
                lengths, self._max_pages, self.page_size,
                self.page_size * pool.shape[-1] * pool.dtype.itemsize,
            )
        attrs["kv_tokens_read"] = read
        attrs["kv_tokens_live"] = live
        attrs["kv_read_amplification"] = read / live

    def _live_read_attrs(self, attrs: dict, ready) -> Tuple[float, float]:
        """Key positions the live read touches per layer against those a
        query can see, by cache kind (``kv_tokens_read_<kind>`` /
        ``kv_tokens_live_<kind>``) and, weighted by each kind's layers,
        for the mean layer. A window layer's table is gathered whole
        for every slot; a full layer's is walked group by group, as
        ``live_read_positions`` and the program's own trips count it."""
        from ..ops.attention import live_read_positions

        ps = self.page_size
        lengths = [a.length for _, a in ready]
        widths = self._kind_widths(1)
        total_read = total_live = layers = 0.0
        for kind in self._pool_layout.kinds:
            width, n = widths[kind.name], len(kind.layers)
            if kind.window:
                read = self.max_slots * width * ps
                live = sum(min(l, kind.window) for l in lengths)
            else:
                read = live_read_positions(
                    lengths, self.max_slots, width, ps
                )
                live = sum(lengths)
            attrs[f"kv_tokens_read_{kind.name}"] = read
            attrs[f"kv_tokens_live_{kind.name}"] = live
            total_read += n * read
            total_live += n * live
            layers += n
        return total_read / layers, total_live / layers

    def _note_routing(self, sp, counts, occupancy: int) -> None:
        """One decode step's routing, from the pair counts the program
        returned with its tokens (``[expert layers x experts]``): the
        ``serve.decode_step`` span's ``experts_hit`` (distinct experts
        a layer touched, mean over layers) and
        ``expert_load_max_over_mean`` (the fullest expert's pairs over
        the mean expert's, mean over layers), and the pairs counter.
        Idle slots route too (their rows are computed and thrown away);
        the counter counts the live ones."""
        per_layer = np.asarray(counts).reshape(-1, self.spec.n_experts)
        _m_tokens_routed.inc(occupancy * self._pairs_per_token)
        if sp is None:
            return
        mean = np.maximum(per_layer.mean(axis=1), 1e-9)
        sp.attrs["experts_hit"] = float((per_layer > 0).sum(axis=1).mean())
        sp.attrs["expert_load_max_over_mean"] = float(
            (per_layer.max(axis=1) / mean).mean()
        )

    def _charge_prefill(
        self, timings: dict, prog, tokens: int, recompute: int,
        wall: float, routed: Optional[int] = None,
    ) -> None:
        """One prefill dispatch of ``prog`` took ``wall`` seconds and put
        ``tokens`` prompt tokens through the model, ``recompute`` of
        which a preemption had computed once; ``routed`` (a chunk's
        token-expert pairs) marks the chunk program. Owed, not done in
        the gap: nothing here is an argument of the next program."""
        self._charge_collectives()
        timings["prefill_tokens"] = timings.get("prefill_tokens", 0) + tokens
        if recompute:
            timings["recomputed_tokens"] = (
                timings.get("recomputed_tokens", 0) + recompute
            )
            _m_recomputed.inc(recompute)
        timings["prefill_s"] = timings.get("prefill_s", 0.0) + wall
        self._charge_flops(timings, prog)
        if routed is not None:
            timings["prefill_chunks"] = timings.get("prefill_chunks", 0) + 1
            _m_prefill_chunks.inc()
            if routed:
                _m_tokens_routed.inc(routed)

    # -- speculative decoding ---------------------------------------------

    def _spec_slot_k(self, act: _Active) -> int:
        """This step's EFFECTIVE draft length for one slot: the per-slot
        adaptive k (seeded from the compiled static k), clamped so the
        verify span never outruns the sequence bound or the request's
        remaining budget, then clamped to the pages actually granted —
        speculation degrades to a shorter k under pool pressure, it
        never preempts live work for lookahead room."""
        if act.spec_k < 0:
            act.spec_k = self.draft_len
        k = min(
            act.spec_k,
            self.draft_len,
            act.remaining - 1,
            self.max_seq_len - act.length,
        )
        k = max(0, k)
        if k > 1:
            # QoS: low-priority slots surrender speculative page
            # appetite first under pool pressure (identity when the
            # plane is off). Acceptance is exact-match, so a shorter
            # k never changes emitted bytes.
            k = _tenancy.clamp_spec_k(
                k, act.req.priority,
                self.pool.pages_free, self.pool.num_pages,
            )
        if k > 0:
            try:
                act.seq.ensure(act.length + k)
            except PagePoolExhausted:
                k = max(0, act.seq.capacity - act.length)
        return k

    def _draft_advance(self, ready: List[Tuple[int, _Active]]):
        """ONE draft dispatch per engine step: each slot ingests its
        next ``k + 1``-token window of un-ingested tokens (positions
        ``draft_pos .. length - 1``, teacher-forced) through phase 1.
        Slots whose window reaches the newest token are CAUGHT UP —
        their proposals are live this step; slots still lagging (a
        fresh long prefill, a preemption replay) advance one window per
        step and decode plainly meanwhile, exactly the bounded-stall
        discipline chunked prefill established: catch-up never turns
        one engine step into O(prompt / k) back-to-back dispatches that
        would spike every concurrent stream's inter-token latency.
        Returns ``({slot: [k] proposals}, caught_up_slots)``."""
        s = self.max_slots
        w = self.draft_len + 1
        g = self._draft_group
        mp = self._max_pages
        trash = self.pool.trash_page
        ctx = np.zeros((s, w), np.int32)
        starts = np.zeros(s, np.int32)
        n_ctx = np.ones(s, np.int32)
        ptabs = np.full((s, mp), trash, np.int32)
        temps = np.zeros(s, np.float32)
        seeds = np.zeros(s, np.int32)
        top_ps = np.ones(s, np.float32)
        caught_up: set = set()
        for idx, act in ready:
            l = act.length
            if act.draft_pos >= l:
                # caught up: re-ingest the newest token (rewrites
                # identical draft k/v) so phase 1 seeds proposals
                # from its logits
                act.draft_pos = l - 1
            lag = l - act.draft_pos
            n = min(lag, w)
            if lag <= w:
                caught_up.add(idx)
            start = act.draft_pos
            # slice just the window (positions start .. start+n-1) out
            # of prompt/generated — materializing the whole sequence
            # here would put O(length) host copies per slot on every
            # step's inter-token critical path
            end = start + n
            plen = len(act.req.prompt)
            window: List[np.ndarray] = []
            if start < plen:
                window.append(act.req.prompt[start : min(end, plen)])
            if end > plen:
                window.append(
                    np.asarray(
                        act.generated[max(0, start - plen) : end - plen],
                        np.int32,
                    )
                )
            ctx[idx, :n] = (
                window[0]
                if len(window) == 1
                else np.concatenate(window)
            )
            starts[idx] = start
            n_ctx[idx] = n
            ptabs[idx] = act.seq.table(mp)
            temps[idx] = act.req.temperature
            seeds[idx] = act.req.seed
            top_ps[idx] = act.req.top_p
        args = (ctx, starts, n_ctx, ptabs, temps, seeds, top_ps)
        self._record_program("draft", self._draft_dev, g.k, *args)

        def dispatch():
            import jax

            return jax.block_until_ready(
                self._draft_jit(self._draft_dev, g.k, g.v, *args)
            )

        with _span(
            "serve.draft", chain=self._phases, occupancy=len(ready)
        ) as sp:
            g.k, g.v, out = run_with_retries(
                dispatch, what="serve.draft"
            )
            self._note_hosted(sp, self._draft_jit)
        # no _charge_collectives: the draft program is replicated —
        # it runs no cross-chip gathers even under a TP mesh
        for idx, act in ready:
            act.draft_pos = int(starts[idx]) + int(n_ctx[idx])
        props = np.asarray(out)
        return {idx: props[idx] for idx, _ in ready}, caught_up

    def _spec_batch(self, ready: List[Tuple[int, _Active]]) -> None:
        """One SPECULATIVE step for the decode batch: draft proposals,
        one batched ``[max_slots, k + 1]`` verify dispatch, exact-match
        acceptance. Every emitted token is the TARGET's own sampled
        token (the verify program applies the decode rule at each
        absolute position), so streams stay byte-identical to solo
        non-speculative decode; the draft only decides how many
        positions one dispatch covers. Rejected speculative KV rolls
        back via length bookkeeping alone — positions past the accepted
        length are never read before the next step overwrites them."""
        s = self.max_slots
        kmax = self.draft_len
        c = kmax + 1
        t_draft0 = time.perf_counter()
        k_eff = {idx: self._spec_slot_k(act) for idx, act in ready}
        proposals, caught_up = self._draft_advance(ready)
        for idx, _ in ready:
            if idx not in caught_up:
                # the draft is still windowing this slot's backlog
                # (long prefill, preemption replay): decode plainly
                # this step — its proposals are mid-catch-up garbage
                k_eff[idx] = 0
        draft_wall = time.perf_counter() - t_draft0
        toks = np.zeros((s, c), np.int32)
        starts = np.zeros(s, np.int32)
        n_valid = np.ones(s, np.int32)
        ptabs = np.full(
            (s, self._max_pages), self.pool.trash_page, np.int32
        )
        temps = np.zeros(s, np.float32)
        seeds = np.zeros(s, np.int32)
        top_ps = np.ones(s, np.float32)
        for idx, act in ready:
            k = k_eff[idx]
            toks[idx, 0] = act.generated[-1]
            toks[idx, 1 : 1 + k] = proposals[idx][:k]
            starts[idx] = act.length - 1  # the pending token's position
            n_valid[idx] = k + 1
            ptabs[idx] = act.seq.table(self._max_pages)
            temps[idx] = act.req.temperature
            seeds[idx] = act.req.seed
            top_ps[idx] = act.req.top_p
        args = (toks, starts, n_valid, ptabs, temps, seeds, top_ps)
        pool = self.pool
        self._record_program("verify", self._params_dev, pool.k, *args)

        def dispatch():
            import jax

            _chaos.site("serve.verify")
            return jax.block_until_ready(
                self._verify_jit(self._params_dev, pool.k, pool.v, *args)
            )

        t0 = time.perf_counter()
        with _span(
            "serve.verify", chain=self._phases, occupancy=len(ready)
        ) as sp:
            pool.k, pool.v, u = run_with_retries(
                dispatch, what="serve.verify"
            )
            self._note_hosted(sp, self._verify_jit)
        verify_wall = self._wait_returned_t - t0
        _m_verify_s.observe(verify_wall)
        self._charge_collectives()
        u = np.asarray(u)
        t_roll0 = time.perf_counter()
        for idx, act in ready:
            k = k_eff[idx]
            target = u[idx]
            prop = proposals[idx]
            accept = 0
            while accept < k and int(prop[accept]) == int(target[accept]):
                accept += 1
            l0 = act.length
            if idx in caught_up:
                # draft KV stands for the accepted proposals the scan
                # wrote (t_1 .. t_{k-1}); everything past that rolls
                # back by this counter alone. Lagging slots keep the
                # window progress _draft_advance recorded instead.
                act.draft_pos = l0 + min(accept, kmax - 1)
            if act.spec_k < 0:
                act.spec_k = kmax
            if k > 0 and accept == k:
                act.spec_k = min(kmax, act.spec_k + 1)  # hot: grow
            elif k > 0 and accept * 2 < k:
                act.spec_k = max(1, act.spec_k - 1)  # cold: shrink
            self._spec_proposed += k
            self._spec_accepted += accept
            if k:
                _m_spec_proposed.inc(k)
            if accept:
                _m_spec_accepted.inc(accept)
            timings = act.req.handle.timings
            timings["draft_s"] = (
                timings.get("draft_s", 0.0) + draft_wall
            )
            timings["verify_s"] = (
                timings.get("verify_s", 0.0) + verify_wall
            )
            timings["spec_proposed"] = (
                timings.get("spec_proposed", 0) + k
            )
            timings["spec_accepted"] = (
                timings.get("spec_accepted", 0) + accept
            )
            timings["spec_rolled_back"] = (
                timings.get("spec_rolled_back", 0) + (k - accept)
            )
            spec_share = 1.0 / max(1, len(ready))
            self._charge_flops(timings, self._draft_jit, spec_share)
            self._charge_flops(timings, self._verify_jit, spec_share)
            # emit the target's tokens: the accepted run plus the
            # correction/bonus token — u[accept] is what solo decode
            # would have emitted at that position either way
            for j in range(accept + 1):
                self._emit(idx, act, int(target[j]))
                if self.scheduler.slots[idx] is not act:
                    break  # EOS or budget mid-burst: the rest is moot
        roll_wall = time.perf_counter() - t_roll0
        for idx, act in ready:
            if self.scheduler.slots[idx] is act:
                t = act.req.handle.timings
                t["rollback_s"] = t.get("rollback_s", 0.0) + roll_wall
        if self._spec_proposed:
            _m_spec_accept_rate.set(
                self._spec_accepted / self._spec_proposed,
                engine=self.name,
            )

    def _emit(self, idx: int, act: _Active, tok: int) -> None:
        """The part of a token's emission the next program waits for:
        the token joins ``generated`` (the next decode's input), and a
        finished slot goes back with its pages, so :meth:`admit` fills
        it in the very next step. Handing the token and the end mark to
        the handle, and what is counted and charged with them, is owed
        (:meth:`_deliver_token`, :meth:`_deliver_finish`)."""
        first = act.req.emitted == 0 and not act.generated
        act.generated.append(tok)
        self._owe(self._deliver_token, act, tok, first)
        eos = act.req.eos_id
        if (eos is not None and tok == eos) or act.remaining <= 0:
            # the cost record counts the pages the slot held to its end
            self._owe(self._deliver_finish, act, len(act.seq.pages))
            self.scheduler.detach(idx)

    # -- deferred delivery -------------------------------------------------

    def _owe(self, fn, *args) -> None:
        """Queue ``fn(*args)`` for the next delivery: work a step owes
        that no program's arguments need. Stepping thread, step lock
        held; run in order by :meth:`_drain`."""
        self._owed.append((fn, args))

    def _deliver_token(self, act: _Active, tok: int, first: bool) -> None:
        """Hand one token to its handle — this wakes the stream's
        thread — and stamp what a client sees: TTFT and ITL are taken
        at the hand-over, not when the program that made the token
        returned."""
        handle = act.req.handle
        if handle.done:
            # closed past the step lock meanwhile (a wedged engine's
            # handles, serve/fleet.py::_fence): what was not delivered
            # before the end mark never is, and a replay recomputes it
            return
        now = time.monotonic()
        handle._emit(tok)
        self._delivered[0] += 1
        if first:
            _m_ttft.observe(now - act.req.submitted_at)
        elif act.last_emit_t is not None:
            _m_itl.observe(now - act.last_emit_t)
        if act.last_emit_t is not None:
            t = handle.timings
            t["decode_s"] = t.get("decode_s", 0.0) + now - act.last_emit_t
        act.last_emit_t = now

    def _deliver_finish(self, act: _Active, kv_pages: int) -> None:
        """Close a finished request's handle, after its last token (the
        queue keeps the order): the cost record first, as
        :meth:`Scheduler.finish` has it."""
        handle = act.req.handle
        if handle.done:
            return
        try:
            self._account_request(act, None, kv_pages)
        except Exception:  # an accounting bug must not hang a handle
            logger.warning("request cost record failed", exc_info=True)
        _m_requests.inc(status="completed")
        handle._finish(None)
        self._delivered[1] += 1

    def _drain(self, under_program: bool) -> Tuple[int, int]:
        """Run everything owed, oldest first; returns the tokens and end
        marks handed over. Delivery cannot fail a step: what an entry
        raises is logged and the rest still runs."""
        owed = self._owed
        tokens, finished = self._delivered
        while owed:
            fn, args = owed.popleft()
            try:
                fn(*args)
            except Exception:
                logger.warning(
                    "deferred delivery step %s failed",
                    getattr(fn, "__name__", fn), exc_info=True,
                )
        tokens = self._delivered[0] - tokens
        finished = self._delivered[1] - finished
        if tokens:
            _m_tokens.inc(tokens)
            _m_delivered.inc(tokens)
            if under_program:
                _m_delivered_under_program.inc(tokens)
        return tokens, finished

    def _deliver_hosted(self) -> float:
        """``after_issue`` of every step program: the program is issued,
        the device works, and the thread is about to sleep on it with
        the interpreter lock released — the streams' threads run then,
        not in the gap before the next program. Returns the seconds it
        took (the program's ``wait_s`` leaves them out)."""
        if not self._owed:
            return 0.0
        t0 = time.perf_counter()
        tokens, _ = self._drain(under_program=True)
        seconds = time.perf_counter() - t0
        self._hosted[0] += tokens
        self._hosted[1] += seconds
        return seconds

    def _note_hosted(self, sp, prog) -> None:
        """The wait of ``prog`` (an instrumented step program) just
        returned: note when, for the next decode's ``host_gap_s``, and
        onto its span how its wall split — ``issue_s``, what it hosted
        (``delivered_tokens``, ``deliver_s``) and ``wait_s``, which sum
        to the registry's dispatch wall of the call — and, on a live
        span only, this thread's CPU clock, for ``host_gap_offcpu_s``."""
        self._wait_returned_t = time.perf_counter()
        tokens, seconds = self._hosted
        self._hosted = [0, 0.0]
        if sp is None:
            self._wait_returned_cpu = None
            return
        self._wait_returned_cpu = (threading.get_ident(), time.thread_time())
        sp.attrs.update(
            issue_s=prog.issue_s, delivered_tokens=tokens,
            deliver_s=seconds, wait_s=prog.wait_s,
        )

    def _deliver_now(self, chain: Optional[_SpanChain] = None) -> None:
        """Deliver with no program to hide behind, in a span of its own
        so that a device gap it causes has a name. Step lock held."""
        if not self._owed:
            return
        with _span("serve.deliver", chain=chain) as sp:
            tokens, finished = self._drain(under_program=False)
            if sp is not None:
                sp.attrs.update(tokens=tokens, finished=finished)

    def _deliver_idle(self) -> None:
        """The step loop has no next program in sight (it goes idle, or
        hands control back to its caller): deliver as a phase of its
        own."""
        with self._step_lock:
            self._deliver_now(chain=self._phases)

    def _deliver_unless_wedged(self) -> None:
        """The scheduler's ``before_release``: a request is about to be
        failed, preempted, expired or closed, so its tokens go out
        first. Whoever comes here holds the step lock or finds it free,
        but for the fleet's fence of a wedged engine, which fails the
        handles past a step that may never return: then what that step
        owes stays undelivered for good (:meth:`_deliver_token`)."""
        if self._step_lock.acquire(blocking=False):
            try:
                self._deliver_now()
            finally:
                self._step_lock.release()

    @staticmethod
    def _charge_flops(timings: dict, prog, share: float = 1.0) -> None:
        """Accumulate one dispatch's estimated FLOPs into a request's
        cost ledger: ``share`` of the program's ``ProgramRecord`` FLOP
        estimate (batched dispatches apportion equally over the
        requests the batch served). Silently zero until the program's
        first-dispatch cost estimate lands, and under ``TFT_OBS=0``."""
        rec = getattr(prog, "record", None)
        flops = getattr(rec, "flops", None) if rec is not None else None
        if flops:
            timings["est_flops"] = (
                timings.get("est_flops", 0.0) + float(flops) * share
            )

    def _account_request(
        self, act: _Active, error, kv_pages: Optional[int] = None
    ) -> None:
        """The request's terminal cost record (``obs/requests.py``).
        As the scheduler's finish hook (a failure) it is taken while the
        slot still holds its pages, so holdings are countable; a
        finished request's is owed, with the ``kv_pages`` the slot held
        when it went back. ``timings`` gets the same keys so the HTTP
        response echoes them."""
        req = act.req
        t = req.handle.timings
        t["tokens"] = req.emitted + len(act.generated)
        if kv_pages is None:
            kv_pages = len(act.seq.pages)
        t["kv_pages"] = max(int(t.get("kv_pages", 0)), kv_pages)
        if req.tenant:
            t["tenant"] = req.tenant
        _obs_requests.record_request(
            request_id=req.request_id,
            engine=self.name,
            tenant=req.tenant,
            status="failed" if error is not None else "completed",
            tokens=t["tokens"],
            kv_pages=t["kv_pages"],
            prefix_cached_tokens=int(t.get("prefix_cached_tokens", 0)),
            spec_proposed=int(t.get("spec_proposed", 0)),
            spec_accepted=int(t.get("spec_accepted", 0)),
            est_flops=float(t.get("est_flops", 0.0)),
            queue_wait_s=t.get("queue_wait_s"),
            prefill_s=t.get("prefill_s"),
            decode_s=t.get("decode_s"),
            wait_slots_s=t.get("wait_slots_s", 0.0),
            wait_pages_s=t.get("wait_pages_s", 0.0),
            requeue_wait_s=t.get("requeue_wait_s", 0.0),
            preemptions=int(t.get("preemptions", 0)),
            prefill_tokens=int(t.get("prefill_tokens", 0)),
            recomputed_tokens=int(t.get("recomputed_tokens", 0)),
        )

    def _refresh_gauges(self) -> None:
        _m_queue_depth.set(float(self.scheduler.queue_depth))
        _m_active_slots.set(
            float(sum(s is not None for s in self.scheduler.slots))
        )
        _m_pages_in_use.set(float(self.pool.pages_in_use))
        _m_pages_shared.set(float(self.pool.pages_shared))
        if self.layout is not None:
            pool = self.pool
            for kind, n in pool.kind_in_use.items():
                _m_kind_pages.set(float(n), kind=kind)
            now = (
                pool.kind_allocated.get("window", 0),
                pool.kind_released.get("window", 0),
            )
            _m_window_allocated.inc(now[0] - self._window_counted[0])
            _m_window_released.inc(now[1] - self._window_counted[1])
            self._window_counted = list(now)
        if _tenancy.enabled():
            _tenancy.update_active_gauge(self.scheduler.slots)

    def run_until_idle(self) -> None:
        """Drive :meth:`step` until queue and slots are empty (the
        synchronous mode — tests and batch jobs)."""
        self._phases.reset()
        try:
            while self._step_once():
                pass
        finally:
            self._deliver_idle()

    def defragment(self):
        """Compact live KV pages to the lowest pool indices between steps
        (page tables are rebuilt from the sequences every step, so the
        renumbering is transparent to in-flight generation). Returns the
        ``old -> new`` page remap (prefix-cache entries and pending
        copy-on-write donors are renumbered too). See
        :meth:`PagePool.defragment`."""
        with self._step_lock:
            return self._defragment_locked()

    # -- live slot migration (serve/tiers.py) ------------------------------

    def detach_slot(self, request_id: int, reason: str = "handoff"):
        """Serialize and remove one decode-phase slot for live
        migration: the slot's page rows (target + every page group)
        come back as a host :class:`~.tiers.SlotSnapshot`, its pages
        return to this pool, and its handle stays OPEN — the stream
        continues wherever :meth:`attach_slot` lands the snapshot.
        Returns ``None`` when the request is not currently migratable
        (unknown, queued, still prefilling). See ``serve/tiers.py``."""
        from . import tiers as _tiers

        return _tiers.export_slot(self, request_id, reason=reason)

    def attach_slot(self, snap, _handle_factory=None):
        """Adopt a migrated slot: allocate its page set, write the
        snapshot's rows (eager indexing like ``_apply_cow`` — zero new
        step programs), and seat it directly in decode phase. Returns
        the new handle (``_handle_factory`` substitutes the fleet's
        relay, exactly like :meth:`submit`). Raises
        :class:`~.tiers.TierMigrationError` /
        :class:`~.scheduler.QueueFullError` /
        :class:`~..utils.failures.PagePoolExhausted` with the engine
        untouched — the caller's fallback still owns the request."""
        from . import tiers as _tiers

        return _tiers.restore_slot(self, snap, _handle_factory=_handle_factory)

    # -- supervision -------------------------------------------------------

    def inject_fault(self, error: BaseException) -> None:
        """Queue a hard fault for the NEXT step: the stepping loop raises
        it at the step boundary and the supervisor fails every in-flight
        handle with it. This is how an external supervisor (the fleet
        router, ``serve/fleet.py``) kills a replica without racing a
        step in progress — calling :meth:`_fail_inflight` from another
        thread would contend with the step lock and could let the doomed
        engine keep emitting (or, after device-state corruption, emit
        WRONG bytes) until the contender wins. ``healthy`` flips now so
        ``submit`` sheds immediately; the drain lands within one step."""
        self.healthy = False
        self._poison = error
        with self.scheduler._lock:
            self.scheduler._lock.notify_all()  # wake an idle stepping loop

    def _fail_inflight(self, error: BaseException) -> None:
        """The fail-fast path: close EVERY in-flight handle (active slots
        and the whole admission queue) with the real error, NOW, and mark
        the engine unhealthy until :meth:`restart`. A consumer must see
        a doomed stream's failure within a step — never hang to its
        timeout against an engine that will not produce another token."""
        self.healthy = False
        reason = _fail_reason(error)
        with self._step_lock:
            n = self.scheduler.fail_all(error)
        if n:
            _m_requests.inc(n, status="failed")
            _m_handles_failed.inc(n, reason=reason)
        self._refresh_gauges()
        # the flight recorder's moment: every consumer has its error, so
        # snapshotting here cannot delay anyone — dump the black box
        _flight.record(
            "serve", "engine_fatal", reason=reason,
            error=f"{type(error).__name__}: {_first_line(error)}",
            handles_failed=n,
        )
        _flight.dump_bundle(
            "engine_fatal",
            health=self.health(),
            series_prefix="serve.",
            extra={
                "error_type": type(error).__name__,
                "error": str(error)[:2000],
                "handles_failed": n,
            },
        )

    def restart(self) -> "GenerationEngine":
        """Rebuild device state from host-side scheduler progress after a
        crash (lost pool arrays, a fatal step error). Every active
        sequence is preempted — its progress folds into its prompt, so
        re-admission re-prefills prompt + emitted tokens and the stream's
        emitted bytes stay identical — the page pool is re-zeroed, and
        the engine is marked healthy again. The compiled step programs
        survive (every shape is unchanged), so recovery adds zero
        recompiles: ``num_step_programs`` stays within its budget
        (<= 2, or <= 3 with chunked prefill / the prefix cache)."""
        if self._stop_wedged:
            # the old stepping thread never exited; flipping healthy here
            # would accept work nothing can step (start() still refuses
            # while _thread is set). stop() again to retry the join.
            raise RuntimeError(
                "cannot restart a wedged engine: the stepping thread "
                "never exited its stop join — stop() again to retry, or "
                "recycle the process"
            )
        with self._step_lock:
            # youngest-first so the OLDEST request ends up at the queue
            # front (each preempt requeues at the front) — re-admission
            # preserves the oldest-first service order
            for idx, _ in reversed(self.scheduler.active):
                self.scheduler.preempt(idx)
            self.pool.reset()
            if self.prefix_cache is not None:
                # the cached k/v died with the device state; reset()
                # already rebuilt the free list, so drop host entries
                # WITHOUT releasing pages
                self.prefix_cache.clear(free_pages=False)
            self._consecutive_ooms = 0
            self._poison = None  # a queued kill is moot on rebuilt state
            self.healthy = True
            self._last_step_t = time.monotonic()
        _m_restarts.inc()
        _flight.record(
            "serve", "engine_restart",
            requeued=self.scheduler.queue_depth,
        )
        _flight.dump_bundle(
            "engine_restart",
            health=self.health(),
            series_prefix="serve.",
            extra={"requeued": self.scheduler.queue_depth},
        )
        with self.scheduler._lock:
            self.scheduler._lock.notify_all()  # wake the stepping thread
        logger.warning(
            "engine restarted: device state rebuilt, %d request(s) "
            "requeued for recompute",
            self.scheduler.queue_depth,
        )
        return self

    def swap_weights(self, model) -> Dict[str, object]:
        """Hot weight swap: replace the served checkpoint in place.

        Weights enter every compiled step program as an ARGUMENT (the
        swap-safe design noted at construction), so swapping is one
        ``device_put`` plus a pointer flip under the step lock — **zero
        recompiles** (shapes and dtypes are validated identical, so the
        jit caches all hit) and zero dropped streams (in-flight
        sequences simply decode their next token under the new
        weights; the step between old and new is a clean boundary
        because the lock excludes a half-dispatched step).

        ``model`` is a :class:`~tensorframes_tpu.models.TransformerLM`
        or its raw params dict. A checkpoint whose tree structure,
        shapes, dtypes, or head count differ raises ``ValueError``
        *before* anything is touched — the rollout machinery
        (``serve/membership.py``) treats that exactly like a failed
        probe: roll back, halt the rollout. Returns the PREVIOUS params
        dict so callers can roll back with a second ``swap_weights``.
        Under tensor parallelism the new copy is sharded at rest with
        the same specs as the original (structure equality makes them
        reusable)."""
        import jax

        params = getattr(model, "params", model)
        if not isinstance(params, dict) or "blocks" not in params:
            raise ValueError(
                "swap_weights expects a TransformerLM or its params "
                f"dict; got {type(params).__name__}"
            )
        old = self._host_params
        if int(params.get("n_heads", 0)) != int(old.get("n_heads", 0)):
            raise ValueError(
                f"swap_weights: head count mismatch (served "
                f"{old.get('n_heads')}, checkpoint {params.get('n_heads')})"
            )
        new_host = {k: v for k, v in params.items() if k != "n_heads"}
        old_host = {k: v for k, v in old.items() if k != "n_heads"}

        def _sig(tree):
            return jax.tree.map(
                lambda a: (tuple(a.shape), str(np.dtype(a.dtype))), tree
            )

        if jax.tree.structure(new_host) != jax.tree.structure(old_host):
            raise ValueError(
                "swap_weights: checkpoint tree structure differs from "
                "the served weights — same architecture required for a "
                "hot swap"
            )
        if _sig(new_host) != _sig(old_host):
            raise ValueError(
                "swap_weights: checkpoint shapes/dtypes differ from the "
                "served weights — same shapes required (a shape change "
                "would recompile every step program; restart instead)"
            )
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            dev = jax.device_put(
                new_host,
                jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s),
                    self._tp_param_specs,
                    is_leaf=lambda x: not isinstance(x, (dict, list)),
                ),
            )
        else:
            dev = jax.device_put(new_host, self._device)
        with self._step_lock:
            self._params_dev = dev
            self._host_params = params
        _flight.record("serve", "weight_swap", engine=self.name)
        logger.info(
            "engine %s: weights hot-swapped (zero recompiles)", self.name
        )
        return old

    def health(self) -> Dict[str, object]:
        """Liveness snapshot for ``GET /healthz``: the last-step watchdog
        age, queue/batch/pool occupancy, and the unhealthy flags the
        supervisor and :meth:`stop` raise."""
        thread = self._thread
        return {
            "healthy": bool(self.healthy and not self._stop_wedged),
            "last_step_age_s": round(
                time.monotonic() - self._last_step_t, 3
            ),
            # the step in progress compiles a program it has not run
            # before: its age is compile time, not a wedge
            "compiling": self._compiling,
            "queue_depth": self.scheduler.queue_depth,
            "active_slots": sum(
                s is not None for s in self.scheduler.slots
            ),
            "pages_in_use": self.pool.pages_in_use,
            "pages_capacity": self.pool.num_pages,
            "pages_shared": self.pool.pages_shared,
            # the CHOSEN perf knobs (page size may come from the
            # measured-best hint or a tuned winner — ISSUE 13): the
            # probe shows what this engine actually runs with
            "page_size": self.page_size,
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            # tensor parallelism (serve/tp.py): degree 1 = solo. Under
            # TP each page spans the shards, so "per shard" pages equal
            # the pool's logical counts while the BYTES per chip are
            # the pool's divided by the degree — the capacity-scaling
            # view operators size HBM with (ISSUE 14)
            "tp_degree": self.tp_degree,
            "tp": (
                None
                if self.mesh is None
                else {
                    "degree": self.tp_degree,
                    "axis": self._tp_axis,
                    "pages_capacity": self.pool.num_pages,
                    "pages_in_use_per_shard": self.pool.pages_in_use,
                    "kv_bytes_per_shard": int(
                        (self.pool.k.nbytes + self.pool.v.nbytes)
                        // max(1, self.tp_degree)
                    ),
                    "collective_seconds_per_step_est": round(
                        self._collective_step_s, 6
                    ),
                    "collective_bytes_per_step_est": int(
                        self._collective_bytes_per_step
                    ),
                }
            ),
            "prefix_cache": (
                self.prefix_cache.stats()
                if self.prefix_cache is not None
                else None
            ),
            # speculative decoding (docs/serving_llm.md): None with no
            # draft model; the acceptance rate is the draft-length
            # controller's signal and the tuning cookbook's first read
            "speculative": (
                None
                if not self.draft_len
                else {
                    "draft_len": self.draft_len,
                    "proposed": self._spec_proposed,
                    "accepted": self._spec_accepted,
                    "acceptance_rate": round(
                        self._spec_accepted
                        / max(1, self._spec_proposed),
                        4,
                    ),
                }
            ),
            "stepping_thread_alive": (
                thread.is_alive() if thread is not None else None
            ),
            "stop_wedged": self._stop_wedged,
        }

    # -- background serving ------------------------------------------------

    def start(self) -> "GenerationEngine":
        """Step in a daemon thread until :meth:`stop` — the serving mode
        (pair with the scoring server's generate endpoint)."""
        if self._thread is not None:
            raise RuntimeError("engine already started")
        if self._long and not self.program_signatures:
            with self._step_lock:
                self._warm_programs()
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._supervised_loop, daemon=True
        )
        self._thread.start()
        return self

    def _supervised_loop(self) -> None:
        """The serving loop under supervision. Recoverable failures never
        reach here (transient retries and OOM recovery live inside
        :meth:`step`); whatever does escape is terminal for the in-flight
        work, so every handle is failed promptly with the real error and
        the engine flips unhealthy (submit sheds, ``/healthz`` goes red)
        until :meth:`restart`. The loop itself keeps running either way —
        it never dies silently with streams still attached."""
        try:
            while not self._stop.is_set():
                try:
                    worked = self._step_once()
                except Exception as e:
                    # split, not splitlines: str(e) may be empty (bare
                    # asserts), and "".splitlines()[0] would kill the
                    # loop this handler exists to keep alive
                    logger.error(
                        "generation step failed terminally (%s); failing "
                        "all in-flight requests and marking the engine "
                        "unhealthy — restart() to recover",
                        f"{type(e).__name__}: "
                        + str(e).split("\n", 1)[0][:200],
                    )
                    self._fail_inflight(e)
                    worked = False
                if not worked:
                    # no program follows: nothing waits out the sleep
                    self._deliver_idle()
                    with _span(
                        "serve.idle_wait", chain=self._phases
                    ), self.scheduler._lock:
                        if not self.scheduler._waiting:
                            self.scheduler._lock.wait(0.02)
        except BaseException as e:  # the supervisor must never die silently
            if not self._stop.is_set():
                logger.error("stepping thread died", exc_info=True)
                self._fail_inflight(e)
            raise

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        with self.scheduler._lock:
            self.scheduler._lock.notify_all()
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            # pretending the stop worked would hand the caller a zombie
            # stepping thread; surface it loudly, shed new work, and keep
            # the thread reference so a later stop() can retry the join
            logger.warning(
                "stepping thread did not stop within 10s (wedged device "
                "call?); engine marked unhealthy — stop() again to retry"
            )
            self._stop_wedged = True
            self.healthy = False
            return
        self._stop_wedged = False
        self._thread = None
        # anything still in flight will never get another step: fail the
        # handles now instead of stranding their consumers
        with self._step_lock:
            n = self.scheduler.fail_all(
                RuntimeError("engine stopped with the request in flight")
            )
        if n:
            _m_requests.inc(n, status="failed")
            _m_handles_failed.inc(n, reason="shutdown")
            logger.warning(
                "engine stopped with %d request(s) in flight; their "
                "handles were failed",
                n,
            )
            self._refresh_gauges()

    def __enter__(self) -> "GenerationEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- convenience -------------------------------------------------------

    def generate(
        self,
        prompts: Sequence[Sequence[int]],
        max_new_tokens: int,
        **kw,
    ) -> List[np.ndarray]:
        """Submit every prompt, run to completion, return each request's
        generated tokens (prompt excluded). Synchronous when no
        background thread is running."""
        handles = [self.submit(p, max_new_tokens, **kw) for p in prompts]
        if self._thread is None:
            self.run_until_idle()
        timeout = get_config().serve_result_timeout_s
        return [h.result(timeout=timeout) for h in handles]
