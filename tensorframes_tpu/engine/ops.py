"""The local (single-host) execution engine: the nine-function public API.

Analog of the reference's ``DebugRowOps`` execution engine
(``/root/reference/src/main/scala/org/tensorframes/impl/DebugRowOps.scala:281-593``)
re-designed for XLA:

- where the reference opens a TF ``Session`` per Spark task and feeds NIO
  buffers through JNI (``performMap``, ``DebugRowOps.scala:766-803``), this
  engine jits the captured program once and executes it per partition block;
  XLA's jit cache plays the role of the broadcast graph + session pool;
- where the reference merges reduce partials two rows at a time *on the
  driver* through a local session (``reducePairBlock``,
  ``DebugRowOps.scala:741-750``), this engine folds partials on device with
  one fixed ``[2, ...]``-shaped merge program (and, distributed, replaces
  the fold with collectives — see ``tensorframes_tpu.parallel``);
- where the reference's ``TensorFlowUDAF`` buffers rows per group and
  compacts through TF when full (``DebugRowOps.scala:601-695``), ``aggregate``
  computes per-row partials with ``vmap`` and combines them with a single
  *segmented associative scan* on device — one XLA program for any number of
  groups, instead of a JVM shuffle.

Semantics parity: lazy maps / eager reduces (``Operations.scala:20-135``),
fetches name the new columns, collisions error, no implicit casting, reduce
naming conventions ``x_input`` / ``x_1``+``x_2``, trim maps may change the
row count (``TrimmingOperationsSuite.scala:25-39``).
"""

from __future__ import annotations

import inspect
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..capture import CapturedGraph, Node, TensorSpec, build_graph
from ..capture import dsl as _dsl
from ..frame import GroupedFrame, TensorFrame
from ..frame import transfer as _transfer
from ..frame.table import _build_column, _ColumnData
from ..obs import span as _span
from ..obs import programs as _programs
from ..obs.metrics import counter as _counter
from ..schema import ColumnInfo, FrameInfo, Shape, Unknown
from ..utils import ensure_x64, get_logger
from ..utils.failures import record_oom_split
from .validation import (
    InputNotFoundError,
    InvalidDimensionError,
    check_output_collisions,
    resolve_column,
    validate_map_inputs,
    validate_reduce_block_graph,
    validate_reduce_row_graph,
)

__all__ = [
    "map_blocks",
    "map_rows",
    "reduce_blocks",
    "reduce_rows",
    "aggregate",
    "analyze",
    "print_schema",
    "explain",
    "block",
    "row",
]

logger = get_logger("engine")

# re-export the auto-placeholder helpers at the API level (reference
# ``core.py:397-450``)
block = _dsl.block
row = _dsl.row

#: per-callable CapturedGraph memo (see _graph_from_callable)
_callable_graphs: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
#: (code, spec) signatures already captured once — used to warn (once per
#: signature) on recompile churn from lambdas recreated per call
_seen_callable_codes: set = set()
_warned_callable_codes: set = set()

# -- engine telemetry (tensorframes_tpu.obs; docs/observability.md) ---------
_m_graph_hits = _counter(
    "engine.graph_memo_hits_total",
    "Callable-frontend captures resolved from the per-function memo",
)
_m_graph_misses = _counter(
    "engine.graph_memo_misses_total",
    "Callable-frontend captures that traced a fresh CapturedGraph",
)
_m_recapture = _counter(
    "engine.callable_recapture_total",
    "Re-captures of identical code under a new function identity "
    "(recompile churn: a lambda recreated per call)",
)
_m_jit_builds = _counter(
    "engine.jit_cache_builds_total",
    "jax.jit wrappers built for a CapturedGraph (first use)",
)
_m_jit_reuse = _counter(
    "engine.jit_cache_reuse_total",
    "Engine calls that reused a CapturedGraph's existing jit wrapper",
)
_m_rows = _counter(
    "engine.rows_processed_total",
    "Input rows processed, by op",
    labels=("op",),
)
_m_blocks = _counter(
    "engine.blocks_processed_total",
    "Device dispatches (partition blocks / row chunks), by op",
    labels=("op",),
)
# pre-bound series for the dispatch loops (label resolution paid once)
_m_blocks_map_blocks = _m_blocks.bind(op="map_blocks")
_m_blocks_map_rows = _m_blocks.bind(op="map_rows")
_m_rows_map_blocks = _m_rows.bind(op="map_blocks")
_m_rows_map_rows = _m_rows.bind(op="map_rows")


# ---------------------------------------------------------------------------
# graph normalization: Node(s) | CapturedGraph | plain callable
# ---------------------------------------------------------------------------


def _as_graph(
    fetches,
    df: TensorFrame,
    *,
    cell_inputs: bool,
    feed_dict: Optional[Dict[str, str]] = None,
    constants: Optional[Dict[str, Any]] = None,
    schema: Optional[FrameInfo] = None,
) -> CapturedGraph:
    """Accept the three frontend forms and return a CapturedGraph.

    ``cell_inputs=False``: placeholders for a plain callable get *block*
    shapes (lead Unknown); ``True``: cell shapes (map_rows / reduce_rows).
    ``constants``: placeholder name -> host array fed per call instead of a
    column — unlike DSL constants (baked into the program, forcing a
    recompile when the value changes) these are ordinary traced arguments,
    so iterative algorithms reuse one compiled program (e.g. k-means
    centroids each Lloyd step)."""
    if isinstance(fetches, CapturedGraph):
        g = fetches
    elif isinstance(fetches, Node):
        g = build_graph([fetches])
    elif isinstance(fetches, (list, tuple)) and fetches and all(
        isinstance(f, Node) for f in fetches
    ):
        g = build_graph(list(fetches))
    elif callable(fetches):
        g = _graph_from_callable(
            fetches, df, cell_inputs, feed_dict, constants, schema=schema
        )
    else:
        raise TypeError(
            f"fetches must be Node(s), a CapturedGraph, or a callable; got "
            f"{type(fetches).__name__}"
        )
    if feed_dict:
        # memoize the renamed wrapper on the underlying graph: a fresh
        # CapturedGraph per call would drop every jitted-program cache
        # attached to it and recompile on each invocation
        fd_key = tuple(sorted(feed_dict.items()))
        cache = getattr(g, "_with_inputs_cache", None)
        if cache is None:
            cache = g._with_inputs_cache = {}
        if fd_key not in cache:
            cache[fd_key] = g.with_inputs(feed_dict)
        g = cache[fd_key]
    return g


#: fn -> bindable parameter names. inspect.signature costs ~70us per call
#: — measurable against a ~3ms scoring pass — and a function's signature
#: cannot change, so it is resolved once per function object.
_fn_params_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _fn_params(fn: Callable) -> List[str]:
    try:
        cached = _fn_params_cache.get(fn)
    except TypeError:  # unhashable/unweakrefable callable: resolve inline
        cached = None
    if cached is None:
        cached = [
            p.name
            for p in inspect.signature(fn).parameters.values()
            if p.kind in (p.POSITIONAL_OR_KEYWORD, p.KEYWORD_ONLY)
        ]
        try:
            _fn_params_cache[fn] = cached
        except TypeError:
            pass
    return cached


def _graph_from_callable(
    fn: Callable,
    df: TensorFrame,
    cell_inputs: bool,
    feed_dict: Optional[Dict[str, str]],
    constants: Optional[Dict[str, Any]] = None,
    schema: Optional[FrameInfo] = None,
) -> CapturedGraph:
    """Plain-function frontend: parameter names are placeholder names, bound
    to columns directly or via feed_dict / reduce suffixes, or to per-call
    ``constants`` arrays."""
    from ..schema import for_numpy_dtype

    schema = schema if schema is not None else df.schema
    params = _fn_params(fn)
    specs: Dict[str, Tuple] = {}
    bound: Dict[str, str] = {}
    missing = []
    for p in params:
        if constants and p in constants:
            arr = np.asarray(constants[p])
            specs[p] = (for_numpy_dtype(arr.dtype), Shape(arr.shape))
            continue
        col = resolve_column(p, feed_dict or {}, schema.names)
        if col is None:
            missing.append(p)
            continue
        bound[p] = col
        info = schema[col]
        if cell_inputs:
            shape = info.cell_shape
        elif p.endswith("_input"):
            # block-reduce convention: one dim higher than the cell
            shape = info.cell_shape.prepend(Unknown)
        else:
            shape = info.block_shape.with_lead(Unknown)
        specs[p] = (info.scalar_type, shape)
    if missing:
        raise InputNotFoundError(missing, schema.names)
    # memoize per function object + spec signature: a fn defined once and
    # passed to an op repeatedly (e.g. inside an iterative algorithm) keeps
    # one CapturedGraph and therefore one compiled program
    cache_key = (
        cell_inputs,
        tuple(sorted((k, st.name, s.dims) for k, (st, s) in specs.items())),
        tuple(sorted((feed_dict or {}).items())),
    )
    try:
        per_fn = _callable_graphs.setdefault(fn, {})
    except TypeError:  # unhashable/unweakrefable callables skip the cache
        per_fn = {}
    if cache_key in per_fn:
        _m_graph_hits.inc()
        return per_fn[cache_key]
    _m_graph_misses.inc()
    # capture is memoized by FUNCTION IDENTITY; a lambda recreated inside a
    # loop has the same code but a new identity every pass, silently
    # recompiling its programs. Detect the churn and tell the user once.
    # Bound methods, closures, and default-args carriers legitimately share
    # code across distinct functions, so only bare code-only functions warn.
    code = getattr(fn, "__code__", None)
    if (
        code is not None
        and getattr(fn, "__closure__", True) is None
        and getattr(fn, "__defaults__", True) is None
        and not hasattr(fn, "__self__")
    ):
        code_key = (code, cache_key)
        if code_key in _seen_callable_codes:
            # the log line fires once per signature; the counter counts
            # EVERY recapture, so churn magnitude stays measurable after
            # the warning has been emitted
            _m_recapture.inc()
            if code_key not in _warned_callable_codes:
                _warned_callable_codes.add(code_key)
                logger.warning(
                    "capturing %s again for identical code — it is a new "
                    "function object each call, so compiled programs are "
                    "not reused; define the function once and pass the "
                    "same object to avoid recompilation",
                    getattr(fn, "__qualname__", fn),
                )
        elif len(_seen_callable_codes) < 4096:  # bounded diagnostic state
            _seen_callable_codes.add(code_key)
    probe_feed = None
    if any(st.name == "binary" for st, _ in specs.values()):
        # binary programs cannot be abstract-traced; discover outputs by
        # running on the first row's real cells (host path)
        if df.num_rows == 0:
            raise ValueError("cannot capture a binary-input program on an empty frame")
        probe_feed = {p: df.column_data(c).cell(0) for p, c in bound.items()}
    g = CapturedGraph.from_callable(fn, specs, probe_feed=probe_feed)
    per_fn[cache_key] = g
    return g


#: monotonically increasing program sequence — the cost-registry key
#: component that keeps two graphs with identical labels distinct
#: (id() can be recycled after GC; this cannot). Lock-guarded: two
#: threads forcing ops concurrently must not mint one seq for two
#: graphs and merge their cost records.
_prog_seq = 0
_prog_seq_lock = threading.Lock()


def _program_key(g: CapturedGraph, variant: str) -> Tuple[str, str]:
    """(key, name) for a graph's compiled program in the cost registry
    (``obs/programs.py``). Fused plan composites carry a ``plan_label``
    set by ``engine/plan.py``; plain graphs are named by their
    fetches."""
    global _prog_seq
    with _prog_seq_lock:
        seq = getattr(g, "_prog_seq", None)
        if seq is None:
            _prog_seq += 1
            seq = g._prog_seq = _prog_seq
    label = getattr(g, "plan_label", None)
    if not label:
        fetches = ",".join(list(getattr(g, "fetch_names", ())) or ["anon"])
        label = f"engine:{fetches}"
    if variant:
        label = f"{label}:{variant}"
    return f"g{seq}:{label}", label


def _jitted(g: CapturedGraph):
    j = getattr(g, "_jit_cache", None)
    if j is None:
        import jax

        key, name = _program_key(g, "")
        j = _programs.instrument(
            jax.jit(g.fn), key=key, name=name, kind="engine.block",
        )
        g._jit_cache = j
        _m_jit_builds.inc()
    else:
        _m_jit_reuse.inc()
    return j


def _jitted_vmap(g: CapturedGraph):
    j = getattr(g, "_jit_vmap_cache", None)
    if j is None:
        import jax

        key, name = _program_key(g, "vmap")
        j = _programs.instrument(
            jax.jit(jax.vmap(g.fn)), key=key, name=name, kind="engine.row",
        )
        g._jit_vmap_cache = j
        _m_jit_builds.inc()
    else:
        _m_jit_reuse.inc()
    return j


def _feeder_streams_host(cd) -> bool:
    """Whether :func:`_block_feeder` would stream HOST slices for this
    dense column (over the device-cache budget) — checkable WITHOUT
    building the feeder, because building one for an in-budget column
    starts its chunked device upload as a side effect."""
    from ..frame.table import _is_device_array
    from ..utils import get_config

    dense = cd.dense
    return (
        not _is_device_array(dense)
        and dense.nbytes > get_config().device_cache_bytes
    )


def _block_feeder(cd):
    """Per-partition feed source for a dense column, plus whether it streams.

    Returns ``(feed_fn, streams_host)``: a chunked-upload stream slicer
    when the column fits the device-cache budget (the first blocks
    compute while later transfer chunks are still in the air; once every
    chunk has landed the memoized assembled column feeds exactly like
    the old whole-``device_put`` copy), else host slices streamed one
    block at a time so HBM stays bounded by a single block.
    Device-resident columns (results of a previous op) feed directly — no
    transfer, no budget check. NOTE: building the stream slicer STARTS
    the column's upload — callers that may still bail out of their pass
    must run every bail-out check first (``_feeder_streams_host`` covers
    the budget check side-effect-free)."""
    from ..frame.table import _is_device_array

    def _slicer(arr):
        # a [0:n] slice of a device array is an eager on-device copy — for
        # a single-partition frame that would double the pass's HBM
        # traffic, so the full range returns the array itself
        n = arr.shape[0]
        return lambda lo, hi: arr if lo == 0 and hi == n else arr[lo:hi]

    dense = cd.dense
    if _is_device_array(dense):
        return _slicer(dense), False
    if not _feeder_streams_host(cd):
        return cd.device_stream().slice, False
    return (lambda lo, hi: dense[lo:hi]), True


def _ensure_precision(g: CapturedGraph, schema: FrameInfo) -> None:
    if any(p.scalar_type.is_64bit for p in g.placeholders.values()) or any(
        c.scalar_type.is_64bit for c in schema
    ):
        ensure_x64()


def _fetch_column_info(name: str, spec: TensorSpec, block_output: bool) -> ColumnInfo:
    """Result-column schema for a fetch (reference embeds the output shape in
    the new column's metadata, ``DebugRowOps.scala:349-360``)."""
    if block_output:
        shape = spec.shape
        nesting = max(spec.shape.num_dims - 1, 0)
    else:
        shape = spec.shape.prepend(Unknown)
        nesting = spec.shape.num_dims
    return ColumnInfo(
        name, spec.scalar_type, analyzed_shape=shape, nesting=nesting
    )


def _empty_output(spec: TensorSpec, block_output: bool) -> np.ndarray:
    cell = spec.shape.tail() if block_output else spec.shape
    dims = tuple(0 if d == Unknown else d for d in cell.dims)
    return np.zeros((0,) + dims, dtype=spec.scalar_type.np_dtype)


# ---------------------------------------------------------------------------
# map_blocks
# ---------------------------------------------------------------------------


def _resolve_decoder_cols(
    decoders: Dict[str, Callable],
    feed_dict: Optional[Dict[str, str]],
    schema_names: Sequence[str],
) -> Dict[str, Callable]:
    """Decoder keys are column names, or placeholder names routed through
    ``feed_dict`` (explicit feed_dict routing wins: a placeholder may
    collide with an unrelated column name). Returns column -> codec."""
    out: Dict[str, Callable] = {}
    for key, fn in decoders.items():
        if feed_dict and key in feed_dict:
            col = feed_dict[key]
        elif key in schema_names:
            col = key
        else:
            raise InputNotFoundError([key], schema_names)
        out[col] = fn
    return out


#: partitions of decoded blocks kept in flight ahead of the device: decode
#: of partition p+1..p+N proceeds on the host pool while the chip runs p
_DECODE_PREFETCH = 4

#: host-streamed (over-budget) columns keep this many partition uploads
#: in flight ahead of the device: block i+1 crosses the link while the
#: chip runs block i (the streaming-ingest overlap). ONE ahead — these
#: blocks belong to a column that exceeded the device-cache budget, so
#: the streaming contract of ~one resident block loosens to exactly two
#: (current + next), the minimum that buys any overlap at all
_UPLOAD_PREFETCH = 1


def map_blocks(
    fetches,
    dframe: TensorFrame,
    trim: bool = False,
    feed_dict: Optional[Dict[str, str]] = None,
    constants: Optional[Dict[str, Any]] = None,
    decoders: Optional[Dict[str, Callable]] = None,
    _ledger=None,
    _plan: bool = True,
) -> TensorFrame:
    """Transform the frame block by block; fetches become new columns
    (``trim=False``) or the entire output (``trim=True``, row count may
    change). Lazy, like the reference (``core.py:266-309``).

    Each partition block is one XLA program execution; XLA's jit cache keys
    on the block shape, so frames with equal-sized partitions compile once.
    ``constants`` feed placeholders with per-call host arrays (same shape ->
    no recompile), for iterative algorithms like k-means centroids.

    ``decoders`` maps a binary column (or its placeholder) to a host codec
    ``bytes -> array``; that column then feeds the program as decoded
    numeric blocks, with decode running on a thread pool several
    partitions AHEAD of the device — host codec work overlaps chip compute
    instead of serializing before it (the reference gets this overlap from
    Spark's partition iterator feeding the TF session,
    ``DebugRowOps.scala:766-803``; here it is explicit double-buffering).
    The decoded shape/dtype is probed from row 0; all rows must decode to
    that shape (varying shapes: use ``map_rows``, which shape-buckets).
    The result frame carries the ORIGINAL (undecoded) columns — decoded
    blocks are transient feed buffers, never a materialized column.

    ``_ledger`` (private) is the durable-job hook: ``engine/jobs.py``
    threads a :class:`~tensorframes_tpu.engine.jobs.BlockLedger` through
    the partition loop so completed partitions restore from / spool to a
    journal and poisoned partitions quarantine instead of killing the
    job (docs/fault_tolerance.md).
    """
    decode_fns: Dict[str, Callable] = {}
    probe_cells: Dict[str, np.ndarray] = {}
    schema = dframe.schema
    if decoders:
        from ..frame.table import _as_cell
        from ..schema import for_numpy_dtype

        decode_fns = _resolve_decoder_cols(
            decoders, feed_dict, schema.names
        )
        if dframe.num_rows == 0:
            raise ValueError(
                "map_blocks(decoders=...) on an empty frame (no row to "
                "probe the decoded schema from)"
            )
        infos = []
        for ci in schema:
            if ci.name in decode_fns:
                probe = _as_cell(
                    decode_fns[ci.name](
                        dframe.column_data(ci.name).cell(0)
                    )
                )
                if isinstance(probe, bytes):
                    raise TypeError(
                        f"decoder for column {ci.name!r} produced bytes; "
                        f"block programs need numeric cells"
                    )
                probe_cells[ci.name] = probe
                infos.append(
                    ColumnInfo(
                        ci.name,
                        for_numpy_dtype(probe.dtype),
                        analyzed_shape=Shape(
                            [Unknown] + list(probe.shape)
                        ),
                        nesting=probe.ndim,
                    )
                )
            else:
                infos.append(ci)
        schema = FrameInfo(infos)
    g = _as_graph(
        fetches, dframe, cell_inputs=False, feed_dict=feed_dict,
        constants=constants, schema=schema,
    )
    # the validate/analyze/result-schema prologue depends only on
    # (graph, schema, trim, constant names) — memoize it on the graph so
    # chained passes over the same frame (the pipeline steady state) pay
    # a dict lookup, not a re-derivation. Keys hold the schema objects
    # themselves, so an id() collision after GC cannot alias. Decoder
    # passes rebuild their probe schema per call and naturally miss.
    plan_key = (id(schema), id(dframe.schema), trim,
                tuple(sorted(constants or ())))
    plan_cache = getattr(g, "_map_plan_cache", None)
    if plan_cache is None:
        from collections import OrderedDict

        plan_cache = g._map_plan_cache = OrderedDict()
    hit = plan_cache.get(plan_key)
    if hit is not None and hit[0] is schema and hit[1] is dframe.schema:
        plan_cache.move_to_end(plan_key)  # LRU, like _generate_cache
        _, _, binding, out_specs, fetch_names, result_info = hit
    else:
        binding = validate_map_inputs(
            g, schema, block=True, constants=set(constants or ())
        )
        # ragged/binary columns are rejected when blocks are materialized
        # in the thunk (column_block raises), keeping construction
        # metadata-only/lazy
        _ensure_precision(g, schema)
        input_shapes = {
            ph: schema[col].block_shape.with_lead(Unknown)
            for ph, col in binding.items()
        }
        out_specs = g.analyze(input_shapes)
        for name, spec in out_specs.items():
            if spec.shape.num_dims == 0:
                raise InvalidDimensionError(
                    f"map_blocks output {name!r} is a scalar; map outputs "
                    f"must keep the leading row dimension (use "
                    f"reduce_blocks to reduce a frame to one row)"
                )
        if not trim:
            check_output_collisions(out_specs, dframe.schema)

        fetch_names = sorted(out_specs)  # outputs sorted by name (ref)
        fetch_infos = [
            _fetch_column_info(n, out_specs[n], block_output=True)
            for n in fetch_names
        ]
        if trim:
            result_info = FrameInfo(fetch_infos)
        else:
            result_info = FrameInfo(fetch_infos + list(dframe.schema))
        # decoder passes rebuild their probe schema per call, so their
        # entries could never hit — don't insert them
        if not decode_fns:
            while len(plan_cache) >= 64:  # bound; evict oldest
                plan_cache.popitem(last=False)
            plan_cache[plan_key] = (
                schema, dframe.schema, binding, out_specs, fetch_names,
                result_info,
            )

    jit_fn = _jitted(g)
    parent = dframe

    const_feed = {
        ph: np.asarray(v) for ph, v in (constants or {}).items()
    }

    def _run() -> TensorFrame:
        from ..utils import get_config

        pieces: Dict[str, List] = {n: [] for n in fetch_names}
        part_sizes: List[int] = []
        # decoded columns feed through a PREFETCHING codec: partition p's
        # block is decoded on the pool while the chip still runs earlier
        # partitions, and decode for p+1..p+N is submitted the moment p's
        # block is consumed
        decode_pool = None
        decode_futs: Dict[Tuple[str, int], Any] = {}
        bounds = list(parent.partition_bounds())
        part_of = {tuple(b): i for i, b in enumerate(bounds)}

        def _submit_decode(col: str, p: int) -> None:
            if (col, p) in decode_futs or p >= len(bounds):
                return
            lo, hi = bounds[p]
            fn = decode_fns[col]
            cd = parent.column_data(col)
            pc = probe_cells[col]

            def job(lo=lo, hi=hi, fn=fn, cd=cd, pc=pc):
                if hi == lo:
                    return np.empty((0,) + pc.shape, dtype=pc.dtype)
                cells = []
                for i in range(lo, hi):
                    if i == 0:
                        # row 0 was decoded by the schema probe; reuse it
                        # (a stateful or expensive codec must not run
                        # twice per row)
                        cells.append(np.asarray(pc))
                        continue
                    c = np.asarray(fn(cd.cell(i)))
                    if c.shape != pc.shape:
                        raise ValueError(
                            f"decoder for column {col!r} produced shape "
                            f"{c.shape} at row {i}, but row 0 probed "
                            f"{pc.shape}; block programs need uniform "
                            f"decoded shapes (use map_rows for varying "
                            f"ones)"
                        )
                    cells.append(c)
                return np.stack(cells).astype(pc.dtype, copy=False)

            decode_futs[(col, p)] = decode_pool.submit(job)

        def _make_decode_feeder(col: str):
            def feeder(lo: int, hi: int) -> np.ndarray:
                p = part_of[(lo, hi)]
                _submit_decode(col, p)
                for q in range(p + 1, p + 1 + _DECODE_PREFETCH):
                    _submit_decode(col, q)
                return decode_futs.pop((col, p)).result()

            return feeder

        # host-streamed (over-budget) columns upload through a PREFETCHING
        # pipeline: partition p+1's block crosses the link while the chip
        # runs p, each block retried per chunk by the transfer layer —
        # the same submit/pop state machine as the decode prefetch above
        # (a recovery re-run of a consumed partition simply resubmits)
        upload_pool = None
        upload_futs: Dict[Tuple[str, int], Any] = {}

        def _submit_upload(
            ph: str, p: int, host_feed, prefetch: bool = False
        ) -> None:
            if (ph, p) in upload_futs or p >= len(bounds):
                return
            if prefetch and _ledger is not None and _ledger.peek(p) != "todo":
                # journaled pass: restored/quarantined blocks never
                # recompute, so their bytes must never cross the link
                # (the demanded block itself is always todo — only the
                # speculative window consults the ledger)
                return
            lo, hi = bounds[p]
            if hi == lo:
                return
            upload_futs[(ph, p)] = upload_pool.submit(
                _transfer.h2d, host_feed(lo, hi), f"map_blocks block {p}"
            )

        def _make_upload_feeder(ph: str, host_feed):
            def feeder(lo: int, hi: int):
                p = part_of[(lo, hi)]
                _submit_upload(ph, p, host_feed)
                for q in range(p + 1, p + 1 + _UPLOAD_PREFETCH):
                    _submit_upload(ph, q, host_feed, prefetch=True)
                return upload_futs.pop((ph, p)).result()

            return feeder

        # device-resident columns when they fit; streamed blocks otherwise
        feeders = {}
        streaming = False
        streamed_phs: List[str] = []
        for ph, col in binding.items():
            if col in decode_fns:
                if decode_pool is None:
                    import os
                    from concurrent.futures import ThreadPoolExecutor

                    decode_pool = ThreadPoolExecutor(
                        min(32, os.cpu_count() or 1)
                    )
                feeders[ph] = _make_decode_feeder(col)
                continue
            parent.column_block(col, None)  # rejects ragged/binary
            feeders[ph], streams = _block_feeder(parent.column_data(col))
            if streams:
                streamed_phs.append(ph)
            streaming = streaming or streams
        if streamed_phs:
            from concurrent.futures import ThreadPoolExecutor

            # one waited block + the prefetch window PER streamed column:
            # a shared too-small pool would queue column B's current
            # block behind column A's prefetch and serialize the pass
            upload_pool = ThreadPoolExecutor(
                min(16, (1 + _UPLOAD_PREFETCH) * len(streamed_phs)),
                thread_name_prefix="tft-upload-prefetch",
            )
            for ph in streamed_phs:
                feeders[ph] = _make_upload_feeder(ph, feeders[ph])
        # Outputs stay device-resident only when HBM stays bounded: if any
        # input streams from the host (over-budget column), or the full
        # output itself would blow the device-cache budget, pull each
        # partition's result to host as it lands (the pre-device-residency
        # behavior), keeping peak HBM at ~one block.
        budget = get_config().device_cache_bytes
        if not streaming and not trim:
            est = 0
            for spec in out_specs.values():
                cell = spec.shape.tail()
                if all(d != Unknown for d in cell.dims):
                    est += (
                        int(np.prod(cell.dims)) if cell.dims else 1
                    ) * spec.scalar_type.np_dtype.itemsize * parent.num_rows
            streaming = est > budget
        if _ledger is not None:
            # journaled jobs: a deterministic per-partition block plan,
            # and host materialization per block (results spool to the
            # journal, so device residency buys nothing here)
            _ledger.ensure_plan(
                [{"rows": hi - lo, "lo": lo, "hi": hi} for lo, hi in bounds],
                graph=g, schema=schema, rows=parent.num_rows,
                extra={"trim": trim},
            )
            streaming = True
        # trim maps and Unknown-dim fetches have no static size estimate:
        # track actual accumulated bytes and demote to host streaming the
        # moment the budget is crossed mid-run
        acc_bytes = 0
        # streaming materialization is WINDOWED (double-buffered): pulling a
        # partition's output to host blocks the host until that transfer
        # lands, so materializing the append immediately would serialize
        # transfer against the next partition's dispatch. Keeping a couple
        # of partitions in flight lets the device run ahead while earlier
        # outputs stream down; peak HBM stays at ~window+1 blocks, which is
        # the streaming mode's contract.
        from collections import deque

        STREAM_WINDOW = 2
        pending: "deque[int]" = deque()
        #: pieces index -> partition index, so a failure surfacing at
        #: materialization can be traced back and re-run selectively
        piece_part: List[int] = []

        def compute_partition(p: int):
            """Dispatch one partition's program (feed assembly included) —
            called by the main loop AND by materialization-time recovery,
            so a lost async result re-runs only its own partition."""
            lo, hi = bounds[p]
            n = hi - lo
            _m_blocks_map_blocks.inc()
            from ..utils import is_oom, run_with_retries
            from ..utils.chaos import site as _chaos_site

            def dispatch():
                _chaos_site("engine.dispatch")
                out = jit_fn(feed)
                if _ledger is not None:
                    # journaled blocks materialize right after anyway;
                    # syncing INSIDE the retry window gives transient
                    # async failures retry coverage (the map_rows rule)
                    import jax

                    out = jax.block_until_ready(out)
                return out

            try:
                # feed assembly sits INSIDE the OOM envelope: for
                # host-streamed columns it includes the block's device
                # upload (prefetched or synchronous), and an OOM there
                # deserves the same repartition hint as one in compute
                feed = {ph: feeders[ph](lo, hi) for ph in binding}
                feed.update(const_feed)
                return run_with_retries(
                    dispatch, what=f"map_blocks partition {p}"
                )
            except Exception as e:
                if is_oom(e):
                    from ..utils.failures import DeviceOOMError

                    raise DeviceOOMError(
                        f"map_blocks partition {p} ({n} rows) exhausted "
                        f"device memory; repartition the frame into smaller "
                        f"blocks (block programs see a whole partition, so "
                        f"the engine cannot split one for you)"
                    ) from e
                raise

        def drain_pending(to_size: int) -> None:
            while len(pending) > to_size:
                idx = pending.popleft()
                try:
                    for nm in fetch_names:
                        pieces[nm][idx] = np.asarray(pieces[nm][idx])
                except Exception:
                    _recover_piece(idx)

        def _recover_piece(idx: int) -> None:
            """A transient failure during ASYNC execution surfaces when the
            partition's output is first touched; re-run just that
            partition (completed partitions are never recomputed) and
            materialize the replacement. Deterministic failures re-raise
            from the re-run itself."""
            p = piece_part[idx]
            logger.warning(
                "map_blocks partition %d result was lost to an async "
                "failure; re-running that partition only", p,
            )
            res = compute_partition(p)
            for nm in fetch_names:
                pieces[nm][idx] = np.asarray(res[nm])

        def _recover_lost_partitions() -> int:
            """Probe every partition's result; re-run the poisoned ones.
            Returns how many were recovered. EVERY fetch column is probed
            — an async failure can poison a single output buffer of a
            multi-output program, and probing only the first fetch would
            miss it (re-raising the original error instead of recovering)."""
            recovered = 0
            for idx in range(len(piece_part)):
                for nm in fetch_names:
                    probe = pieces[nm][idx]
                    try:
                        if hasattr(probe, "block_until_ready"):
                            probe.block_until_ready()
                        else:
                            np.asarray(probe)
                    except Exception:
                        _recover_piece(idx)  # re-runs ALL fetches for idx
                        recovered += 1
                        break
            return recovered

        try:
            for p in range(parent.num_partitions):
                lo, hi = bounds[p]
                n = hi - lo
                if n == 0:
                    part_sizes.append(0)
                    continue
                # NOTE: map_blocks keeps results device-resident so chained
                # passes pipeline without host syncs (why chained maps stay
                # lazy). Only errors raised at DISPATCH are retried here;
                # a failure during async execution surfaces later, at
                # materialization — where _recover_lost_partitions re-runs
                # just the partitions whose outputs were lost. map_rows
                # and the reduces, which materialize promptly, sync inside
                # their retry windows and get full coverage.
                if _ledger is not None:
                    st, res = _ledger.lookup(p)
                    if st == "quarantined":
                        part_sizes.append(0)
                        continue
                    if st == "todo":
                        res = _ledger.run_block(
                            p,
                            lambda p=p: {
                                nm: np.asarray(v)
                                for nm, v in compute_partition(p).items()
                                if nm in out_specs
                            },
                            rows=n,
                        )
                        if res is None:  # quarantined just now
                            part_sizes.append(0)
                            continue
                else:
                    res = compute_partition(p)
                # results stay device-resident: shape checks need no host sync,
                # and the host transfer happens only on host access (collect /
                # column host materialization) — chained ops feed from HBM
                out_n = None
                for name in fetch_names:
                    arr = res[name]
                    if not trim and arr.shape[0] != n:
                        raise ValueError(
                            f"map_blocks output {name!r} produced {arr.shape[0]} "
                            f"rows for a block of {n}; only trimmed maps may "
                            f"change the row count"
                        )
                    if trim and out_n is not None and arr.shape[0] != out_n:
                        raise ValueError(
                            f"map_blocks(trim=True) fetches disagree on the "
                            f"output row count in partition {p}: {name!r} "
                            f"produced {arr.shape[0]} rows, a previous fetch "
                            f"produced {out_n}"
                        )
                    out_n = arr.shape[0]
                    if not streaming:
                        acc_bytes += arr.nbytes
                        if acc_bytes > budget:
                            streaming = True
                            # demote what's accumulated — a lost async
                            # result can surface at these asarray calls
                            # too, so recover per piece like drain_pending
                            for idx in range(len(piece_part)):
                                try:
                                    for nm in fetch_names:
                                        pieces[nm][idx] = np.asarray(
                                            pieces[nm][idx]
                                        )
                                except Exception:
                                    _recover_piece(idx)
                    pieces[name].append(arr)
                piece_part.append(p)
                if streaming:
                    pending.append(len(pieces[fetch_names[0]]) - 1)
                    drain_pending(STREAM_WINDOW)
                part_sizes.append(out_n if trim else n)
            drain_pending(0)

            def build_cols() -> Dict[str, _ColumnData]:
                out: Dict[str, _ColumnData] = {}
                for name in fetch_names:
                    ps = pieces[name]
                    if not ps:
                        dense = _empty_output(
                            out_specs[name], block_output=True
                        )
                    elif len(ps) == 1:
                        dense = ps[0]
                    elif streaming:
                        dense = np.concatenate(ps, axis=0)
                    else:
                        import jax.numpy as jnp

                        dense = jnp.concatenate(ps, axis=0)  # on-device
                    out[name] = _ColumnData(dense=dense)
                return out

            try:
                cols = build_cols()
            except Exception:
                # an async-execution failure poisons its output buffers and
                # resurfaces here, at the concatenation that first touches
                # them: recover per partition and rebuild (decode feeders
                # are still alive — the pool shuts down in the finally)
                if _recover_lost_partitions() == 0:
                    raise  # not a lost-result failure; propagate as-is
                cols = build_cols()
        finally:
            if decode_pool is not None:
                decode_pool.shutdown(wait=False, cancel_futures=True)
            if upload_pool is not None:
                upload_pool.shutdown(wait=False, cancel_futures=True)
        offsets = np.concatenate([[0], np.cumsum(part_sizes)]).astype(np.int64)
        if trim:
            return TensorFrame(cols, result_info, offsets=offsets)
        dropped = (
            set(_ledger.quarantined_indices) if _ledger is not None else ()
        )
        if dropped:
            # quarantined partitions contribute no output rows, so the
            # carried-through parent columns must drop the same rows to
            # stay aligned (the partial-results contract)
            keep = np.concatenate(
                [
                    np.arange(lo, hi, dtype=np.int64)
                    for p, (lo, hi) in enumerate(bounds)
                    if p not in dropped
                ]
                or [np.empty(0, np.int64)]
            )
            for c in parent.schema:
                cols[c.name] = parent.column_data(c.name).take(keep)
        else:
            for c in parent.schema:
                cols[c.name] = parent.column_data(c.name)
        return TensorFrame(cols, result_info, offsets=offsets)

    def thunk() -> TensorFrame:
        with _span(
            "engine.map_blocks", partitions=parent.num_partitions, trim=trim
        ) as sp:
            out = _run()
            if sp is not None:
                sp.attrs["rows"] = parent.num_rows
        _m_rows_map_blocks.inc(parent.num_rows)
        return out

    if _plan and _ledger is None and not trim and not decode_fns:
        from . import plan as _plan_mod

        if _plan_mod.enabled():
            # record a logical-plan node: chained ops fuse/prune/hoist
            # at force time (docs/pipelines.md); trim maps and decoder
            # passes stay op-at-a-time (they change row counts / probe
            # host data) and act as chain boundaries
            return _plan_mod.make_lazy_map(
                "map_blocks", parent, g, binding, fetch_names,
                result_info, thunk, constants=constants,
            )
    return TensorFrame(
        {}, result_info, num_partitions=parent.num_partitions, _thunk=thunk
    )


def precompile(
    fetches,
    frame_or_schema,
    *,
    block_rows: Optional[Sequence[int]] = None,
    feed_dict: Optional[Dict[str, str]] = None,
    constants: Optional[Dict[str, Any]] = None,
) -> int:
    """Ahead-of-time compile the block programs a ``map_blocks`` call would
    dispatch, without moving any data.

    The reference never needed this — a TF 1.x session executes a GraphDef
    with zero compile cost (``TensorFlowOps.scala:76-95``) — but XLA
    compiles per (program, block shape), and on a fresh process that
    compile lands on the first data pass. With the persistent compilation
    cache (:func:`tensorframes_tpu.utils.enable_compilation_cache`, on by
    default) this both *warms* the on-disk cache and lets a serving
    process front-load all compilation before traffic:

    - pass a :class:`TensorFrame` and the partition block shapes are
      derived from it (one program per distinct partition size);
    - pass a :class:`FrameInfo` (e.g. for a graph loaded from an artifact
      via ``load_graph`` in a process that has no data yet) together with
      ``block_rows``, the partition sizes you will serve.

    Returns the number of distinct programs compiled. Compilation results
    land in XLA's in-process and persistent caches; the first real
    ``map_blocks`` pass then pays only executable-cache lookup.
    """
    import jax

    if isinstance(frame_or_schema, TensorFrame):
        df, schema = frame_or_schema, frame_or_schema.schema
        if block_rows is None:
            block_rows = [
                hi - lo for lo, hi in df.partition_bounds() if hi > lo
            ]
    elif isinstance(frame_or_schema, FrameInfo):
        df, schema = None, frame_or_schema
        if block_rows is None:
            raise ValueError(
                "precompile(schema) needs block_rows= (the partition sizes "
                "to compile for); pass a TensorFrame to derive them"
            )
    else:
        raise TypeError(
            f"frame_or_schema must be a TensorFrame or FrameInfo; got "
            f"{type(frame_or_schema).__name__}"
        )
    g = _as_graph(
        fetches, df, cell_inputs=False, feed_dict=feed_dict,
        constants=constants, schema=schema,
    )
    binding = validate_map_inputs(
        g, schema, block=True, constants=set(constants or ())
    )
    _ensure_precision(g, schema)
    for ph, col in binding.items():
        cell = schema[col].cell_shape
        if any(d == Unknown for d in cell.dims):
            raise ValueError(
                f"cannot precompile: column {col!r} has unknown cell "
                f"dims {cell}; analyze() the frame (or supply an analyzed "
                f"schema) first"
            )
    const_specs = {
        ph: jax.ShapeDtypeStruct(
            np.asarray(v).shape, np.asarray(v).dtype
        )
        for ph, v in (constants or {}).items()
    }
    jit_fn = _jitted(g)
    compiled = 0
    with _span("engine.precompile") as sp:
        for n in sorted(set(block_rows)):
            feed = {
                ph: jax.ShapeDtypeStruct(
                    (n, *schema[col].cell_shape.dims),
                    schema[col].scalar_type.np_dtype,
                )
                for ph, col in binding.items()
            }
            feed.update(const_specs)
            jit_fn.lower(feed).compile()
            compiled += 1
        if sp is not None:
            sp.attrs["programs"] = compiled
    return compiled


# ---------------------------------------------------------------------------
# map_rows
# ---------------------------------------------------------------------------


def _concat_dense(ps: List) -> Any:
    """Concatenate per-chunk result arrays into one dense column buffer:
    single piece passes through untouched (keeps device residency), any
    numpy piece forces a host concatenate, all-device pieces concatenate on
    device."""
    import jax.numpy as jnp

    if len(ps) == 1:
        return ps[0]
    if any(isinstance(p, np.ndarray) for p in ps):
        return np.ascontiguousarray(
            np.concatenate([np.asarray(p) for p in ps], axis=0)
        )
    return jnp.concatenate(ps, axis=0)


def _map_rows_thunk(
    parent: TensorFrame,
    binding: Dict[str, str],
    fetch_names: Sequence[str],
    out_specs: Dict[str, TensorSpec],
    result_info: FrameInfo,
    run_bucket: Callable[[Dict[str, np.ndarray], int], Dict[str, Any]],
    result_partitions: Optional[int] = None,
    device_resident: bool = True,
    ledger=None,
    graph=None,
    explicit_h2d: bool = False,
):
    """Shared row-map execution: bucket rows by input cell shape, assemble
    each bucket's batched feed (dense gather / ragged gather-pad / stack),
    run it through ``run_bucket(feed, m) -> {fetch: [m, ...] array}``, and
    scatter results back into row order. Used by both the local engine
    (vmap per bucket) and the distributed engine (shard_map-of-vmap with a
    main+tail split) so bucketing/ragged semantics cannot diverge.

    ``explicit_h2d`` (the local engine) moves each chunk's feed to device
    through the streaming transfer layer (``frame/transfer.py``) before
    dispatch: the upload is retried per transfer chunk, counted as link
    traffic, and chaos-injectable at ``frame.h2d`` — a transient link
    error during ingest retries one chunk instead of killing the pass.
    The distributed engine keeps host feeds (its shard_map programs own
    their sharded placement).

    ``ledger`` (with ``graph`` for the manifest fingerprint) switches on
    durable-job execution (``engine/jobs.py``): the device-resident fast
    path is skipped in favor of a DETERMINISTIC block plan — fixed
    ``max_rows_per_device_call`` row slices, dense frames in row order,
    bucketed frames per bucket in first-appearance order — so a resumed
    job recomputes exactly the unfinished blocks and concatenates
    byte-identically to a clean run. Quarantined blocks drop their rows
    from the result (partial-results contract)."""

    def thunk() -> TensorFrame:
        from ..data import RaggedBuffer, gather_rows

        n = parent.num_rows
        if n == 0:
            if ledger is not None:
                ledger.ensure_plan(
                    [], graph=graph, schema=parent.schema, rows=0
                )
            cols = {
                name: _ColumnData(
                    dense=_empty_output(out_specs[name], block_output=False)
                )
                for name in fetch_names
            }
            for c in parent.schema:
                cols[c.name] = parent.column_data(c.name)
            return TensorFrame(cols, result_info)
        col_data = {ph: parent.column_data(col) for ph, col in binding.items()}
        # bucket rows by the tuple of input cell shapes (one compiled
        # program per bucket shape; the jit cache handles specialization).
        # Dense columns have ONE cell shape by construction, so their key
        # component is a constant — a frame of only dense columns is a
        # single bucket with no per-row work (and no host materialization
        # via cell()); only ragged columns' cells are visited.
        buckets: Dict[Tuple, List[int]] = {}
        dense_keys = {
            ph: cd.dense.shape[1:]
            for ph, cd in col_data.items()
            if cd.dense is not None
        }
        dense_fast = len(dense_keys) == len(col_data)
        if dense_fast:
            # the index list is only read by the fallback loop; build it
            # there (range(n) boxed as a 10M-int list is real memory)
            pass
        else:
            for i in range(n):
                key = tuple(
                    dense_keys[ph]
                    if ph in dense_keys
                    else col_data[ph].cells[i].shape
                    for ph in binding
                )
                buckets.setdefault(key, []).append(i)
        # ragged 1-D columns pack once into (flat, offsets) so bucket
        # stacking is a native gather instead of a Python stack loop
        ragged_bufs: Dict[str, RaggedBuffer] = {}
        for ph, cd in col_data.items():
            if cd.dense is None and cd.cells[0].ndim == 1:
                ragged_bufs[ph] = RaggedBuffer.from_cells(cd.cells)
        # dense_fast: chunks run in row order over the one bucket, so chunk
        # outputs concatenate straight into dense result columns — no
        # per-row scatter list, no _build_column re-stack of n cells
        dense_pieces: Dict[str, List[np.ndarray]] = {
            name: [] for name in fetch_names
        }
        out_cells: Dict[str, List] = (
            {}
            if dense_fast
            else {name: [None] * n for name in fetch_names}
        )
        from ..utils import get_config

        # buckets larger than the per-call row cap run in chunks: the input
        # bytes may be modest but the program's activations (convs,
        # attention) scale with the batch, so the cap bounds peak HBM
        chunk = max(1, get_config().max_rows_per_device_call)
        from ..utils import is_oom, run_with_retries

        def run_chunk(sub, sink=None):
            _m_blocks_map_rows.inc()
            idx_arr = np.asarray(sub, dtype=np.int64)
            contiguous = bool(
                idx_arr.size
                and idx_arr[-1] - idx_arr[0] + 1 == idx_arr.size
                and np.all(np.diff(idx_arr) == 1)
            )
            feed = {}
            for ph in binding:
                cd = col_data[ph]
                if cd.dense is not None:
                    h = cd.host()
                    feed[ph] = (
                        h[idx_arr[0] : idx_arr[-1] + 1]
                        if contiguous
                        else gather_rows(h, idx_arr)
                    )
                elif ph in ragged_bufs:
                    feed[ph] = ragged_bufs[ph].gather_pad(idx_arr)
                else:
                    feed[ph] = np.stack([cd.cell(i) for i in sub])
            def dispatch():
                import jax

                from ..utils.chaos import site as _chaos_site

                _chaos_site("engine.dispatch")
                # sync INSIDE the retry window: jax dispatch is async, so
                # without this the failure would surface at np.asarray
                # below, past the handlers. The chunk is materialized to
                # host right after anyway, so the sync costs nothing.
                return jax.block_until_ready(run_bucket(feed, len(sub)))

            try:
                if explicit_h2d:
                    # feeds cross the link through the streaming layer:
                    # each transfer chunk retried + counted + chaos-
                    # injectable; a dispatch retry below reuses the
                    # already-landed arrays. Inside THIS try so a device
                    # OOM during the upload halves the chunk like any
                    # other OOM (the recovery envelope must cover the
                    # feed bytes too, not just the program's activations)
                    feed = {
                        ph: _transfer.h2d(v, what="map_rows feed")
                        if isinstance(v, np.ndarray)
                        else v
                        for ph, v in feed.items()
                    }
                res = run_with_retries(dispatch, what="map_rows chunk")
            except Exception as e:
                # rows are independent, so an OOM chunk is safe to halve
                # (unlike a map_blocks partition); recurse down to 1 row
                if is_oom(e):
                    if len(sub) > 1:
                        record_oom_split("map_rows")
                        logger.warning(
                            "map_rows chunk of %d rows exhausted device "
                            "memory; halving", len(sub),
                        )
                        del feed
                        mid = len(sub) // 2
                        run_chunk(sub[:mid], sink)
                        run_chunk(sub[mid:], sink)
                        return
                    from ..utils.failures import DeviceOOMError

                    raise DeviceOOMError(
                        "map_rows row program exhausted device memory even "
                        "at one row per call; the per-row computation "
                        "itself does not fit HBM"
                    ) from e
                raise
            for name in fetch_names:
                arr = np.asarray(res[name])
                if sink is not None:
                    # journaled block execution collects per block (the
                    # halving recursion preserves row order) so the block's
                    # whole result can spool to the journal in one piece
                    sink(name, arr)
                elif dense_fast:
                    dense_pieces[name].append(arr)
                else:
                    for j, i in enumerate(sub):
                        out_cells[name][i] = arr[j]

        def _tuned_chunk(static_rows: int) -> int:
            """The block-row budget through the autotuner
            (``tensorframes_tpu.tune``, surface ``map_rows.block_rows``,
            keyed by per-row input bytes): Config's
            ``max_rows_per_device_call`` is the seed default; an online
            trial dispatches the REAL row program over a discarded
            sample at each candidate chunking (user-shaped, retryable,
            injectable at ``tune.trial`` like every other dispatch), so
            the winner reflects this op's actual dispatch-overhead/
            activation trade. Rows are independent and the halving
            recursion preserves row order, so every candidate is
            byte-identical to the default — the tuning contract."""
            from .. import tune

            if tune.mode() == "off":
                return static_rows
            if ledger is not None and not dense_fast:
                # bucketed (ragged) journal plans re-derive from the
                # live chunk on resume (no contiguous manifest rebuild),
                # so a tuned winner landing in the shared store between
                # a run and its resume would change the plan and fail
                # ensure_plan — ragged journaled jobs stay config-driven
                return static_rows
            per_row = 0
            for cd in col_data.values():
                if cd.dense is not None:
                    per_row += int(
                        np.prod(cd.dense.shape[1:], initial=1)
                    ) * cd.dense.dtype.itemsize
                elif cd.cells is not None and len(cd.cells):
                    c0 = np.asarray(cd.cells[0])
                    per_row += int(
                        np.prod(c0.shape, initial=1)
                    ) * c0.dtype.itemsize
            rb_bucket = 1 << max(2, int(max(per_row, 1) - 1).bit_length())
            # frame size is PART of the signature: candidates and trials
            # are n-dependent (a small frame cannot exercise a large
            # budget), so a winner measured at one scale must never
            # serve a job orders of magnitude bigger
            n_bucket = 1 << max(2, int(max(n, 1) - 1).bit_length())
            sig = (
                f"row_bytes={rb_bucket}|cols={len(col_data)}|n={n_bucket}"
            )
            default = {"rows": int(static_rows)}
            if dense_fast and n > 1:
                # the sample is the fixed workload every candidate
                # chunks; candidates past it would all measure as one
                # dispatch of `sample` rows — indistinguishable — so
                # only offer what the trial can genuinely compare. Two
                # candidates (one down, one up) + the default keep the
                # grid at 3, which the search measures IN FULL — with
                # only a dispatch-count ranking, a larger grid's
                # top-K halving would make the smaller-chunk side
                # structurally unreachable
                sample = int(min(n, static_rows * 2))
                cands = sorted(
                    {max(1, static_rows // 2), static_rows * 2}
                )
                grid = [
                    {"rows": int(c)}
                    for c in cands
                    if c != static_rows and 1 <= c <= sample
                ]

                def discard(name, arr):
                    pass

                def trial(cand):
                    rows = max(1, int(cand["rows"]))
                    lo = 0
                    while lo < sample:
                        hi = min(lo + rows, sample)
                        run_chunk(list(range(lo, hi)), sink=discard)
                        lo = hi

                def feats(cand):
                    rows = max(1, int(cand["rows"]))
                    dispatches = -(-sample // rows)
                    nbytes = float(sample * max(per_row, 1))
                    return 0.0, nbytes, float(dispatches)

            else:
                # ragged frames have no single contiguous bucket to
                # sample; they resolve cached-only (a winner tuned on a
                # matching dense signature still serves)
                grid, feats, trial = [], None, None
            try:
                win = tune.lookup(
                    "map_rows.block_rows", sig, default,
                    grid=grid, feats=feats, trial=trial,
                )
                return max(1, int(win.get("rows", static_rows)))
            except Exception:
                logger.warning(
                    "block-row tuning lookup failed; using "
                    "max_rows_per_device_call", exc_info=True,
                )
                return static_rows

        chunk = _tuned_chunk(chunk)

        def run_dense_fast() -> Optional[Dict[str, _ColumnData]]:
            """Device-resident execution for the all-dense single bucket:
            columns feed from memoized device copies (``_block_feeder``),
            chunks slice ON DEVICE and dispatch without per-chunk host
            syncs (every host round-trip idles the chip; its cost here is
            not re-measured), and results concatenate on device — the
            same residency contract as ``map_blocks``. Returns ``None``
            when HBM would not stay bounded (streaming inputs, over-budget
            or unknown-size outputs) or on any runtime failure, in which
            case the synchronous chunked path (retry + OOM halving) runs
            instead."""
            import jax

            # EVERY bail-out runs before any feeder is built: building a
            # feeder for an in-budget host column STARTS its chunked
            # device upload, and bailing afterwards hands the pass to
            # run_chunk, which uploads the same bytes AGAIN per chunk —
            # the ROADMAP item-2 double-upload bug (an un-analyzed
            # frame's unknown out-spec dims always took that path).
            budget = get_config().device_cache_bytes
            est = 0
            for spec in out_specs.values():
                cell = spec.shape
                if any(d == Unknown for d in cell.dims):
                    return None
                est += (
                    int(np.prod(cell.dims)) if cell.dims else 1
                ) * spec.scalar_type.np_dtype.itemsize * n
            if est > budget:
                return None
            if any(_feeder_streams_host(col_data[ph]) for ph in binding):
                return None
            feeders = {
                ph: _block_feeder(col_data[ph])[0] for ph in binding
            }
            # small rows dispatch in larger chunks: the row cap protects
            # activation memory for heavy per-row programs, but each
            # dispatch pays link latency — scale the chunk up until a
            # call's input+output bytes reach the byte cap (1M scalar
            # rows: 123 row-capped dispatches -> 1)
            per_row = max(1, est // n)
            for ph in binding:
                cd = col_data[ph]
                cell = cd.dense.shape[1:]
                per_row += int(np.prod(cell, initial=1)) * cd.dense.dtype.itemsize
            byte_capped = max(
                chunk, int(get_config().max_bytes_per_device_call // per_row)
            )

            reached_cap = [byte_capped <= chunk]

            def attempt(fast_chunk):
                """One device-resident pass at the given starting chunk.
                The first chunk at each raised size syncs as an OOM probe
                (halving toward the row cap); later same-size chunks
                dispatch async. A late async OOM (memory pressure grows as
                result pieces accumulate) surfaces at the terminal sync
                and is handled by the caller's row-cap retry — unless this
                pass already ran at the cap (``reached_cap``), where a
                repeat would just OOM again."""
                pieces: Dict[str, List] = {name: [] for name in fetch_names}
                lo = 0
                probe_size = fast_chunk if fast_chunk > chunk else None
                from ..utils.chaos import site as _chaos_site

                while lo < n:
                    hi = min(lo + fast_chunk, n)
                    _m_blocks_map_rows.inc()
                    feed = {ph: feeders[ph](lo, hi) for ph in binding}
                    try:
                        # chaos here exercises the degrade path: a
                        # non-OOM failure drops the whole pass to the
                        # synchronous chunked engine (retry + halving)
                        _chaos_site("engine.dispatch")
                        res = run_bucket(feed, hi - lo)
                        # the raised-chunk OOM probe syncs so halving can
                        # react before the rest of the pass dispatches —
                        # pointless when this chunk IS the whole pass (the
                        # terminal sync right below catches it, and the
                        # caller's row-cap retry recovers); skipping it
                        # saves one host round trip per single-chunk
                        # pass
                        if probe_size == fast_chunk and hi < n:
                            jax.block_until_ready(res)
                            probe_size = None
                    except Exception as e:
                        if is_oom(e) and fast_chunk > chunk:
                            record_oom_split("map_rows")
                            fast_chunk = max(chunk, fast_chunk // 2)
                            if fast_chunk <= chunk:
                                reached_cap[0] = True
                            probe_size = (
                                fast_chunk if fast_chunk > chunk else None
                            )
                            logger.warning(
                                "map_rows raised chunk exhausted device "
                                "memory; lowering to %d rows", fast_chunk,
                            )
                            del feed
                            continue
                        raise
                    for name in fetch_names:
                        pieces[name].append(res[name])
                    lo = hi
                cols: Dict[str, _ColumnData] = {}
                for name in fetch_names:
                    # sync (no transfer) so async failures surface in this
                    # window, not later in user code
                    arr = jax.block_until_ready(
                        _concat_dense(pieces[name])
                    )
                    cols[name] = _ColumnData(dense=arr)
                return cols

            try:
                return attempt(byte_capped)
            except Exception as e:
                if is_oom(e) and not reached_cap[0]:
                    # a LATER raised chunk OOMed past the probe: retry the
                    # whole pass at the row cap, keeping device residency
                    # (skipped when the pass already halved to the cap and
                    # still OOMed — a repeat would fail the same way)
                    record_oom_split("map_rows")
                    logger.warning(
                        "map_rows byte-capped pass exhausted device "
                        "memory past the probe; retrying device-resident "
                        "at the %d-row cap", chunk,
                    )
                    try:
                        return attempt(chunk)
                    except Exception:
                        pass
                logger.warning(
                    "map_rows device-resident path failed; falling back "
                    "to synchronous chunked execution",
                    exc_info=True,
                )
                return None

        dropped_rows: List[int] = []
        cols = (
            run_dense_fast()
            if dense_fast and device_resident and ledger is None
            else None
        )
        if cols is None:
            if ledger is not None:
                # -- journaled block loop (engine/jobs.py) -----------------
                if dense_fast:
                    # resume: rebuild the SAME plan the journal was
                    # written with (contiguous row ranges straight off
                    # the manifest) — knobs that shape FRESH plans may
                    # have been retuned since, and a resume must restore
                    # completed blocks, not reject them over a config
                    # delta. The fingerprint still validates everything
                    # else, and ensure_plan re-checks entry equality.
                    plan_subs: Optional[List[Sequence[int]]] = None
                    stored = ledger.stored_plan
                    if stored:
                        subs: List[Sequence[int]] = []
                        nxt = 0
                        for e in stored:
                            first, last = e.get("first"), e.get("last")
                            if (
                                first != nxt
                                or last is None
                                or e.get("rows") != last - first + 1
                            ):
                                subs = None  # bucketed/foreign plan
                                break
                            subs.append(range(first, last + 1))
                            nxt = last + 1
                        if subs is not None and nxt == n:
                            plan_subs = subs
                    if plan_subs is None:
                        # fresh job: the plan chunk is CAPPED at the
                        # transfer-chunk row quantum so a journal block
                        # never spans transfer chunks — a resumed job
                        # re-uploads exactly its unfinished blocks'
                        # bytes and nothing of the completed ones
                        # (docs/ingest.md)
                        per_row_bytes = sum(
                            _transfer.wire_dtype(cd.dense.dtype).itemsize
                            * int(np.prod(cd.dense.shape[1:], initial=1))
                            for cd in col_data.values()
                            if cd.dense is not None
                        )
                        plan_chunk = max(
                            1,
                            min(chunk, _transfer.chunk_rows(per_row_bytes)),
                        )
                        plan_subs = [
                            range(lo, min(lo + plan_chunk, n))
                            for lo in range(0, n, plan_chunk)
                        ]
                else:
                    plan_subs = [
                        idxs[lo : lo + chunk]
                        for _, idxs in buckets.items()
                        for lo in range(0, len(idxs), chunk)
                    ]

                def plan_entry(sub):
                    first, last = int(sub[0]), int(sub[-1])
                    if isinstance(sub, range):
                        total = (first + last) * len(sub) // 2
                    else:
                        total = int(
                            np.asarray(sub, dtype=np.int64).sum()
                        )
                    return {
                        "rows": len(sub),
                        "first": first,
                        "last": last,
                        "ck": int(total % (1 << 31)),
                    }

                ledger.ensure_plan(
                    [plan_entry(s) for s in plan_subs],
                    graph=graph, schema=parent.schema, rows=n,
                )
                for bi, sub in enumerate(plan_subs):
                    st, arrs = ledger.lookup(bi)
                    if st == "quarantined":
                        dropped_rows.extend(int(i) for i in sub)
                        continue
                    if st == "todo":
                        def compute(sub=sub):
                            acc: Dict[str, List[np.ndarray]] = {
                                name: [] for name in fetch_names
                            }
                            run_chunk(
                                sub,
                                sink=lambda name, arr: acc[name].append(arr),
                            )
                            return {
                                name: (
                                    np.concatenate(acc[name], axis=0)
                                    if len(acc[name]) > 1
                                    else acc[name][0]
                                )
                                for name in fetch_names
                            }

                        arrs = ledger.run_block(bi, compute, rows=len(sub))
                        if arrs is None:  # quarantined just now
                            dropped_rows.extend(int(i) for i in sub)
                            continue
                    for name in fetch_names:
                        arr = arrs[name]
                        if dense_fast:
                            dense_pieces[name].append(arr)
                        else:
                            for j, i in enumerate(sub):
                                out_cells[name][i] = arr[j]
            else:
                if dense_fast and not buckets:
                    buckets[tuple(dense_keys[ph] for ph in binding)] = list(
                        range(n)
                    )
                for _, idxs in buckets.items():
                    for lo in range(0, len(idxs), chunk):
                        run_chunk(idxs[lo : lo + chunk])
            cols = {}
            dropped_set = set(dropped_rows)
            if dense_fast:
                for name in fetch_names:
                    ps = dense_pieces[name]
                    if not ps:
                        dense = _empty_output(
                            out_specs[name], block_output=False
                        )
                    else:
                        dense = _concat_dense(ps)
                    cols[name] = _ColumnData(dense=dense)
            elif dropped_set:
                for name in fetch_names:
                    cd, _ = _build_column(
                        name,
                        [
                            out_cells[name][i]
                            for i in range(n)
                            if i not in dropped_set
                        ],
                    )
                    cols[name] = cd
            else:
                for name in fetch_names:
                    cd, _ = _build_column(name, out_cells[name])
                    cols[name] = cd
        if dropped_rows:
            # quarantined blocks' rows vanish from the result: carried
            # parent columns take the survivors, and partition offsets
            # shrink by each partition's dropped count
            dropped_arr = np.asarray(sorted(dropped_rows), dtype=np.int64)
            keep = np.setdiff1d(
                np.arange(n, dtype=np.int64), dropped_arr,
                assume_unique=True,
            )
            for c in parent.schema:
                cols[c.name] = parent.column_data(c.name).take(keep)
            part_counts = [
                int(hi - lo)
                - int(np.searchsorted(dropped_arr, hi)
                      - np.searchsorted(dropped_arr, lo))
                for lo, hi in parent.partition_bounds()
            ]
            offsets = np.concatenate(
                [[0], np.cumsum(part_counts)]
            ).astype(np.int64)
            return TensorFrame(cols, result_info, offsets=offsets)
        for c in parent.schema:
            cols[c.name] = parent.column_data(c.name)
        if result_partitions is not None:
            return TensorFrame(
                cols, result_info, num_partitions=result_partitions
            )
        offsets = np.array(
            [lo for lo, _ in parent.partition_bounds()] + [n], dtype=np.int64
        )
        return TensorFrame(cols, result_info, offsets=offsets)

    def instrumented() -> TensorFrame:
        with _span("engine.map_rows") as sp:
            out = thunk()
            if sp is not None:
                sp.attrs["rows"] = parent.num_rows
        _m_rows_map_rows.inc(parent.num_rows)
        return out

    return instrumented


def apply_decoders(
    dframe: TensorFrame,
    decoders: Dict[str, Callable],
    feed_dict: Optional[Dict[str, str]] = None,
) -> TensorFrame:
    """Stack host decode stages onto a frame (see
    :meth:`TensorFrame.decode_column`). Keys are column names, or
    placeholder names routed through ``feed_dict`` — matching how the
    reference binds its string tensor to the bytes column
    (``read_image.py:158-160``). Decoding is forced here and the result
    ``analyze``d so downstream capture sees concrete cell shapes (the
    reference likewise requires ``tfs.analyze`` before non-scalar ops)."""
    for col, fn in _resolve_decoder_cols(
        decoders, feed_dict, dframe.schema.names
    ).items():
        dframe = dframe.decode_column(col, fn)
    return dframe.analyze()


def map_rows(
    fetches,
    dframe: TensorFrame,
    feed_dict: Optional[Dict[str, str]] = None,
    decoders: Optional[Dict[str, Callable]] = None,
    _ledger=None,
    _plan: bool = True,
) -> TensorFrame:
    """Transform row by row (``core.py:223-264``). Rows with equal cell
    shapes are batched and executed with ``vmap`` in one XLA program per
    shape bucket — the TPU replacement for the reference's one-Session.run-
    per-row loop (``performMapRows``, ``DebugRowOps.scala:819-857``). Ragged
    columns are supported; binary columns run on the host path — or, with
    ``decoders={placeholder_or_column: bytes -> array}``, decode on the
    host and batch the numeric program on device (the reference's
    decode-in-graph image scoring, ``read_image.py:147-167``, done the
    TPU way)."""
    if decoders:
        dframe = apply_decoders(dframe, decoders, feed_dict)
    g = _as_graph(fetches, dframe, cell_inputs=True, feed_dict=feed_dict)
    binding = validate_map_inputs(g, dframe.schema, block=False)
    _ensure_precision(g, dframe.schema)
    host_mode = any(
        dframe.schema[col].scalar_type.name == "binary"
        for col in binding.values()
    )
    if host_mode and _ledger is not None:
        raise ValueError(
            "journaled map_rows does not support binary-column host "
            "programs; decode to numeric columns first (decoders=) and "
            "journal the numeric pass"
        )
    if host_mode:
        # binary programs run on the host; discover output specs from a real
        # first-row execution (the reference analyzes binary graphs via the
        # TF runtime — there is no abstract trace for host programs here)
        if dframe.num_rows == 0:
            raise ValueError("map_rows on an empty binary-column frame")
        from ..schema import for_any

        probe = g.fn(
            {ph: dframe.column_data(col).cell(0) for ph, col in binding.items()}
        )
        out_specs = {
            name: TensorSpec(
                name,
                for_any(np.asarray(v) if not isinstance(v, bytes) else v),
                Shape([Unknown] * np.asarray(v).ndim)
                if not isinstance(v, bytes)
                else Shape.empty(),
            )
            for name, v in probe.items()
            if name in g.fetch_names
        }
    else:
        input_shapes = {
            ph: dframe.schema[col].cell_shape for ph, col in binding.items()
        }
        out_specs = g.analyze(input_shapes, share_lead=False)
    check_output_collisions(out_specs, dframe.schema)
    fetch_names = sorted(out_specs)
    fetch_infos = [
        _fetch_column_info(n, out_specs[n], block_output=False)
        for n in fetch_names
    ]
    result_info = FrameInfo(fetch_infos + list(dframe.schema))
    parent = dframe

    if host_mode:

        def thunk() -> TensorFrame:
            n = parent.num_rows
            if n == 0:
                cols = {
                    name: _ColumnData(
                        dense=_empty_output(
                            out_specs[name], block_output=False
                        )
                    )
                    for name in fetch_names
                }
                for c in parent.schema:
                    cols[c.name] = parent.column_data(c.name)
                return TensorFrame(cols, result_info)
            col_data = {
                ph: parent.column_data(col) for ph, col in binding.items()
            }
            out_cells: Dict[str, List] = {
                name: [None] * n for name in fetch_names
            }
            for i in range(n):
                feed = {ph: cd.cell(i) for ph, cd in col_data.items()}
                res = g.fn(feed)
                for name in fetch_names:
                    v = res[name]
                    out_cells[name][i] = (
                        v
                        if isinstance(v, (bytes, bytearray))
                        else np.asarray(v)
                    )
            cols: Dict[str, _ColumnData] = {}
            for name in fetch_names:
                cd, _ = _build_column(name, out_cells[name])
                cols[name] = cd
            for c in parent.schema:
                cols[c.name] = parent.column_data(c.name)
            offsets = np.array(
                [lo for lo, _ in parent.partition_bounds()] + [n],
                dtype=np.int64,
            )
            return TensorFrame(cols, result_info, offsets=offsets)

        _host_run = thunk

        def thunk() -> TensorFrame:
            with _span("engine.map_rows", host=True) as sp:
                out = _host_run()
                if sp is not None:
                    sp.attrs["rows"] = parent.num_rows
            _m_rows.inc(parent.num_rows, op="map_rows_host")
            return out

    else:
        thunk = _map_rows_thunk(
            parent,
            binding,
            fetch_names,
            out_specs,
            result_info,
            run_bucket=lambda feed, m: _jitted_vmap(g)(feed),
            ledger=_ledger,
            graph=g,
            explicit_h2d=True,
        )

    if _plan and _ledger is None and not host_mode:
        from . import plan as _plan_mod

        if _plan_mod.enabled():
            # logical-plan node (docs/pipelines.md); binary/host-path
            # programs stay op-at-a-time and bound the chain
            return _plan_mod.make_lazy_map(
                "map_rows", parent, g, binding, fetch_names,
                result_info, thunk,
            )
    return TensorFrame(
        {}, result_info, num_partitions=parent.num_partitions, _thunk=thunk
    )


# ---------------------------------------------------------------------------
# reduce_blocks / reduce_rows
# ---------------------------------------------------------------------------


def _unpack_reduce_result(
    acc: Dict[str, Any], fetch_names: Sequence[str]
) -> Union[np.ndarray, List[np.ndarray]]:
    """Reference ``_unpack_row`` (``core.py:110-124``): numpy per fetch,
    unwrapped when there is a single fetch. One batched device_get for all
    fetches — per-fetch np.asarray would pay one host round-trip each."""
    import jax

    host = jax.device_get({f: acc[f] for f in fetch_names})
    vals = []
    for f in fetch_names:
        a = np.asarray(host[f])
        vals.append(a if a.ndim > 0 else a[()])
    return vals[0] if len(vals) == 1 else vals


def reduce_blocks(fetches, dframe: TensorFrame, _ledger=None):
    """Block reduce to a single row (eager; ``core.py:311-349``). One program
    run per partition block, then a fixed ``[2, ...]`` merge program folds
    the partials — replacing the reference's executors→driver funnel
    (``DebugRowOps.scala:503-526``).

    ``_ledger`` (private) is the durable-job hook (``engine/jobs.py``):
    per-partition partials spool to the journal, quarantined partitions
    drop out of the fold, and a resume folds restored + freshly-computed
    partials in partition order (byte-identical to a clean run). Returns
    ``None`` when a journaled job quarantined every partition.

    Over a *pending planned* frame (a recorded map chain that has not
    been forced) this is a plan terminal: with
    ``Config.plan_hoist_reduce`` the reduce folds into the fused map
    program's per-block epilogue, and either way the reduce's bindings
    drive column pruning — the chain's dead ops never run and their
    source columns never cross the link (``engine/plan.py``)."""
    with _span("engine.reduce_blocks", partitions=dframe.num_partitions):
        from . import plan as _plan_mod

        handled, out, rows = (False, None, None)
        if _plan_mod.enabled():
            handled, out, rows = _plan_mod.reduce_terminal(
                fetches, dframe, ledger=_ledger
            )
        if not handled:
            out = _reduce_blocks_impl(fetches, dframe, _ledger)
            rows = dframe.num_rows
    _m_rows.inc(rows, op="reduce_blocks")
    return out


def _reduce_blocks_impl(fetches, dframe: TensorFrame, ledger=None):
    # NOTE: engine/plan.py's `_lower_hoisted_reduce` mirrors this drive
    # (grouped async dispatch unjournaled, per-partition sync + spool
    # journaled, OOM degrade to halved spans merged through the reduce
    # program) with a fused maps+reduce partial program — a semantics
    # change to retry/OOM/quarantine handling here must be applied there
    g = _as_graph(fetches, dframe, cell_inputs=False)
    binding = validate_reduce_block_graph(g, dframe.schema)
    _ensure_precision(g, dframe.schema)
    jit_fn = _jitted(g)
    feeders = {}
    any_streams = False
    for f, col in binding.items():
        dframe.column_block(col, None)  # rejects ragged/binary
        feeders[f], streams = _block_feeder(dframe.column_data(col))
        any_streams = any_streams or streams
    import jax.numpy as jnp

    from ..utils import is_oom, run_with_retries

    bounds = dframe.partition_bounds()

    def merge_two(a, b):
        feed = {
            f"{f}_input": jnp.stack([a[f], b[f]]) for f in binding
        }
        return jit_fn(feed)

    def partial_for_span(lo: int, hi: int, what: str):
        """One partial over rows [lo, hi) — with OOM degrade: a span too
        large for HBM halves recursively and the halves merge through the
        same ``[2, ...]`` program the partition fold uses. Sound for the
        same reason the fold is: reduce_blocks programs are declared
        algebraic over blocks (``Operations.scala:110-120``)."""
        feed = {f"{f}_input": feeders[f](lo, hi) for f in binding}

        def dispatch():
            import jax

            from ..utils.chaos import site as _chaos_site

            _chaos_site("engine.dispatch")
            # sync INSIDE the retry window (partials are consumed by the
            # host-driven fold right after, so the sync costs nothing)
            return jax.block_until_ready(jit_fn(feed))

        try:
            return run_with_retries(dispatch, what=what)
        except Exception as e:
            if is_oom(e):
                if hi - lo > 1:
                    record_oom_split("reduce_blocks")
                    logger.warning(
                        "reduce_blocks span of %d rows exhausted device "
                        "memory; halving and merging the halves",
                        hi - lo,
                    )
                    del feed
                    mid = (lo + hi) // 2
                    a = partial_for_span(lo, mid, what)
                    b = partial_for_span(mid, hi, what)
                    return merge_two(a, b)
                from ..utils.failures import DeviceOOMError

                raise DeviceOOMError(
                    "reduce_blocks partial exhausted device memory even at "
                    "a single row; the per-block reduce itself does not "
                    "fit HBM"
                ) from e
            raise

    if ledger is not None:
        ledger.ensure_plan(
            [{"rows": hi - lo, "lo": lo, "hi": hi} for lo, hi in bounds],
            graph=g, schema=dframe.schema, rows=dframe.num_rows,
        )
    partials: List[Dict[str, Any]] = []
    if ledger is not None or any_streams:
        # per-partition dispatch with a sync each: journaled jobs need
        # host partials to spool (and per-block failure isolation); a
        # streaming column bounds HBM at one block's buffers. A transient
        # failure retries only its own partition, an OOM halves it.
        for p, (lo, hi) in enumerate(bounds):
            if hi == lo:
                continue
            what = f"reduce_blocks partition {p}"
            if ledger is not None:
                st, arrs = ledger.lookup(p)
                if st == "quarantined":
                    continue
                if st == "done":
                    partials.append(arrs)
                    continue
                res = ledger.run_block(
                    p,
                    lambda lo=lo, hi=hi, what=what: {
                        f: np.asarray(v)
                        for f, v in partial_for_span(lo, hi, what).items()
                    },
                    rows=hi - lo,
                )
                if res is not None:
                    partials.append(res)
            else:
                partials.append(partial_for_span(lo, hi, what))
    else:

        def feed_for(p):
            lo, hi = bounds[p]
            if hi - lo == 0:
                return None
            return {f"{f}_input": feeders[f](lo, hi) for f in binding}

        def all_partials() -> List[Dict[str, Any]]:
            import jax

            from ..utils.chaos import site as _chaos_site

            _chaos_site("engine.dispatch")
            ps = [
                jit_fn(feed)
                for feed in map(feed_for, range(dframe.num_partitions))
                if feed is not None
            ]
            # device-cached feeds: dispatch every partition async, ONE sync
            # for the group inside the retry window (per-partition syncing
            # costs one host round-trip per partition; a group retry only
            # re-runs compute, the transfers are memoized)
            return jax.block_until_ready(ps)

        try:
            partials = run_with_retries(
                all_partials, what="reduce_blocks partials"
            )
        except Exception as e:
            if not is_oom(e):
                raise
            # a partial blew HBM inside the grouped async dispatch: fall
            # back to the sequential per-partition path, where an
            # oversized span halves and its halves merge (the map_rows
            # degrade contract, brought to the reduce partials path)
            logger.warning(
                "reduce_blocks grouped dispatch exhausted device memory; "
                "retrying per partition with OOM halving",
            )
            partials = [
                partial_for_span(lo, hi, f"reduce_blocks partition {p}")
                for p, (lo, hi) in enumerate(bounds)
                if hi > lo
            ]
    if not partials:
        if ledger is not None and ledger.quarantined_indices:
            return None  # every partition quarantined; jobs.py surfaces it
        raise ValueError("reduce_blocks on an empty frame")
    _m_blocks.inc(len(partials), op="reduce_blocks")
    acc = partials[0]
    for part in partials[1:]:
        acc = merge_two(acc, part)
    return _unpack_reduce_result(acc, g.fetch_names)


def reduce_rows(fetches, dframe: TensorFrame):
    """Pairwise row reduce (eager; ``core.py:184-221``): fetch ``x`` consumes
    placeholders ``x_1``/``x_2``. Within a partition the fold is a
    ``lax.scan`` over the block (the reference's sequential
    ``performReducePairwise``, ``DebugRowOps.scala:930-969``, with the
    session loop compiled away); across partitions the same merge program
    folds the partials."""
    with _span("engine.reduce_rows", partitions=dframe.num_partitions):
        out = _reduce_rows_impl(fetches, dframe)
    _m_rows.inc(dframe.num_rows, op="reduce_rows")
    return out


def _reduce_rows_impl(fetches, dframe: TensorFrame):
    g = _as_graph(fetches, dframe, cell_inputs=True)
    binding = validate_reduce_row_graph(g, dframe.schema)
    _ensure_precision(g, dframe.schema)
    import jax
    import jax.numpy as jnp
    from jax import lax

    fetch_names = list(g.fetch_names)

    def merge(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
        feed = {}
        for f in fetch_names:
            feed[f"{f}_1"] = a[f]
            feed[f"{f}_2"] = b[f]
        return g.fn(feed)

    fold_block = getattr(g, "_fold_block_cache", None)
    if fold_block is None:

        @jax.jit
        def fold_block(block_feed: Dict[str, Any]) -> Dict[str, Any]:
            init = {f: block_feed[f][0] for f in fetch_names}
            rest = {f: block_feed[f][1:] for f in fetch_names}

            def body(carry, xs):
                return merge(carry, xs), None

            out, _ = lax.scan(body, init, rest)
            return out

        g._fold_block_cache = fold_block

    merge_jit = getattr(g, "_merge_cache", None)
    if merge_jit is None:
        merge_jit = jax.jit(merge)
        g._merge_cache = merge_jit

    feeders = {}
    for f, col in binding.items():
        dframe.column_block(col, None)  # rejects ragged/binary
        feeders[f], _ = _block_feeder(dframe.column_data(col))
    partials: List[Dict[str, Any]] = []
    for p in range(dframe.num_partitions):
        lo, hi = dframe.partition_bounds()[p]
        if hi - lo == 0:
            continue
        feed = {f: feeders[f](lo, hi) for f in binding}
        partials.append(fold_block(feed))
    if not partials:
        raise ValueError("reduce_rows on an empty frame")
    _m_blocks.inc(len(partials), op="reduce_rows")
    acc = partials[0]
    for part in partials[1:]:
        acc = merge_jit(acc, part)
    return _unpack_reduce_result(acc, fetch_names)


# ---------------------------------------------------------------------------
# aggregate
# ---------------------------------------------------------------------------

#: rows per chunk in the large-frame aggregate path: the segmented scan's
#: compile time grows with log2(rows scanned), so large frames are scanned
#: as [m, _AGG_CHUNK] with vmap (fixed depth, one compile per cell shape)
#: and per-chunk boundary partials merged by a recursive final pass
_AGG_CHUNK = 32768


def _group_sort(dframe: TensorFrame, keys: Sequence[str], binding) -> Tuple:
    """Memoizing wrapper around :func:`_group_sort_impl`: frames are
    immutable, so the sort permutation for a given key tuple is computed
    once per frame — repeated aggregates over the same grouping (different
    fetches, iterative passes) skip the sort and its host sync entirely."""
    cache = getattr(dframe, "_group_sort_cache", None)
    if cache is None:
        cache = dframe._group_sort_cache = {}
    ck = tuple(keys)
    hit = cache.get(ck)
    if hit is None:
        hit = cache[ck] = _group_sort_impl(dframe, keys, binding)
    else:
        # the binding checks in the impl are per-call (key/input overlap)
        for k in keys:
            if k in binding.values():
                raise ValueError(
                    f"column {k!r} cannot be both key and input"
                )
    return hit


def _segment_flags(neq, n: int) -> np.ndarray:
    """Host bool segment-start marks from a DEVICE adjacent-inequality
    vector, without the O(n) readback: one scalar (distinct-boundary
    count) and one [G-1] index vector cross the link instead of n bools.
    The host array is reconstructed by scattering True at the starts —
    grouped aggregation's host<->device traffic becomes O(groups) in the
    many-rows-per-group regime, instead of O(rows). High-cardinality
    keys (groups ~ rows) fall back to the plain bool readback, which is
    the smaller transfer there."""
    import jax.numpy as jnp

    g_minus_1 = int(neq.sum())
    if g_minus_1 > max(n // 8, 1):
        return np.concatenate([[True], np.asarray(neq)])
    flags = np.zeros(n, dtype=bool)
    flags[0] = True
    if g_minus_1:
        # round the static nonzero size up to a power of two so a stream
        # of frames with varying group counts compiles O(log n) programs,
        # not one per distinct count; fill_value=-1 marks the padding
        # (a real boundary index can be 0)
        size = 1 << (g_minus_1 - 1).bit_length()
        starts = np.asarray(
            jnp.nonzero(neq, size=size, fill_value=-1)[0]
        )
        starts = starts[:g_minus_1] + 1
        flags[starts] = True
    return flags


def _group_sort_impl(dframe: TensorFrame, keys: Sequence[str], binding) -> Tuple:
    """Group-key machinery shared by the local and distributed aggregates.

    Supports numeric scalar keys, binary (bytes/string) keys, and
    multi-column combinations of both — the reference aggregates under any
    Spark ``groupBy`` key, strings included (``DebugRowOps.scala:547-592``,
    ``core_test.py:213-222``).

    The sort itself runs ON DEVICE (stable argsort over the key column, or
    over host-computed integer codes), so host work is at most the O(n)
    dict-coding pass for binary/multi keys; for a single numeric key the
    host does no per-row work at all.

    Returns ``(order, flags, emit_keys)``:

    - ``order``: DEVICE int row permutation grouping equal keys (stays on
      device — for large frames it is tens of MB that the feed gather
      consumes in HBM anyway),
    - ``flags``: host bool segment-start marks over the sorted rows,
    - ``emit_keys(ends) -> dict[name, column_data]``: the representative key
      value per group, given sorted-row segment-end indices.
    """
    import jax.numpy as jnp

    n = dframe.num_rows
    key_cds = []
    for k in keys:
        kd = dframe.column_data(k)
        if kd.dense is None and not kd.is_binary:
            raise ValueError(
                f"grouping column {k!r} is ragged; group keys must be "
                f"scalars or binary cells"
            )
        if kd.dense is not None and kd.dense.ndim != 1:
            raise ValueError(
                f"grouping column {k!r} must hold scalar cells to group by"
            )
        if k in binding.values():
            raise ValueError(f"column {k!r} cannot be both key and input")
        key_cds.append(kd)

    if all(kd.dense is not None for kd in key_cds):
        # pure numeric: device lexsort via repeated stable argsort
        # (last key first), flags from adjacent inequality on device
        order_dev = None
        for kd in reversed(key_cds):
            kv = kd.device()
            if order_dev is None:
                order_dev = jnp.argsort(kv, stable=True)
            else:
                order_dev = order_dev[
                    jnp.argsort(kv[order_dev], stable=True)
                ]
        sorted_keys = [kd.device()[order_dev] for kd in key_cds]
        neq = None
        for sk in sorted_keys:
            d = sk[1:] != sk[:-1]
            neq = d if neq is None else (neq | d)
        flags = _segment_flags(neq, n)
        order = order_dev  # device-resident; no host round trip

        def emit_keys(ends):
            ends_dev = jnp.asarray(np.asarray(ends))
            return {
                k: sk[ends_dev] for k, sk in zip(keys, sorted_keys)
            }

    else:
        # binary or mixed keys: integer codes by first appearance.
        # pandas' hash-based ``factorize`` does this at C speed with no
        # sort and native first-appearance ordering (measured 0.7s for 10M
        # bytes keys, vs ~35s for a fixed-width-S np.unique sort and ~10s
        # for a per-row dict loop); a numpy np.unique path (provisional
        # codes -> first-appearance renumber) is the no-pandas fallback.
        # The sort over codes still runs on device.
        try:
            import pandas as pd
        except Exception:  # pragma: no cover - pandas is a std dep here
            pd = None

        def first_appearance_codes(arr, axis=None):
            _, first, inv = np.unique(
                arr, axis=axis, return_index=True, return_inverse=True
            )
            rank = np.empty(len(first), dtype=np.int64)
            rank[np.argsort(first, kind="stable")] = np.arange(len(first))
            return rank[inv.reshape(-1)]

        def binary_codes(cells) -> np.ndarray:
            # fastest path: the native thread-pool coder (parallel local
            # dictionaries + first-appearance merge, executor.cpp); it
            # returns None without the compiled library or on non-bytes
            # cells, falling through to pandas/numpy
            from ..data.packer import code_keys

            native = code_keys(cells)
            if native is not None:
                return native.astype(np.int64, copy=False)
            if pd is not None:
                arr = np.empty(n, dtype=object)
                # storage cells are bytes already: direct elementwise
                # assign (C speed) instead of 10M bytes() calls. The
                # TypeError fallback covers non-bytes byte-likes
                # (bytearray, memoryview), which assign fine but are
                # unhashable inside factorize; genuine factorize failures
                # (MemoryError etc.) propagate.
                arr[:] = cells
                try:
                    return pd.factorize(arr)[0].astype(np.int64, copy=False)
                except TypeError:
                    arr[:] = [bytes(c) for c in cells]
                    return pd.factorize(arr)[0].astype(np.int64, copy=False)
            # fallback: fixed-width S array (trailing 0x01 sentinel defeats
            # numpy's trailing-NUL stripping) unless one outlier key would
            # balloon the n x max_len buffer, where the O(total bytes)
            # dict loop is the cheaper pass
            lengths = np.fromiter(
                (len(c) for c in cells), dtype=np.int64, count=n
            )
            padded = n * (int(lengths.max(initial=0)) + 1)
            total = int(lengths.sum()) + n
            if padded > max(total * 8, 1 << 26):
                mapping: Dict[bytes, int] = {}
                out = np.empty(n, dtype=np.int64)
                for i, c in enumerate(cells):
                    c = bytes(c)
                    code = mapping.get(c)
                    if code is None:
                        code = mapping[c] = len(mapping)
                    out[i] = code
                return out
            arr = np.asarray([bytes(c) + b"\x01" for c in cells])
            _, inv = np.unique(arr, return_inverse=True)
            inexact_order.append(True)  # unique sorts; not first-appearance
            return inv.reshape(-1).astype(np.int64)

        #: coders append here when their output is NOT first-appearance
        #: ordered (numpy unique fallbacks sort; the NaN branch appends
        #: singletons at the end of the range); a single-column result
        #: then gets one renumber pass, exact coders skip it
        inexact_order = []

        def numeric_codes(vals: np.ndarray) -> np.ndarray:
            # NaN semantics must match the dense-numeric path and the old
            # dict loop: NaN != NaN, so every NaN row is its own group
            # (factorize/np.unique would collapse or sentinel them)
            if np.issubdtype(vals.dtype, np.floating):
                nan = np.isnan(vals)
                if nan.any():
                    inexact_order.append(True)
                    out = np.empty(n, dtype=np.int64)
                    nn = vals[~nan]
                    if pd is not None:
                        out[~nan] = pd.factorize(nn)[0]
                    else:
                        _, inv = np.unique(nn, return_inverse=True)
                        out[~nan] = inv.reshape(-1)
                    k = n - int(nan.sum())
                    out[nan] = k + np.arange(int(nan.sum()))
                    return out
            if pd is not None:
                return pd.factorize(vals)[0].astype(np.int64, copy=False)
            _, inv = np.unique(vals, return_inverse=True)
            inexact_order.append(True)  # unique sorts; not first-appearance
            return inv.reshape(-1).astype(np.int64)

        per_col = [
            binary_codes(kd.cells) if kd.is_binary else numeric_codes(kd.host())
            for kd in key_cds
        ]
        if pd is not None:
            codes = per_col[0]
            for nxt in per_col[1:]:
                # re-factorize after each pairwise combine so the running
                # code range stays < n and the product cannot overflow;
                # factorize output is first-appearance, so combined codes
                # need no extra renumber
                codes = pd.factorize(
                    codes * (np.int64(nxt.max(initial=0)) + 1) + nxt
                )[0]
            if len(per_col) == 1 and inexact_order:
                # the one non-first-appearance coder: NaN singleton rows
                # appended at the end of the range
                codes = pd.factorize(codes)[0]
            codes = codes.astype(np.int64, copy=False)
        elif len(per_col) == 1:
            codes = (
                first_appearance_codes(per_col[0])
                if inexact_order
                else per_col[0]
            )
        else:
            codes = first_appearance_codes(
                np.stack(per_col, axis=1), axis=0
            )
        # codes are group ids < n: the narrowest dtype cuts the one
        # unavoidable link transfer of the string-key path (the codes
        # upload; order/flags already stay device-side) by 2-4x
        mx = int(codes.max()) if codes.size else 0
        if mx < (1 << 8):
            codes = codes.astype(np.uint8)
        elif mx < (1 << 16):
            codes = codes.astype(np.uint16)
        elif n < 2**31:
            codes = codes.astype(np.int32, copy=False)
        codes_dev = jnp.asarray(codes)
        order_dev = jnp.argsort(codes_dev, stable=True)
        sorted_c = codes_dev[order_dev]
        flags = _segment_flags(sorted_c[1:] != sorted_c[:-1], n)
        order = order_dev  # device-resident, same as the numeric path

        def emit_keys(ends):
            # gather the G representative row indices ON DEVICE and pull
            # only those (the full permutation never crosses the link)
            ends_dev = jnp.asarray(np.asarray(ends))
            rows = np.asarray(order_dev[ends_dev])
            out = {}
            for k, kd in zip(keys, key_cds):
                if kd.is_binary:
                    out[k] = [kd.cells[i] for i in rows]
                else:
                    out[k] = kd.host()[rows]
            return out

    return order, flags, emit_keys


def aggregate(fetches, grouped_data: GroupedFrame) -> TensorFrame:
    """Keyed algebraic aggregation (``core.py:377-395``): for grouped data,
    reduce each group with the block-reduce graph.

    TPU-native design replacing the reference's Spark-shuffle UDAF
    (``TensorFlowUDAF``, ``DebugRowOps.scala:601-695``):

    1. per-row partials: the reduce graph runs on blocks of 1 via ``vmap``
       (one program, any row count);
    2. rows sorted by group key ON DEVICE (stable argsort; binary/mixed
       keys get O(n) host dict-coding first — see :func:`_group_sort`);
    3. one *segmented associative scan* on device combines partials within
       segments — ``combine((a,fa),(b,fb)) = (fb ? b : merge(a,b), fa|fb)``
       where ``merge`` stacks two partials and re-applies the reduce graph;
    4. the last scan element of each segment is that group's result.

    The merge is assumed associative, same as the reference ("algebraic
    aggregation", ``Operations.scala:110-120``). Keys may be numeric
    scalars, binary cells, or multi-column mixes (reference
    ``DebugRowOps.scala:547-592``).
    """
    # chunked aggregates recurse through this wrapper on their partial
    # tables, so nested spans (and per-pass row counts) show the recursion
    with _span("engine.aggregate", keys=",".join(grouped_data.keys)):
        out = _aggregate_impl(fetches, grouped_data)
    return out


def _aggregate_impl(fetches, grouped_data: GroupedFrame) -> TensorFrame:
    dframe = grouped_data.frame
    keys = grouped_data.keys
    if not keys:
        raise ValueError("aggregate requires at least one grouping column")
    g = _as_graph(fetches, dframe, cell_inputs=False)
    binding = validate_reduce_block_graph(g, dframe.schema)
    _ensure_precision(g, dframe.schema)
    from . import plan as _plan_mod

    if _plan_mod.enabled():
        # aggregate is a plan terminal: a pending map chain executes as
        # a demand-pruned fused view (bound inputs + group keys only);
        # the lazy frame itself stays lazy — forcing it later yields its
        # full schema (engine/plan.py, docs/pipelines.md)
        dframe = _plan_mod.pruned_view(
            dframe, set(binding.values()) | set(keys)
        )
    import jax
    import jax.numpy as jnp
    from jax import lax

    fetch_names = list(g.fetch_names)
    n = dframe.num_rows
    if n == 0:
        raise ValueError("aggregate on an empty frame")
    _m_rows.inc(n, op="aggregate")

    order, flags, emit_keys = _group_sort(dframe, keys, binding)

    progs = getattr(g, "_agg_scan_cache", None)
    if progs is None:

        def merge_pair(a: Dict[str, Any], b: Dict[str, Any]) -> Dict[str, Any]:
            feed = {
                f"{f}_input": jnp.stack([a[f], b[f]]) for f in fetch_names
            }
            return g.fn(feed)

        vmerge = jax.vmap(merge_pair)

        def scan_body(block_feed: Dict[str, Any], flags_: Any) -> Dict[str, Any]:
            # per-row partials: reduce graph applied to blocks of one row
            per_row = jax.vmap(
                lambda cells: g.fn(
                    {
                        f"{f}_input": cells[f][None] for f in fetch_names
                    }
                )
            )({f: block_feed[f] for f in fetch_names})

            def combine(x, y):
                vx, fx = x
                vy, fy = y
                merged = vmerge(vx, vy)
                out = {}
                for f in fetch_names:
                    fy_b = fy.reshape(fy.shape + (1,) * (merged[f].ndim - 1))
                    out[f] = jnp.where(fy_b, vy[f], merged[f])
                return out, fx | fy

            scanned, _ = lax.associative_scan(combine, (per_row, flags_), axis=0)
            return scanned

        # plain jit for small frames; vmap-over-chunks for large ones (the
        # chunked program's scan depth is fixed at log2(_AGG_CHUNK), so
        # compile time stops growing with the frame)
        progs = (jax.jit(scan_body), jax.jit(jax.vmap(scan_body)))
        g._agg_scan_cache = progs
    scan_fn, chunked_fn = progs

    # feed gather happens on device: column -> HBM once (memoized), then a
    # device gather by the sorted order — the host never touches the values
    order_dev = jnp.asarray(order)
    sorted_feed = {
        f: dframe.column_data(col).device()[order_dev]
        for f, col in binding.items()
    }

    if n > _AGG_CHUNK:
        # -- chunked path: pad to a multiple of the chunk, force a segment
        # restart at every chunk boundary, scan all chunks in parallel with
        # one fixed-depth program, then merge the boundary partials by
        # recursing on the (tiny) per-chunk-per-group partial table — the
        # same partial/final shape as the distributed engine's shard merge.
        m = -(-n // _AGG_CHUNK)
        n_pad = m * _AGG_CHUNK
        flags_p = np.zeros(n_pad, dtype=bool)
        flags_p[:n] = flags
        flags_p[np.arange(m) * _AGG_CHUNK] = True
        if n_pad > n:
            flags_p[n] = True  # padding forms its own garbage segment
        starts = np.nonzero(flags_p[:n])[0]
        ends = np.append(starts[1:] - 1, n - 1)
        if len(ends) > n // 2:
            # nearly-unique keys: the partial table cannot shrink enough for
            # the recursion to make progress (equal-size recursion would
            # never terminate), so scan the whole frame in one log2(n)-depth
            # program instead — slower to compile, but correct at any group
            # count
            ends = None
    else:
        ends = None

    if ends is not None:
        feed_r = {}
        for f, arr in sorted_feed.items():
            pad_width = [(0, n_pad - n)] + [(0, 0)] * (arr.ndim - 1)
            padded = jnp.pad(arr, pad_width)
            feed_r[f] = padded.reshape((m, _AGG_CHUNK) + arr.shape[1:])
        scanned = chunked_fn(feed_r, flags_p.reshape(m, _AGG_CHUNK))
        ci = jnp.asarray(ends // _AGG_CHUNK)
        co = jnp.asarray(ends % _AGG_CHUNK)
        partial_cols: Dict[str, Any] = dict(emit_keys(ends))
        for f in fetch_names:
            partial_cols[f] = scanned[f][ci, co]  # device gather, #partials rows
        partials = TensorFrame.from_columns(partial_cols).analyze()
        # cache the renamed final-merge graph ON g: a fresh CapturedGraph
        # per pass would drop its jitted scan programs and recompile the
        # final scan on every aggregate call
        g2 = getattr(g, "_agg_final_graph", None)
        if g2 is None:
            g2 = g._agg_final_graph = g.with_inputs(
                {f"{f}_input": f for f in fetch_names}
            )
        # the partial table's KEY STRUCTURE (sort order, segment flags) is
        # deterministic for a given parent frame + keys + chunking, even
        # though its values change per pass — seed the fresh frame's sort
        # cache with the previous pass's WHOLE cache dict (it also carries
        # the deeper recursion levels' seeds), so repeated aggregates skip
        # every per-level device sync after the first pass
        seed_key = (tuple(keys), "__partials__", len(ends))
        seed = dframe._group_sort_cache.get(seed_key)
        if seed is not None:
            partials._group_sort_cache = seed
        result = aggregate(g2, GroupedFrame(partials, keys))
        dframe._group_sort_cache[seed_key] = getattr(
            partials, "_group_sort_cache", {}
        )
        return result

    out_specs = g.analyze(
        {
            f"{f}_input": dframe.schema[col].block_shape.with_lead(Unknown)
            for f, col in binding.items()
        }
    )
    scanned = scan_fn(sorted_feed, flags)
    # last row of each segment holds that group's reduce
    ends = np.append(np.nonzero(flags[1:])[0], n - 1)
    cols: Dict[str, _ColumnData] = {}
    infos: List[ColumnInfo] = []
    for k, kdata in emit_keys(ends).items():
        cd, _ = _build_column(k, kdata)
        cols[k] = cd
        infos.append(dframe.schema[k])
    for f in fetch_names:
        cols[f] = _ColumnData(dense=scanned[f][jnp.asarray(ends)])
        infos.append(_fetch_column_info(f, out_specs[f], block_output=False))
    return TensorFrame(cols, FrameInfo(infos))


# ---------------------------------------------------------------------------
# analyze / print_schema / explain
# ---------------------------------------------------------------------------


def analyze(dframe: TensorFrame) -> TensorFrame:
    """Deep shape analysis (``core.py:362-375``); see
    :meth:`TensorFrame.analyze`."""
    return dframe.analyze()


def explain(dframe: TensorFrame, analyze: bool = False) -> str:
    """Detailed schema string (reference ``DebugRowOps.explain``,
    ``DebugRowOps.scala:528-545``) — and, for a pending planned frame,
    the logical plan first: recorded nodes, which rewrite passes fire,
    pruned columns, and the fused program count (``engine/plan.py``).
    Pure: rendering the plan neither forces the frame nor executes it.

    ``analyze=True`` appends the per-program cost table from the
    observatory's registry (``obs/programs.py``): every compiled
    program this process has dispatched, with compile wall-time,
    FLOP/byte estimates, invocation counts, cumulative dispatch time,
    and roofline utilization — what a forced pipeline actually cost
    (docs/observability.md) — followed by the autotuner's installed
    tuned configs (``tensorframes_tpu.tune``; docs/tuning.md)."""
    from . import plan as _plan_mod

    schema_txt = dframe.schema.explain()
    plan_txt = _plan_mod.explain_plan(dframe)
    if plan_txt is None:
        out = schema_txt
    else:
        out = f"{plan_txt}\n== Schema ==\n{schema_txt}"
    if analyze:
        out = f"{out}\n{_programs.render_table()}"
        from .. import tune as _tune

        out = f"{out}\n{_tune.render_table()}"
    return out


def print_schema(dframe: TensorFrame) -> None:
    """Print the tensor schema (``core.py:351-360``)."""
    print(explain(dframe))
