"""Distributed dataframe ops over a device mesh.

TPU-native replacement for the reference's Spark execution plane
(SURVEY §2.5). Mapping of mechanisms:

==========================  =================================================
reference (Spark)           this module (JAX/XLA over a Mesh)
==========================  =================================================
partition -> executor task  row shard -> chip along the ``dp`` mesh axis
broadcast of graph bytes    jit-compiled program, resident per device
``rdd.mapPartitions``       one ``shard_map`` program: each chip maps its
 (``DebugRowOps:377-391``)  shard in place
``RDD.reduce`` driver       ``lax.all_gather`` of per-shard partials over ICI
 funnel (``:524``,          + an on-device fold of the user's merge program —
 ``reducePair:732-750``)    no host round-trip, executed inside the same XLA
                            program as the local reduction
Spark shuffle + UDAF        global key sort + sharded segmented associative
 (``:547-592``)             scan + small boundary-group merge (partial/final
                            aggregation)
==========================  =================================================

Row counts not divisible by the mesh size are handled with a main+tail
split: the bulk runs in the sharded program, the remainder runs as one extra
block, and reduces merge the tail partial through the same pair-merge
program. Partition boundaries are not semantically observable (same contract
as Spark partitions in the reference), so this is behavior-preserving.

Compilation and transfer are both amortized: every jitted program (sharded
main, tail fold, pair merge) is memoized on the CapturedGraph, and
device-sharded copies of immutable columns are memoized per (mesh, split) —
iterative algorithms pay tracing and host->device movement once.

Multi-host: this module only speaks ``jax.devices()`` — under
``jax.distributed.initialize`` the same compiled programs span all hosts'
devices with collectives over DCN. Host-side feeds, however, must come from
each process's addressable rows: :mod:`tensorframes_tpu.parallel.multihost`
provides the per-host input pipeline (``global_batch``/``local_rows``),
exercised for real by the two-process suite in ``tests/test_multihost.py``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..engine.ops import (
    _as_graph,
    _empty_output,
    _ensure_precision,
    _fetch_column_info,
    _jitted,
    _jitted_vmap,
    _map_rows_thunk,
    _unpack_reduce_result,
)
from ..engine import aggregate as _local_aggregate
from ..engine.validation import (
    InvalidDimensionError,
    check_output_collisions,
    validate_map_inputs,
    validate_reduce_block_graph,
    validate_reduce_row_graph,
)
from ..frame import GroupedFrame, TensorFrame
from ..schema import FrameInfo, Shape, Unknown
from ..utils import get_config, get_logger
from jax import shard_map as _shard_map
from .mesh import DATA_AXIS, default_mesh

__all__ = ["map_blocks", "map_rows", "reduce_blocks", "reduce_rows", "aggregate"]

logger = get_logger("parallel")


def _mesh_or_default(mesh):
    return mesh if mesh is not None else default_mesh()


def _dp_size(mesh) -> int:
    return mesh.shape[DATA_AXIS]


def _dp_spec():
    from jax.sharding import PartitionSpec as P

    return P(DATA_AXIS)


def _split(n: int, ndev: int):
    main = (n // ndev) * ndev
    return main, n - main


# ---------------------------------------------------------------------------
# per-graph program + feed caches
# ---------------------------------------------------------------------------


def _cached_program(g, key, build: Callable[[], Any]):
    """Memoize a compiled program on the CapturedGraph (the distributed
    analog of the local engine's ``g._jit_cache``). Dispatches retry on
    transient runtime failures, same policy as the local engine
    (``utils.failures``; the reference leans on Spark task retry here)."""
    from ..utils import run_with_retries

    cache = getattr(g, "_shard_cache", None)
    if cache is None:
        cache = {}
        g._shard_cache = cache
    if key not in cache:
        prog = build()

        def dispatch(*a, _prog=prog, _key=key, **k):
            import jax

            def _run():
                # sync inside the retry window — async failures would
                # otherwise surface later, past the handler; distributed
                # results are materialized promptly by their callers
                return jax.block_until_ready(_prog(*a, **k))

            if jax.process_count() > 1:
                # no local retries in a multi-process run: a transient
                # error seen by ONE process would re-enter the collective
                # program alone while peers that succeeded do not, leaving
                # the retried collectives without matching participants
                # (a silent hang at the Gloo/DCN barrier). Fail fast and
                # let the job-level restart (checkpoint/resume) recover —
                # the same contract as a lost Spark executor taking down
                # the stage in the reference.
                return _run()
            return run_with_retries(_run, what=f"distributed program {_key}")

        cache[key] = dispatch
    return cache[key]


def _shard_mapped(g, mesh, body, kind: str, const_names=()):
    """jit(shard_map(body)) with column inputs/outputs row-sharded over
    ``dp`` and ``const_names`` replicated; memoized per (mesh, kind)."""
    import jax
    from jax.sharding import PartitionSpec as P

    const_names = tuple(sorted(const_names))

    def build():
        return jax.jit(
            _shard_map(
                body,
                mesh=mesh,
                in_specs=(
                    {
                        ph: (P() if ph in const_names else _dp_spec())
                        for ph in g.placeholders
                    },
                ),
                out_specs=_dp_spec(),
            )
        )

    return _cached_program(g, (mesh, kind, const_names), build)


def _sharded_main_feed(
    df: TensorFrame, binding: Dict[str, str], mesh, main: int, key_fmt=str
) -> Dict[str, Any]:
    """Feed dict for the sharded main region.

    Columns within the device-cache budget are device_put once with the
    row-sharded NamedSharding and memoized per (mesh, main) on the column;
    larger columns stream as host slices (re-transferred per call, HBM
    bounded)."""
    import jax
    from jax.sharding import NamedSharding

    thr = get_config().device_cache_bytes
    out: Dict[str, Any] = {}
    for ph, col in binding.items():
        cd = df.column_data(col)
        arr = cd.dense
        if arr.nbytes <= thr:
            cache = cd._sharded_cache
            if cache is None:
                cache = {}
                cd._sharded_cache = cache
            ckey = (mesh, main)
            if ckey not in cache:
                cache[ckey] = jax.device_put(
                    arr[:main], NamedSharding(mesh, _dp_spec())
                )
            out[key_fmt(ph)] = cache[ckey]
        else:
            out[key_fmt(ph)] = arr[:main]
    return out


def _tail_feed(
    df: TensorFrame, binding: Dict[str, str], main: int, key_fmt=str
) -> Dict[str, Any]:
    return {
        key_fmt(ph): df.column_data(col).dense[main:]
        for ph, col in binding.items()
    }


# ---------------------------------------------------------------------------
# map_blocks
# ---------------------------------------------------------------------------


def map_blocks(
    fetches,
    dframe: TensorFrame,
    mesh=None,
    trim: bool = False,
    feed_dict: Optional[Dict[str, str]] = None,
    constants: Optional[Dict[str, Any]] = None,
) -> TensorFrame:
    """``map_blocks`` with one row shard per chip: a single ``shard_map``
    program executes the captured graph on every chip's shard concurrently
    (the distributed analog of the reference's per-partition tasks,
    ``DebugRowOps.scala:377-391``). ``constants`` are replicated per-call
    inputs (see the local engine docstring)."""
    mesh = _mesh_or_default(mesh)
    g = _as_graph(
        fetches, dframe, cell_inputs=False, feed_dict=feed_dict,
        constants=constants,
    )
    binding = validate_map_inputs(
        g, dframe.schema, block=True, constants=set(constants or ())
    )
    _ensure_precision(g, dframe.schema)
    input_shapes = {
        ph: dframe.schema[col].block_shape.with_lead(Unknown)
        for ph, col in binding.items()
    }
    out_specs = g.analyze(input_shapes)
    for name, spec in out_specs.items():
        if spec.shape.num_dims == 0:
            raise InvalidDimensionError(
                f"map_blocks output {name!r} is a scalar; map outputs must "
                f"keep the leading row dimension (use reduce_blocks to "
                f"reduce a frame to one row)"
            )
    if not trim:
        check_output_collisions(out_specs, dframe.schema)
    fetch_names = sorted(out_specs)
    fetch_infos = [
        _fetch_column_info(n, out_specs[n], block_output=True)
        for n in fetch_names
    ]
    result_info = FrameInfo(
        fetch_infos if trim else fetch_infos + list(dframe.schema)
    )
    ndev = _dp_size(mesh)
    parent = dframe
    const_feed = {ph: np.asarray(v) for ph, v in (constants or {}).items()}

    def thunk() -> TensorFrame:
        from ..frame.table import _ColumnData

        for col in binding.values():
            parent.column_block(col, None)  # rejects ragged/binary
        n = parent.num_rows
        main, tail = _split(n, ndev)
        pieces: Dict[str, List[np.ndarray]] = {f: [] for f in fetch_names}

        def check_rows(arr, expect, f):
            if not trim and arr.shape[0] != expect:
                raise ValueError(
                    f"map_blocks output {f!r} changed the row count; "
                    f"only trimmed maps may do that"
                )

        if main:
            prog = _shard_mapped(
                g, mesh, g.fn, kind="map", const_names=const_feed
            )
            res = prog(
                _sharded_main_feed(parent, binding, mesh, main) | const_feed
            )
            for f in fetch_names:
                arr = np.asarray(res[f])
                check_rows(arr, main, f)
                pieces[f].append(arr)
        if tail:
            res = _jitted(g)(
                _tail_feed(parent, binding, main) | const_feed
            )
            for f in fetch_names:
                arr = np.asarray(res[f])
                check_rows(arr, tail, f)
                pieces[f].append(arr)
        cols: Dict[str, _ColumnData] = {}
        for f in fetch_names:
            dense = (
                np.concatenate(pieces[f], axis=0)
                if pieces[f]
                else _empty_output(out_specs[f], block_output=True)
            )
            cols[f] = _ColumnData(dense=np.ascontiguousarray(dense))
        if trim:
            return TensorFrame(cols, result_info, num_partitions=ndev)
        for c in parent.schema:
            cols[c.name] = parent.column_data(c.name)
        return TensorFrame(cols, result_info, num_partitions=ndev)

    return TensorFrame({}, result_info, num_partitions=ndev, _thunk=thunk)


# ---------------------------------------------------------------------------
# map_rows
# ---------------------------------------------------------------------------


def map_rows(
    fetches,
    dframe: TensorFrame,
    mesh=None,
    feed_dict: Optional[Dict[str, str]] = None,
    decoders: Optional[Dict[str, Callable]] = None,
) -> TensorFrame:
    """Distributed row-wise map: rows are bucketed by input cell shape (as in
    the local engine), and each bucket runs as one ``shard_map``-of-``vmap``
    program with rows sharded over the ``dp`` axis — every chip maps its
    slice of the bucket concurrently. Ragged 1-D columns pack into
    (flat, offsets) buffers and feed buckets via a native gather-pad. The
    distributed analog of the reference's per-task row loop
    (``performMapRows``, ``DebugRowOps.scala:396-477,819-857``).

    Binary (host-path) programs have no device program to shard; they
    delegate to the local engine, same as the reference runs them inside an
    ordinary task."""
    import jax

    mesh = _mesh_or_default(mesh)
    if decoders:
        from ..engine.ops import apply_decoders

        dframe = apply_decoders(dframe, decoders, feed_dict)
    g = _as_graph(fetches, dframe, cell_inputs=True, feed_dict=feed_dict)
    binding = validate_map_inputs(g, dframe.schema, block=False)
    host_mode = any(
        dframe.schema[col].scalar_type.name == "binary"
        for col in binding.values()
    )
    if host_mode:
        from ..engine import map_rows as local_map_rows

        return local_map_rows(g, dframe)  # feed_dict already merged into g
    _ensure_precision(g, dframe.schema)
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
    ndev = _dp_size(mesh)
    parent = dframe

    def run_bucket(feed: Dict[str, Any], m: int) -> Dict[str, Any]:
        """Sharded main region + local tail, concatenated per fetch."""
        main, tail = _split(m, ndev)
        parts = []
        if main:
            vprog = _shard_mapped(g, mesh, jax.vmap(g.fn), kind="map_rows")
            parts.append(vprog({ph: feed[ph][:main] for ph in binding}))
        if tail:
            parts.append(
                _jitted_vmap(g)({ph: feed[ph][main:] for ph in binding})
            )
        if len(parts) == 1:
            return parts[0]
        return {
            f: np.concatenate([np.asarray(r[f]) for r in parts])
            for f in fetch_names
        }

    thunk = _map_rows_thunk(
        parent,
        binding,
        fetch_names,
        out_specs,
        result_info,
        run_bucket=run_bucket,
        result_partitions=ndev,
        # the sharded run_bucket feeds jit(shard_map) programs that expect
        # dp-sharded rows; the local engine's _block_feeder whole-column
        # device copy is the wrong residency for that path, so the
        # device-resident dense fast path is disabled here
        device_resident=False,
    )
    return TensorFrame({}, result_info, num_partitions=ndev, _thunk=thunk)


# ---------------------------------------------------------------------------
# reduce_blocks / reduce_rows
# ---------------------------------------------------------------------------


def reduce_blocks(fetches, dframe: TensorFrame, mesh=None):
    """Distributed block reduce: each chip reduces its shard, partials are
    ``all_gather``-ed over the ``dp`` axis (ICI), and the user's own merge
    program folds them — all in one compiled program. This replaces the
    reference's executors→driver funnel (``DebugRowOps.scala:503-526``)
    with a collective."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    mesh = _mesh_or_default(mesh)
    g = _as_graph(fetches, dframe, cell_inputs=False)
    binding = validate_reduce_block_graph(g, dframe.schema)
    for col in binding.values():
        dframe.column_block(col, None)
    _ensure_precision(g, dframe.schema)
    fetch_names = list(g.fetch_names)

    def prog(feed: Dict[str, Any]) -> Dict[str, Any]:
        local = g.fn(feed)  # per-shard partial
        gathered = {
            f: lax.all_gather(local[f], DATA_AXIS) for f in fetch_names
        }

        def body(carry, xs):
            merged = g.fn(
                {
                    f"{f}_input": jnp.stack([carry[f], xs[f]])
                    for f in fetch_names
                }
            )
            return merged, None

        init = {f: gathered[f][0] for f in fetch_names}
        rest = {f: gathered[f][1:] for f in fetch_names}
        out, _ = lax.scan(body, init, rest)
        # emit as a sharded [1, ...] row per shard; identical on every shard
        return {f: out[f][None] for f in fetch_names}

    n = dframe.num_rows
    if n == 0:
        raise ValueError("reduce_blocks on an empty frame")
    ndev = _dp_size(mesh)
    main, tail = _split(n, ndev)
    fmt = "{}_input".format
    acc = None
    if main:
        sharded = _shard_mapped(g, mesh, prog, kind="reduce_blocks")
        res = sharded(_sharded_main_feed(dframe, binding, mesh, main, fmt))
        acc = {f: res[f][0] for f in fetch_names}
    if tail:
        part = _jitted(g)(_tail_feed(dframe, binding, main, fmt))
        if acc is None:
            acc = part
        else:
            merge = _cached_program(
                g,
                "pair_merge",
                lambda: jax.jit(
                    lambda a, b: g.fn(
                        {
                            f"{f}_input": jnp.stack([a[f], b[f]])
                            for f in fetch_names
                        }
                    )
                ),
            )
            acc = merge(acc, part)
    return _unpack_reduce_result(acc, fetch_names)


def reduce_rows(fetches, dframe: TensorFrame, mesh=None):
    """Distributed pairwise row reduce: per-shard ``lax.scan`` fold, then the
    same all_gather + on-device merge fold as :func:`reduce_blocks`
    (reference ``DebugRowOps.scala:479-501``)."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_or_default(mesh)
    g = _as_graph(fetches, dframe, cell_inputs=True)
    binding = validate_reduce_row_graph(g, dframe.schema)
    for col in binding.values():
        dframe.column_block(col, None)
    _ensure_precision(g, dframe.schema)
    fetch_names = list(g.fetch_names)

    def merge(a, b):
        feed = {}
        for f in fetch_names:
            feed[f"{f}_1"] = a[f]
            feed[f"{f}_2"] = b[f]
        return g.fn(feed)

    def local_fold(feed: Dict[str, Any]) -> Dict[str, Any]:
        init = {f: feed[f][0] for f in fetch_names}
        rest = {f: feed[f][1:] for f in fetch_names}

        def body(c, x):
            return merge(c, x), None

        out, _ = lax.scan(body, init, rest)
        return out

    def prog(feed: Dict[str, Any]) -> Dict[str, Any]:
        local = local_fold(feed)
        gathered = {
            f: lax.all_gather(local[f], DATA_AXIS) for f in fetch_names
        }

        def body(c, x):
            return merge(c, x), None

        init = {f: gathered[f][0] for f in fetch_names}
        rest = {f: gathered[f][1:] for f in fetch_names}
        out, _ = lax.scan(body, init, rest)
        return {f: out[f][None] for f in fetch_names}

    n = dframe.num_rows
    if n == 0:
        raise ValueError("reduce_rows on an empty frame")
    ndev = _dp_size(mesh)
    main, tail = _split(n, ndev)
    acc = None
    if main:
        # the sharded program is fed whole columns keyed by fetch name
        sm = _cached_program(
            g,
            (mesh, "reduce_rows"),
            lambda: jax.jit(
                _shard_map(
                    prog,
                    mesh=mesh,
                    in_specs=({f: P(DATA_AXIS) for f in fetch_names},),
                    out_specs=P(DATA_AXIS),
                )
            ),
        )
        res = sm(_sharded_main_feed(dframe, binding, mesh, main))
        acc = {f: res[f][0] for f in fetch_names}
    if tail:
        fold = _cached_program(
            g, "tail_fold", lambda: jax.jit(local_fold)
        )
        part = fold(_tail_feed(dframe, binding, main))
        if acc is None:
            acc = part
        else:
            pm = _cached_program(g, "pair_merge", lambda: jax.jit(merge))
            acc = pm(acc, part)
    return _unpack_reduce_result(acc, fetch_names)


# ---------------------------------------------------------------------------
# aggregate
# ---------------------------------------------------------------------------


def aggregate(
    fetches, grouped_data: GroupedFrame, mesh=None
) -> TensorFrame:
    """Distributed keyed aggregation, two-phase (classic partial/final):

    1. rows are globally key-sorted on the host, then one ``shard_map``
       program runs the heavy phase on every chip in parallel: per-row
       partials (the reduce graph on blocks of 1 via ``vmap``) combined by a
       *segmented associative scan*, with segment starts forced at shard
       boundaries so each shard's scan is self-contained;
    2. each shard contributes one partial per locally-seen group (last scan
       element of each segment); a key split across a shard boundary yields
       at most one extra partial, and the small (key, partial) table is
       merged with a final local aggregate.

    This parallelizes the pattern the reference's optimized k-means builds
    *by hand* (in-graph pre-aggregation + global merge,
    ``kmeans_demo.py:101-171``) and its UDAF approximates with bounded
    buffers (``DebugRowOps.scala:644-676``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    mesh = _mesh_or_default(mesh)
    df = grouped_data.frame
    keys = grouped_data.keys
    ndev = _dp_size(mesh)
    n = df.num_rows
    if n == 0:
        raise ValueError("aggregate on an empty frame")
    if n < 2 * ndev:
        return _local_aggregate(fetches, grouped_data)

    g = _as_graph(fetches, df, cell_inputs=False)
    binding = validate_reduce_block_graph(g, df.schema)
    _ensure_precision(g, df.schema)
    fetch_names = list(g.fetch_names)

    # global key sort on device (binary/mixed keys dict-code on host first);
    # main/tail split for non-divisible row counts
    from ..engine.ops import _group_sort

    order, flags, emit_keys = _group_sort(df, keys, binding)
    main, tail = _split(n, ndev)
    # each shard's scan restarts: force a segment start at shard boundaries.
    # _group_sort memoizes its result on the frame, so mutate a copy
    flags = flags.copy()
    shard_rows = main // ndev
    flags[np.arange(1, ndev) * shard_rows] = True
    if tail:
        flags[main] = True

    def scan_body(feed: Dict[str, Any], flags_: Any) -> Dict[str, Any]:
        per_row = jax.vmap(
            lambda cells: g.fn(
                {f"{f}_input": cells[f][None] for f in fetch_names}
            )
        )({f: feed[f] for f in fetch_names})

        def merge_pair(a, b):
            return g.fn(
                {f"{f}_input": jnp.stack([a[f], b[f]]) for f in fetch_names}
            )

        vmerge = jax.vmap(merge_pair)

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

    import jax.numpy as jnp

    # feed gather on device: memoized HBM column + device gather by order
    order_dev = jnp.asarray(order)
    sorted_feed = {
        f: df.column_data(col).device()[order_dev]
        for f, col in binding.items()
    }
    # segment ends (known before the scan runs — flags are host bools):
    # last row before each segment start, plus the final row. Gathering the
    # per-group rows ON DEVICE means only #groups rows cross to the host,
    # not the full n-row scan output.
    starts = np.nonzero(flags)[0]
    ends = np.append(starts[1:] - 1, n - 1)
    ends_main = ends[ends < main]
    ends_tail = ends[ends >= main] - main
    pieces: Dict[str, List[np.ndarray]] = {f: [] for f in fetch_names}
    if main:
        sharded_scan = _cached_program(
            g,
            (mesh, "aggregate"),
            lambda: jax.jit(
                _shard_map(
                    scan_body,
                    mesh=mesh,
                    in_specs=(
                        {f: P(DATA_AXIS) for f in fetch_names},
                        P(DATA_AXIS),
                    ),
                    out_specs=P(DATA_AXIS),
                )
            ),
        )
        scanned = sharded_scan(
            {f: a[:main] for f, a in sorted_feed.items()}, flags[:main]
        )
        em = jnp.asarray(ends_main)
        for f in fetch_names:
            pieces[f].append(np.asarray(scanned[f][em]))
    if tail:
        tail_scan = _cached_program(
            g, "aggregate_tail", lambda: jax.jit(scan_body)
        )
        scanned = tail_scan(
            {f: a[main:] for f, a in sorted_feed.items()}, flags[main:]
        )
        et = jnp.asarray(ends_tail)
        for f in fetch_names:
            pieces[f].append(np.asarray(scanned[f][et]))

    partial_cols: Dict[str, Any] = dict(emit_keys(ends))
    for f in fetch_names:
        ps = pieces[f]
        partial_cols[f] = (
            ps[0] if len(ps) == 1 else np.concatenate(ps, axis=0)
        )
    partials = TensorFrame.from_columns(partial_cols).analyze()
    # partial value columns are named after the fetches; rebind the merge
    # graph's f_input placeholders to them and fold boundary duplicates
    g2 = g.with_inputs({f"{f}_input": f for f in fetch_names})
    return _local_aggregate(g2, GroupedFrame(partials, keys))
