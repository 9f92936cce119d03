"""Multi-host (multi-process) execution: the DCN story.

The reference's cross-host communication *is* Spark: Py4J control plane,
torrent broadcast of the graph, shuffle for groupBy, and an
executors-to-driver funnel for reduces
(``/root/reference/src/main/scala/org/tensorframes/impl/DebugRowOps.scala:376,524,576``).
The TPU-native replacement has no driver funnel: every host runs the SAME
program, ``jax.distributed.initialize`` wires the processes into one
runtime, meshes span every host's devices, and XLA routes collectives over
ICI within a pod and DCN across pods/hosts (SURVEY §2.5). Each host feeds
only its addressable shard (per-host input pipelines — the part the
reference never solved, SURVEY §7 hard-part 6).

On CPU this is exercised for real: multiple processes with virtual
devices, cross-process collectives over Gloo — the same code path
``jax.distributed`` uses across TPU hosts over DCN.
"""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from jax import shard_map as _shard_map

__all__ = [
    "initialize",
    "is_multihost",
    "process_count",
    "process_index",
    "global_batch",
    "local_rows",
    "sync_global",
    "map_blocks",
    "map_rows",
    "reduce_blocks",
    "reduce_rows",
    "aggregate",
]


def initialize(
    coordinator_address: str,
    num_processes: int,
    process_id: int,
    local_device_count: Optional[int] = None,
) -> None:
    """Join this process into a multi-host runtime.

    Thin wrapper over ``jax.distributed.initialize`` that can also size the
    CPU backend at ``local_device_count`` virtual devices per process —
    the testing topology (N processes x M virtual devices) that stands in
    for N hosts x M chips. Must run before any jax computation initializes
    the backends."""
    import jax

    if local_device_count is not None:
        try:
            jax.config.update("jax_num_cpu_devices", local_device_count)
        except Exception as e:  # backends already initialized, or old jax
            from ..utils import get_logger

            get_logger("multihost").warning(
                "could not size the CPU backend at %d devices (%s); "
                "device count will be whatever the backend reports",
                local_device_count,
                e,
            )
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_multihost() -> bool:
    import jax

    return jax.process_count() > 1


def process_count() -> int:
    import jax

    return jax.process_count()


def process_index() -> int:
    import jax

    return jax.process_index()


def global_batch(local: np.ndarray, mesh, spec=None):
    """Assemble a globally-sharded array from each process's local rows.

    ``local`` is THIS process's slice along the leading (row) axis; every
    process contributes its own. ``spec`` defaults to rows-over-``dp``,
    trailing dims replicated. The result is addressable-shard-backed: no
    host ever materializes the global array (the reference, by contrast,
    funnels global state through the driver)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh import DATA_AXIS

    if spec is None:
        spec = P(DATA_AXIS, *([None] * (np.ndim(local) - 1)))
    sharding = NamedSharding(mesh, spec)
    return jax.make_array_from_process_local_data(sharding, np.asarray(local))


def local_rows(n_rows: int) -> slice:
    """The contiguous row range this process should load, under the even
    row split ``global_batch`` expects: process i of p takes rows
    ``[i*n/p, (i+1)*n/p)``."""
    import jax

    p, i = jax.process_count(), jax.process_index()
    if n_rows % p != 0:
        raise ValueError(
            f"{n_rows} rows do not split evenly over {p} processes; pad or "
            f"trim the dataset so every host feeds the same shard size"
        )
    per = n_rows // p
    return slice(i * per, (i + 1) * per)


def sync_global(x):
    """Fetch a (replicated or sharded) global array to every host, via an
    all-gather across processes when needed. For small results only —
    this is the one deliberate host materialization point."""
    import jax

    arr = x
    if hasattr(arr, "is_fully_addressable") and not arr.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))
    return np.asarray(arr)


# ---------------------------------------------------------------------------
# dataframe ops over a multi-process mesh: each host feeds its local rows
# ---------------------------------------------------------------------------


def _mh_registry(df) -> dict:
    """The frame's registry of globally-sharded device arrays, one per
    column: ``{col: (mesh, jax.Array)}``. Frames are immutable, so a cached
    global assembly of a column stays valid for the frame's lifetime."""
    reg = getattr(df, "_mh_global", None)
    if reg is None:
        reg = {}
        df._mh_global = reg
    return reg


def _global_feed_col(local_df, col, mesh):
    """The globally-sharded device feed for one column, memoized so that
    chained multihost ops (and repeated passes over the same frame) reuse
    the sharded array instead of re-assembling it from host rows — the
    multi-process analog of the local engine's device residency
    (single-chip results chain in HBM without host round-trips; here the
    global result chains in the fleet's HBM without ever touching a host).
    The reference re-marshals rows through the JVM on every Session.run
    (``TFDataOps.scala:27-59``); neither plane here does.

    Two cache levels: the frame-level ``_mh_global`` registry (a lazy
    multihost result's own fetch arrays — their column storage doesn't
    exist until the thunk runs), then the column-level ``_sharded_cache``
    on ``_ColumnData`` — shared with every frame aliasing the column and
    released by ``unpersist_device`` on any of them. Caching honors the
    same ``device_cache_bytes`` budget as the single-process sharded feed
    (``distributed.py``): a column over budget is assembled transiently
    and freed after the op, so HBM use stays bounded."""
    from ..utils import get_config

    reg = getattr(local_df, "_mh_global", None)
    if reg:
        hit = reg.get(col)
        if hit is not None and hit[0] == mesh:
            return hit[1]
    cd = local_df.column_data(col)
    local_df.column_block(col)  # dense check (raises for ragged/binary)
    host = cd.host()
    if host.nbytes > get_config().device_cache_bytes:
        return global_batch(host, mesh)  # transient: over budget
    cache = cd._sharded_cache
    if cache is None:
        cache = cd._sharded_cache = {}
    key = ("mh_global", mesh)
    arr = cache.get(key)
    if arr is None:
        arr = global_batch(host, mesh)
        cache[key] = arr
    return arr


def _lazy_mh_result(res, g, local_df, mesh, out_specs, block_output, feed, binding):
    """Build the lazy local result frame for a multihost map: the global
    result arrays stay sharded over the mesh (registered for reuse by the
    next multihost op); this process's host rows materialize only if the
    frame is actually read. Input columns alias the parent's storage, same
    as the single-process engine."""
    from ..engine.ops import _fetch_column_info
    from ..frame import TensorFrame
    from ..frame.table import _ColumnData
    from ..schema import FrameInfo
    from ..utils import get_config

    fetch_names = list(g.fetch_names)
    result_info = FrameInfo(
        [
            _fetch_column_info(n, out_specs[n], block_output=block_output)
            for n in fetch_names
        ]
        + list(local_df.schema)
    )

    def thunk():
        cols = {
            n: _ColumnData(dense=_local_rows_of(res[n])) for n in fetch_names
        }
        for c in local_df.schema:
            cols[c.name] = local_df.column_data(c.name)
        return TensorFrame(
            cols, result_info, num_partitions=local_df.num_partitions
        )

    out = TensorFrame(
        {}, result_info, num_partitions=local_df.num_partitions, _thunk=thunk
    )
    reg = _mh_registry(out)
    for n in fetch_names:
        reg[n] = (mesh, res[n])
    # every parent column passes through, so keep a chained op on ANY of
    # them lazy: propagate the parent's registry (its fetch arrays), and
    # reference this pass's input feeds when they fit the cache budget
    # (over-budget feeds were transient — pinning them here would defeat
    # the HBM bound). These are refs to arrays the _ColumnData cache
    # already holds, not extra copies; release is per-frame, see
    # ``unpersist_device``.
    budget = get_config().device_cache_bytes
    for ph, col in binding.items():
        # same byte basis as _global_feed_col's cache decision (per-process
        # host bytes, not the global array): a column cached there must be
        # registered here, or a chained op on a pass-through column would
        # force the lazy frame and re-materialize every fetch column
        if feed[ph].nbytes // process_count() <= budget:
            reg.setdefault(col, (mesh, feed[ph]))
    parent_reg = getattr(local_df, "_mh_global", None)
    if parent_reg:
        for col, entry in parent_reg.items():
            reg.setdefault(col, entry)
    return out


def map_blocks(fetches, local_df, mesh, feed_dict=None):
    """Multi-host ``map_blocks``: ``local_df`` holds THIS process's rows;
    all processes call with the same program and their own shard. Returns
    a lazy local frame of this process's result rows (fetch columns +
    inputs). The collective program dispatches NOW (multi-host programs
    are SPMD — every process must reach the rendezvous), but the result
    stays sharded over the fleet's devices: chained multihost ops feed it
    straight back without any host round-trip, and this process's host
    rows materialize only if the frame is actually read."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..engine.ops import _as_graph, _ensure_precision
    from ..engine.validation import (
        InvalidDimensionError,
        check_output_collisions,
        validate_map_inputs,
    )
    from ..schema import Unknown
    from .distributed import _cached_program
    from .mesh import DATA_AXIS

    g = _as_graph(fetches, local_df, cell_inputs=False, feed_dict=feed_dict)
    binding = validate_map_inputs(g, local_df.schema, block=True)
    _ensure_precision(g, local_df.schema)
    # same pre-flight contract as the single-process engine: no scalar
    # outputs, no collisions with existing columns
    out_specs = g.analyze(
        {
            ph: local_df.schema[col].block_shape.with_lead(Unknown)
            for ph, col in binding.items()
        }
    )
    for name, spec in out_specs.items():
        if spec.shape.num_dims == 0:
            raise InvalidDimensionError(
                f"map_blocks output {name!r} is a scalar; map outputs must "
                f"keep the leading row dimension (use reduce_blocks)"
            )
    check_output_collisions(out_specs, local_df.schema)
    feed = {
        ph: _global_feed_col(local_df, col, mesh)
        for ph, col in binding.items()
    }
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    prog = _cached_program(
        g,
        (mesh, "mh_map"),
        lambda: jax.jit(
            g.fn, out_shardings={f: sharding for f in g.fetch_names}
        ),
    )
    res = prog(feed)
    return _lazy_mh_result(
        res, g, local_df, mesh, out_specs, True, feed, binding
    )


def _local_rows_of(arr) -> np.ndarray:
    """This process's rows of a dp-sharded global array, in row order,
    deduplicated: on a multi-axis mesh the row shard is replicated over the
    other axes and ``addressable_shards`` yields every replica."""
    seen = set()
    parts = []
    for s in sorted(
        arr.addressable_shards, key=lambda s: s.index[0].start or 0
    ):
        key = (s.index[0].start, s.index[0].stop)
        if key in seen:
            continue
        seen.add(key)
        parts.append(np.asarray(s.data))
    return np.concatenate(parts)


def reduce_blocks(fetches, local_df, mesh):
    """Multi-host ``reduce_blocks``: block-reduce over the GLOBAL rows with
    each process feeding its shard; the result is replicated, so every
    process returns the same numpy value(s) — no driver funnel."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..engine.ops import (
        _as_graph,
        _ensure_precision,
        _unpack_reduce_result,
    )
    from ..engine.validation import validate_reduce_block_graph
    from .mesh import DATA_AXIS

    g = _as_graph(fetches, local_df, cell_inputs=False)
    binding = validate_reduce_block_graph(g, local_df.schema)
    _ensure_precision(g, local_df.schema)
    feed = {
        f"{f}_input": _global_feed_col(local_df, col, mesh)
        for f, col in binding.items()
    }
    from .distributed import _cached_program

    rep = NamedSharding(mesh, P())
    prog = _cached_program(
        g,
        (mesh, "mh_reduce"),
        lambda: jax.jit(
            g.fn, out_shardings={f: rep for f in g.fetch_names}
        ),
    )
    res = prog(feed)
    host = {f: sync_global(res[f]) for f in g.fetch_names}
    return _unpack_reduce_result(host, g.fetch_names)


# ---------------------------------------------------------------------------
# map_rows / reduce_rows / aggregate: the rest of the op surface
# ---------------------------------------------------------------------------


def map_rows(fetches, local_df, mesh, feed_dict=None):
    """Multi-host row-wise map. All five frame ops run through the
    distributed plane, matching the reference where every op executes
    inside the cluster (row maps run inside Spark tasks,
    ``DebugRowOps.scala:396-477``).

    Execution picks the shape that fits the data:

    - **dense frames** (every bound column has one cell shape): one global
      program — each process contributes its rows via ``global_batch`` and
      a ``vmap`` of the row graph runs over the globally row-sharded
      array; results come back as this process's rows.
    - **ragged / binary frames**: rows with differing cell shapes compile
      per shape bucket, and bucket membership is a property of *local*
      data — so each process maps its own rows with the local engine, the
      exact analog of the reference's partition-local row loop (a Spark
      row map never leaves its executor either). No cross-process
      rendezvous is needed because a row map carries no cross-row
      dataflow.

    Returns a lazy local frame of this process's result rows (fetch
    columns followed by the input columns), like :func:`map_blocks`: the
    global result stays sharded over the mesh for chained multihost ops,
    host rows materialize only on access.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..engine.ops import _as_graph, _ensure_precision
    from ..engine.validation import (
        check_output_collisions,
        validate_map_inputs,
    )
    from .distributed import _cached_program
    from .mesh import DATA_AXIS

    g = _as_graph(fetches, local_df, cell_inputs=True, feed_dict=feed_dict)
    binding = validate_map_inputs(g, local_df.schema, block=False)
    reg = getattr(local_df, "_mh_global", None) or {}

    def _col_is_dense(col):
        # a column whose global sharded assembly is already registered is
        # dense by construction — answering from the registry keeps a lazy
        # chained frame lazy (no thunk force just to inspect storage)
        hit = reg.get(col)
        if hit is not None and hit[0] == mesh:
            return True
        return (
            local_df.schema[col].scalar_type.name != "binary"
            and local_df.column_data(col).dense is not None
        )

    dense = all(_col_is_dense(col) for col in binding.values())
    if not dense:
        from ..engine import map_rows as local_map_rows

        return local_map_rows(g, local_df)  # feed_dict already merged
    _ensure_precision(g, local_df.schema)
    input_shapes = {
        ph: local_df.schema[col].cell_shape for ph, col in binding.items()
    }
    out_specs = g.analyze(input_shapes, share_lead=False)
    check_output_collisions(out_specs, local_df.schema)
    feed = {
        ph: _global_feed_col(local_df, col, mesh)
        for ph, col in binding.items()
    }
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    prog = _cached_program(
        g,
        (mesh, "mh_map_rows"),
        lambda: jax.jit(
            jax.vmap(g.fn),
            out_shardings={f: sharding for f in g.fetch_names},
        ),
    )
    res = prog(feed)
    return _lazy_mh_result(
        res, g, local_df, mesh, out_specs, False, feed, binding
    )


def reduce_rows(fetches, local_df, mesh):
    """Multi-host pairwise row reduce: one ``shard_map`` program over the
    global mesh — per-shard ``lax.scan`` fold, ``all_gather`` of the
    per-shard partials (ICI within a host, DCN across hosts), and an
    on-device fold of the user's merge graph. Every process returns the
    same value; no driver funnel (reference:
    ``DebugRowOps.scala:479-501``, executors→driver).

    The global row count must divide the mesh size (each process's rows
    already split evenly by ``local_rows``; pad or trim to a multiple of
    the device count).
    """
    import jax
    from jax import lax

    from ..engine.ops import (
        _as_graph,
        _ensure_precision,
        _unpack_reduce_result,
    )
    from ..engine.validation import validate_reduce_row_graph
    from .distributed import _cached_program, _dp_spec
    from .mesh import DATA_AXIS

    g = _as_graph(fetches, local_df, cell_inputs=True)
    binding = validate_reduce_row_graph(g, local_df.schema)
    _ensure_precision(g, local_df.schema)
    fetch_names = list(g.fetch_names)
    # pre-flight the row count BEFORE assembling the feed, so a bad count
    # raises the actionable error (global_batch would die on an opaque
    # sharding mismatch first). The count comes from the frame registry
    # when the input is a lazy chained result — no host force — else from
    # the local frame.
    ndev = int(np.prod(list(mesh.shape.values())))
    reg = getattr(local_df, "_mh_global", None) or {}
    hit = next(
        (
            reg[c][1]
            for c in binding.values()
            if c in reg and reg[c][0] == mesh
        ),
        None,
    )
    if hit is not None:
        n_global = int(hit.shape[0])
    else:
        n_local = local_df.num_rows
        if n_local == 0:
            raise ValueError("reduce_rows on an empty frame")
        n_global = n_local * process_count()
    if n_global % ndev != 0:
        raise ValueError(
            f"{n_global} global rows do not shard evenly over {ndev} "
            f"devices; pad or trim to a multiple of the device count"
        )
    feed = {
        f: _global_feed_col(local_df, col, mesh)
        for f, col in binding.items()
    }

    def merge(a, b):
        feed = {}
        for f in fetch_names:
            feed[f"{f}_1"] = a[f]
            feed[f"{f}_2"] = b[f]
        return g.fn(feed)

    def prog_body(feed):
        init = {f: feed[f][0] for f in fetch_names}
        rest = {f: feed[f][1:] for f in fetch_names}

        def body(c, x):
            return merge(c, x), None

        local, _ = lax.scan(body, init, rest)
        gathered = {
            f: lax.all_gather(local[f], DATA_AXIS) for f in fetch_names
        }
        init = {f: gathered[f][0] for f in fetch_names}
        rest = {f: gathered[f][1:] for f in fetch_names}
        out, _ = lax.scan(body, init, rest)
        # one identical [1, ...] row per shard; any addressable shard
        # holds the final value
        return {f: out[f][None] for f in fetch_names}

    prog = _cached_program(
        g,
        (mesh, "mh_reduce_rows"),
        lambda: jax.jit(
            _shard_map(
                prog_body,
                mesh=mesh,
                in_specs=({f: _dp_spec() for f in fetch_names},),
                out_specs=_dp_spec(),
            )
        ),
    )
    res = prog(feed)
    acc = {
        f: np.asarray(res[f].addressable_shards[0].data)[0]
        for f in fetch_names
    }
    return _unpack_reduce_result(acc, fetch_names)


def _allgather_partials(partials_df):
    """Exchange each process's (small) partial-aggregate table so every
    process holds the global partial set.

    Group counts differ per process, and ``process_allgather`` requires
    identical shapes — so counts are gathered first, every column is
    padded to the max count, gathered, then trimmed per process and
    concatenated. Binary key columns ride as (lengths, fixed-width uint8)
    pairs sized by the gathered max key length. Partial tables are one row
    per locally-seen group — the only data that crosses hosts, same as the
    reference's partial-aggregation shuffle (``DebugRowOps.scala:547-592``).
    """
    from ..frame import TensorFrame
    from jax.experimental.multihost_utils import process_allgather as ag

    nproc = process_count()
    local_n = partials_df.num_rows
    counts = np.asarray(
        ag(np.asarray([local_n], dtype=np.int64))
    ).reshape(nproc)
    maxc = int(counts.max())

    def gather_numeric(arr):
        pad_shape = (maxc - local_n,) + arr.shape[1:]
        padded = np.concatenate(
            [arr, np.zeros(pad_shape, dtype=arr.dtype)], axis=0
        )
        stacked = np.asarray(ag(padded))  # [P, maxc, ...]
        return np.concatenate(
            [stacked[p, : counts[p]] for p in range(nproc)], axis=0
        )

    cols = {}
    for ci in partials_df.schema:
        cd = partials_df.column_data(ci.name)
        if ci.scalar_type.name == "binary":
            cells = [bytes(c) for c in cd.cells]
            lens = np.asarray(
                [len(c) for c in cells] + [0] * (maxc - local_n),
                dtype=np.int64,
            )
            maxlen = int(np.asarray(ag(lens.max(initial=0))).max())
            buf = np.zeros((maxc, maxlen), dtype=np.uint8)
            for i, c in enumerate(cells):
                buf[i, : len(c)] = np.frombuffer(c, dtype=np.uint8)
            all_lens = np.asarray(ag(lens))  # [P, maxc]
            all_buf = np.asarray(ag(buf))  # [P, maxc, maxlen]
            out = []
            for p in range(nproc):
                for i in range(int(counts[p])):
                    out.append(
                        all_buf[p, i, : all_lens[p, i]].tobytes()
                    )
            cols[ci.name] = out
        else:
            cols[ci.name] = gather_numeric(cd.host())
    return TensorFrame.from_columns(cols)


def aggregate(fetches, grouped_data, mesh):
    """Multi-host keyed aggregation, two-phase partial/final:

    1. each process aggregates its LOCAL rows with the full local engine
       (device sort + segmented associative scan over this host's chips),
       yielding one partial row per locally-seen group;
    2. the small partial tables are all-gathered across processes and a
       replicated final aggregate merges same-key partials — every
       process returns the identical global result.

    The shuffle the reference leans on (``DebugRowOps.scala:547-592``)
    moves raw rows between executors; here only per-group partials cross
    hosts. Keys may be numeric, binary, or multi-column mixes, same as
    the local engine.
    """
    from ..engine import aggregate as local_aggregate
    from ..engine.ops import _as_graph
    from ..frame import GroupedFrame

    local_df = grouped_data.frame
    keys = grouped_data.keys
    g = _as_graph(fetches, local_df, cell_inputs=False)
    partials = local_aggregate(g, grouped_data)._force()
    global_partials = _allgather_partials(partials).analyze()
    g2 = g.with_inputs({f"{f}_input": f for f in g.fetch_names})
    return local_aggregate(g2, GroupedFrame(global_partials, keys))
