"""Paged KV cache: a static-shape page pool + per-sequence page tables.

The decode-memory problem continuous batching creates: requests arrive
and finish at different times with different lengths, but a compiled
decode step wants ONE static cache shape. A per-request contiguous cache
(what :func:`~tensorframes_tpu.models.transformer_generate` allocates)
either recompiles as shapes change or wastes ``max_len`` rows per slot.
The paged layout (Ragged Paged Attention / vLLM's PagedAttention, see
PAPERS.md) decouples the two lifetimes:

- **device**: one pool of ``num_pages`` fixed-size pages per layer,
  ``[n_layers, num_pages + 1, page_size, n_kv_heads * head_dim]`` — the
  shape never changes, so the decode step compiles exactly once. The
  extra row at index ``num_pages`` is the TRASH page: writes from
  inactive slots and prompt padding land there, keeping every program
  input in-bounds without per-slot branches.

  **Why heads are merged into the last axis.** The TPU stores an array
  in tiles of 8 sublanes x 128 lanes over its two minor dimensions. A
  pool whose minor dimensions are ``(n_kv_heads, head_dim)`` = (25, 64)
  (GPT-2 XL) would pad to 32 x 128, 2.56x its bytes, so the runtime
  used to pick a page-minor device layout instead and every step
  program paid whole-pool copies to convert it and back (PERF.md §5,
  PR 24-26). With ``(page_size, n_kv_heads * head_dim)`` = (16, 1600)
  minor the row-major layout is the natural one (1,600 lanes pad to
  1,664: 4 %), a token's k/v is one contiguous row, a page is one
  contiguous slab, and the step programs read and write the pool's
  buffer in place. The layout is one rule for every model — nothing
  here looks at the widths — and it is written down ONCE, in
  :func:`kv_pool_shape`, :func:`write_rows`, :func:`write_prompt`,
  :func:`read_pages` and the page-level methods of :class:`PagePool` / :class:`PageGroup`;
  nothing else in the package indexes ``pool.k`` / ``pool.v`` by hand.
- **host**: a free-list allocator and per-sequence page tables
  (:class:`SequencePages`). Sequences grow one page at a time; a
  finished sequence's pages return to the pool immediately, so HBM is
  bounded by LIVE tokens, not by slots × max_len.

Pages are REFCOUNTED: the page indirection means any number of page
tables may name the same physical page, which is what shared-prefix KV
caching rides on — :class:`PrefixCache` maps token prefixes to the
pages that already hold their k/v, so identical system prompts /
few-shot templates dedup to one physical copy and a new request's
prefill skips the shared span entirely. A page returns to the free list
only when its LAST reference drops. Shared pages are immutable by
construction (only COMPLETE prompt pages are ever registered, and
decode appends past them); a request diverging inside a cached page
gets a private copy-on-write clone (the engine copies the page row,
then overwrites from the divergence point).

**Cache kinds.** A model whose layers keep different spans of the past
(full layers every position, window layers the last ``window``) has one
cache KIND per layer type, all in this one pool: a pool page is
``depth`` layers deep (:class:`CacheLayout`: the greatest common divisor
of the kinds' layer counts; every layer, for a model of one kind), a
position-page of a kind takes ``layers / depth`` of them, and both kinds
draw on the same free list, so memory moves between them as the traffic
does. A window kind gives a page back once every position in it is more
than ``window`` behind the next query (:meth:`SequencePages.advance`).

Exhaustion raises
:class:`~tensorframes_tpu.utils.failures.PagePoolExhausted` — the
scheduler's cue to evict cache entries, then preempt-and-requeue,
never a crash.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import threading
from collections import OrderedDict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import chaos as _chaos
from ..utils.failures import PagePoolExhausted
from . import tenancy as _tenancy

__all__ = [
    "CacheKind",
    "CacheLayout",
    "PageGroup",
    "PagePool",
    "PrefixCache",
    "SequencePages",
    "kv_pool_shape",
    "pages_needed",
    "read_pages",
    "split_heads",
    "write_prompt",
    "write_rows",
]


def pages_needed(tokens: int, page_size: int) -> int:
    """Pages required to hold ``tokens`` positions."""
    return -(-int(tokens) // int(page_size))


@dataclasses.dataclass(frozen=True)
class CacheKind:
    """One kind of K/V state: the model layers that keep it, how many
    pool pages one of its position-pages takes, and its window (0 =
    every position is kept)."""

    name: str
    layers: Tuple[int, ...]
    units: int
    window: int = 0


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """How a model's layers lie in the pool: ``depth`` layers to a pool
    page, the kinds (full first), and the positions a window kind may
    hold ahead of the next query (``lookahead``: the prefill chunk)."""

    depth: int
    kinds: Tuple[CacheKind, ...]
    page_size: int
    lookahead: int = 0

    @staticmethod
    def of(layer_types, window: int, page_size: int, lookahead: int = 0):
        """``layer_types``: one of "full" / "window" per layer."""
        by_kind = {
            name: tuple(i for i, t in enumerate(layer_types) if t == name)
            for name in ("full", "window")
        }
        by_kind = {k: v for k, v in by_kind.items() if v}
        if sum(len(v) for v in by_kind.values()) != len(layer_types):
            raise ValueError(f"unknown layer type in {set(layer_types)}")
        if "window" in by_kind and (window < 1 or window % page_size):
            raise ValueError(
                f"window {window} must be a positive multiple of the "
                f"page size {page_size}"
            )
        depth = math.gcd(*(len(v) for v in by_kind.values()))
        return CacheLayout(
            depth=depth,
            kinds=tuple(
                CacheKind(
                    name, layers, len(layers) // depth,
                    window if name == "window" else 0,
                )
                for name, layers in by_kind.items()
            ),
            page_size=int(page_size),
            lookahead=int(lookahead),
        )

    @functools.lru_cache(maxsize=None)
    def locate(self, li: int) -> Tuple[int, int, int]:
        """Layer ``li`` -> (its kind's index, which of a position-page's
        pool pages holds it, its row of that pool page)."""
        for ki, kind in enumerate(self.kinds):
            if li in kind.layers:
                j = kind.layers.index(li)
                return ki, j // self.depth, j % self.depth
        raise ValueError(f"layer {li} is in no cache kind")

    def held_pages(self, kind: CacheKind, tokens: int, frontier: int = 0):
        """Position-pages of ``kind`` a sequence covering ``tokens``
        positions holds when its next query is at ``frontier``: a window
        kind never reaches past ``frontier + window + lookahead``."""
        want = pages_needed(tokens, self.page_size)
        if kind.window:
            ahead = frontier + kind.window + self.lookahead
            want = min(want, pages_needed(ahead, self.page_size) + 1)
        return want

    def units_needed(self, tokens: int) -> int:
        """Most pool pages one sequence of ``tokens`` positions holds."""
        return sum(
            k.units * self.held_pages(k, tokens) for k in self.kinds
        )


# -- the device layout, and the step programs' accesses to it --------------


def kv_pool_shape(
    n_layers: int, num_pages: int, page_size: int, n_kv_heads: int,
    head_dim: int,
) -> Tuple[int, int, int, int]:
    """THE pool layout: ``[n_layers, num_pages + 1 (trash), page_size,
    n_kv_heads * head_dim]`` (module docstring: why heads are merged
    into the lane axis). Head ``h`` of a row is lanes
    ``h * head_dim .. (h + 1) * head_dim - 1``, so an even split of the
    last axis across a tensor-parallel mesh lands on head boundaries
    whenever ``n_kv_heads`` divides."""
    return (
        int(n_layers), int(num_pages) + 1, int(page_size),
        int(n_kv_heads) * int(head_dim),
    )


def write_rows(pages, li: int, page, off, rows):
    """Write new k (or v) rows into layer ``li`` of a pool array, inside
    a step program: ``page`` / ``off`` are int32 index arrays of one
    shape ``[...]`` and ``rows`` is ``[..., n_kv, hd]`` or already
    ``[..., n_kv * hd]``. One scatter whose updates are whole rows, on
    the donated buffer: it touches the rows it writes, nothing else."""
    rows = rows.reshape(page.shape + (pages.shape[-1],))
    return pages.at[li, page, off].set(rows)


def write_prompt(pages, li: int, page_table, length, rows, trash: int):
    """Write one sequence's prompt rows ``[P, n_kv, hd]`` (positions
    ``0 .. P-1``, real up to ``length``) into layer ``li`` through its
    ``page_table`` ``[max_pages]`` — :func:`write_rows`' result, paid
    per PAGE instead of per row: every page the prompt fills completely
    goes in as one contiguous ``[page_size, n_kv * hd]`` slab (pages
    past it go to the ``trash`` page), and only the one page ``length``
    ends inside is written row by row, so positions past the real
    prompt still land in the trash page and nowhere else. On the TPU a
    scatter costs per update: 1,024 row updates a layer took as long as
    the whole-pool copy they replaced, 64 slabs and 16 rows take a
    seventh of it (PERF.md §6, PR 26).

    The body is traced ONCE per program, not once per layer and array:
    a prefill calls this 2 x ``n_layers`` times, and the layer index is
    data to a scatter anyway, so it goes in as an operand of one inner
    ``jit`` (96 traced copies cost 1.4 s of every engine's set-up)."""
    return _write_prompt()(pages, li, page_table, length, rows, trash)


@functools.lru_cache(maxsize=None)
def _write_prompt():
    import jax
    import jax.numpy as jnp

    def write_prompt(pages, li, page_table, length, rows, trash):
        ps, width = pages.shape[-2:]
        plen = rows.shape[0]
        rows = rows.reshape(plen, width)
        full = plen // ps
        ends = (jnp.arange(full) + 1) * ps
        slab_page = jnp.where(ends <= length, page_table[:full], trash)
        pages = pages.at[li, slab_page].set(
            rows[: full * ps].reshape(full, ps, width)
        )
        edge = length // ps
        pos = edge * ps + jnp.arange(ps)
        edge_page = jnp.where(
            pos < length,
            page_table[jnp.minimum(edge, page_table.shape[0] - 1)],
            trash,
        )
        return write_rows(
            pages, li, edge_page, pos % ps, rows[jnp.minimum(pos, plen - 1)]
        )

    return jax.jit(write_prompt, static_argnums=5)


def read_pages(pages, li: int, page_table):
    """Gather layer ``li``'s pages named by ``page_table`` ``[...,
    max_pages]`` into position order: ``[..., max_pages * page_size,
    n_kv * hd]`` — :func:`~tensorframes_tpu.ops.attention.gather_pages`,
    the one gather the decode read uses too."""
    from ..ops.attention import gather_pages

    return gather_pages(pages, page_table, li)


def split_heads(rows, head_dim: int):
    """``[..., n_kv * hd] -> [..., n_kv, hd]``: the per-head view of
    gathered rows, for the span programs' many-query einsums. This DOES
    re-tile the block on the TPU (lanes split 25 x 64); the decode read
    (:func:`~tensorframes_tpu.ops.paged_attention`) never calls it."""
    return rows.reshape(rows.shape[:-1] + (-1, int(head_dim)))


class _PageArrays:
    """The two device arrays of one page family (``k`` / ``v`` in the
    :func:`kv_pool_shape` layout) and every page-level operation on
    them — shared by :class:`PagePool` (the main arrays) and
    :class:`PageGroup` (a draft model's). The eager operations here
    re-pin their result through :meth:`place`, so the compiled step
    programs always receive already-placed inputs instead of resharding
    on dispatch."""

    n_layers: int
    n_kv_heads: int
    head_dim: int
    sharding = None

    def _geometry(self) -> Tuple[int, int]:
        """``(num_pages, page_size)`` of the index space."""
        raise NotImplementedError

    def _alloc_arrays(self, dtype) -> None:
        import jax.numpy as jnp

        shape = kv_pool_shape(
            self.n_layers, *self._geometry(), self.n_kv_heads,
            self.head_dim,
        )
        self.k = jnp.zeros(shape, dtype, device=self.sharding)
        self.v = jnp.zeros(shape, dtype, device=self.sharding)

    def place(self, arr):
        """Pin ``arr`` to this family's sharding (identity when
        unsharded)."""
        if self.sharding is None:
            return arr
        import jax

        return jax.device_put(arr, self.sharding)

    def _map_arrays(self, fn) -> None:
        self.k = self.place(fn(self.k))
        self.v = self.place(fn(self.v))

    def copy_page(self, src: int, dst: int) -> None:
        """Page ``dst`` becomes a copy of page ``src`` in every layer
        (the prefix cache's copy-on-write clone)."""
        self._map_arrays(lambda a: a.at[:, dst].set(a[:, src]))

    def permute_pages(self, perm) -> None:
        """Row ``new`` takes the contents of row ``perm[new]``
        (:meth:`PagePool.defragment`)."""
        self._map_arrays(lambda a: a[:, perm])

    def take_pages(self, rows):
        """Pages ``rows`` of every layer as ``(k, v)`` device arrays in
        the LOGICAL geometry ``[n_layers, len(rows), page_size,
        n_kv_heads, head_dim]`` — what a tier snapshot carries, whatever
        the device layout is."""
        logical = (
            self.n_layers, len(rows), self._geometry()[1],
            self.n_kv_heads, self.head_dim,
        )
        return (
            self.k[:, rows].reshape(logical),
            self.v[:, rows].reshape(logical),
        )

    def put_pages(self, rows, k_src, v_src) -> None:
        """Write logical-geometry page rows (:meth:`take_pages`'s
        shape; host or device) at indices ``rows``."""
        stored = (self.n_layers, len(rows)) + tuple(self.k.shape[2:])
        self.k = self.place(self.k.at[:, rows].set(k_src.reshape(stored)))
        self.v = self.place(self.v.at[:, rows].set(v_src.reshape(stored)))

    def fill(self, k_value: float, v_value: float) -> None:
        """Overwrite every element (the chaos drills' simulated device
        loss)."""
        self.k = self.place(self.k * 0.0 + k_value)
        self.v = self.place(self.v * 0.0 + v_value)


class PagePool(_PageArrays):
    """Fixed-size KV page pool: device arrays with a STATIC shape plus a
    host-side free-list allocator.

    ``k``/``v`` are ``[n_layers, num_pages + 1, page_size, n_kv_heads *
    head_dim]`` jax arrays (:func:`kv_pool_shape`) — page ``num_pages``
    is the trash page (see module docstring). The arrays are exposed as
    plain attributes because the engine's compiled step functions
    consume and return them functionally (donated); the pool only
    tracks WHICH pages are live, never their contents. Code outside
    this module passes them to the step programs whole and otherwise
    goes through :func:`write_rows` / :func:`read_pages` (traced) and
    :meth:`copy_page` / :meth:`take_pages` / :meth:`put_pages` /
    :meth:`defragment` / :meth:`reset` (eager)."""

    def __init__(
        self,
        n_layers: int,
        n_kv_heads: int,
        head_dim: int,
        num_pages: int,
        page_size: int,
        dtype=None,
        sharding=None,
    ):
        import jax.numpy as jnp

        if num_pages < 1 or page_size < 1:
            raise ValueError(
                f"need num_pages >= 1 and page_size >= 1; got "
                f"{num_pages}, {page_size}"
            )
        self.n_layers = int(n_layers)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        #: optional jax sharding of the pool arrays: a single-device
        #: sharding pins a solo replica's pool to its chip; a mesh
        #: sharding splits the KV-HEAD axis across the mesh
        #: (tensor-parallel serving, ``serve/tp.py``) — each chip holds
        #: its slice of every page, so one page costs 1/N of its solo
        #: bytes per chip and a fixed per-chip HBM budget holds N× the
        #: pages — the aggregate-capacity unlock. Page BOOKKEEPING (free
        #: list, refcounts, tables) is untouched: a page is still one
        #: logical unit spanning all shards.
        self.sharding = sharding
        #: index of the trash page (valid to write, never read unmasked)
        self.trash_page = self.num_pages
        self._alloc_arrays(jnp.float32 if dtype is None else dtype)
        #: named parallel page-array families addressed by the SAME page
        #: indices as ``k``/``v`` (:meth:`add_group`) — how a draft
        #: model's KV rides the pool without its own allocator: one
        #: logical page spans the main arrays AND every group's, so
        #: alloc/free/refcount/defragment stay single-sourced here
        self.groups: Dict[str, "PageGroup"] = {}
        self._lock = threading.Lock()
        # LIFO free list: recently-freed pages are reused first (their
        # contents are hottest in any cache hierarchy, and reuse keeps
        # the live set compact without explicit defragmentation). The
        # shadow set makes the double-free guard O(1) per page — free()
        # sits on the request-finish/preempt hot path.
        self._free: List[int] = list(range(self.num_pages - 1, -1, -1))
        self._free_set = set(self._free)
        #: per-page reference count: 1 at alloc, +1 per ref() (a second
        #: page table or the prefix cache naming the same page), -1 per
        #: free(); the page returns to the free list at 0
        self._refcount = np.zeros(self.num_pages, np.int32)
        #: pool pages the sequences' cache kinds took, gave back before
        #: their sequence ended (a window kind's), and hold now, by
        #: kind name — :class:`SequencePages` keeps them
        self.kind_allocated: Dict[str, int] = {}
        self.kind_released: Dict[str, int] = {}
        self.kind_in_use: Dict[str, int] = {}

    def _count(self, kind: str, taken: int = 0, released: int = 0,
               dropped: int = 0) -> None:
        """A sequence took ``taken`` pool pages for ``kind``, its window
        released ``released``, or it let go of ``dropped`` at its end."""
        with self._lock:
            if taken:
                self.kind_allocated[kind] = (
                    self.kind_allocated.get(kind, 0) + taken
                )
            if released:
                self.kind_released[kind] = (
                    self.kind_released.get(kind, 0) + released
                )
            self.kind_in_use[kind] = (
                self.kind_in_use.get(kind, 0) + taken - released - dropped
            )

    def _geometry(self) -> Tuple[int, int]:
        return self.num_pages, self.page_size

    def families(self) -> Dict[str, "_PageArrays"]:
        """Every page family addressed by this pool's page indices: the
        main arrays under ``""`` and each :meth:`add_group` by name."""
        return {"": self, **self.groups}

    def add_group(
        self,
        name: str,
        n_layers: int,
        n_kv_heads: int,
        head_dim: int,
        dtype=None,
        sharding=None,
    ) -> "PageGroup":
        """Attach a named PARALLEL page-array family (``[n_layers,
        num_pages + 1, page_size, n_kv_heads * head_dim]``) addressed by
        the same page indices as the pool's own ``k``/``v`` — the
        speculative-decoding draft model's KV page group
        (docs/serving_llm.md "Speculative decoding"). Page BOOKKEEPING
        (free list, refcounts, tables) is untouched: a page is one
        logical unit spanning the main arrays and every group, so a
        sequence's single page list covers its target AND draft KV, and
        shared-prefix pages dedup both at once. :meth:`defragment`
        renumbers group contents with the same permutation;
        :meth:`reset` re-zeros them."""
        if name in self.groups:
            raise ValueError(f"page group {name!r} already exists")
        g = PageGroup(
            self, n_layers, n_kv_heads, head_dim,
            dtype=dtype, sharding=sharding,
        )
        self.groups[name] = g
        return g

    def copy_page(self, src: int, dst: int) -> None:
        """Page ``dst`` becomes a copy of page ``src`` — in the main
        arrays AND every group: a page is one logical unit."""
        super().copy_page(src, dst)
        for g in self.groups.values():
            g.copy_page(src, dst)

    # -- allocation --------------------------------------------------------

    def alloc(self, n: int = 1) -> List[int]:
        """Take ``n`` pages off the free list — all or nothing (a partial
        grant would leak pages when the caller unwinds). Raises
        :class:`PagePoolExhausted` when fewer than ``n`` are free."""
        _chaos.site("kv_pages.alloc")
        with self._lock:
            if n > len(self._free):
                raise PagePoolExhausted(
                    f"KV page pool exhausted: need {n} page(s), "
                    f"{len(self._free)}/{self.num_pages} free"
                )
            grant = self._free[-n:][::-1]
            del self._free[len(self._free) - n :]
            self._free_set.difference_update(grant)
            self._refcount[grant] = 1
            return grant

    def ref(self, pages: Iterable[int]) -> None:
        """Take one more reference on each LIVE page — how a second page
        table (or the prefix cache) comes to share a physical page. The
        sharer releases through the same :meth:`free` as an owner."""
        with self._lock:
            pages = [int(p) for p in pages]
            for p in pages:
                if not 0 <= p < self.num_pages:
                    raise ValueError(f"page {p} is not a pool page")
                if p in self._free_set or self._refcount[p] < 1:
                    raise ValueError(f"cannot ref free page {p}")
            for p in pages:
                self._refcount[p] += 1

    def free(self, pages: Iterable[int]) -> int:
        """Drop one reference per page; pages whose LAST reference this
        was return to the free list. Returns how many actually freed
        (the prefix cache's eviction loop needs the distinction: evicting
        an entry whose pages live sequences still share frees nothing
        NOW — those pages free later, when the sequences release)."""
        freed = 0
        with self._lock:
            for p in pages:
                p = int(p)
                if not 0 <= p < self.num_pages:
                    raise ValueError(f"page {p} is not a pool page")
                if p in self._free_set or self._refcount[p] < 1:
                    raise ValueError(f"double free of page {p}")
                self._refcount[p] -= 1
                if self._refcount[p] == 0:
                    self._free.append(p)
                    self._free_set.add(p)
                    freed += 1
        return freed

    @property
    def pages_free(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.num_pages - self.pages_free

    @property
    def pages_shared(self) -> int:
        """Pages currently named by more than one reference (sequences
        and/or the prefix cache) — the dedup the shared-prefix cache is
        buying, exported as the ``serve.kv_pages_shared`` gauge."""
        with self._lock:
            return int((self._refcount > 1).sum())

    def reset(self) -> None:
        """Crash recovery: discard ALL device state and bookkeeping —
        fresh zeroed page arrays, every page back on the free list. The
        caller (:meth:`GenerationEngine.restart`) must first requeue
        every live sequence (their KV contents are rebuilt from
        host-side progress by re-prefill); any :class:`SequencePages`
        still holding pages after this call is stale."""
        with self._lock:
            for fam in self.families().values():
                fam._alloc_arrays(fam.k.dtype)
            self._free = list(range(self.num_pages - 1, -1, -1))
            self._free_set = set(self._free)
            self._refcount[:] = 0

    # -- defragmentation ---------------------------------------------------

    def defragment(
        self,
        sequences: Sequence["SequencePages"],
        page_lists: Sequence[List[int]] = (),
    ) -> Dict[int, int]:
        """Compact every live page to the lowest pool indices: one device
        gather per pool array rewrites page CONTENTS, and each sequence's
        table is renumbered in place. Returns the ``old -> new`` remap.

        ``page_lists``: additional page-number lists to renumber in
        place — the prefix cache's entries pass theirs here, so cached
        prefixes survive compaction. A page named by several owners is
        legitimate exactly when its refcount covers them; anything past
        the refcount is the corruption this check existed to catch.

        With an indirection table any free page is as good as any other,
        so steady-state serving never needs this; it exists for pool
        RESIZE (shrink to the live prefix, then slice the arrays) and for
        snapshot/restore, where a contiguous live region is the useful
        invariant."""
        with self._lock:
            owners: Dict[int, int] = {}
            all_lists: List[List[int]] = [
                held for seq in sequences for held in seq.held
            ]
            all_lists.extend(page_lists)
            for pages in all_lists:
                for p in pages:
                    owners[p] = owners.get(p, 0) + 1
            for p, n in owners.items():
                if n > int(self._refcount[p]):
                    raise ValueError(
                        f"page {p} named by {n} owners but refcount is "
                        f"{int(self._refcount[p])}"
                    )
            remap = {old: new for new, old in enumerate(sorted(owners))}
            # perm[new] = old for live pages; free pages fill the tail in
            # index order; trash stays trash
            tail = [p for p in range(self.num_pages) if p not in remap]
            perm = np.empty(self.num_pages + 1, np.int32)
            for old, new in remap.items():
                perm[new] = old
            perm[len(remap) : self.num_pages] = tail
            perm[self.num_pages] = self.trash_page
            # a page is one logical unit across every group: the draft
            # KV rows move with the same permutation, so page lists
            # stay valid for both models
            for fam in self.families().values():
                fam.permute_pages(perm)
            self._refcount = self._refcount[perm[: self.num_pages]]
            for pages in all_lists:
                pages[:] = [remap[p] for p in pages]
            self._free = list(range(self.num_pages - 1, len(remap) - 1, -1))
            self._free_set = set(self._free)
            return remap

    def __repr__(self) -> str:
        return (
            f"PagePool(pages={self.num_pages}, page_size={self.page_size}, "
            f"in_use={self.pages_in_use})"
        )


class PageGroup(_PageArrays):
    """One named parallel page-array family over a :class:`PagePool`'s
    index space (:meth:`PagePool.add_group`): its own ``k``/``v`` device
    arrays with the pool's ``num_pages + 1`` / ``page_size`` geometry
    (trash row included) but its own layer/head/dim shape and dtype —
    the speculative-decoding DRAFT model's KV. No allocator of its own:
    page index ``p`` in a sequence's table names row ``p`` here exactly
    as it does in the main arrays. ``sharding`` is its own: the draft
    group stays replicated even under a tensor-parallel pool."""

    def __init__(
        self,
        pool: "PagePool",
        n_layers: int,
        n_kv_heads: int,
        head_dim: int,
        dtype=None,
        sharding=None,
    ):
        self.pool = pool
        self.n_layers = int(n_layers)
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.sharding = sharding
        self._alloc_arrays(pool.k.dtype if dtype is None else dtype)

    def _geometry(self) -> Tuple[int, int]:
        return self.pool.num_pages, self.pool.page_size


_ONE_KIND = CacheLayout(
    depth=0, kinds=(CacheKind("full", (), 1),), page_size=0
)


class SequencePages:
    """One sequence's slice of the pool: per cache kind the ordered pool
    pages it holds (position-page ``i`` of a kind holds positions
    ``i*page_size .. (i+1)*page_size - 1`` of that kind's layers, in
    ``units`` consecutive entries) and growth / release bookkeeping. Pure
    host state — the device-visible form is :meth:`table`.

    Without a ``layout`` there is one kind that keeps every position and
    whose position-page is one pool page: ``pages`` is then the whole
    holding, as it is for every model whose layers are all alike."""

    def __init__(self, pool: PagePool, layout: Optional[CacheLayout] = None):
        self.pool = pool
        self.layout = layout
        self.kinds = (layout or _ONE_KIND).kinds
        #: per kind, the pool pages held, in position order
        self.held: List[List[int]] = [[] for _ in self.kinds]
        #: per kind, the position-page its first held entry belongs to
        self.first: List[int] = [0 for _ in self.kinds]
        #: the next query's position (:meth:`advance`)
        self.frontier = 0

    @property
    def pages(self) -> List[int]:
        """The first kind's pool pages (the full kind's where there is
        one): the whole holding of a model with one kind."""
        return self.held[0]

    @pages.setter
    def pages(self, value: List[int]) -> None:
        self.held[0] = value

    def _covered(self, ki: int) -> int:
        """Position-pages of kind ``ki`` reached so far."""
        return self.first[ki] + len(self.held[ki]) // self.kinds[ki].units

    @property
    def capacity(self) -> int:
        """Token positions the currently-held pages can store."""
        return self.pool.page_size * min(
            self._covered(ki) for ki in range(len(self.kinds))
        )

    def ensure(self, tokens: int) -> None:
        """Grow every kind until ``tokens`` positions fit (a window kind
        only as far ahead of the next query as it may reach,
        :meth:`CacheLayout.held_pages`). All-or-nothing per call; raises
        :class:`PagePoolExhausted` (holdings unchanged) when the pool
        cannot supply the missing pages."""
        ps = self.pool.page_size
        missing = []
        for ki, kind in enumerate(self.kinds):
            want = pages_needed(tokens, ps)
            if kind.window:
                want = self.layout.held_pages(kind, tokens, self.frontier)
            missing.append(max(0, want - self._covered(ki)) * kind.units)
        if not any(missing):
            return
        grant = self.pool.alloc(sum(missing))
        for ki, n in enumerate(missing):
            if n:
                self.held[ki].extend(grant[:n])
                self.pool._count(self.kinds[ki].name, taken=n)
                del grant[:n]

    def advance(self, frontier: int) -> int:
        """The next query is at position ``frontier``: every window kind
        gives back the pages whose positions are all more than its
        window behind it (key ``j`` is visible to query ``p`` iff ``p -
        window < j``). Returns the pool pages released."""
        self.frontier = int(frontier)
        released = 0
        for ki, kind in enumerate(self.kinds):
            if not kind.window:
                continue
            dead = max(0, self.frontier - kind.window + 1) // (
                self.pool.page_size
            )
            if dead <= self.first[ki]:
                continue
            n = kind.units * min(
                dead - self.first[ki], len(self.held[ki]) // kind.units
            )
            if n:
                self.pool.free(self.held[ki][:n])
                del self.held[ki][:n]
                self.pool._count(kind.name, released=n)
                released += n
            self.first[ki] = dead
        return released

    def release(self) -> None:
        """Return every held page to the pool (idempotent)."""
        for ki, kind in enumerate(self.kinds):
            if self.held[ki]:
                self.pool.free(self.held[ki])
                self.pool._count(kind.name, dropped=len(self.held[ki]))
                self.held[ki] = []
            self.first[ki] = 0
        self.frontier = 0

    def table(self, max_pages: int, kind: int = 0) -> np.ndarray:
        """The int32 page table the compiled step reads for one kind —
        ``[max_pages]`` (``[max_pages, units]`` for a kind whose
        position-page takes several pool pages), row ``r`` the
        position-page ``first[kind] + r``, held pages in position order,
        trash-filled past the end (those entries are masked by the
        position mask, but must stay in bounds)."""
        units = self.kinds[kind].units
        held = self.held[kind]
        if len(held) > max_pages * units:
            raise ValueError(
                f"sequence holds {len(held) // units} pages > max_pages "
                f"{max_pages}"
            )
        out = np.full(max_pages * units, self.pool.trash_page, np.int32)
        out[: len(held)] = held
        return out if units == 1 else out.reshape(max_pages, units)


class _PrefixEntry:
    """One cached prompt prefix: the page-aligned token span and the
    physical pages holding its k/v (the cache holds one reference on
    each). ``keys`` are the per-page-count digests registered in the
    lookup index, kept so eviction can remove exactly its own keys."""

    __slots__ = ("tokens", "pages", "keys", "full_key", "priority")

    def __init__(
        self, tokens: np.ndarray, pages: List[int], priority: int = 1
    ):
        self.tokens = tokens
        self.pages = pages
        self.keys: List[bytes] = []
        self.full_key: bytes = b""
        #: highest tenant-priority rank that registered this prefix
        #: (``serve/tenancy.py``): priority-weighted eviction drops
        #: low-rank entries first when the QoS plane is on
        self.priority = int(priority)


class PrefixCache:
    """Token-prefix -> physical-pages index over a :class:`PagePool` —
    shared-prefix KV caching (vLLM's automatic prefix caching shaped for
    the static-pool engine).

    A finished prefill registers its prompt's COMPLETE pages
    (:meth:`insert`); admission asks :meth:`acquire` for the longest
    page-aligned cached prefix of a new prompt and gets those pages
    refcounted into the new sequence's table, so the engine prefills
    only the uncached suffix (chunked prefill picks up mid-prompt).
    Shared pages are immutable: decode appends strictly past a prompt's
    complete pages, so divergence never writes into one. A prompt that
    diverges INSIDE a cached page gets a private copy-on-write clone:
    :meth:`acquire` returns the donor page to copy plus how many of its
    leading positions are reusable; the engine copies the page row and
    overwrites from the divergence point.

    Keys are sha1 digests of the token bytes per page-aligned prefix
    length, verified against the stored tokens on hit (digest collision
    can downgrade a hit to a miss, never corrupt). Entries are LRU:
    bounded by ``max_entries``, and evicted on demand when the pool runs
    dry (:meth:`evict_pages` — the scheduler tries that before
    preempting live sequences). Thread-safety: a lock guards the maps —
    mutation happens on the engine's stepping thread, but stats and
    ``/healthz`` read concurrently."""

    def __init__(self, pool: PagePool, max_entries: int = 256):
        self.pool = pool
        self.page_size = pool.page_size
        self.max_entries = int(max_entries)
        self._entries: "OrderedDict[bytes, _PrefixEntry]" = OrderedDict()
        self._index: Dict[bytes, _PrefixEntry] = {}
        self._lock = threading.Lock()
        #: host-side stats (obs counters live in the engine): acquire
        #: calls, acquires that returned any cached tokens, and tokens
        #: whose prefill was skipped thanks to the cache
        self.lookups = 0
        self.hits = 0
        self.tokens_saved = 0

    @staticmethod
    def _key(tokens: np.ndarray) -> bytes:
        return hashlib.sha1(
            np.ascontiguousarray(tokens, np.int32).tobytes()
        ).digest()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "lookups": self.lookups,
                "hits": self.hits,
                "tokens_saved": self.tokens_saved,
            }

    # -- registration ------------------------------------------------------

    def insert(
        self,
        prompt: np.ndarray,
        pages: Sequence[int],
        priority: int = 1,
    ) -> bool:
        """Register a prefilled prompt's COMPLETE pages (``len(prompt) //
        page_size`` of them — a partial trailing page is still mutable
        and never shared). Takes one pool reference per page; idempotent
        for an already-registered prompt (LRU touch only, and the entry
        keeps the HIGHEST priority any registrant gave it — a prefix an
        interactive tenant shares must not evict on a batch tenant's
        rank). Returns whether a new entry was created."""
        prompt = np.asarray(prompt, np.int32).ravel()
        k_full = len(prompt) // self.page_size
        if k_full < 1:
            return False
        tokens = prompt[: k_full * self.page_size].copy()
        full_key = self._key(tokens)
        with self._lock:
            if full_key in self._entries:
                ent = self._entries[full_key]
                ent.priority = max(ent.priority, int(priority))
                self._entries.move_to_end(full_key)
                return False
            ent = _PrefixEntry(
                tokens, [int(p) for p in pages[:k_full]], priority
            )
            self.pool.ref(ent.pages)
            ent.full_key = full_key
            for k in range(1, k_full + 1):
                key = self._key(tokens[: k * self.page_size])
                # longest-prefix lookups walk k downward, so pointing a
                # shorter shared prefix at the newest entry is safe even
                # when it displaces an older entry's short keys
                self._index[key] = ent
                ent.keys.append(key)
            self._entries[full_key] = ent
            while len(self._entries) > self.max_entries:
                self._drop_locked(next(iter(self._entries)))
            return True

    # -- lookup ------------------------------------------------------------

    def acquire(
        self, prompt: np.ndarray
    ) -> Tuple[List[int], Optional[int], int]:
        """Longest cached page-aligned prefix of ``prompt``; returns
        ``(shared_pages, cow_src_page, cached_tokens)``.

        ``shared_pages`` arrive with one NEW reference each (the caller
        owns it; release through the usual ``free``). ``cow_src_page``,
        when set, also carries one TEMPORARY reference: the prompt
        diverges (or simply ends) inside the donor's next page, and its
        first ``cached_tokens - len(shared_pages) * page_size``
        positions are reusable once the caller clones the page — the
        caller must ``pool.free([cow_src_page])`` after cloning (the
        reference pins the donor contents until then).

        ``cached_tokens`` is capped at ``len(prompt) - 1``: the last
        prompt position must always be recomputed, because the first
        sampled token needs its logits."""
        prompt = np.asarray(prompt, np.int32).ravel()
        ps = self.page_size
        with self._lock:
            self.lookups += 1
            kcap = (len(prompt) - 1) // ps
            for k in range(kcap, 0, -1):
                ent = self._index.get(self._key(prompt[: k * ps]))
                if ent is None:
                    continue
                if not np.array_equal(ent.tokens[: k * ps], prompt[: k * ps]):
                    continue  # digest collision: treat as a miss
                cached = k * ps
                cow_src: Optional[int] = None
                if len(ent.pages) > k:
                    # partial-page extension: count matching tokens into
                    # the donor's next page, capped to plen - 1
                    upto = min(len(prompt) - 1, (k + 1) * ps) - k * ps
                    nxt = ent.tokens[k * ps : k * ps + upto]
                    m = int(
                        np.argmin(
                            np.concatenate(
                                [
                                    nxt == prompt[k * ps : k * ps + upto],
                                    [False],
                                ]
                            )
                        )
                    )
                    if m > 0:
                        cow_src = ent.pages[k]
                        cached += m
                shared = list(ent.pages[:k])
                self.pool.ref(shared)
                if cow_src is not None:
                    self.pool.ref([cow_src])
                self._entries.move_to_end(ent.full_key)
                self.hits += 1
                self.tokens_saved += cached
                return shared, cow_src, cached
            return [], None, 0

    # -- eviction ----------------------------------------------------------

    def _drop_locked(self, full_key: bytes) -> int:
        ent = self._entries.pop(full_key)
        for key in ent.keys:
            if self._index.get(key) is ent:
                del self._index[key]
        return self.pool.free(ent.pages)

    def evict_pages(self, need: int) -> int:
        """Drop least-recently-used entries until at least ``need`` pages
        returned to the free list, or the cache is empty. Returns pages
        actually freed — entries whose pages live sequences still share
        free nothing NOW (the sequence's release frees them later), so a
        0 return with entries remaining is possible and the caller
        should fall through to preemption."""
        freed = 0
        with self._lock:
            if _tenancy.enabled():
                # priority-weighted: low-rank tenants' prefixes pay
                # first; the sort is stable over insertion order, so
                # WITHIN a rank eviction stays exactly LRU. QoS off
                # takes the plain-LRU loop below, byte-identical to
                # the pre-tenancy cache.
                order = sorted(
                    self._entries.values(),
                    key=lambda ent: ent.priority,
                )
                for ent in order:
                    if freed >= need:
                        break
                    freed += self._drop_locked(ent.full_key)
                return freed
            while freed < need and self._entries:
                freed += self._drop_locked(next(iter(self._entries)))
        return freed

    def clear(self, free_pages: bool = True) -> None:
        """Drop every entry. ``free_pages=False`` skips the pool
        release — for use right AFTER :meth:`PagePool.reset`, which
        already rebuilt the free list (freeing then would corrupt it)."""
        with self._lock:
            if free_pages:
                while self._entries:
                    self._drop_locked(next(iter(self._entries)))
            else:
                self._entries.clear()
                self._index.clear()

    def entry_page_lists(self) -> List[List[int]]:
        """The live entries' page lists, for
        :meth:`PagePool.defragment`'s in-place renumbering."""
        with self._lock:
            return [ent.pages for ent in self._entries.values()]
