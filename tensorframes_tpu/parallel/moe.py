"""Expert parallelism: a mixture-of-experts FFN sharded over an ``ep`` axis.

The reference has no model parallelism of any kind (SURVEY §2.5: "one graph
replica per partition"); this module and :mod:`.pipeline` complete the mesh
axes the TPU build treats as first-class (dp / tp / sp / ep / pp).

Design, TPU-first: experts are sharded over ``ep`` — each chip holds
``n_experts / n`` expert FFNs. Tokens stay replicated across the axis;
every chip runs its local experts over all tokens with the router's
one-hot mask folded into the expert output, and a single ``psum``
combines the per-chip partials. Static shapes throughout — no
capacity buffers, no token dropping, bit-identical to the dense oracle.
The classic all-to-all token dispatch (:func:`moe_dispatch_apply`) trades
that exactness for lower FLOPs at high expert counts: per-expert capacity
buffers (Switch convention), top-k in k dispatch rounds, fully
differentiable. Both paths train; grads match the dense oracle wherever
no token dropped.
"""

from __future__ import annotations

import functools
from typing import Dict

import numpy as np
from jax import shard_map as _shard_map
from jax.lax import axis_size as _axis_size

__all__ = [
    "init_moe",
    "moe_ffn",
    "moe_grouped",
    "moe_ffn_sharded",
    "moe_apply",
    "moe_dispatch_apply",
    "moe_load_balance_loss",
]

#: canonical expert-parallel axis name
EXPERT_AXIS = "ep"

Params = Dict[str, np.ndarray]


def init_moe(
    seed: int, d_model: int, d_ff: int, n_experts: int, dtype=np.float32
) -> Params:
    """Router + ``n_experts`` two-layer FFNs (stacked on a leading expert
    axis so the expert dim shards cleanly over the mesh)."""
    rng = np.random.default_rng(seed)

    def dense(*shape, fan_in):
        return rng.normal(0, fan_in**-0.5, shape).astype(dtype)

    return {
        "router": dense(d_model, n_experts, fan_in=d_model),
        "w_up": dense(n_experts, d_model, d_ff, fan_in=d_model),
        "b_up": np.zeros((n_experts, d_ff), dtype=dtype),
        "w_down": dense(n_experts, d_ff, d_model, fan_in=d_ff),
        "b_down": np.zeros((n_experts, d_model), dtype=dtype),
    }


def _expert_partials(params, x, expert_offset, gates, expert_ids):
    """Sum of local experts' outputs over tokens routed to them.

    ``x``: [B, L, D]; params hold the LOCAL expert slab (leading axis =
    local expert count); ``expert_ids``/``gates``: [B, L, k] global top-k
    routing (k=1 for switch-style). Masked compute: an expert's output is
    scaled by the sum of the gates of whichever top-k slots chose it."""
    import jax
    import jax.numpy as jnp

    # jnp-ify once: the loop indexes the expert axis with a traced index,
    # which raw numpy arrays cannot do
    w_up_all = jnp.asarray(params["w_up"])
    w_down_all = jnp.asarray(params["w_down"])
    gated = "w_gate" in params  # SiLU-gated experts carry no bias
    if gated:
        w_gate_all = jnp.asarray(params["w_gate"])
    else:
        b_up_all = jnp.asarray(params["b_up"])
        b_down_all = jnp.asarray(params["b_down"])

    def one_expert(e_local, acc):
        w_up = w_up_all[e_local]
        w_down = w_down_all[e_local]
        if gated:
            y = (jax.nn.silu(x @ w_gate_all[e_local]) * (x @ w_up)) @ w_down
        else:
            h = jax.nn.gelu(x @ w_up + b_up_all[e_local])
            y = h @ w_down + b_down_all[e_local]
        mask = (expert_ids == e_local + expert_offset).astype(x.dtype)
        combined_gate = (gates * mask).sum(axis=-1)  # over the k slots
        return acc + y * combined_gate[..., None]

    n_local = w_up_all.shape[0]
    acc0 = jnp.zeros_like(x)
    return jax.lax.fori_loop(
        0, n_local, lambda e, a: one_expert(e, a), acc0
    )


def _route_topk(params, x, k):
    """Top-k routing: ``(gates [B, L, k], expert_ids [B, L, k])``; for
    k > 1 the kept gates renormalize to sum to one (standard top-2
    convention)."""
    import jax
    import jax.numpy as jnp

    # bound by the router's width (the GLOBAL expert count) — inside
    # shard_map params hold only the local expert slab
    n_experts = params["router"].shape[-1]
    if not 1 <= k <= n_experts:
        raise ValueError(f"k={k} must be in [1, {n_experts}]")
    logits = x @ jnp.asarray(params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, expert_ids = jax.lax.top_k(probs, k)
    if k > 1:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, expert_ids


def moe_grouped(params: Params, x, k: int, expert_offset: int = 0):
    """The dropless grouped expert path: every token's ``k`` experts and
    no others, whatever the routing.

    Route over the router's whole width (softmax in float32 from a
    product at ``highest`` precision, the ``k`` largest, renormalised to
    sum 1 for ``k > 1``); sort the token-expert pairs by expert; one
    grouped product per weight over the experts HELD
    (``ops.grouped.grouped_matmul``: each expert's rows padded to whole
    tiles, a tile meets ``w[e]`` and no other, so the FLOPs are the
    pairs' and their padding's, not experts x tokens, and an expert's
    weight is read once); weigh each pair by its gate and sum a token's
    pairs. Nothing is dropped
    and no capacity is set: an expert with every token and one with none
    are group sizes like any other.

    ``params`` hold the experts this chip holds on their leading axis —
    global experts ``expert_offset .. expert_offset + n_local`` — and the
    router at its published width; pairs routed elsewhere add nothing,
    so the shares of a layer divided over chips add up to the layer
    (:func:`_expert_partials`' signature, the masked oracle's). Experts
    are SiLU-gated (``w_gate``, ``w_up``, ``w_down``); the GELU pair with
    biases that ``init_moe`` builds is served by :func:`moe_ffn`.
    ``x`` is ``[..., D]``. Returns
    ``(y [..., D] float32, counts [n_local] int32)``, ``counts`` the
    pairs each held expert got."""
    import jax
    import jax.numpy as jnp

    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    t = xt.shape[0]
    w_up = jnp.asarray(params["w_up"])
    w_down = jnp.asarray(params["w_down"])
    n_local = w_up.shape[0]
    router = jnp.asarray(params["router"])
    if not 1 <= k <= router.shape[-1]:
        raise ValueError(f"k={k} must be in [1, {router.shape[-1]}]")
    logits = jnp.dot(
        xt.astype(jnp.float32), router.astype(jnp.float32),
        precision="highest",
    )
    gates, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    if k > 1:
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # every expert's pairs in whole tiles of rows, one expert to a tile
    # (ops/grouped.py): the rows of expert e meet w[e] and no other
    from ..ops.grouped import grouped_matmul, tile_layout

    local = ids.reshape(-1) - expert_offset
    held = (local >= 0) & (local < n_local)
    pairs = t * k
    tile_rows = 16 if pairs <= 2048 else 128
    row, tile_expert, n_active, sizes = tile_layout(
        jnp.where(held, local, n_local), n_local, tile_rows
    )
    n_rows = tile_expert.shape[0] * tile_rows
    # the padded rows' tokens: a row no pair sits at reads token 0 and
    # is read by nobody
    token = jnp.zeros((n_rows + 1,), jnp.int32).at[row].set(
        jnp.arange(pairs, dtype=jnp.int32) // k
    )[:n_rows]
    rows = xt[token].astype(w_up.dtype)  # [rows, d]

    def grouped(a, w):
        return grouped_matmul(a, w, tile_expert, n_active, tile_rows)

    h = jax.nn.silu(grouped(rows, jnp.asarray(params["w_gate"])))
    y = grouped(h * grouped(rows, w_up), w_down)
    # back to token order: pair j of token i sits at row[i * k + j]
    mine = y[jnp.minimum(row, n_rows - 1)].reshape(t, k, d)
    mine = jnp.where(held.reshape(t, k, 1), mine, 0.0)
    out = (mine * gates[..., None]).sum(axis=1)
    return out.reshape(lead + (d,)), sizes


def moe_ffn(params: Params, x, k: int = 1):
    """Dense oracle: top-``k`` routed MoE FFN, all experts local.
    ``x``: [B, L, D] -> [B, L, D]."""
    gates, expert_ids = _route_topk(params, x, k)
    return _expert_partials(params, x, 0, gates, expert_ids)


def moe_ffn_sharded(
    params: Params, x, axis_name: str = EXPERT_AXIS, k: int = 1
):
    """Per-shard body (call inside ``shard_map``): params hold this chip's
    expert slab (leading expert axis sharded over ``axis_name``), ``x`` is
    replicated. Router runs replicated; local experts compute masked
    partials; one ``psum`` combines. Top-k composes for free here: a
    token's k experts may live on different chips, each contributing its
    gate-scaled partial to the same psum."""
    import jax

    my = jax.lax.axis_index(axis_name)
    n_local = params["w_up"].shape[0]
    gates, expert_ids = _route_topk(params, x, k)
    partial = _expert_partials(
        params, x, my * n_local, gates, expert_ids
    )
    return jax.lax.psum(partial, axis_name)


@functools.lru_cache(maxsize=32)
def _moe_program(mesh, axis_name: str, k: int = 1):
    import jax
    from jax.sharding import PartitionSpec as P

    expert_sharded = {
        "router": P(),  # replicated
        "w_up": P(axis_name),
        "b_up": P(axis_name),
        "w_down": P(axis_name),
        "b_down": P(axis_name),
    }
    return jax.jit(
        _shard_map(
            functools.partial(moe_ffn_sharded, axis_name=axis_name, k=k),
            mesh=mesh,
            in_specs=(expert_sharded, P()),
            out_specs=P(),
            # the masked-partial accumulator mixes replicated tokens with
            # ep-varying expert slabs; the closing psum re-establishes
            # replication, which is what the VMA checker cannot see
            check_vma=False,
        )
    )


def moe_apply(
    params: Params, x, mesh=None, axis_name: str = EXPERT_AXIS, k: int = 1
):
    """Full-array entry point: shards the expert slabs over the mesh's
    ``axis_name`` axis and applies the top-``k`` routed MoE FFN.
    ``n_experts`` must divide by the axis size."""
    import jax

    if mesh is None:
        from .mesh import make_mesh

        mesh = make_mesh({axis_name: len(jax.devices())})
    n = mesh.shape[axis_name]
    n_experts = params["w_up"].shape[0]
    if n_experts % n:
        raise ValueError(
            f"n_experts={n_experts} must divide by the {axis_name!r} axis "
            f"size {n}"
        )
    if not 1 <= k <= n_experts:  # fail fast, before tracing
        raise ValueError(f"k={k} must be in [1, {n_experts}]")
    return _moe_program(mesh, axis_name, k)(params, x)


# ---------------------------------------------------------------------------
# all-to-all (capacity-based) dispatch — the Switch-Transformer data path
# ---------------------------------------------------------------------------


def _dispatch_body(params, x, capacity, axis_name, k):
    """Per-shard body: ``x`` [T_local, D] tokens sharded over ``axis_name``;
    params hold the local expert slab. Tokens are ROUTED: for each of the
    ``k`` routing slots, every chip packs its tokens into a PER-EXPERT
    send buffer of ``capacity`` slots (the Switch convention: capacity
    counts tokens per (source shard, expert), so one expert hogging a
    chip cannot evict its neighbors' traffic), one ``all_to_all``
    exchanges the buffers, local experts run on what arrived, and a
    second ``all_to_all`` returns results to the owning chips. Overflow
    beyond an expert's capacity is dropped (contributes zero) — the
    standard Switch trade; communication is O(E*C*D) per slot instead of
    replicating T. Expert identity travels POSITIONALLY (buffer row =
    local expert), with a validity mask so empty slots contribute nothing
    (an expert's bias would otherwise leak into unused slots)."""
    import jax
    import jax.numpy as jnp

    n = _axis_size(axis_name)
    t_local, d = x.shape
    n_local = params["w_up"].shape[0]
    n_experts = n * n_local

    w_up = jnp.asarray(params["w_up"])
    b_up = jnp.asarray(params["b_up"])
    w_down = jnp.asarray(params["w_down"])
    b_down = jnp.asarray(params["b_down"])

    gates, ids = _route_topk(params, x, k)
    out = jnp.zeros_like(x)
    for j in range(k):  # k static dispatch rounds, one per routing slot
        expert = ids[..., j]                      # global expert id [T]
        gate = gates[..., j]                      # [T]
        # position of each token within ITS EXPERT's send buffer: running
        # count of earlier tokens routed to the same expert (stable
        # priority by position, the Switch convention); >= capacity drops
        onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - onehot)[
            jnp.arange(t_local), expert
        ]
        keep = pos < capacity
        # dropped tokens target the out-of-bounds slot `capacity` so
        # mode="drop" discards them (a clipped in-bounds index would
        # clobber a kept token's slot)
        safe = jnp.where(keep, pos, capacity)
        send = jnp.zeros((n_experts, capacity, d), x.dtype)
        send = send.at[expert, safe].set(x, mode="drop")
        valid = jnp.zeros((n_experts, capacity), x.dtype)
        valid = valid.at[expert, safe].set(
            jnp.ones_like(gate), mode="drop"
        )

        # exchange: destination chip = expert // n_local, positional
        recv = jax.lax.all_to_all(
            send.reshape(n, n_local * capacity, d),
            axis_name, 0, 0, tiled=False,
        ).reshape(n, n_local, capacity, d)
        recv_v = jax.lax.all_to_all(
            valid.reshape(n, n_local * capacity),
            axis_name, 0, 0, tiled=False,
        ).reshape(n, n_local, capacity)

        # local experts over their received slabs ([n_src * C, D] each)
        def one_expert(e, acc):
            te = recv[:, e].reshape(n * capacity, d)
            h = jax.nn.gelu(te @ w_up[e] + b_up[e])
            y = (h @ w_down[e] + b_down[e]).reshape(n, capacity, d)
            y = y * recv_v[:, e][..., None]  # empty slots: no bias leak
            return acc.at[:, e].set(y)

        out_buf = jax.lax.fori_loop(
            0, n_local, one_expert, jnp.zeros_like(recv)
        )

        # return trip, then gather each token's result from its
        # (expert, pos) slot
        back = jax.lax.all_to_all(
            out_buf.reshape(n, n_local * capacity, d),
            axis_name, 0, 0, tiled=False,
        ).reshape(n_experts, capacity, d)
        res = back[expert, jnp.where(keep, pos, 0)]
        out = out + jnp.where(keep[:, None], res * gate[:, None], 0.0)
    return out


@functools.lru_cache(maxsize=32)
def _dispatch_program(mesh, capacity: int, axis_name: str, k: int):
    import jax
    from jax.sharding import PartitionSpec as P

    expert_sharded = {
        "router": P(),
        "w_up": P(axis_name),
        "b_up": P(axis_name),
        "w_down": P(axis_name),
        "b_down": P(axis_name),
    }
    return jax.jit(
        _shard_map(
            functools.partial(
                _dispatch_body, capacity=capacity, axis_name=axis_name, k=k
            ),
            mesh=mesh,
            in_specs=(expert_sharded, P(axis_name)),
            out_specs=P(axis_name),
            check_vma=False,
        )
    )


def moe_dispatch_apply(
    params: Params,
    x,
    mesh=None,
    axis_name: str = EXPERT_AXIS,
    capacity_factor: float = 1.25,
    k: int = 1,
):
    """All-to-all routed MoE over ``[B, L, D]`` (Switch-Transformer data
    path): tokens sharded over ``axis_name``, routed to their experts'
    chips with ``capacity = ceil(cf * T_local / E)`` slots PER
    (source shard, expert) per round, processed, and returned; ``k``
    routing slots dispatch in ``k`` rounds whose gate-scaled results
    sum. Tokens beyond
    an expert's capacity are DROPPED (contribute zero) — choose
    ``capacity_factor`` >= E/k for exactness under any routing, or keep
    the default and accept the standard Switch behavior. Fully
    differentiable (grads match the dense oracle wherever no token
    dropped). Use :func:`moe_apply` for the exact masked-compute variant.
    """
    import jax
    import jax.numpy as jnp

    if mesh is None:
        from .mesh import make_mesh

        mesh = make_mesh({axis_name: len(jax.devices())})
    n = mesh.shape[axis_name]
    n_experts = params["w_up"].shape[0]
    if n_experts % n:
        raise ValueError(
            f"n_experts={n_experts} must divide by the {axis_name!r} axis "
            f"size {n}"
        )
    if not 1 <= k <= n_experts:
        raise ValueError(f"k={k} must be in [1, {n_experts}]")
    b, l, d = x.shape
    t = b * l
    if t % n:
        raise ValueError(
            f"token count {t} (= {b}x{l}) must divide by the {axis_name!r} "
            f"axis size {n}"
        )
    t_local = t // n
    # capacity is PER ROUND (each of the k rounds dispatches every token
    # exactly once, so expected per-expert load per round is T_local / E
    # regardless of k); total slots across rounds stay at the Switch
    # convention cf * k * T_local / E
    capacity = int(np.ceil(capacity_factor * t_local / n_experts))
    flat = jnp.reshape(jnp.asarray(x), (t, d))
    out = _dispatch_program(mesh, capacity, axis_name, k)(params, flat)
    return jnp.reshape(out, (b, l, d))


def moe_load_balance_loss(params: Params, x, k: int = 1):
    """Switch-Transformer auxiliary load-balancing loss:
    ``E * sum_e f_e * p_e`` where ``f_e`` is the fraction of ROUTING SLOTS
    assigned to expert ``e`` (mean one-hot over all ``k`` top-k slots, so
    the loss reflects actual assignment under top-k routing) and ``p_e``
    the mean router probability. Equals 1.0 under perfectly uniform
    routing; add a small multiple to the task loss to keep experts
    utilized (dropped-token rates down under the capacity dispatch).
    Differentiable through ``p_e`` (the ``f_e`` factor carries no
    gradient, per the standard formulation). Recomputes the router
    projection — one [T, D] x [D, E] matmul, negligible next to the
    expert FFNs — so it composes with any apply path without changing
    their signatures."""
    import jax
    import jax.numpy as jnp

    n_experts = params["w_up"].shape[0]
    logits = x @ jnp.asarray(params["router"])
    probs = jax.nn.softmax(logits, axis=-1).reshape(-1, n_experts)
    _, ids = jax.lax.top_k(probs, k)              # [T, k]
    f = jnp.mean(
        jax.nn.one_hot(ids, n_experts, dtype=probs.dtype), axis=(0, 1)
    )
    p = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(jax.lax.stop_gradient(f) * p)
