"""Tensor-parallel step programs: one serving replica spans N chips.

The solo :class:`~.engine.GenerationEngine` compiles three step programs
(prefill ``[1, max_seq_len]``, prefill-chunk ``[1, C]``, decode
``[max_slots]``) for one chip — plus the speculative VERIFY program
(``[max_slots, k + 1]``) when a draft model is attached. This module
builds the SAME programs as ``jit(shard_map(...))`` over a 1-D device
mesh (ROADMAP item 1a), so one replica's model weights and KV pool span
``N`` chips while keeping every contract solo serving established (the
DRAFT program is deliberately not here: it runs replicated — its
proposals steer how many positions verify covers, never their values):

- **byte-identical decode streams at every TP degree** — greedy AND
  seeded. Float matmuls are not associative, so any plan that changes a
  reduction's shape (Megatron row-parallel partial sums, column-sliced
  GEMMs) can flip a late-decode argmax and break the contract. The plan
  here shards only what is bit-exact by construction:

  * the **KV page pool and the per-head attention walk** shard along
    the KV-HEAD axis. The head axis is a pure batch axis in every
    attention contraction (scores reduce over ``head_dim``, the
    weighted sum over positions, both per head), so each shard's local
    heads compute bit-for-bit what the solo program computes for those
    heads, and the tiled all-gather of per-head context reassembles the
    solo activation exactly;
  * **weights shard AT REST** (``transformer_tp_specs``: qkv/up on
    output columns, proj/down on their hidden rows) and are
    **all-gathered to full inside the step** (``gather_tp_params``) —
    a tiled gather reconstructs the solo weight matrix bit-for-bit, so
    every dense matmul runs at the solo program's exact shape on exact
    inputs. Logits are computed replicated off the (replicated, tied)
    embedding; sampling runs on those replicated logits, identical on
    every shard.

  The trade: per-chip WEIGHT and KV memory scale ~1/N (the
  model-bigger-than-one-chip unlock) and the decode-dominant paged
  read's bandwidth and FLOPs scale 1/N, while dense projections are
  computed replicated (decode batches are tiny — the paged read is the
  steady-state ceiling) at the cost of per-step weight gathers, the
  FSDP-style bytes-for-determinism trade this contract forces.

- **≤ 3 compiled step programs per replica** at any TP degree: the
  mesh is static program structure, shapes are unchanged, and jit keys
  on the same abstract signatures the solo programs key on.

- **aggregate KV capacity scales with N**: each page spans the shards
  (1/N bytes per chip), so the engine sizes the pool at
  ``num_pages × N`` total pages for the same per-chip budget —
  ``serve.pages_capacity`` reports the scaled total, and a workload
  that exhausts TP=1 admission serves preemption-free at TP=2.

Tests drive TP=2/4 on the CPU-simulated mesh
(``xla_force_host_platform_device_count``, the conftest default), so
tier-1 exercises the whole plan without hardware; on real chips the
collectives ride ICI exactly like the ``parallel/`` primitives
(``chip_smoke.py`` phase 4 runs a tp=4 engine across four chips).
"""

from __future__ import annotations

import time
from typing import Tuple

import numpy as np

from ..models.transformer import (
    filter_logits,
    gather_tp_params,
    transformer_prefill_chunk,
    transformer_step,
    transformer_verify_chunk,
)
from .kv_pages import read_pages, split_heads, write_prompt, write_rows

__all__ = [
    "estimate_collective_seconds",
    "tp_decode_impl",
    "tp_kv_specs",
    "tp_prefill_chunk_impl",
    "tp_prefill_impl",
    "tp_verify_impl",
    "validate_tp_mesh",
]


def validate_tp_mesh(mesh, n_heads: int, n_kv: int, d_ff: int) -> str:
    """Reject meshes the plan cannot shard evenly; returns the mesh's
    (single) axis name. Head counts must divide so the KV-head slicing
    lands on whole heads; ``d_ff`` must divide so the at-rest weight
    shards are even (``shard_map`` requires even shards)."""
    axes = tuple(mesh.axis_names)
    if len(axes) != 1:
        raise ValueError(
            f"serving meshes are 1-D (one tensor-parallel axis); got "
            f"axes {axes} — compose dp by running one replica per mesh "
            f"(the fleet), not inside one engine"
        )
    tp = int(mesh.devices.size)
    for what, val in (
        ("n_kv_heads", n_kv),
        ("n_heads", n_heads),
        ("d_ff", d_ff),
    ):
        if val % tp:
            raise ValueError(
                f"{what} ({val}) must divide by the mesh size ({tp}): "
                f"the KV pool and weight shards split evenly or not at "
                f"all"
            )
    return axes[0]


def tp_kv_specs(axis: str):
    """(in/out) PartitionSpec for the pool's ``[L, pages, ps, n_kv *
    hd]`` arrays (``kv_pages.kv_pool_shape``): sharded on the merged
    head axis, whose even split lands on KV-head boundaries because
    ``validate_tp_mesh`` made ``n_kv`` divide — each shard holds its
    ``n_kv / tp`` heads' lanes of every row."""
    from jax.sharding import PartitionSpec as P

    return P(None, None, None, axis)


def _local_heads(arr, axis: str, kloc: int, head_axis: int):
    """This shard's contiguous KV-head slice of a full-head tensor."""
    import jax

    ti = jax.lax.axis_index(axis)
    return jax.lax.dynamic_slice_in_dim(
        arr, ti * kloc, kloc, axis=head_axis
    )


def _wrap(body, mesh, axis: str, param_specs, n_scalars: int):
    """jit-ready shard_map over one step body: params tree sharded per
    ``param_specs``, the two pool arrays on the KV-head axis, every
    other input replicated, outputs ``(k_pool, v_pool, tokens)``."""
    import jax
    from jax.sharding import PartitionSpec as P

    kv = tp_kv_specs(axis)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, kv, kv) + (P(),) * n_scalars,
        out_specs=(kv, kv, P()),
        # replicated outputs (the sampled tokens) come from replicated
        # logits by construction; the static checker cannot infer that
        # through the gathers, so it is disabled exactly like the ring
        # and ulysses programs disable it
        check_vma=False,
    )


def tp_prefill_impl(engine, mesh, axis: str, n_heads: int, moe_top_k: int):
    """The TP prefill ``[1, max_seq_len]`` body with the ATTENTION
    sharded along KV heads (ROADMAP 1 follow-on — it used to compute
    full heads replicated, sharding only the KV scatter): the prompt
    runs the delegated chunk walk at positions ``0 .. P-1``, and each
    shard computes the dense causal attention for ITS head slice only —
    the head axis is a pure batch axis in both einsums, so every local
    head's scores/softmax/weighted-sum are bit-for-bit the solo
    program's for that head, and the tiled all-gather reassembles the
    solo context exactly. Per-chip prefill attention FLOPs and the
    ``O(P^2)`` score matrix both scale ~1/N. The shard's own k/v slice
    scatters straight into its pool shard (no full-head tensor is ever
    materialized), and sampling mirrors
    :meth:`GenerationEngine._prefill_impl` exactly."""
    import jax
    import jax.numpy as jnp

    from ..ops.attention import _NEG_BIG

    trash = engine.pool.trash_page
    top_k = engine.top_k
    tp = int(mesh.devices.size)
    kloc = engine.pool.n_kv_heads // tp

    def prefill(p_loc, kp, vp, prompt, length, ptab, temp, seed, top_p):
        full = {**gather_tp_params(p_loc, axis), "n_heads": n_heads}
        plen = prompt.shape[1]
        pos = jnp.arange(plen)
        state = [kp, vp]

        def attend(li, q, k, v):
            # local heads only: q [1, P, n_kv, g, hd] -> [P, kloc, g,
            # hd]; k/v [1, P, n_kv, hd] -> [P, kloc, hd]
            ql = _local_heads(q[0], axis, kloc, 1)
            kl = _local_heads(k[0], axis, kloc, 1)
            vl = _local_heads(v[0], axis, kloc, 1)
            state[0] = write_prompt(state[0], li, ptab, length, kl, trash)
            state[1] = write_prompt(state[1], li, ptab, length, vl, trash)
            hd = kl.shape[2]
            scale = 1.0 / float(np.sqrt(hd))
            # dense causal attention WITHIN the prompt, local heads:
            # the same einsum family as transformer_prefill's, minus
            # its batch axis — per head, bit-exact
            s = jnp.einsum("qkgd,tkd->kgqt", ql, kl) * scale
            causal = pos[:, None] >= pos[None, :]
            s = jnp.where(causal[None, None], s, _NEG_BIG)
            att = jnp.einsum(
                "kgqt,tkd->kgqd", jax.nn.softmax(s, axis=-1), vl
            )
            att = jax.lax.all_gather(att, axis, axis=0, tiled=True)
            # [n_kv, g, P, hd] -> [1, P, n_kv * g * hd]
            return att.transpose(2, 0, 1, 3).reshape(1, plen, -1)

        logits = transformer_prefill_chunk(
            full, prompt, pos, attend, moe_top_k=moe_top_k
        )
        last = logits[0, length - 1]
        greedy = jnp.argmax(last, axis=-1)
        key = jax.random.fold_in(jax.random.PRNGKey(seed), length - 1)
        scaled = last[None] / jnp.maximum(
            jnp.asarray(temp, jnp.float32), 1e-6
        )
        filt = filter_logits(scaled, top_k=top_k, top_p=top_p)
        sampled = jax.random.categorical(key, filt, axis=-1)[0]
        tok = jnp.where(temp > 0, sampled, greedy).astype(jnp.int32)
        return state[0], state[1], tok

    return _wrap(prefill, mesh, axis, engine._tp_param_specs, 6)


def tp_prefill_chunk_impl(
    engine, mesh, axis: str, n_heads: int, moe_top_k: int
):
    """The TP ``[1, C]`` chunk body: per-head chunk attention on the
    local pool shard (scatter local k/v, gather local pages, the SAME
    einsum/mask family as the solo chunk program), context all-gathered
    back to full heads before the replicated residual walk."""
    import jax
    import jax.numpy as jnp

    from ..ops.attention import _NEG_BIG

    ps = engine.page_size
    trash = engine.pool.trash_page
    top_k = engine.top_k
    mp = engine._max_pages
    max_len = engine.max_seq_len
    tp = int(mesh.devices.size)
    kloc = engine.pool.n_kv_heads // tp

    def chunk_step(
        p_loc, kp, vp, chunk, start, valid, total_len, ptab, temp, seed,
        top_p,
    ):
        full = {**gather_tp_params(p_loc, axis), "n_heads": n_heads}
        c = chunk.shape[1]
        offs = jnp.arange(c)
        pos = start + offs
        pos_clipped = jnp.minimum(pos, max_len - 1)
        state = [kp, vp]

        def attend(li, q, k, v):
            # local heads only: q [1, C, n_kv, g, hd] -> [C, kloc, g,
            # hd]; k/v [1, C, n_kv, hd] -> [C, kloc, hd]
            ql = _local_heads(q[0], axis, kloc, 1)
            kl = _local_heads(k[0], axis, kloc, 1)
            vl = _local_heads(v[0], axis, kloc, 1)
            page = jnp.where(offs < valid, ptab[pos_clipped // ps], trash)
            off = pos_clipped % ps
            state[0] = write_rows(state[0], li, page, off, kl)
            state[1] = write_rows(state[1], li, page, off, vl)
            hd = kl.shape[2]
            t = mp * ps
            kg = split_heads(read_pages(state[0], li, ptab), hd)
            vg = split_heads(read_pages(state[1], li, ptab), hd)
            scale = 1.0 / float(np.sqrt(hd))
            s = jnp.einsum("ckgd,tkd->ckgt", ql, kg) * scale
            visible = jnp.arange(t)[None, :] <= pos[:, None]
            s = jnp.where(visible[:, None, None, :], s, _NEG_BIG)
            att = jnp.einsum(
                "ckgt,tkd->ckgd", jax.nn.softmax(s, axis=-1), vg
            )
            att = jax.lax.all_gather(att, axis, axis=1, tiled=True)
            return att.reshape(1, c, att.shape[1] * q.shape[3] * hd)

        logits = transformer_prefill_chunk(
            full, chunk, pos_clipped, attend, moe_top_k=moe_top_k
        )
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

    return _wrap(chunk_step, mesh, axis, engine._tp_param_specs, 8)


def tp_verify_impl(engine, mesh, axis: str, n_heads: int, moe_top_k: int):
    """The TP VERIFY ``[max_slots, k + 1]`` body — speculative
    decoding's batched multi-token check, sharded on KV heads exactly
    like decode: each shard scatters its head slice of the whole verify
    span into its pool shard, walks the per-slot paged history for its
    heads only (the chunk read, batched over slots — bit-exact per
    head), and all-gathers the context before the replicated residual
    walk. Sampling runs on replicated logits with the per-step key
    folded at each ABSOLUTE position, mirroring
    :meth:`GenerationEngine._verify_impl` — so speculative streams stay
    byte-identical to solo at every TP degree."""
    import jax
    import jax.numpy as jnp

    from ..ops.attention import _NEG_BIG
    from .engine import _sample_slot_tokens

    ps = engine.page_size
    trash = engine.pool.trash_page
    top_k = engine.top_k
    mp = engine._max_pages
    max_len = engine.max_seq_len
    c = engine.draft_len + 1
    tp = int(mesh.devices.size)
    kloc = engine.pool.n_kv_heads // tp

    def verify(
        p_loc, kp, vp, toks, starts, n_valid, ptabs, temps, seeds, top_ps
    ):
        full = {**gather_tp_params(p_loc, axis), "n_heads": n_heads}
        slots = toks.shape[0]
        offs = jnp.arange(c)
        pos = starts[:, None] + offs[None, :]
        pos_c = jnp.clip(pos, 0, max_len - 1)
        state = [kp, vp]

        def attend(li, q, k, v):
            # local heads: q [S, C, n_kv, g, hd] -> [S, C, kloc, g,
            # hd]; k/v -> [S, C, kloc, hd]
            ql = _local_heads(q, axis, kloc, 2)
            kl = _local_heads(k, axis, kloc, 2)
            vl = _local_heads(v, axis, kloc, 2)
            valid = (offs[None, :] < n_valid[:, None]) & (pos < max_len)
            page = jnp.where(
                valid,
                jnp.take_along_axis(ptabs, pos_c // ps, axis=1),
                trash,
            )
            off = pos_c % ps
            state[0] = write_rows(state[0], li, page, off, kl)
            state[1] = write_rows(state[1], li, page, off, vl)
            hd = kl.shape[3]
            t = mp * ps
            kg = split_heads(read_pages(state[0], li, ptabs), hd)
            vg = split_heads(read_pages(state[1], li, ptabs), hd)
            scale = 1.0 / float(np.sqrt(hd))
            s = jnp.einsum("sckgd,stkd->sckgt", ql, kg) * scale
            visible = (
                jnp.arange(t)[None, None, :] <= pos_c[:, :, None]
            )
            s = jnp.where(visible[:, :, None, None, :], s, _NEG_BIG)
            att = jnp.einsum(
                "sckgt,stkd->sckgd", jax.nn.softmax(s, axis=-1), vg
            )
            att = jax.lax.all_gather(att, axis, axis=2, tiled=True)
            return att.reshape(slots, c, att.shape[2] * q.shape[3] * hd)

        logits = transformer_verify_chunk(
            full, toks, pos_c, attend, moe_top_k=moe_top_k
        )
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

    return _wrap(verify, mesh, axis, engine._tp_param_specs, 7)


def tp_decode_impl(engine, mesh, axis: str, n_heads: int, moe_top_k: int):
    """The TP decode ``[max_slots]`` body: each shard writes its heads'
    k/v into its pool shard, runs the paged read (gather reference or
    the fused ragged kernel — both are head-batched, so the local walk
    is bit-exact) over its heads only, and all-gathers the per-head
    context. Residuals, MLP, logits, and sampling run replicated and
    match the solo decode bit-for-bit."""
    import jax
    import jax.numpy as jnp

    from ..ops import paged_attention, ragged_paged_attention

    ps = engine.page_size
    d_model = engine._d_model
    top_k = engine.top_k
    fused = engine.attention_impl == "fused"
    tp = int(mesh.devices.size)
    kloc = engine.pool.n_kv_heads // tp

    def decode(p_loc, kp, vp, toks, positions, ptabs, temps, seeds, top_ps):
        full = {**gather_tp_params(p_loc, axis), "n_heads": n_heads}
        slots = toks.shape[0]
        state = [kp, vp]

        def attend(li, q, k, v):
            ql = _local_heads(q, axis, kloc, 1)  # [S, kloc, g, hd]
            kl = _local_heads(k, axis, kloc, 1)  # [S, kloc, hd]
            vl = _local_heads(v, axis, kloc, 1)
            page = ptabs[jnp.arange(slots), positions // ps]
            off = positions % ps
            state[0] = write_rows(state[0], li, page, off, kl)
            state[1] = write_rows(state[1], li, page, off, vl)
            read = ragged_paged_attention if fused else paged_attention
            ctx = read(
                ql, state[0], state[1], ptabs, positions + 1, layer=li
            )
            ctx = jax.lax.all_gather(ctx, axis, axis=1, tiled=True)
            return ctx.reshape(slots, d_model)

        logits = transformer_step(
            full, toks, positions, attend, moe_top_k=moe_top_k
        )
        greedy = jnp.argmax(logits, axis=-1)
        keys = jax.vmap(
            lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
        )(seeds, positions)
        scaled = logits / jnp.maximum(temps[:, None], 1e-6)
        filt = filter_logits(scaled, top_k=top_k, top_p=top_ps[:, None])
        sampled = jax.vmap(jax.random.categorical)(keys, filt)
        nxt = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
        return state[0], state[1], nxt

    return _wrap(decode, mesh, axis, engine._tp_param_specs, 6)


def estimate_collective_seconds(
    engine, mesh, axis: str
) -> Tuple[float, float]:
    """One-time micro-measurement of the per-step collective pattern:
    a jitted program that runs exactly the step's gathers — the at-rest
    weight shards back to full plus one per-layer context gather — is
    timed (one warmup, median of 3), and the engine charges the result
    to the ``serve.collective_seconds`` counter per dispatched step.
    An ESTIMATE by construction (the real gathers overlap compute
    inside the step program; XLA may also schedule them differently
    there), labeled as such in docs/observability.md. Returns
    ``(seconds_per_step, gathered_bytes_per_step)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    params = engine._params_dev
    n_layers = len(params["blocks"])
    n_kv = engine.pool.n_kv_heads
    hd = engine.pool.head_dim
    tp = int(mesh.devices.size)
    group = engine._d_model // hd // n_kv
    # GLOBAL shape; the in_spec shards the head axis to kloc per chip
    ctx_loc = jnp.zeros(
        (engine.max_slots, n_kv, group, hd), jnp.float32
    )

    def body(p_loc, ctx):
        full = gather_tp_params(p_loc, axis)
        outs = [
            jax.lax.all_gather(ctx, axis, axis=1, tiled=True)
            for _ in range(n_layers)
        ]
        # touch every gathered leaf so nothing is dead-code-eliminated
        acc = sum(jnp.sum(b["qkv"][0, 0] + b["proj"][0, 0]
                          + b["up"][0, 0] + b["down"][0, 0])
                  for b in full["blocks"])
        return acc + sum(jnp.sum(o[0, 0]) for o in outs)

    prog = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(engine._tp_param_specs, P(None, axis, None, None)),
            out_specs=P(),
            check_vma=False,
        )
    )
    try:
        jax.block_until_ready(prog(params, ctx_loc))  # compile + warm
        walls = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(prog(params, ctx_loc))
            walls.append(time.perf_counter() - t0)
        est = sorted(walls)[1]
    except Exception:
        est = 0.0
    # bytes RECEIVED per chip per step ((tp-1)/tp of each gathered
    # array), weights and per-layer context alike — one consistent unit
    gathered = 0
    frac = (tp - 1) / tp if tp > 1 else 0.0
    for b in params["blocks"]:
        for name in ("qkv", "proj", "up", "down"):
            gathered += b[name].size * b[name].dtype.itemsize * frac
    gathered += (
        n_layers * ctx_loc.size * ctx_loc.dtype.itemsize * frac
    )
    return est, float(gathered)
