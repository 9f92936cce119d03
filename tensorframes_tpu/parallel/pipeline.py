"""Pipeline parallelism: layer stages sharded over a ``pp`` axis.

GPipe-style schedule, TPU-first: every chip holds ONE stage's parameters
(the stage axis of a stacked parameter pytree is sharded over ``pp``), the
batch splits into microbatches, and activations hop chip-to-chip with
``ppermute`` — neighbor traffic on ICI, the same primitive the ring
attention uses. One ``shard_map`` program runs the whole schedule as a
``lax.scan`` over ``n_micro + n_stages - 1`` ticks; at each tick a chip
applies its stage to whatever microbatch is currently resident, then
passes the result downstream. No reference analog exists (SURVEY §2.5:
model parallelism "absent").

The stage function is uniform (same code per stage, per-stage parameters
differ) — the standard homogeneous-transformer-block case.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

from jax import shard_map as _shard_map
from jax.lax import axis_size as _axis_size

__all__ = ["pipeline_apply", "pipeline_reference", "pipeline_train_step"]

#: canonical pipeline axis name
PIPE_AXIS = "pp"



def _check_batch_axis(mesh, axis_name, batch_axis, mb):
    """Shared pre-flight for the batch-parallel composition: the batch axis
    must be a real mesh axis distinct from the pipeline axis, and the
    microbatch must shard evenly over it."""
    if batch_axis is None:
        return
    if batch_axis == axis_name:
        raise ValueError(
            f"batch_axis must differ from the pipeline axis "
            f"{axis_name!r}: sharding rows over the stage axis would "
            f"feed only one rank's rows through the schedule"
        )
    if batch_axis not in mesh.shape:
        raise ValueError(
            f"batch_axis {batch_axis!r} is not a mesh axis; mesh has "
            f"{tuple(mesh.shape)}"
        )
    if mb % mesh.shape[batch_axis]:
        raise ValueError(
            f"microbatch size {mb} must divide by the {batch_axis!r} "
            f"axis size {mesh.shape[batch_axis]}"
        )


def pipeline_reference(stage_fn, stacked_params, x):
    """Oracle: apply the stages sequentially on one device.
    ``stacked_params``: pytree whose leaves have a leading stage axis."""
    import jax

    n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    h = x
    for s in range(n_stages):
        p_s = jax.tree.map(lambda a: a[s], stacked_params)
        h = stage_fn(p_s, h)
    return h


def _pipeline_body(
    stage_fn, n_micro, params_local, x_micro, axis_name, batch_axis=None
):
    """Per-shard schedule. ``params_local``: this chip's stage params (no
    stage axis). ``x_micro``: [n_micro, mb, ...] microbatched input —
    replicated over the pipeline axis (only stage 0 consumes it) and, with
    ``batch_axis``, row-sharded over that axis. Returns [n_micro, mb, ...]
    outputs (valid on the LAST stage; the psum over the PIPELINE axis
    distributes them to every stage; batch shards stay sharded)."""
    import jax
    import jax.numpy as jnp

    n = _axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    first = my == 0
    last = my == n - 1
    total_ticks = n_micro + n - 1
    mb_shape = x_micro.shape[1:]

    from ..ops.seq_common import pcast_varying

    def vary(t):
        # carries inherit the microbatch input's variance: pp always, plus
        # the batch axis when microbatch rows are dp-sharded (pp x dp)
        t = pcast_varying(t, axis_name)
        if batch_axis is not None:
            t = pcast_varying(t, batch_axis)
        return t

    perm = [(i, i + 1) for i in range(n - 1)]  # downstream neighbor

    def tick(carry, t):
        held, outs = carry
        # stage 0 loads microbatch t (when one remains); others use the
        # activation received at the end of the previous tick
        mb_idx = jnp.minimum(t, n_micro - 1)
        incoming = jnp.where(
            first, x_micro[mb_idx], held
        )
        y = stage_fn(params_local, incoming)
        # the last stage emits microbatch t - (n - 1) at tick t
        out_idx = t - (n - 1)
        emit = jnp.logical_and(last, out_idx >= 0)
        outs = jax.lax.cond(
            emit,
            lambda o: o.at[jnp.maximum(out_idx, 0)].set(y),
            lambda o: o,
            outs,
        )
        # hand the activation downstream (chip i -> i+1); chip 0 receives
        # garbage it never reads (it always loads fresh microbatches)
        held = jax.lax.ppermute(y, axis_name, perm)
        return (held, outs), None

    held0 = vary(jnp.zeros(mb_shape, x_micro.dtype))
    outs0 = vary(jnp.zeros((n_micro,) + mb_shape, x_micro.dtype))
    (_, outs), _ = jax.lax.scan(
        tick, (held0, outs0), jnp.arange(total_ticks)
    )
    # outputs live on the last stage only; broadcast so every chip (and the
    # replicated out_spec) returns the same array
    keep = jnp.where(last, 1.0, 0.0).astype(outs.dtype)
    return jax.lax.psum(outs * keep, axis_name)


@functools.lru_cache(maxsize=8)
def _pipeline_program(stage_fn, n_micro, mesh, axis_name, batch_axis=None):
    import jax
    from jax.sharding import PartitionSpec as P

    def body(stacked_params, x_micro):
        params_local = jax.tree.map(
            lambda a: a[0], stacked_params
        )  # shard_map gives [1, ...] slabs on the stage axis
        return _pipeline_body(
            stage_fn, n_micro, params_local, x_micro, axis_name, batch_axis
        )

    # microbatch rows ([n_micro, mb, ...] axis 1) shard over batch_axis
    # when given: pp x dp in one program
    x_spec = P(None, batch_axis)
    return jax.jit(
        _shard_map(
            body,
            mesh=mesh,
            in_specs=(P(axis_name), x_spec),
            out_specs=x_spec,
            # the schedule mixes pp-replicated microbatch input with
            # ppermute-varying activations inside jnp.where; the final
            # psum re-establishes replication over pp (batch shards stay
            # sharded over batch_axis), which the VMA check cannot see
            check_vma=False,
        )
    )


def pipeline_apply(
    stage_fn: Callable[[Any, Any], Any],
    stacked_params,
    x,
    n_micro: int,
    mesh=None,
    axis_name: str = PIPE_AXIS,
    batch_axis=None,
):
    """Run ``x`` through ``n_stages`` pipeline stages sharded over the
    mesh's ``axis_name`` axis.

    ``stage_fn(params, h) -> h``: one stage, shape-preserving. The compiled
    schedule is cached by ``stage_fn``'s IDENTITY — define the stage
    function once and pass the same object every call (an inline lambda
    recreated per call recompiles the whole pipeline each time, the same
    rule as the engine's function frontend).
    ``stacked_params``: pytree with leading stage axis == the axis size.
    ``x``: [B, ...] with ``B % n_micro == 0``.
    """
    import jax
    import jax.numpy as jnp

    if mesh is None:
        from .mesh import make_mesh

        mesh = make_mesh({axis_name: len(jax.devices())})
    n = mesh.shape[axis_name]
    n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_stages != n:
        raise ValueError(
            f"stacked_params has {n_stages} stages; the {axis_name!r} axis "
            f"has {n} devices — they must match (one stage per chip)"
        )
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(
            f"batch {b} must divide by n_micro={n_micro}"
        )
    mb = b // n_micro
    _check_batch_axis(mesh, axis_name, batch_axis, mb)
    x_micro = jnp.reshape(jnp.asarray(x), (n_micro, mb) + x.shape[1:])
    out = _pipeline_program(stage_fn, n_micro, mesh, axis_name, batch_axis)(
        stacked_params, x_micro
    )
    return jnp.reshape(out, x.shape)


# ---------------------------------------------------------------------------
# training through the pipeline
# ---------------------------------------------------------------------------


def _tree_zeros_like(t):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.zeros_like, t)


def _pipeline_1f1b_body(
    stage_fn,
    loss_fn,
    n_micro,
    params_local,
    extra_params,
    x_micro,
    y_micro,
    axis_name,
    batch_axis=None,
):
    """One-forward-one-backward schedule with recompute-in-backward.

    Per shard: at tick ``t`` chip ``i`` forwards microbatch ``t - i`` (when
    in range) and backwards microbatch ``t - (2(n-1) - i + 1)``. Forward
    activations hop downstream, cotangents hop upstream, both by
    ``ppermute``. Each chip saves only the INPUT activation of in-flight
    microbatches in a ring buffer of depth ``min(n_micro, 2n)`` — the 1F1B
    memory bound — and recomputes the stage forward inside its backward
    (standard rematerialization: ~2 fwd + 1 bwd FLOPs per microbatch).
    GPipe-through-autodiff, by contrast, checkpoints every scan carry:
    O(n_micro) activations per chip.

    The LAST stage fuses ``loss_fn`` into its backward: the cotangent seed
    is d(loss)/d(stage output), so the loss never leaves the device. Chip 0
    collects the input cotangents so embedding-style layers OUTSIDE the
    pipeline can continue the chain (``dx``).

    Returns ``(loss_sum, grads_local, extra_grads, dx)``; every value is a
    SUM over microbatches (callers normalize).
    """
    import jax
    import jax.numpy as jnp

    from ..ops.seq_common import pcast_varying

    n = _axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    first = my == 0
    last = my == n - 1
    depth = min(n_micro, 2 * n)
    total_ticks = 2 * (n - 1) + n_micro + 1
    mb_shape = x_micro.shape[1:]

    def vary(t):
        t = pcast_varying(t, axis_name)
        if batch_axis is not None:
            t = pcast_varying(t, batch_axis)
        return t

    perm_down = [(i, i + 1) for i in range(n - 1)]
    perm_up = [(i + 1, i) for i in range(n - 1)]

    def tick(carry, t):
        held_f, held_b, ring, grads, extra_grads, dxs, loss_acc = carry

        # ---- forward slot: chip i forwards microbatch t - i
        f_idx = t - my
        fwd_on = jnp.logical_and(f_idx >= 0, f_idx < n_micro)
        f_clip = jnp.clip(f_idx, 0, n_micro - 1)
        x_in = jnp.where(first, x_micro[f_clip], held_f)
        ring = jax.lax.cond(
            fwd_on,
            lambda r: r.at[f_clip % depth].set(x_in),
            lambda r: r,
            ring,
        )
        y_out = stage_fn(params_local, x_in)

        # ---- backward slot: chip i backwards microbatch
        #      t - (2(n-1) - i + 1); recompute the stage forward from the
        #      saved input, seed the cotangent from the loss on the last
        #      stage, from the downstream ppermute otherwise
        b_idx = t - (2 * (n - 1) - my + 1)
        bwd_on = jnp.logical_and(b_idx >= 0, b_idx < n_micro)
        b_clip = jnp.clip(b_idx, 0, n_micro - 1)
        h_saved = ring[b_clip % depth]
        yb, stage_vjp = jax.vjp(
            lambda p, h: stage_fn(p, h), params_local, h_saved
        )
        lb, loss_vjp = jax.vjp(
            lambda e, yy: loss_fn(e, yy, y_micro[b_clip]), extra_params, yb
        )
        d_extra_b, g_seed = loss_vjp(jnp.ones_like(lb))
        g_use = jnp.where(last, g_seed, held_b)
        dp_b, dh_b = stage_vjp(g_use)

        acc_on = bwd_on
        grads = jax.tree.map(
            lambda a, d: a + jnp.where(acc_on, d, jnp.zeros_like(d)),
            grads,
            dp_b,
        )
        extra_on = jnp.logical_and(acc_on, last)
        extra_grads = jax.tree.map(
            lambda a, d: a + jnp.where(extra_on, d, jnp.zeros_like(d)),
            extra_grads,
            d_extra_b,
        )
        loss_acc = loss_acc + jnp.where(extra_on, lb, 0.0)
        # chip 0's input cotangent continues the chain outside the pipeline
        dxs = jax.lax.cond(
            jnp.logical_and(acc_on, first),
            lambda d: d.at[b_clip].set(dh_b),
            lambda d: d,
            dxs,
        )

        held_f = jax.lax.ppermute(y_out, axis_name, perm_down)
        dh_send = jnp.where(acc_on, dh_b, jnp.zeros_like(dh_b))
        held_b = jax.lax.ppermute(dh_send, axis_name, perm_up)
        return (held_f, held_b, ring, grads, extra_grads, dxs, loss_acc), None

    carry0 = (
        vary(jnp.zeros(mb_shape, x_micro.dtype)),
        vary(jnp.zeros(mb_shape, x_micro.dtype)),
        vary(jnp.zeros((depth,) + mb_shape, x_micro.dtype)),
        vary(_tree_zeros_like(params_local)),
        vary(_tree_zeros_like(extra_params)),
        vary(jnp.zeros((n_micro,) + mb_shape, x_micro.dtype)),
        vary(jnp.zeros((), jnp.float32)),
    )
    (_, _, _, grads, extra_grads, dxs, loss_acc), _ = jax.lax.scan(
        tick, carry0, jnp.arange(total_ticks)
    )
    # loss/extra grads live on the last stage, dx on the first: psum
    # replicates them over pp (per-stage grads stay per-shard)
    loss_acc = jax.lax.psum(loss_acc, axis_name)
    extra_grads = jax.tree.map(
        lambda a: jax.lax.psum(
            jnp.where(last, a, jnp.zeros_like(a)), axis_name
        ),
        extra_grads,
    )
    keep0 = jnp.where(first, 1.0, 0.0)
    dxs = jax.lax.psum(dxs * keep0.astype(dxs.dtype), axis_name)
    if batch_axis is not None:
        # data-parallel reduction: each batch shard saw its own rows.
        # dx stays per-shard (each shard's cotangent rows are its own) but
        # needs the same 1/nb: the global loss is the mean of shard-local
        # mean losses, so every shard-local derivative carries 1/nb.
        nb = _axis_size(batch_axis)
        loss_acc = jax.lax.psum(loss_acc, batch_axis) / nb
        grads = jax.tree.map(
            lambda a: jax.lax.psum(a, batch_axis) / nb, grads
        )
        extra_grads = jax.tree.map(
            lambda a: jax.lax.psum(a, batch_axis) / nb, extra_grads
        )
        dxs = dxs / nb
    return loss_acc, grads, extra_grads, dxs


@functools.lru_cache(maxsize=8)
def _pipeline_train_program(
    stage_fn, loss_fn, n_micro, mesh, axis_name, batch_axis, schedule
):
    import jax
    from jax.sharding import PartitionSpec as P

    x_spec = P(None, batch_axis)

    if schedule == "1f1b":

        def body(stacked_params, extra_params, x_micro, y_micro):
            params_local = jax.tree.map(lambda a: a[0], stacked_params)
            loss_sum, grads, extra_grads, dxs = _pipeline_1f1b_body(
                stage_fn,
                loss_fn,
                n_micro,
                params_local,
                extra_params,
                x_micro,
                y_micro,
                axis_name,
                batch_axis,
            )
            # normalize: total loss = mean over microbatches
            inv = 1.0 / n_micro
            grads = jax.tree.map(lambda a: (a * inv)[None], grads)
            extra_grads = jax.tree.map(lambda a: a * inv, extra_grads)
            return loss_sum * inv, grads, extra_grads, dxs * inv

        return jax.jit(
            _shard_map(
                body,
                mesh=mesh,
                in_specs=(P(axis_name), P(), x_spec, x_spec),
                out_specs=(P(), P(axis_name), P(), x_spec),
                check_vma=False,
            )
        )

    if schedule != "gpipe":
        raise ValueError(
            f"unknown schedule {schedule!r}; expected 'gpipe' or '1f1b'"
        )

    # GPipe: autodiff straight through the forward schedule (shard_map,
    # ppermute and scan all transpose); simple and the correctness oracle
    # for 1f1b, at O(n_micro) checkpointed activations per chip
    fwd = _shard_map(
        lambda stacked, x_micro: _pipeline_body(
            stage_fn,
            n_micro,
            jax.tree.map(lambda a: a[0], stacked),
            x_micro,
            axis_name,
            batch_axis,
        ),
        mesh=mesh,
        in_specs=(P(axis_name), x_spec),
        out_specs=x_spec,
        check_vma=False,
    )

    def total_loss(stacked, extra, x_micro, y_micro):
        import jax.numpy as jnp

        out = fwd(stacked, x_micro)  # [n_micro, mb, ...]
        losses = jax.vmap(lambda o, t: loss_fn(extra, o, t))(out, y_micro)
        return jnp.mean(losses)

    def step(stacked, extra, x_micro, y_micro):
        loss, (g_stacked, g_extra, dx) = jax.value_and_grad(
            total_loss, argnums=(0, 1, 2)
        )(stacked, extra, x_micro, y_micro)
        return loss, g_stacked, g_extra, dx

    return jax.jit(step)


def pipeline_train_step(
    stage_fn: Callable[[Any, Any], Any],
    loss_fn: Callable[[Any, Any, Any], Any],
    stacked_params,
    extra_params,
    x,
    y,
    n_micro: int,
    mesh=None,
    axis_name: str = PIPE_AXIS,
    batch_axis=None,
    schedule: str = "1f1b",
):
    """One training step through the pipeline: loss + grads.

    ``loss_fn(extra_params, y_out_mb, target_mb) -> scalar`` (mean over its
    rows) is fused into the LAST stage's backward. ``extra_params`` are
    replicated parameters consumed by the loss head (unembedding, final
    norm); their grads come back replicated. ``x``/``y``: [B, ...] with
    ``B % n_micro == 0``.

    Returns ``(loss, grads_stacked, grads_extra, dx)`` where ``dx`` (shape
    of ``x``) continues the chain into layers applied BEFORE the pipeline
    (embeddings), so the full model trains even though only the blocks are
    staged. Both schedules produce identical grads; ``'1f1b'`` holds
    ``min(n_micro, 2 * n_stages)`` activations per chip (recompute in
    backward), ``'gpipe'`` autodiffs the forward scan and checkpoints all
    ``n_micro``.

    Like :func:`pipeline_apply`, the compiled program caches on the
    IDENTITY of ``stage_fn``/``loss_fn`` — define them once.
    """
    import jax
    import jax.numpy as jnp

    if mesh is None:
        from .mesh import make_mesh

        mesh = make_mesh({axis_name: len(jax.devices())})
    n = mesh.shape[axis_name]
    n_stages = jax.tree.leaves(stacked_params)[0].shape[0]
    if n_stages != n:
        raise ValueError(
            f"stacked_params has {n_stages} stages; the {axis_name!r} axis "
            f"has {n} devices — they must match (one stage per chip)"
        )
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} must divide by n_micro={n_micro}")
    mb = b // n_micro
    _check_batch_axis(mesh, axis_name, batch_axis, mb)
    x_micro = jnp.reshape(jnp.asarray(x), (n_micro, mb) + x.shape[1:])
    y_micro = jnp.reshape(jnp.asarray(y), (n_micro, mb) + y.shape[1:])
    prog = _pipeline_train_program(
        stage_fn, loss_fn, n_micro, mesh, axis_name, batch_axis, schedule
    )
    loss, g_stacked, g_extra, dx = prog(
        stacked_params, extra_params, x_micro, y_micro
    )
    return loss, g_stacked, g_extra, jnp.reshape(dx, x.shape)
