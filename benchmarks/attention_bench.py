"""Flash-attention microbench: Pallas kernel vs dense XLA attention.

The long-context stack's hot op (the reference has no attention at all —
SURVEY §5 "long context: absent"). Run on the attached backend:

    python benchmarks/attention_bench.py [seq_lens...]

Prints one JSON line per (sequence length, dtype) with ms/call, achieved
TFLOP/s, and MFU — always as % of the device's bf16 MXU pass rate (197
TF/s on a v5e, from ``obs.programs``): under TPU
default matmul precision f32 inputs ride the same bf16 pass the kernel
uses for bf16 (the 49 TF/s figure is the highest-precision mode this
kernel does not request); f32 rows carry a note saying so.

Methodology — CHAIN-LENGTH DIFFERENTIAL: any single timed dispatch
carries a constant dispatch/fetch term, and per-iteration dispatch adds
host-side overhead that does NOT run on the chip; dividing by the
iteration count leaks both into "per-call" numbers. Here each row times
TWO single-dispatch programs that chain the op n1 and n2 times inside
one ``lax.fori_loop`` and reports (T(n2) - T(n1)) / (n2 - n1): the
constant terms cancel exactly, leaving on-chip time. Chain lengths are
sized so the compute delta is ~1.5s (reps take the min). A profiler
trace gives kernel time directly (on-chip-measurement guide §3); this
script predates one and is not re-measured on the current machine. bf16 inputs run the kernel's matmuls in the MXU's
native bf16 mode (f32 accumulation); dense attention materializes the
[L, L] score matrix, flash streams K/V through VMEM so its memory stays
O(L).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.configs import _sync


def _peak_flops():
    """The running device's bf16 matmul peak (``obs.programs``): a kernel
    utilization needs the chip it was measured on, so off TPU this
    fails instead of borrowing another device's peak."""
    import jax

    from tensorframes_tpu.obs.programs import peak_flops

    dev = jax.devices()[0]  # the peak reads an initialized backend
    peak = peak_flops()
    if peak is None:
        raise SystemExit(
            f"attention_bench needs a TPU; jax found {dev.platform} "
            f"({dev.device_kind})"
        )
    return peak


def _make_qkv(L, B, H, D, dtype):
    """Shared benchmark inputs: every row (forward, dense, train-step)
    measures the same distribution and dtype handling."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    shape = (B, H, L, D)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    mk = lambda: jnp.asarray(
        rng.normal(size=shape).astype(np.float32)
    ).astype(dt)
    return mk(), mk(), mk()


def _diff_time(make_chain, args, est_per_call, target_delta_s=1.5, reps=3):
    """(T(n2) - T(n1)) / (n2 - n1) with chains sized so the compute delta
    dominates link noise; min over reps."""
    delta = max(20, int(target_delta_s / max(est_per_call, 1e-6)))
    n1 = max(5, delta // 5)
    n2 = n1 + delta
    f1, f2 = make_chain(n1), make_chain(n2)
    _sync(f1(*args))
    _sync(f2(*args))
    best = None
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(f1(*args))
        t1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        _sync(f2(*args))
        t2 = time.perf_counter() - t0
        per = (t2 - t1) / (n2 - n1)
        best = per if best is None else min(best, per)
    return best, (n1, n2)


def bench_one(L, B=4, H=8, D=64, causal=True, dtype="bfloat16",
              block_q=None, block_k=None):
    import jax
    import jax.numpy as jnp

    from tensorframes_tpu.ops.attention import (
        _best_blocks,
        attention_reference,
        flash_attention,
    )

    q, k, v = _make_qkv(L, B, H, D, dtype)
    bq, bk = _best_blocks(
        jnp.bfloat16 if dtype == "bfloat16" else jnp.float32, D, L
    )
    if block_q:
        bq = block_q
    if block_k:
        bk = block_k

    def flash_chain(n):
        def f(a, b, c):
            def body(_, acc):
                return flash_attention(
                    acc, b, c, causal=causal, block_q=bq, block_k=bk
                ).astype(a.dtype)

            return jax.lax.fori_loop(0, n, body, a)

        return jax.jit(f)

    def dense_chain(n):
        # the carry MUST feed the op (as in flash_chain): a loop-invariant
        # body would be hoisted by XLA and the differential would measure
        # nothing
        def f(a, b, c):
            def body(_, acc):
                return attention_reference(acc, b, c, causal=causal).astype(
                    a.dtype
                )

            return jax.lax.fori_loop(0, n, body, a)

        return jax.jit(f)

    flash1 = jax.jit(
        lambda a, b, c: flash_attention(
            a, b, c, causal=causal, block_q=bq, block_k=bk
        )
    )
    dense1 = jax.jit(
        lambda a, b, c: attention_reference(a, b, c, causal=causal)
    )

    out_f = _sync(flash1(q, k, v))
    err = None
    dense_ok = True
    try:
        out_d = _sync(dense1(q, k, v))
        err = float(
            jnp.max(
                jnp.abs(
                    out_f.astype(jnp.float32) - out_d.astype(jnp.float32)
                )
            )
        )
    except Exception:
        dense_ok = False  # [L, L] score matrix no longer fits HBM

    # attention FLOPs: 2 matmuls of [L,L]x[L,D] per head (causal ~half).
    # MFU denominator: on TPU default matmul precision, f32 inputs ride
    # the MXU's bf16 pass too, so the f32 "peak" is the same bf16
    # pass rate (a v5e's 49 TF/s figure is the HIGHEST-precision mode this
    # kernel does not request) — without this the f32 row reports >100%.
    flops = 4.0 * B * H * L * L * D * (0.5 if causal else 1.0)
    peak = _peak_flops()
    est = flops / (0.5 * peak)
    tf_, chains = _diff_time(flash_chain, (q, k, v), est)
    td = None
    if dense_ok:
        try:
            # dense does 2x the causal FLOPs (no tile skipping) at lower
            # efficiency; size its chains from a conservative estimate
            td, _ = _diff_time(
                dense_chain, (q, k, v),
                (flops * (2.0 if causal else 1.0)) / (0.25 * peak),
                target_delta_s=1.0, reps=2,
            )
        except Exception:
            td = None
    tflops = flops / tf_ / 1e12
    row = {
        "metric": "flash_attention_ms",
        "seq_len": L,
        "batch": B,
        "heads": H,
        "head_dim": D,
        "causal": causal,
        "dtype": dtype,
        "block_q": bq,
        "block_k": bk,
        "flash_ms": round(tf_ * 1e3, 3),
        "dense_ms": round(td * 1e3, 3) if td else None,
        "speedup_vs_dense": round(td / tf_, 3) if td else None,
        "flash_tflops": round(tflops, 2),
        "mfu_pct_of_peak": round(100.0 * tflops * 1e12 / peak, 1),
        "max_abs_err_vs_dense": round(err, 6) if err is not None else None,
        "chain_lengths": chains,
    }
    if dtype == "float32":
        row["note"] = (
            "f32 inputs ride the MXU's default-precision bf16 pass; MFU "
            "is vs the bf16 pass rate, not the highest-precision mode"
        )
    return row


def bench_backward(L, B=4, H=8, D=64, causal=True, dtype="bfloat16",
                   block_q=None, block_k=None):
    """Train-step row: fwd + FlashAttention-2 backward (the custom VJP's
    two pallas kernels), the op long-context TRAINING actually runs.
    FLOP model: fwd 1x + bwd 2.5x (dq/dk/dv matmuls + softmax tile
    recompute) of the forward's 4*B*H*L^2*D."""
    import jax
    import jax.numpy as jnp

    from tensorframes_tpu.ops.attention import _best_blocks, flash_attention

    q, k, v = _make_qkv(L, B, H, D, dtype)
    bq, bk = _best_blocks(
        jnp.bfloat16 if dtype == "bfloat16" else jnp.float32, D, L
    )
    if block_q:
        bq = block_q
    if block_k:
        bk = block_k
    # defaulted tiles let the VJP pick its own tuned backward tiles
    # (_BEST_BLOCKS_BWD); explicit overrides bind fwd AND bwd
    kw = (
        {}
        if (block_q is None and block_k is None)
        else {"block_q": bq, "block_k": bk}
    )

    def loss(a, b, c):
        return flash_attention(
            a, b, c, causal=causal, **kw
        ).astype(jnp.float32).sum()

    def chain(n):
        # summing all three grads into the next query keeps dq AND dk/dv
        # live — nothing DCEs
        def f(a, b, c):
            def body(_, acc):
                dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(acc, b, c)
                return (dq + dk + dv).astype(a.dtype)

            return jax.lax.fori_loop(0, n, body, a)

        return jax.jit(f)

    flops = 3.5 * 4.0 * B * H * L * L * D * (0.5 if causal else 1.0)
    peak = _peak_flops()  # see bench_one's MFU note
    dt_step, chains = _diff_time(chain, (q, k, v), flops / (0.4 * peak))
    return {
        "metric": "flash_attention_train_step_ms",
        "seq_len": L,
        "batch": B,
        "heads": H,
        "head_dim": D,
        "causal": causal,
        "dtype": dtype,
        "block_q": bq,
        "block_k": bk,
        "fwd_bwd_ms": round(dt_step * 1e3, 3),
        "tflops": round(flops / dt_step / 1e12, 2),
        "mfu_pct_of_peak": round(
            100.0 * flops / dt_step / peak, 1
        ),
        "chain_lengths": chains,
    }


def bench_ring_hop(chunk=32768, hops=4, B=1, H=4, D=128, dtype="bfloat16"):
    """The blockwise ring-attention hop chain at a long-context chunk
    size, on one chip: fold ``hops`` visiting k/v chunks of ``chunk``
    tokens through the carry-mode flash kernel exactly as an
    ``hops``-chip ring runs per chip (hop 0 = causal diagonal, later
    hops = fully-visible past chunks), minus only the ppermute. The
    pre-blockwise implementation materialized a [chunk, chunk] f32 score
    matrix per (batch, head) per hop — at this size that is
    B*H*chunk^2*4 bytes (16 GiB at the defaults), beyond HBM; the
    blockwise path streams tiles, so this row EXISTING is the >HBM
    regression test. The figure of merit is the hop chain's TFLOP/s
    relative to the single-chip flash kernel at the same chunk
    (ring_vs_flash_pct) — the fraction of kernel throughput the ring
    path retains."""
    import jax
    import jax.numpy as jnp

    from tensorframes_tpu.ops.attention import (
        _NEG_BIG,
        _best_blocks,
        _finalize,
        flash_attention,
        flash_carry,
    )

    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(
        rng.normal(size=(B * H, chunk, D)).astype(np.float32)
    ).astype(dt)
    qf = mk()
    kcs = [mk() for _ in range(hops)]
    vcs = [mk() for _ in range(hops)]
    bq, bk = _best_blocks(dt, D, chunk)

    def hop_chain(n):
        def f(q, ks, vs):
            def body(_, q_in):
                m = jnp.full((B * H, chunk, 1), _NEG_BIG, jnp.float32)
                l = jnp.zeros((B * H, chunk, 1), jnp.float32)
                acc = jnp.zeros((B * H, chunk, D), jnp.float32)
                # hop 0: the causal diagonal; hops 1..n-1: past chunks
                m, l, acc = flash_carry(
                    q_in, ks[0], vs[0], m, l, acc,
                    causal=True, offset=0, block_q=bq, block_k=bk,
                    interpret=False,
                )
                for h in range(1, hops):
                    m, l, acc = flash_carry(
                        q_in, ks[h], vs[h], m, l, acc,
                        causal=False, offset=0, block_q=bq, block_k=bk,
                        interpret=False,
                    )
                return _finalize(l, acc).astype(q_in.dtype)

            return jax.lax.fori_loop(0, n, body, q)

        return jax.jit(f)

    # hop-chain FLOPs: diagonal is half-masked, the rest are full
    flops = 4.0 * B * H * chunk * chunk * D * (0.5 + (hops - 1))
    peak = _peak_flops()  # see bench_one's MFU note
    per, chains = _diff_time(
        hop_chain, (qf, kcs, vcs), flops / (0.5 * peak)
    )
    hop_tflops = flops / per / 1e12

    # single-chip flash reference at the same chunk + blocks
    q4 = qf.reshape(B, H, chunk, D)
    k4 = kcs[0].reshape(B, H, chunk, D)
    v4 = vcs[0].reshape(B, H, chunk, D)

    def flash_chain(n):
        def f(a, b, c):
            def body(_, acc):
                return flash_attention(
                    acc, b, c, causal=True, block_q=bq, block_k=bk
                ).astype(a.dtype)

            return jax.lax.fori_loop(0, n, body, a)

        return jax.jit(f)

    fl_flops = 4.0 * B * H * chunk * chunk * D * 0.5
    fl_per, _ = _diff_time(
        flash_chain, (q4, k4, v4), fl_flops / (0.5 * peak)
    )
    fl_tflops = fl_flops / fl_per / 1e12
    return {
        "metric": "ring_hop_chain_tflops",
        "chunk_per_chip": chunk,
        "hops": hops,
        "batch": B,
        "heads": H,
        "head_dim": D,
        "dtype": dtype,
        "block_q": bq,
        "block_k": bk,
        "hop_chain_ms": round(per * 1e3, 3),
        "hop_chain_tflops": round(hop_tflops, 2),
        "flash_single_chip_tflops": round(fl_tflops, 2),
        "ring_vs_flash_pct": round(100.0 * hop_tflops / fl_tflops, 1),
        "dense_path_score_bytes": int(B * H * chunk * chunk * 4),
        "chain_lengths": chains,
        "note": "old dense-score ring would allocate "
        f"{B * H * chunk * chunk * 4 / (1 << 30):.0f} GiB of scores per "
        "hop at this size (> HBM); the blockwise path runs it",
    }


def main():
    from tensorframes_tpu.utils.profiling import device_stamp

    def emit(row):
        print(json.dumps(device_stamp() | row))

    lens = [int(a) for a in sys.argv[1:]] or [8192, 16384, 32768]
    for L in lens:
        for dtype in ("bfloat16", "float32"):
            emit(bench_one(L, dtype=dtype))
    for L in lens:
        if L >= 8192:
            emit(bench_backward(L))
    emit(bench_ring_hop())


if __name__ == "__main__":
    main()
