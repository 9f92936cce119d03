"""Decode throughput: KV-cached autoregressive generation tok/s.

The decode-as-first-class-workload row (the reference has no generation
at all — its models only score; SURVEY §5). One compiled scan per
config; the whole decode is a single dispatch, so link RTT amortizes
over every generated token.

    python benchmarks/decode_bench.py
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.configs import _sync


def _model(vocab=8192, d_model=512, n_heads=8, n_layers=8, max_len=512,
           n_kv_heads=None):
    from tensorframes_tpu.models import TransformerLM

    return TransformerLM.init(
        0, vocab, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        max_len=max_len, n_kv_heads=n_kv_heads,
    )


def bench_decode(mode="greedy", batch=8, prompt_len=32, new_tokens=256,
                 iters=3):
    """One decode mode's tok/s. Modes: greedy, sampled (temperature +
    top-k + nucleus), ragged (left-padded variable-length prompts)."""
    import jax

    from tensorframes_tpu.models import left_pad_prompts

    lm = _model(max_len=prompt_len + new_tokens + 1)
    rng = np.random.default_rng(0)
    kw = {}
    if mode == "ragged":
        seqs = [
            rng.integers(0, 8192, size=rng.integers(4, prompt_len + 1))
            .tolist()
            for _ in range(batch)
        ]
        prompt, lens = left_pad_prompts(seqs)
        kw["prompt_lengths"] = lens
    else:
        prompt = rng.integers(0, 8192, size=(batch, prompt_len)).astype(
            np.int32
        )
    if mode == "sampled":
        kw.update(temperature=0.8, seed=1, top_k=50, top_p=0.95)

    lm.generate(prompt, new_tokens, **kw)  # compile + weights upload
    t0 = time.perf_counter()
    for i in range(iters):
        if mode == "sampled":
            kw["seed"] = i  # traced arg: same program, no recompile
        out = lm.generate(prompt, new_tokens, **kw)
    dt = (time.perf_counter() - t0) / iters
    n_params = sum(
        int(np.prod(np.shape(v)))
        for v in jax.tree_util.tree_leaves(
            {k: v for k, v in lm.params.items() if k != "n_heads"}
        )
    )
    return {
        "metric": f"decode_{mode}_tok_per_sec",
        "value": round(batch * new_tokens / dt, 1),
        "unit": "tok/s",
        "batch": batch,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "params_m": round(n_params / 1e6, 1),
        "seconds_per_decode": round(dt, 4),
        "per_sequence_tok_per_sec": round(new_tokens / dt, 1),
        "note": "one compiled scan per decode (single dispatch; RTT "
        "amortizes over all generated tokens); compiled program reused "
        "across iters" + (
            " and across seeds (traced)" if mode == "sampled" else ""
        ),
    }


def bench_gqa(batch=16, prompt_len=32, new_tokens=1024, iters=3):
    """Long-context decode, MHA vs grouped-query (n_kv_heads=2): the KV
    cache — the decode memory ceiling and the per-step read — shrinks by
    the group factor (4x here), which is GQA's practical win."""
    import jax

    rows = []
    for label, kv in (("mha", None), ("gqa4", 2)):
        lm = _model(max_len=prompt_len + new_tokens + 1, n_kv_heads=kv)
        rng = np.random.default_rng(0)
        prompt = rng.integers(0, 8192, size=(batch, prompt_len)).astype(
            np.int32
        )
        lm.generate(prompt, new_tokens)  # compile + upload
        t0 = time.perf_counter()
        for _ in range(iters):
            lm.generate(prompt, new_tokens)
        dt = (time.perf_counter() - t0) / iters
        # k cache + v cache, [layers, B, n_kv, plen+new, hd] f32 each —
        # geometry derived from the model, matching transformer_generate
        d_model = lm.params["embed"].shape[1]
        hd = d_model // lm.params["n_heads"]
        qkv_cols = lm.params["blocks"][0]["qkv"].shape[1]
        heads = ((qkv_cols - d_model) // 2) // hd
        cache_mb = (
            2 * len(lm.params["blocks"]) * batch * heads
            * (prompt_len + new_tokens) * hd * 4 / 1e6
        )
        rows.append({
            "metric": f"decode_longctx_{label}_tok_per_sec",
            "value": round(batch * new_tokens / dt, 1),
            "unit": "tok/s",
            "batch": batch,
            "new_tokens": new_tokens,
            "kv_heads": heads,
            "kv_cache_mb": round(cache_mb, 1),
            "seconds_per_decode": round(dt, 4),
        })
    return rows


def run_all():
    return [
        bench_decode("greedy"),
        bench_decode("sampled"),
        bench_decode("ragged"),
        *bench_gqa(),
    ]


if __name__ == "__main__":
    from tensorframes_tpu.utils.profiling import device_stamp

    for row in run_all():
        print(json.dumps(device_stamp() | row))
