"""chip_smoke.py — does the system still start on the chip?

One process drives the repo's main paths once, through the entry points a
user would call, and checks what comes out by the repo's own means:

0. device gate — jax must report a TPU and a known peak, else exit non-zero
   before anything else runs;
1. frame plane — BASELINE config 3 (MNIST-LR scoring, 1,000,000 x 784 f32)
   through ``analyze`` / ``tft.precompile`` / ``map_blocks`` /
   ``reduce_blocks``, a 1,024-group ``aggregate`` and a ragged ``map_rows``,
   each against a numpy oracle;
2. serving plane — GPT-2 small widths behind ``ScoringServer(engine=...)``:
   eight ``POST /generate`` requests over the socket, then ``/metrics`` and
   ``/healthz``;
3. kernels — the fused ragged paged-attention engine, three flash-attention
   training steps, flash forward + grad against the reference; then the KV
   pool's layout: the decode and prefill programs compiled at 25 heads of
   64 must hold no copy or transpose of the pool, of a layer of it or of
   the gathered block; the same for the decode and prefill-chunk programs
   of a rotary / gated-expert model with window and full layers at
   Mellum2-12B's published widths (one period of its layers);
4. four chips (when the host has them) — dp frame ops, a tp=4 engine, a
   four-replica fleet, ring attention;
5. README flow 1 on a float64 column, last, because it flips jax's x64 flag;
6. the closing ledger, then the exit code.

Run it on the machine with the chip as ``python chip_smoke.py``. The last
line of standard output is one JSON object, ``{"ok": true, "device": {...}}``;
the exit code is 0 only if every check of every phase passed. Every phase
runs even after an earlier one failed; a failure is printed with its
traceback and counted, never swallowed.

``--rehearse-cpu`` is the only other mode: the same phases at toy sizes on
the CPU backend with the Pallas kernels interpreted, every line stamped
``"platform": "cpu", "rehearsal": true``. It exists for tier-1 and for
debugging before a chip call; nothing selects it implicitly. The timings
this script prints are observations, not benchmark metrics.
"""

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out")

#: bf16-pass f32 matmuls (the MXU's default for f32 operands) keep 8
#: mantissa bits per product: two correct evaluations of the same logits —
#: a slot-batched paged step, a solo dense scan, a full-sequence forward —
#: agree to about 2^-8 of the logit scale per matmul, compounded through
#: 12 blocks and the tied head. Streams may therefore part at a near-tie;
#: they may not part where the reference separates the two tokens by more
#: than this fraction of its largest logit magnitude.
LOGIT_TOL_FRACTION = 0.05
#: argmax agreement of chip predictions with the f32 numpy oracle on
#: MNIST-LR scoring: same bf16-pass rounding, near-ties flip (the bar the
#: r01 scoring rounds set)
SCORING_AGREEMENT = 0.99


class Sizes:
    """Real sizes for the chip; ``toy()`` for the CPU rehearsal."""

    rows = 1_000_000
    features, classes = 784, 10
    agg_rows, groups = 262_144, 1024
    ragged_rows, ragged_max_len = 20_000, 48
    lm = dict(d_model=768, n_heads=12, n_layers=12, max_len=1024)
    vocab = 50257
    prompt_lens = (16, 128, 320, 512)
    new_tokens = 32
    max_slots, page_size = 8, 16
    fit_batch, fit_len, fit_steps = 4, 1024, 3
    flash_l, flash_d, flash_heads = 2048, 128, 2
    ring_l, ring_d, ring_heads = 4096, 64, 4
    # GPT-2 XL's widths for the pool-layout guard: 25 heads of 64
    layout_lm = dict(d_model=1600, n_heads=25, n_layers=8, max_len=1024)
    layout_vocab, layout_pages = 1024, 176
    # a rotary / RMS / gated-expert model with window and full layers at
    # Mellum2-12B's published widths, one period of its layers
    long_lm = dict(
        hidden=2304, heads=32, kv_heads=4, head_dim=128, experts=64,
        expert_width=896, per_token=8, window=1024, vocab=98304,
        max_len=16768, layer_types=("window",) * 3 + ("full",),
    )
    # a pool the compiler cannot stage whole in fast memory: at 4,096
    # pages (67 MB an array) it prefetches the pool with an async copy
    long_slots, long_pages = 32, 16384

    @classmethod
    def toy(cls):
        s = cls()
        s.rows = 4096
        s.agg_rows, s.groups = 4096, 64
        s.ragged_rows, s.ragged_max_len = 300, 12
        s.lm = dict(d_model=64, n_heads=4, n_layers=2, max_len=128)
        s.vocab = 512
        s.prompt_lens = (4, 16, 40, 64)
        s.new_tokens = 4
        s.fit_batch, s.fit_len = 2, 128
        s.flash_l, s.flash_d = 256, 64
        s.ring_l = 512
        s.layout_lm = dict(d_model=40, n_heads=5, n_layers=2, max_len=64)
        s.layout_vocab, s.layout_pages = 64, 12
        s.long_lm = dict(
            hidden=32, heads=4, kv_heads=2, head_dim=16, experts=8,
            expert_width=16, per_token=2, window=32, vocab=64, max_len=160,
            layer_types=("window",) * 3 + ("full",),
        )
        s.long_slots, s.long_pages = 4, 48
        return s


class Run:
    """Output and bookkeeping: stamped JSON lines, checks, phases."""

    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.stamp = (
            {"platform": "cpu", "rehearsal": True} if rehearsal else {}
        )
        self.failed = []
        self.phase = "gate"
        self.compile_s = 0.0  # jax backend-compile seconds, all programs
        self.cache_hits = 0

    def emit(self, **fields):
        print(json.dumps({**fields, **self.stamp}, default=str), flush=True)

    def check(self, ok, what, **detail):
        ok = bool(ok)
        self.emit(phase=self.phase, check=what, ok=ok, **detail)
        if not ok:
            self.failed.append(f"{self.phase}: {what}")
        return ok

    def run_phase(self, name, fn, *args):
        """Run one phase to its end or to its first exception; either way
        the next phase still runs. The traceback is printed and the phase
        counted as failed — nothing is swallowed."""
        from tensorframes_tpu.obs import programs

        self.phase = name
        before_c, before_d = _registry_seconds(programs)
        compile0, t0 = self.compile_s, time.perf_counter()
        self.emit(phase=name, event="start")
        try:
            fn(self, *args)
        except Exception:
            traceback.print_exc()
            sys.stderr.flush()
            self.failed.append(f"{name}: raised (traceback on stderr)")
        after_c, after_d = _registry_seconds(programs)
        self.emit(
            phase=name, event="end",
            wall_s=round(time.perf_counter() - t0, 3),
            jax_compile_s=round(self.compile_s - compile0, 3),
            registry_compile_s=round(after_c - before_c, 3),
            registry_dispatch_s=round(after_d - before_d, 3),
        )
        gc.collect()


def _registry_seconds(programs):
    recs = programs.programs()
    return (
        sum(r.compile_s or 0.0 for r in recs),
        sum(r.dispatch_s for r in recs),
    )


def _counter_total(name, contains=""):
    from tensorframes_tpu import obs

    values = (obs.registry().snapshot().get(name) or {}).get("values", {})
    return sum(v for k, v in values.items() if contains in k)


def _memory(run, label):
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    run.emit(
        phase=run.phase, memory=label,
        bytes_in_use=[s.get("bytes_in_use") for s in stats],
        peak_bytes_in_use=[s.get("peak_bytes_in_use") for s in stats],
    )
    return [s.get("bytes_in_use") for s in stats]


# ---------------------------------------------------------------------------
# phase 1: the frame plane
# ---------------------------------------------------------------------------


def _scoring_workload(S, rng, rows):
    """BASELINE config 3: ``rows`` x 784 f32 features, the MNIST-LR
    weights, the scoring function and its numpy oracle."""
    import jax.numpy as jnp
    import numpy as np

    from tensorframes_tpu.models import MLPClassifier

    x = rng.standard_normal((rows, S.features), dtype=np.float32)
    clf = MLPClassifier.init(0, [S.features, S.classes])
    w = np.asarray(clf.params[0]["w"])
    b = np.asarray(clf.params[0]["b"])

    def score(features):
        logits = features @ w + b
        return {"prediction": jnp.argmax(logits, axis=-1).astype(jnp.int32)}

    return x, score, np.argmax(x @ w + b, axis=-1)


def phase_frame(run, S):
    import numpy as np

    import tensorframes_tpu as tft
    from tensorframes_tpu import obs
    from tensorframes_tpu.data.packer import native_available

    rng = np.random.default_rng(0)
    x, score, ref = _scoring_workload(S, rng, S.rows)
    run.emit(phase=run.phase, rows=S.rows, bytes=int(x.nbytes))
    df = tft.TensorFrame.from_columns({"features": x}).analyze()
    n_programs = tft.precompile(score, df)
    t0 = time.perf_counter()
    scored = tft.map_blocks(score, df).cache()
    preds = np.asarray(scored.column_data("prediction").host())
    dt = time.perf_counter() - t0
    hist = obs.registry().snapshot()["frame.h2d_seconds"]["values"]
    chunks = sum(v["count"] for v in hist.values())
    run.emit(
        phase=run.phase, precompiled_programs=n_programs,
        first_pass_wall_s=round(dt, 3), h2d_chunks=chunks,
        h2d_bytes=_counter_total("frame.h2d_bytes_total"),
    )
    agree = float((preds == ref).mean())
    run.check(
        preds.shape == (S.rows,) and agree >= SCORING_AGREEMENT,
        "map_blocks predictions agree with numpy", agreement=agree,
    )
    if not run.rehearsal:
        run.check(
            chunks > 1, "the upload crossed as more than one chunk",
            chunks=chunks,
        )
    total = tft.reduce_blocks(
        lambda prediction_input: {"prediction": prediction_input.sum()},
        scored,
    )
    run.check(
        int(total) == int(preds.sum()),
        "reduce_blocks of the predictions equals their numpy sum",
        got=int(total), want=int(preds.sum()),
    )
    del df, scored, x

    # keyed aggregation against a host oracle
    key = rng.integers(0, S.groups, size=S.agg_rows).astype(np.int32)
    v = rng.standard_normal((S.agg_rows, 8), dtype=np.float32)
    gdf = tft.TensorFrame.from_columns({"key": key, "v": v}).analyze()
    res = tft.aggregate(
        lambda v_input: {"v": v_input.sum(axis=0)}, gdf.group_by("key")
    ).cache()
    got_k = np.asarray(res.column_block("key"))
    got_v = np.asarray(res.column_block("v"))
    want = np.zeros((S.groups, 8), np.float64)
    np.add.at(want, key, v.astype(np.float64))
    order = np.argsort(got_k)
    # f32 sums of ~rows/groups N(0,1) terms in a device-chosen order
    run.check(
        np.array_equal(got_k[order], np.unique(key))
        and np.allclose(
            got_v[order], want[np.unique(key)], rtol=1e-3, atol=1e-2
        ),
        "aggregate over %d groups matches the host oracle" % S.groups,
        groups_out=int(got_k.size),
    )

    # ragged map_rows: the native packer's path
    lens = rng.integers(1, S.ragged_max_len + 1, size=S.ragged_rows)
    cells = [rng.standard_normal(int(n)).astype(np.float32) for n in lens]
    rdf = tft.TensorFrame.from_columns({"y": cells}).analyze()
    sums = tft.map_rows(lambda y: {"s": y.sum()}, rdf).cache()
    got = np.asarray(sums.column_data("s").host())
    want = np.asarray([c.sum(dtype=np.float64) for c in cells])
    run.check(
        np.allclose(got, want, rtol=1e-4, atol=1e-4),
        "ragged map_rows matches the host oracle", rows=S.ragged_rows,
    )
    calls = (
        obs.registry().snapshot().get("packer.kernel_calls_total") or {}
    ).get("values", {})
    run.emit(
        phase=run.phase,
        packer="native" if native_available() else "fallback",
        packer_calls=calls,
    )
    _memory(run, "after frame plane")


# ---------------------------------------------------------------------------
# the KV pool's layout: the step programs use the pool's buffer in place
# ---------------------------------------------------------------------------

#: opcodes that only move or retype data; a fusion made of nothing else is
#: a relayout whatever the compiler calls it
_MOVES = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "reshape", "copy", "transpose", "convert", "slice", "broadcast",
))
_INSTR = r"^\s*(?:ROOT )?%?[\w.\-]+ = (\(.*?\)|\S+) ([\w\-]+)\((.*)$"


def _hlo_computations(text):
    import re

    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head and " = " not in line.split("{")[0]:
            cur = comps.setdefault(head.group(2), [])
            if head.group(1):
                comps["ENTRY"] = cur
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            m = re.match(_INSTR, line)
            if m:
                cur.append((m.group(2), m.group(1), m.group(3)))
    return comps


def _elements(shape):
    """Element counts of the arrays an HLO result shape names."""
    import re

    return [
        math.prod(int(d) for d in dims.split(",") if d)
        for dims in re.findall(r"\w+\[([\d,]*)\]", shape)
    ]


def _in_memory(comps):
    """The computations of a parsed module whose instructions leave
    arrays in memory: the entry, every loop body, branch and callee —
    all but the bodies of fusions, whose values never leave the core."""
    import re

    fused = {
        re.search(r"calls=%?([\w.\-]+)", rest).group(1)
        for body in comps.values()
        for opcode, _, rest in body if opcode == "fusion"
    }
    return [
        body for name, body in comps.items()
        if name not in fused and name != "ENTRY"  # the entry's alias
    ]


def hlo_arrays(hlo_text):
    """``(opcode, result shape)`` of every instruction of an optimised
    HLO module that leaves an array in memory (:func:`_in_memory`)."""
    import re

    return [
        (opcode, re.sub(r"\{[^}]*\}", "", shape))
        for body in _in_memory(_hlo_computations(hlo_text))
        for opcode, shape, _ in body
    ]


def pool_relayouts(hlo_text, counts, loops=False):
    """Instructions of an optimised HLO module's entry computation (with
    ``loops``, of every computation but a fusion's body: a walk gathers
    its blocks inside a loop) that only MOVE an array whose element
    count is one of ``counts`` (the whole pool, one layer's slice of it,
    the gathered block): ``copy``, ``transpose``, their asynchronous
    forms, and fusions made of nothing but moves. Returns ``[(opcode,
    result shape), ...]`` — empty when the program reads and writes the
    pool in the layout it was given."""
    import re

    comps = _hlo_computations(hlo_text)
    scope = _in_memory(comps) if loops else [comps.get("ENTRY", ())]
    found = []
    for opcode, shape, rest in (i for body in scope for i in body):
        if opcode == "fusion":
            body = comps.get(
                re.search(r"calls=%?([\w.\-]+)", rest).group(1), ()
            )
            if not all(op in _MOVES for op, _, _ in body):
                continue
        elif opcode not in ("copy", "transpose", "copy-start"):
            continue
        if any(n in counts for n in _elements(shape)):
            found.append((opcode, re.sub(r"\{[^}]*\}", "", shape)))
    return found


def span_attention_leaks(arrays, c, n_kv, group, spans):
    """Of ``arrays`` (:func:`hlo_arrays` of a chunk program over ``c``
    queries of ``n_kv`` K/V heads of ``group`` query heads each, its
    tables walked in blocks of ``spans`` positions), those that show the
    chunk's attention outside the fused fold: a float32 array of ``c x
    heads x span`` elements (a block of scores or probabilities written
    to memory) and online-softmax state with ``[.., c, n_kv, group]``
    dimensions (whose two minor ones the chip pads to a whole (8, 128)
    tile: 32 times the bytes at 4 x 8)."""
    import re

    scores = {c * n_kv * group * span for span in spans}
    state = re.compile(rf"f32\[(?:\d+,)*{c},{n_kv},{group}\]")
    return [
        (opcode, shape) for opcode, shape in arrays
        if opcode not in ("parameter", "get-tuple-element", "tuple")
        and (
            any(
                n in scores
                for n, dt in zip(
                    _elements(shape), re.findall(r"(\w+)\[", shape)
                )
                if dt == "f32"
            )
            or state.search(shape)
        )
    ]


def phase_pool_layout(run, S):
    """Compile the decode and prefill programs at 25 heads of 64 (the
    widths whose (25, 64) minor dimensions once forced a page-minor pool
    and whole-pool copies around every step) and fail if the optimised
    program copies or transposes the pool, a layer of it or the gathered
    block. Eight layers, so that the pool is larger than any fast memory
    the compiler could stage it in whole."""
    from tensorframes_tpu.models import TransformerLM
    from tensorframes_tpu.serve import GenerationEngine

    lm = TransformerLM.init(0, S.layout_vocab, **S.layout_lm)
    eng = GenerationEngine(
        lm, max_slots=S.max_slots, page_size=S.page_size,
        num_pages=S.layout_pages, attention_impl="gather",
    )
    pool, decode_args, prefill_args = _step_specs(eng)
    whole = math.prod(pool.shape)
    layer = whole // pool.shape[0]
    block = eng.max_slots * eng._max_pages * math.prod(pool.shape[2:])
    programs = {
        "jit_decode": (eng._decode_jit, (whole, layer, block), decode_args),
        "jit_prefill": (eng._prefill_jit, (whole, layer), prefill_args),
    }
    for name, (fn, counts, args) in programs.items():
        compiled = fn.lower(eng._params_dev, pool, pool, *args).compile()
        mem = compiled.memory_analysis()
        moved = pool_relayouts(compiled.as_text(), counts)
        run.emit(
            phase=run.phase, program=name, pool=list(pool.shape),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
            relayouts=moved[:8],
        )
        if not run.rehearsal:
            # XLA:CPU lays arrays out by other rules; the property is
            # the chip's
            run.check(
                not moved,
                f"{name} neither copies nor transposes the pool, a layer "
                f"of it or the gathered block",
                found=len(moved),
            )
            run.check(
                mem.alias_size_in_bytes >= 2 * whole * pool.dtype.itemsize,
                f"{name} updates both pool arrays in place (donated)",
            )


def _described_model(m):
    """A params tree with a model description (zeros: the phase compiles,
    it does not run): rotary, RMSNorm, SiLU-gated experts through the
    grouped product, window and full layers."""
    import jax.numpy as jnp

    d, e, f = m["hidden"], m["experts"], m["expert_width"]
    q, kv = m["heads"] * m["head_dim"], m["kv_heads"] * m["head_dim"]
    z = lambda *shape: jnp.zeros(shape, jnp.bfloat16)
    gain = lambda: {"g": jnp.ones((d,), jnp.bfloat16)}
    return {
        "embed": z(m["vocab"], d), "head": z(d, m["vocab"]),
        "ln_f": gain(),
        "blocks": [
            {
                "ln1": gain(), "qkv": z(d, q + 2 * kv), "proj": z(q, d),
                "ln2": gain(),
                "moe": {
                    "router": z(d, e), "w_gate": z(e, d, f),
                    "w_up": z(e, d, f), "w_down": z(e, f, d),
                },
            }
            for _ in m["layer_types"]
        ],
        "spec": {
            "n_heads": m["heads"], "n_kv_heads": m["kv_heads"],
            "head_dim": m["head_dim"], "max_len": m["max_len"],
            "norm": "rms", "norm_eps": 1e-6, "position": "rotary",
            "layer_types": list(m["layer_types"]), "window": m["window"],
            "rope": {
                "window": {"theta": 500000.0},
                "full": {
                    "theta": 500000.0, "kind": "yarn", "factor": 16.0,
                    "original_max_position": 8192,
                    "attention_factor": 1.2772588722239782,
                },
            },
            "mlp": "gated_experts", "n_experts": e,
            "experts_per_token": m["per_token"], "tied_head": False,
            "residual_dtype": "float32",
        },
    }


def phase_pool_layout_long(run, S):
    """The same guard for a long-sequence model: the decode and the
    prefill-chunk program of a rotary / RMS / gated-expert model with
    window and full layers, at Mellum2-12B's published widths and one
    period of its layers, must compile for the chip and hold no copy or
    transpose of the pool or of a row of it (a pool page is as deep as
    the cache kinds' common divisor, ``serve/kv_pages.py``)."""
    from tensorframes_tpu.ops.attention import live_read_blocks
    from tensorframes_tpu.serve import GenerationEngine
    from tensorframes_tpu.serve.kv_pages import SequencePages

    import jax
    import numpy as np

    eng = GenerationEngine(
        _described_model(S.long_lm), max_slots=S.long_slots,
        page_size=S.page_size, num_pages=S.long_pages,
    )
    run.check(
        eng._long and eng.layout is not None
        and [k.name for k in eng.layout.kinds] == ["full", "window"],
        "a model with window layers gets two cache kinds, chunked "
        "prefill and the live-bounded read by itself",
        chunk=eng.prefill_chunk_tokens,
    )
    spec = lambda a: jax.ShapeDtypeStruct(
        np.shape(a), a.dtype, sharding=getattr(a, "sharding", None)
    )
    pool = spec(eng.pool.k)
    whole = math.prod(pool.shape)
    programs = {
        "jit_decode": (eng._decode_jit, eng._decode_args([])),
        "jit_chunk_step": (
            eng._prefill_chunk_jit,
            eng._chunk_args(
                np.zeros(1, np.int32), 0, 1, 1,
                SequencePages(eng.pool, eng.layout), 0.0, 0, 1.0,
            ),
        ),
    }
    # a chunk's walks: the blocks each cache kind's table is gathered in
    c, m = eng._chunk_c, S.long_lm
    spans = sorted({
        live_read_blocks(width)[1] * S.page_size
        for width in eng._kind_widths(c).values()
    })
    for name, (fn, args) in programs.items():
        args = jax.tree.map(lambda a: spec(np.asarray(a)), args)
        compiled = fn.lower(eng._params_dev, pool, pool, *args).compile()
        mem = compiled.memory_analysis()
        text = compiled.as_text()
        moved = pool_relayouts(text, (whole, whole // pool.shape[0]))
        leaks = []
        if name == "jit_chunk_step":
            # the walks gather inside loops: a gathered block moved there
            moved += pool_relayouts(
                text, [span * pool.shape[-1] for span in spans], loops=True
            )
            leaks = span_attention_leaks(
                hlo_arrays(text), c, m["kv_heads"],
                m["heads"] // m["kv_heads"], spans,
            )
        run.emit(
            phase=run.phase, program=name, pool=list(pool.shape),
            temp_bytes=getattr(mem, "temp_size_in_bytes", None),
            alias_bytes=getattr(mem, "alias_size_in_bytes", None),
            relayouts=moved[:8], attention_leaks=leaks[:8],
        )
        if not run.rehearsal:
            run.check(
                not moved,
                f"{name} (long) neither copies nor transposes the pool, "
                f"a row of it or a gathered block",
                found=len(moved),
            )
            run.check(
                not leaks,
                f"{name} (long) keeps a chunk's score blocks and softmax "
                f"state on the chip",
                found=len(leaks),
            )
            run.check(
                mem.alias_size_in_bytes >= 2 * whole * pool.dtype.itemsize,
                f"{name} (long) updates both pool arrays in place",
            )


# ---------------------------------------------------------------------------
# phases 2-3a: the serving plane
# ---------------------------------------------------------------------------


def _requests(S):
    """Eight requests — each prompt length once greedy and once seeded —
    plus the first greedy and the first sampled one again."""
    import numpy as np

    rng = np.random.default_rng(1)
    reqs = []
    for i, n in enumerate(S.prompt_lens):
        prompt = rng.integers(0, S.vocab, size=n).tolist()
        reqs.append(dict(prompt=prompt, max_new_tokens=S.new_tokens))
        reqs.append(
            dict(
                prompt=rng.integers(0, S.vocab, size=n).tolist(),
                max_new_tokens=S.new_tokens, temperature=0.8,
                seed=100 + i,
            )
        )
    return reqs, [0, 1]  # indices submitted twice


def _http(addr, method, path, body=None):
    import http.client

    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=600)
    try:
        payload = None if body is None else json.dumps(body).encode()
        conn.request(method, path, body=payload)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def _serve_over_http(run, engine, S, label):
    """Start a ScoringServer on ``engine``, POST the request set over the
    socket (concurrently, so slots batch), scrape, stop. Returns the token
    lists in request order (None where a request failed)."""
    from tensorframes_tpu.interop.serving import ScoringServer

    reqs, twice = _requests(S)
    order = list(range(len(reqs))) + twice
    server = ScoringServer(engine=engine)
    server.start()
    try:
        addr = server.address
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=len(order)) as pool:
            answers = list(
                pool.map(
                    lambda i: _http(addr, "POST", "/generate", reqs[i]),
                    order,
                )
            )
        wall = time.perf_counter() - t0
        m_status, metrics = _http(addr, "GET", "/metrics")
        h_status, health = _http(addr, "GET", "/healthz")
    finally:
        server.stop()
    tokens = []
    for status, raw in answers:
        tokens.append(json.loads(raw)["tokens"] if status == 200 else None)
    run.emit(
        phase=run.phase, engine=label, requests=len(order),
        wall_s=round(wall, 3),
    )
    run.check(
        all(
            t is not None
            and len(t) == S.new_tokens
            and all(0 <= tok < S.vocab for tok in t)
            for t in tokens
        ),
        f"{label}: every response holds {S.new_tokens} in-range tokens",
        statuses=[s for s, _ in answers],
    )
    n = len(reqs)
    run.check(
        all(tokens[n + j] == tokens[i] for j, i in enumerate(twice)),
        f"{label}: repeated requests are identical to themselves",
    )
    run.check(
        m_status == 200 and b"tft_serve_" in metrics,
        f"{label}: /metrics carries the tft_serve_* series",
    )
    run.check(
        h_status == 200, f"{label}: /healthz answers 200",
        body=health[:200].decode(errors="replace"),
    )
    return tokens[:n]


def _step_specs(eng):
    """Shapes alone: the pool, and what the decode and the prefill
    program take after it."""
    import jax
    import jax.numpy as jnp

    s, mp = eng.max_slots, eng._max_pages
    spec = jax.ShapeDtypeStruct
    pool = spec(
        eng.pool.k.shape, eng.pool.k.dtype, sharding=eng.pool.k.sharding
    )
    decode = (
        spec((s,), jnp.int32), spec((s,), jnp.int32),
        spec((s, mp), jnp.int32), spec((s,), jnp.float32),
        spec((s,), jnp.int32), spec((s,), jnp.float32),
    )
    prefill = (
        spec((1, eng.max_seq_len), jnp.int32), spec((), jnp.int32),
        spec((mp,), jnp.int32), spec((), jnp.float32),
        spec((), jnp.int32), spec((), jnp.float32),
    )
    return pool, decode, prefill


def _engine_checks(run, eng, label):
    """The checks that read one engine after it served."""
    from tensorframes_tpu.obs import programs

    run.check(
        eng.num_step_programs <= 2,
        f"{label}: at most two compiled step programs",
        programs=eng.num_step_programs,
    )
    run.check(
        eng.pool.pages_in_use == 0, f"{label}: pages_in_use back to 0",
        pages_in_use=eng.pool.pages_in_use,
    )
    recs = [
        r for r in programs.programs()
        if r.name.endswith(f"[{eng.name}]") and r.invocations
    ]
    run.check(
        len(recs) >= 2
        and all(r.flops and r.bytes_accessed for r in recs),
        f"{label}: the step programs' registry records hold costs",
        records={
            r.name: dict(
                flops=r.flops, bytes=r.bytes_accessed,
                source=r.cost_source, compile_s=r.compile_s,
                dispatches=r.dispatches,
                dispatch_s=round(r.dispatch_s, 4),
            )
            for r in recs
        },
    )
    # the decode program as the engine builds it, lowered on shapes alone
    pool, decode_args, _ = _step_specs(eng)
    text = eng._decode_jit.lower(
        eng._params_dev, pool, pool, *decode_args
    ).as_text()
    run.check(
        "tf.aliasing_output" in text or "jax.buffer_donor" in text,
        f"{label}: the decode program donates the KV pool",
    )
    return text


def _compare_streams(run, what, got, want, kinds):
    """Report how many streams match, by kind; a mismatch is reported,
    not failed — the caller decides what a mismatch may be."""
    same = [g == w for g, w in zip(got, want)]
    run.emit(
        phase=run.phase, compare=what,
        match=f"{sum(same)}/{len(same)}",
        greedy=f"{sum(s for s, k in zip(same, kinds) if k == 'greedy')}"
        f"/{kinds.count('greedy')}",
        sampled=f"{sum(s for s, k in zip(same, kinds) if k == 'sampled')}"
        f"/{kinds.count('sampled')}",
    )
    return same


def phase_serve(run, S, state):
    import jax
    import numpy as np

    from tensorframes_tpu.models import TransformerLM
    from tensorframes_tpu.models.transformer import transformer_logits
    from tensorframes_tpu.serve import GenerationEngine

    t0 = time.perf_counter()
    lm = TransformerLM.init(0, S.vocab, **S.lm)
    n_params = sum(
        int(np.size(a)) for a in jax.tree.leaves(lm.params)
        if hasattr(a, "shape")
    )
    run.emit(
        phase=run.phase, model=S.lm, vocab=S.vocab, parameters=n_params,
        init_s=round(time.perf_counter() - t0, 2),
    )
    state["lm"] = lm
    eng = GenerationEngine(
        lm, max_slots=S.max_slots, page_size=S.page_size,
        attention_impl="gather",
    )
    served = _serve_over_http(run, eng, S, "gather engine")
    _engine_checks(run, eng, "gather engine")
    _memory(run, "gather engine resident")
    del eng
    gc.collect()
    state["served"] = served
    if any(t is None for t in served):
        return  # already failed above; nothing to compare
    reqs, _ = _requests(S)
    kinds = ["sampled" if "seed" in r else "greedy" for r in reqs]
    state["kinds"] = kinds

    # solo decode: the repo's own oracle for a served stream
    solo = []
    for r in reqs:
        kw = {k: r[k] for k in ("temperature", "seed") if k in r}
        out = lm.generate(
            np.asarray([r["prompt"]], np.int32), S.new_tokens, **kw
        )
        solo.append(np.asarray(out)[0, len(r["prompt"]):].tolist())
    same = _compare_streams(run, "served vs solo lm.generate", served, solo, kinds)

    # the reference logits of any prefix, from ONE compiled program: causal
    # attention makes row i of a zero-padded sequence the logits after
    # tokens[:i+1]
    static = lm.params["n_heads"]
    dev = jax.device_put(
        {k: v for k, v in lm.params.items() if k != "n_heads"}
    )
    width = max(S.prompt_lens) + S.new_tokens

    @jax.jit
    def logits_row(p, toks, i):
        return transformer_logits({**p, "n_heads": static}, toks)[0, i]

    def ref_logits(prefix):
        toks = np.zeros((1, width), np.int32)
        toks[0, : len(prefix)] = prefix
        return np.asarray(logits_row(dev, toks, len(prefix) - 1))

    worst = 0.0
    for r, got, want, ok, kind in zip(reqs, served, solo, same, kinds):
        if kind != "greedy":
            continue  # a sampled pick has no margin to read; k/n above
        # first generated position against the full-sequence forward, and
        # the first position where the served stream left the solo one
        spots = [(0, got[0], None)]
        if not ok:
            j = next(i for i in range(len(got)) if got[i] != want[i])
            spots.append((j, got[j], want[j]))
        for j, tok, other in spots:
            ref = ref_logits(r["prompt"] + got[:j])
            scale = float(np.abs(ref).max())
            picks = [tok] if other is None else [tok, other]
            gap = max(float(ref.max() - ref[t]) for t in picks) / scale
            worst = max(worst, gap)
            run.check(
                gap <= LOGIT_TOL_FRACTION,
                "served token within tolerance of the reference argmax",
                prompt_len=len(r["prompt"]), position=j,
                gap_fraction=round(gap, 5), tolerance=LOGIT_TOL_FRACTION,
            )
    run.emit(phase=run.phase, worst_logit_gap_fraction=round(worst, 6))


def phase_kernels(run, S, state):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tensorframes_tpu.models import TransformerLM
    from tensorframes_tpu.ops import (
        attention_reference,
        flash_attention,
        paged_attention,
        ragged_paged_attention,
    )
    from tensorframes_tpu.serve import GenerationEngine

    lm = state["lm"]
    # (a) the fused ragged paged-attention read, in the engine and alone
    eng = GenerationEngine(
        lm, max_slots=S.max_slots, page_size=S.page_size,
        attention_impl="fused",
    )
    fused = _serve_over_http(run, eng, S, "fused engine")
    text = _engine_checks(run, eng, "fused engine")
    if not run.rehearsal:
        run.check(
            "tpu_custom_call" in text,
            "the fused decode program holds a tpu_custom_call",
        )
    del eng
    gc.collect()
    if state.get("served") and all(t is not None for t in fused):
        _compare_streams(
            run, "fused engine vs gather engine", fused, state["served"],
            state["kinds"],
        )
    state["fused"] = fused

    hd = S.lm["d_model"] // S.lm["n_heads"]
    n_kv, ps = S.lm["n_heads"], S.page_size
    max_pages = S.lm["max_len"] // ps
    rng = np.random.default_rng(2)
    pages = S.max_slots * max_pages
    q = jnp.asarray(rng.standard_normal((S.max_slots, n_kv, 1, hd)), jnp.float32)
    # the pool's layout: heads merged into the lane axis (serve/kv_pages.py)
    kp = jnp.asarray(rng.standard_normal((pages + 1, ps, n_kv * hd)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pages + 1, ps, n_kv * hd)), jnp.float32)
    table = rng.permutation(pages).reshape(S.max_slots, max_pages).astype(np.int32)
    lengths = rng.integers(1, S.lm["max_len"] + 1, size=S.max_slots).astype(np.int32)
    lengths[0], lengths[-1] = 1, S.lm["max_len"]
    want = np.asarray(paged_attention(q, kp, vp, table, lengths))
    got = np.asarray(ragged_paged_attention(q, kp, vp, table, lengths))
    err = float(np.abs(got - want).max())
    # unit-variance q/k/v: the gather's einsums and the kernel's dots each
    # round products to bf16 (default precision), ~2e-3 on outputs of
    # magnitude <= 1; 2e-2 leaves an order of magnitude
    run.check(
        np.isfinite(got).all() and err <= 2e-2,
        "ragged_paged_attention agrees with paged_attention",
        max_abs_err=err, tolerance=2e-2,
        geometry=dict(n_kv=n_kv, group=1, hd=hd, page_size=ps),
    )

    # (b) three training steps through the flash kernels' custom VJP
    trainee = TransformerLM(dict(lm.params))
    tokens = rng.integers(0, S.vocab, size=(S.fit_batch, S.fit_len + 1))
    t0 = time.perf_counter()
    losses = [
        float(v)
        for v in trainee.fit(tokens, steps=S.fit_steps, attn_impl="flash")
    ]
    uniform = math.log(S.vocab)
    run.check(
        len(losses) == S.fit_steps
        and all(math.isfinite(v) for v in losses)
        and abs(losses[0] - uniform) <= 0.5,
        "flash training steps: finite losses, the first near ln(vocab)",
        losses=[round(v, 4) for v in losses], ln_vocab=round(uniform, 4),
        wall_s=round(time.perf_counter() - t0, 2),
    )
    del trainee
    gc.collect()

    # (c) flash forward + grad against the dense reference, kernel math in
    # true f32. bf16 bounds are tests/test_attention.py's (bf16 storage
    # of outputs and grads); f32 bounds are its CPU ones loosened 100x for
    # the chip's transcendental units and accumulation order. The f32 call
    # pins 512x512 tiles: under fp32 contract precision the default
    # 1024-wide f32 backward tiles overrun the 16 MB scoped VMEM (they fit
    # at default precision, which (b) just ran).
    shape = (1, S.flash_heads, S.flash_l, S.flash_d)
    for dtype, tiles, fwd_tol, grad_tol in (
        (jnp.bfloat16, {}, dict(rtol=5e-2, atol=5e-2), dict(rtol=0.1, atol=0.15)),
        (jnp.float32, dict(block_q=512, block_k=512),
         dict(rtol=2e-3, atol=2e-3), dict(rtol=2e-2, atol=2e-2)),
    ):
        qkv = [
            jnp.asarray(rng.standard_normal(shape), dtype) for _ in range(3)
        ]
        cot = jnp.asarray(rng.standard_normal(shape), jnp.float32)

        def flash_loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, **tiles)
            return (out.astype(jnp.float32) * cot).sum()

        def ref_loss(q, k, v):
            out = attention_reference(q, k, v, causal=True)
            return (out.astype(jnp.float32) * cot).sum()

        with jax.default_matmul_precision("float32"):
            out = flash_attention(*qkv, causal=True, **tiles)
            ref = attention_reference(*qkv, causal=True)
            g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(*qkv)
            g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(*qkv)
        f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
        fwd_err = float(np.abs(f32(out) - f32(ref)).max())
        grad_err = max(
            float(np.abs(f32(a) - f32(b)).max())
            for a, b in zip(g_flash, g_ref)
        )
        run.check(
            np.allclose(f32(out), f32(ref), **fwd_tol)
            and all(
                np.allclose(f32(a), f32(b), **grad_tol)
                for a, b in zip(g_flash, g_ref)
            ),
            f"flash_attention forward + grad vs reference, {dtype.__name__}",
            shape=shape, fwd_max_abs_err=fwd_err,
            grad_max_abs_err=grad_err, tiles=tiles or "table default",
        )
    _memory(run, "after kernels")


# ---------------------------------------------------------------------------
# phase 4: four chips — four sub-phases, each run (and failed) on its own
# ---------------------------------------------------------------------------


def _spread(run, label):
    """Print every device's bytes in use; at least four must hold state."""
    used = _memory(run, f"{label} resident")
    run.check(
        all(b is None for b in used) or sum(1 for b in used if b) >= 4,
        f"{label}: four devices hold state", bytes_in_use=used,
    )


def phase_dp(run, S):
    """One row shard per chip: phase 1's scoring pass over a dp mesh."""
    import numpy as np

    import tensorframes_tpu as tft
    import tensorframes_tpu.parallel as par

    mesh = par.make_mesh()
    n_dev = mesh.devices.size
    x, score, ref = _scoring_workload(
        S, np.random.default_rng(3), S.rows - S.rows % n_dev
    )
    df = tft.TensorFrame.from_columns({"features": x}).analyze()
    scored = par.map_blocks(score, df, mesh=mesh)
    preds = np.asarray(scored.column_data("prediction").host())
    # the single-process dp engine gathers map outputs to the host; what
    # stays on the chips is the input column, memoized as one row shard
    # per device
    (feed,) = df.column_data("features")._sharded_cache.values()
    homes = {s.device for s in feed.addressable_shards}
    run.check(
        len(homes) == n_dev,
        "par.map_blocks ran on one row shard per device",
        devices=len(homes), mesh=dict(mesh.shape),
    )
    _spread(run, "dp frame")
    agree = float((preds == ref).mean())
    total = par.reduce_blocks(
        lambda prediction_input: {"prediction": prediction_input.sum()},
        scored, mesh=mesh,
    )
    run.check(
        agree >= SCORING_AGREEMENT and int(total) == int(preds.sum()),
        "par.map_blocks / par.reduce_blocks match numpy",
        agreement=agree, got=int(total), want=int(preds.sum()),
    )


def _one_chip_streams(state):
    lm, served = state.get("lm"), state.get("served")
    if lm is None or not served or any(t is None for t in served):
        raise RuntimeError("the one-chip serving phase left no streams")
    return lm, served


def phase_tp(run, S, state):
    """One engine over four chips, same bytes as the one-chip engine."""
    import tensorframes_tpu.parallel as par
    from tensorframes_tpu.serve import GenerationEngine

    lm, served = _one_chip_streams(state)
    eng = GenerationEngine(
        lm, max_slots=S.max_slots, page_size=S.page_size,
        attention_impl="gather", mesh=par.make_mesh({"tp": 4}),
    )
    tp = _serve_over_http(run, eng, S, "tp=4 engine")
    _engine_checks(run, eng, "tp=4 engine")
    _spread(run, "tp=4 engine")
    run.check(
        tp == served, "tp=4 engine emits the one-chip engine's bytes",
        match=f"{sum(a == b for a, b in zip(tp, served))}/{len(served)}",
    )


def phase_fleet(run, S, state):
    """Four replicas: replica i on chip i mod n."""
    from tensorframes_tpu.serve import Fleet

    lm, served = _one_chip_streams(state)
    fleet = Fleet(
        lm, replicas=4, max_slots=S.max_slots, page_size=S.page_size,
        attention_impl="gather",
    )
    got = _serve_over_http(run, fleet, S, "fleet of 4")
    _spread(run, "fleet of 4")
    run.check(
        all(e.pool.pages_in_use == 0 for e in fleet.engines)
        and all(n <= 2 for n in fleet.program_counts().values()),
        "fleet of 4: pages back to 0, at most two programs per replica",
        programs=fleet.program_counts(),
    )
    if all(t is not None for t in got):
        _compare_streams(
            run, "fleet of 4 vs one-chip engine", got, served,
            state["kinds"],
        )


def phase_ring(run, S):
    """Ring attention across the chips, forward and grad."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import tensorframes_tpu.parallel as par
    from tensorframes_tpu.ops import attention_reference
    from tensorframes_tpu.ops.ring import ring_attention

    rng = np.random.default_rng(4)
    sp = par.make_mesh({"sp": 4})
    shape = (1, S.ring_heads, S.ring_l, S.ring_d)
    qkv = [
        jnp.asarray(rng.standard_normal(shape), jnp.float32)
        for _ in range(3)
    ]
    cot = jnp.asarray(rng.standard_normal(shape), jnp.float32)

    def ring_loss(q, k, v):
        return (ring_attention(q, k, v, mesh=sp, causal=True) * cot).sum()

    def ref_loss(q, k, v):
        return (attention_reference(q, k, v, causal=True) * cot).sum()

    out = ring_attention(*qkv, mesh=sp, causal=True)
    _spread(run, "ring attention")
    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(*qkv)
    ref = attention_reference(*qkv, causal=True)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(*qkv)
    fwd_err = float(np.abs(np.asarray(out) - np.asarray(ref)).max())
    grad_err = max(
        float(np.abs(np.asarray(a) - np.asarray(b)).max())
        for a, b in zip(g_ring, g_ref)
    )
    # default precision on both sides: bf16-pass products, unit-variance
    # inputs, outputs of magnitude <= 1 and grads of a few units
    run.check(
        fwd_err <= 3e-2 and grad_err <= 1e-1,
        "ring_attention forward + grad vs reference",
        shape=shape, fwd_max_abs_err=fwd_err, grad_max_abs_err=grad_err,
    )


# ---------------------------------------------------------------------------
# phase 5: README flow 1, float64 — last, it flips jax_enable_x64
# ---------------------------------------------------------------------------


def phase_float64(run, S):
    import jax
    import numpy as np

    import tensorframes_tpu as tft

    run.emit(phase=run.phase, x64_before=bool(jax.config.jax_enable_x64))
    df = tft.TensorFrame.from_rows([dict(x=float(x)) for x in range(10)])
    with tft.graph():
        x = tft.block(df, "x")
        df2 = tft.map_blocks((x + 3).named("z"), df)
    rows = df2.collect()
    z = np.asarray([r.z for r in rows])
    run.check(
        z.dtype == np.float64
        and np.array_equal(z, np.arange(10, dtype=np.float64) + 3.0),
        "add 3 on a float64 column", z=z.tolist(),
        x64_after=bool(jax.config.jax_enable_x64),
    )


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="toy sizes on the CPU backend, kernels interpreted",
    )
    args = ap.parse_args(argv)
    run = Run(args.rehearse_cpu)
    os.makedirs(OUT_DIR, exist_ok=True)
    os.environ.setdefault("TFT_DEBUG_DIR", os.path.join(OUT_DIR, "debug"))
    if run.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()

    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    run.emit(phase="gate", jax=jax.__version__, device=device)
    if device["platform"] != ("cpu" if run.rehearsal else "tpu"):
        print(
            f"chip_smoke: jax found platform {device['platform']!r} "
            f"({device['kind']}, {device['count']} device(s)); this script "
            f"needs a TPU (or --rehearse-cpu for the CPU rehearsal)",
            file=sys.stderr,
        )
        return 2

    from tensorframes_tpu.obs import programs

    peaks = {
        "flops": programs.peak_flops(),
        "bytes_per_s": programs.peak_bytes_per_s(),
    }
    run.emit(phase="gate", peaks=peaks)
    if not run.rehearsal and not all(peaks.values()):
        print(
            f"chip_smoke: no peak on record for {device['kind']!r}",
            file=sys.stderr,
        )
        return 2

    import jax.monitoring

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            run.compile_s += seconds

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            run.cache_hits += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    import tensorframes_tpu as tft

    cache_dir = tft.enable_compilation_cache()

    def cache_entries():
        if not cache_dir or not os.path.isdir(cache_dir):
            return 0
        return sum(f.endswith("-cache") for f in os.listdir(cache_dir))

    entries_before = cache_entries()
    run.emit(
        phase="gate", compile_cache=cache_dir, entries=entries_before,
        placed_by_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
    )

    S = Sizes.toy() if run.rehearsal else Sizes()
    state = {}
    t0 = time.perf_counter()
    run.run_phase("frame", phase_frame, S)
    run.run_phase("serve", phase_serve, S, state)
    run.run_phase("kernels", phase_kernels, S, state)
    run.run_phase("pool_layout", phase_pool_layout, S)
    run.run_phase("pool_layout.long", phase_pool_layout_long, S)
    if len(jax.devices()) >= 4:
        run.run_phase("four_chips.dp", phase_dp, S)
        run.run_phase("four_chips.tp", phase_tp, S, state)
        run.run_phase("four_chips.fleet", phase_fleet, S, state)
        run.run_phase("four_chips.ring", phase_ring, S)
    else:
        run.emit(phase="four_chips", skipped="needs 4 chips")
    run.run_phase("float64", phase_float64, S)

    # closing ledger
    run.phase = "ledger"
    run.emit(
        phase="ledger", wall_s=round(time.perf_counter() - t0, 2),
        jax_compile_s=round(run.compile_s, 2),
        compile_cache=cache_dir, entries_before=entries_before,
        entries_after=cache_entries(), cache_hits=run.cache_hits,
    )
    run.check(
        _counter_total("failures.retries_total") == 0
        and _counter_total("failures.oom_splits_total") == 0
        and _counter_total("failures.preemptions_total", "op=serve") == 0,
        "no retries, OOM splits or serve preemptions",
        retries=_counter_total("failures.retries_total"),
        oom_splits=_counter_total("failures.oom_splits_total"),
        serve_preemptions=_counter_total(
            "failures.preemptions_total", "op=serve"
        ),
    )
    _memory(run, "at exit")
    ok = not run.failed
    if not ok:
        run.emit(phase="ledger", failed=run.failed)
    print(
        json.dumps({"ok": ok, "device": device, **run.stamp}), flush=True
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
