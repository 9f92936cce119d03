"""Driver ``serve``: a language model behind ``POST /generate`` on
``ScoringServer(engine=GenerationEngine(...))``, loaded by a child
process, open loop (requests sent when due) or closed loop (each client's
next request when the last one answered), as the cell's traffic says.

The process that holds the chip runs the server and the engine; the
child (``chipbench/loadgen.py``) never imports jax. Requests stream, the
child stamps every token, and all times are on the system-wide monotonic
clock. After the window the child's records give the end-to-end metrics,
and a sample of the finished requests, drawn from the seed with the
longest among them, is run through the plain reference: the number
compared is the widest gap by which a served token's reference logit
lies below the reference's best at its position.
"""

import gc
import json
import os
import subprocess
import sys
import time

import numpy as np

from chipbench import traffic
from chipbench.stats import percentile

LOADGEN = os.path.join(os.path.dirname(os.path.dirname(__file__)), "loadgen.py")


def _sleep_until(t):
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def _warm_up(addr, vocab):
    """One short request through the socket: the prefill program has one
    shape (the prompt row is padded to the positional table), the decode
    program one (all slots), so this compiles or loads both."""
    from chipbench import loadgen

    req = {"id": -1, "body": {
        "prompt": list(range(1, 18)), "max_new_tokens": 3, "stream": True,
    }}
    rec = loadgen.new_record(req, time.monotonic())
    loadgen.send(addr, req, rec)
    if rec["error"] is not None or len(rec["tokens"]) != 3:
        raise RuntimeError(f"warm-up request failed: {rec}")
    if not all(0 <= t < vocab for t in rec["tokens"]):
        raise RuntimeError(f"warm-up tokens out of range: {rec['tokens']}")


def _ok(rec):
    return (
        rec["status"] == 200 and rec["error"] is None
        and rec["done"] is not None
        and len(rec["tokens"]) == rec["max_new_tokens"]
    )


def run(run):
    from tensorframes_tpu.interop.serving import ScoringServer
    from tensorframes_tpu.serve import GenerationEngine

    cfg, geo, spec = run.config, run.config["engine"], run.cell["traffic"]
    model = run.model
    params = model.init_params(run.seed, cfg, run.config["precision"]["parameters"])
    memory = {"after_weights": run.memory_in_use()}
    engine = GenerationEngine(
        params, max_slots=geo["max_slots"], page_size=geo["page_size"],
        num_pages=geo["num_pages"], max_seq_len=cfg["n_positions"],
        queue_capacity=geo["queue_capacity"],
    )
    memory["after_engine"] = run.memory_in_use()
    run.mark("weights_and_engine")
    server = ScoringServer(engine=engine, max_connections=geo["max_connections"])
    server.start()
    facts = {"window_s": run.seconds, "chips": run.cell["chips"], "peaks": run.peaks}
    try:
        addr = server.address
        plan = traffic.generate(spec, run.seed, run.seconds, cfg["vocab_size"])
        bodies = {r["id"]: r["body"] for r in plan["requests"]}
        _warm_up(addr, cfg["vocab_size"])
        run.mark("warm_up")
        child = subprocess.Popen(
            [sys.executable, LOADGEN], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
        )
        try:
            t0 = time.monotonic() + 1.0
            plan.update(address=addr, t0=t0)
            child.stdin.write(json.dumps(plan).encode())
            child.stdin.close()
            t_open = t0 + plan["ramp_s"]
            t_close = t_open + run.seconds
            _sleep_until(t_open)
            compiles0, table0 = run.compile_count(), run.program_table()
            preempt0 = run.counter_total("failures.preemptions_total")
            if run.trace:
                _sleep_until(t_open + 0.25 * run.seconds)
                with run.capture(facts):
                    time.sleep(min(float(run.cell["trace_s"]), 0.5 * run.seconds))
            _sleep_until(t_close)
            window_compiles = run.compile_count() - compiles0
            table1 = run.program_table()
            preemptions = run.counter_total("failures.preemptions_total") - preempt0
            raw = child.stdout.read()
            child.wait(timeout=30)
        finally:
            if child.poll() is None:
                child.kill()
                child.wait()
        memory_peak = run.memory_peak()
    finally:
        server.stop()
        engine.stop()
    records = json.loads(raw)["records"]
    setup_s = t_open - run.started

    # -- the window's requests and tokens -------------------------------
    in_window = lambda t: t is not None and t_open <= t < t_close
    if plan["loop"] == "open":
        measured = [r for r in records if in_window(r["due"])]
    else:
        measured = [r for r in records if in_window(r["sent"])]
    failed = [r for r in measured if not _ok(r)]
    run_end = max([t_close] + [r["end"] or t_close for r in records])
    ttft = [
        ((r["times"][0] if r["times"] else run_end) - r["due"]) * 1e3
        for r in measured
    ]
    gaps, out_tokens, prompt_tokens, flops = [], 0, 0, 0
    contexts = {"decode": [], "prefill": []}
    tw = facts.get("trace_window", (0.0, 0.0))
    for r in records:
        n = r["prompt_len"]
        for k, t in enumerate(r["times"]):
            if not in_window(t):
                continue
            out_tokens += 1
            if k == 0:
                prompt_tokens += n
                flops += model.sequence_flops(cfg, 0, n, 1)
            else:
                gaps.append((t - r["times"][k - 1]) * 1e3)
                flops += model.sequence_flops(cfg, n + k - 1, n + k, 1)
            if tw[0] <= t < tw[1]:
                if k == 0:
                    contexts["prefill"].append(n)
                else:
                    contexts["decode"].append(n + k)
    end_to_end = {
        "setup_s": setup_s,
        "itl_p99_ms": percentile(gaps, 99) if gaps else None,
        "tokens_per_s": (prompt_tokens + out_tokens) / run.seconds,
    }
    late = [(r["sent"] - r["due"]) * 1e3 for r in measured if r["sent"]]
    facts.update(
        measured=measured, ttft_ms=ttft, registry={"before": table0, "after": table1},
        needed_flops=flops,
    )
    if run.peaks and facts.get("trace"):
        facts["least_s"] = _least_times(model, cfg, run.peaks, facts["trace"], contexts)

    # -- the comparison, once the engine's state is freed ----------------
    pages_left = engine.pool.pages_in_use
    del engine, server
    gc.collect()
    good = [r for r in measured if _ok(r)]
    rng = np.random.default_rng(run.seed)
    n_check = min(int(run.cell["check"]["requests"]), len(good))
    sample = []
    if good:
        longest = max(good, key=lambda r: r["prompt_len"] + len(r["tokens"]))
        others = [r for r in good if r is not longest]
        picks = rng.choice(len(others), size=max(0, n_check - 1), replace=False)
        sample = [longest] + [others[i] for i in picks]
    t_ref = time.monotonic()
    compared = _compare(model, params, cfg, bodies, sample, run.args.control)
    reference_s = time.monotonic() - t_ref
    served = compared.get("served", {"widest": 0.0, "mean": 0.0})
    limits = run.config["limits"]
    checks = {
        "logit_gap": (served["widest"], limits["logit_gap"]),
        "logit_gap_mean": (served["mean"], limits["logit_gap_mean"]),
        "requests_failed": (len(failed), 0),
        "requests_not_compared": (n_check - len(sample) + (0 if good else 1), 0),
        "window_compiles": (int(window_compiles), 0),
    }
    notes = {
        "requests_measured": len(measured), "requests_compared": len(sample),
        "compared": compared, "reference_s": reference_s,
        "output_tokens": out_tokens,
        "prompt_tokens": prompt_tokens, "gaps": len(gaps),
        "generator_late_ms": {
            "p50": percentile(late, 50), "p99": percentile(late, 99),
            "max": max(late),
        } if late and plan["loop"] == "open" else None,
        "itl_p50_p95_ms": [percentile(gaps, 50), percentile(gaps, 95)] if gaps else None,
        "backlog_open_mid_close": [
            sum(
                1 for r in records
                if r["sent"] and r["sent"] <= t and (r["end"] or run_end) > t
            )
            for t in (t_open, (t_open + t_close) / 2, t_close)
        ],
        "bound": {k: v["bound"] for k, v in facts.get("least_s", {}).items()},
        "memory_in_use": memory, "preemptions": preemptions, "pages_left_in_use": pages_left,
        "first_error": failed[0]["error"] if failed else None,
    }
    return {
        "attempted": len(measured), "failed": len(failed),
        "end_to_end": end_to_end, "checks": checks,
        "memory_peak_bytes": memory_peak, "facts": facts, "notes": notes,
    }


def _least_times(model, cfg, peaks, trace, contexts):
    """Mean least seconds of one decode step and of one prefill in the
    traced window, and which of bandwidth and compute bounds each."""
    out = {}
    bw, fl = peaks["hbm_bytes_per_s"], peaks["flops_per_s"]
    steps = sum(
        rec["calls"] for name, rec in trace["programs"].items()
        if name.startswith("jit_decode")
    )
    if steps and contexts["decode"]:
        # the mean step: every weight once, its share of the live K and V
        by_bytes = model.decode_step_bytes(cfg, [sum(contexts["decode"]) / steps]) / bw
        by_flops = sum(
            model.sequence_flops(cfg, c - 1, c, 1) for c in contexts["decode"]
        ) / fl / steps
        out["decode"] = {
            "seconds": max(by_bytes, by_flops),
            "bound": "memory" if by_bytes >= by_flops else "compute",
        }
    if contexts["prefill"]:
        each = [
            (model.prefill_bytes(cfg, n) / bw, model.sequence_flops(cfg, 0, n, 1) / fl)
            for n in contexts["prefill"]
        ]
        by_bytes = sum(e[0] for e in each) / len(each)
        by_flops = sum(e[1] for e in each) / len(each)
        out["prefill"] = {
            "seconds": sum(max(e) for e in each) / len(each),
            "bound": "memory" if by_bytes >= by_flops else "compute",
        }
    return out


def _compare(model, params, cfg, bodies, sample, with_control, chunk=8):
    """The gaps of the sample's served tokens under the reference: the
    widest, their mean over all tokens compared, and how many tokens were
    not the reference's first; with ``--control`` the same for the tokens
    the bfloat16 control puts first at the same positions.

    The reference is read twice: in float32 at ``highest``, and in float32
    with products at the chip's default precision, which is what the
    configuration states. A number is the smaller of its two readings, so
    that a program which computes exactly as stated and one which computes
    more precisely both read near nought, and one that rounds more does
    not."""
    import jax.numpy as jnp

    width = cfg["n_positions"]
    gaps = {}
    for lo in range(0, len(sample), chunk):
        part = sample[lo : lo + chunk]
        tokens = np.zeros((len(part), width), np.int32)
        rows, cols, served = [], [], []
        for i, r in enumerate(part):
            prompt = bodies[r["id"]]["prompt"]
            seq = prompt + r["tokens"][:-1]
            tokens[i, : len(seq)] = seq
            for j, tok in enumerate(r["tokens"]):
                rows.append(i)
                cols.append(len(prompt) - 1 + j)
                served.append(tok)
        rows, cols = np.asarray(rows, np.int32), np.asarray(cols, np.int32)
        picks = {"served": jnp.asarray(served, jnp.int32)}
        if with_control:
            low = model.reference_logits(params, cfg, tokens, rows, cols, "bfloat16")
            picks["control"] = jnp.argmax(low, axis=-1)
        for precision in ("float32", "default"):
            ref = model.reference_logits(params, cfg, tokens, rows, cols, precision)
            best = jnp.max(ref, axis=-1)
            for who, toks in picks.items():
                got = jnp.take_along_axis(ref, toks[:, None], axis=-1)[:, 0]
                gaps.setdefault((who, precision), []).append(np.asarray(best - got))
    out = {}
    for (who, precision), parts in gaps.items():
        g = np.concatenate(parts)
        out.setdefault(who, {})[precision] = {
            "widest": float(g.max()), "mean": float(g.mean()),
            "off_first": int((g > 0).sum()), "tokens": int(g.size),
        }
    for who, by in out.items():
        for key in ("widest", "mean"):
            by[key] = min(v[key] for v in by.values() if isinstance(v, dict))
    return out
