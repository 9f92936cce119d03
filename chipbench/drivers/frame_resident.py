"""Driver ``frame_resident``: ``tft.map_blocks(score, frame)`` on a frame
that lives on the device, pass after pass for the window.

Set-up makes the features on the device from the seed, holds them in a
cached ``TensorFrame``, and warms the one scoring program. The window
keeps a bounded number of passes in flight and forces each pass's
predictions on the device; a pass counts when its predictions were ready
before the window closed. After the window, the predictions of a sample
of passes drawn from the seed, the last one among them, are compared row
by row with the plain reference: the predicted class by the gap of its
reference logit below the reference's best, its score by its distance
from that logit.
"""

import collections
import time

import numpy as np


class _Passes:
    """The window's loop: issue a pass, keep ``depth`` in flight, force
    the oldest."""

    def __init__(self, one_pass, depth, t_close, sample):
        self.one_pass, self.depth, self.t_close = one_pass, depth, t_close
        self.sample = sample
        self.inflight = collections.deque()
        self.kept, self.last = {}, None
        self.issued = self.done = 0

    def pump(self, until):
        while time.monotonic() < until:
            self.inflight.append((self.issued, self.one_pass()))
            self.issued += 1
            if len(self.inflight) >= self.depth:
                i, out = self.inflight.popleft()
                for column in out:
                    column.block_until_ready()
                if time.monotonic() < self.t_close:
                    self.done += 1
                    self.last = (i, out)
                    if i in self.sample:
                        self.kept[i] = out


def run(run):
    import jax

    import tensorframes_tpu as tft

    cfg, frame_spec = run.config, run.cell["frame"]
    rows = int(frame_spec["rows"])
    depth = int(frame_spec["passes_in_flight"])
    model = run.model
    x = model.make_features(run.seed, rows, cfg)
    w, b = model.init_weights(run.seed, cfg)
    score = model.score_fn(w, b, run.config["precision"]["matmul"])
    df = tft.TensorFrame.from_columns({"features": x}).analyze().cache()

    run.mark("frame")

    def one_pass():
        out = tft.map_blocks(score, df).cache()
        return out.column_block("prediction"), out.column_block("score")

    jax.block_until_ready(one_pass())  # compiles, or loads from the cache
    t = time.monotonic()
    jax.block_until_ready([one_pass() for _ in range(depth)])
    pass_s = (time.monotonic() - t) / depth
    run.mark("warm_up")

    expected = max(1, int(run.seconds / max(pass_s, 1e-6)))
    rng = np.random.default_rng(run.seed)
    n_check = int(run.cell["check"]["passes"])
    sample = set(rng.integers(0, expected, size=n_check).tolist())
    facts = {"window_s": run.seconds, "chips": run.cell["chips"], "peaks": run.peaks}

    compiles0, table0 = run.compile_count(), run.program_table()
    t_open = time.monotonic()
    t_close = t_open + run.seconds
    setup_s = t_open - run.started
    loop = _Passes(one_pass, depth, t_close, sample)
    if run.trace:
        loop.pump(t_open + 0.25 * run.seconds)
        with run.capture(facts):
            span = min(float(run.cell["trace_s"]), 0.5 * run.seconds)
            loop.pump(time.monotonic() + span)
    loop.pump(t_close)
    window_compiles = run.compile_count() - compiles0
    table1 = run.program_table()
    jax.block_until_ready([o for _, o in loop.inflight])
    loop.inflight.clear()
    memory_peak = run.memory_peak()
    kept = loop.kept
    if loop.last is not None:
        kept[loop.last[0]] = loop.last[1]

    # -- the comparison, after the window and the memory reading ----------
    del df
    worst, flips, off, short = 0.0, 0, 0.0, 0
    for pred, top in kept.values():
        if pred.shape != (rows,) or top.shape != (rows,):
            short += 1
            continue
        gap, n, err = model.reference_gap(x, w, b, pred, top)
        worst, flips, off = max(worst, gap), flips + n, max(off, err)
    control = None
    if run.args.control:
        gap, _, err = model.reference_gap(x, w, b, *model.control_predictions(x, w, b))
        control = {"logit_gap": gap, "score_error": err}
    limits = run.config["limits"]
    checks = {
        "logit_gap": (worst, limits["logit_gap"]),
        "score_error": (off, limits["score_error"]),
        "passes_not_compared": (short + (0 if kept else 1), 0),
        "window_compiles": (int(window_compiles), 0),
    }
    facts.update(
        registry={"before": table0, "after": table1},
        needed_flops=model.row_flops(cfg) * rows * loop.done,
    )
    if run.peaks:
        by_bytes = model.pass_bytes(cfg, rows) / run.peaks["hbm_bytes_per_s"]
        by_flops = model.row_flops(cfg) * rows / run.peaks["flops_per_s"]
        facts["least_s"] = {
            "score": {
                "seconds": max(by_bytes, by_flops),
                "bound": "memory" if by_bytes >= by_flops else "compute",
            }
        }
    return {
        "attempted": loop.done, "failed": 0,
        "end_to_end": {
            "rows_per_s": rows * loop.done / run.seconds, "setup_s": setup_s,
        },
        "checks": checks, "memory_peak_bytes": memory_peak, "facts": facts,
        "notes": {
            "passes": loop.done, "passes_issued": loop.issued,
            "passes_compared": len(kept), "rows_off_argmax": flips,
            "warm_pass_s": pass_s, "control": control,
            "bound": {k: v["bound"] for k, v in facts.get("least_s", {}).items()},
        },
    }
