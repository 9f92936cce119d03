"""One run of one cell: ``python -m chipbench.run --workload <name> --seed
<n> --seconds <s> --trace <0|1>``.

A new process loads the cell's files, builds its inputs and weights from
the seed, warms up every shape the window will use, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as the last line of standard
output. Without a TPU (or with fewer chips than the cell asks for) it
exits non-zero and prints no result; ``--rehearsal`` is the only way to
run it elsewhere: toy sizes on whatever jax finds, the line says so, and
no number of such a run is a device number.

Everything that belongs to one cell, one configuration or one per-layer
metric is a file found by its name in ``BENCHMARK.json``:
``workloads/<cell>.json`` (driver, traffic, check), ``configs/<config>.json``
(sizes, precision, engine geometry, limits), ``metrics/<metric>.json``
(reader and its arguments), ``drivers/<driver>.py``, ``readers/<reader>.py``,
``models/<family>.py``.
"""

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

_T_IMPORT = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:  # ``python chipbench/run.py`` as well as ``-m``
    sys.path.insert(0, ROOT)

_COMPILES = [0]  # jax's own compile and cache-fetch events, this process


def process_start():
    """The monotonic time at which this process started, from
    ``/proc/self/stat``; the time this module was imported where that
    cannot be read."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= age < 3600.0:
            return time.monotonic() - age
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def assign(tree, dotted, value):
    """``--set traffic.rate_per_s=2.0``: the sweep's way to try a value
    without editing a cell's file."""
    keys = dotted.split(".")
    for k in keys[:-1]:
        tree = tree[k]
    tree[keys[-1]] = value


def merge(base, over):
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            merge(base[k], v)
        else:
            base[k] = v


class Run:
    """What a driver gets: the cell, its configuration, the arguments, the
    device, and the helpers every driver shares."""

    def __init__(self, args, manifest, cell, config, started):
        self.args, self.manifest = args, manifest
        self.cell, self.config = cell, config
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.rehearsal = args.rehearsal
        self.started = started
        self.device = None
        self.peaks = None
        self.phases = {}
        self.model = importlib.import_module(
            f"chipbench.models.{config['family']}"
        )

    def mark(self, phase):
        """Seconds since the process started, at the end of a set-up
        phase: they go into the line's notes, so that a ``setup_s`` that
        moved says where."""
        self.phases[phase] = time.monotonic() - self.started

    # -- device ----------------------------------------------------------

    def claim_device(self):
        """The gate: a TPU with the chips the cell asks for, or exit."""
        import jax

        from chipbench import peaks

        devices = jax.devices()
        self.device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": int(self.cell["chips"]),
        }
        if self.rehearsal:
            self.device["count"] = len(devices)
            self.device["rehearsal"] = True
            self.watch_compiles()
            return
        if devices[0].platform != "tpu" or len(devices) < self.cell["chips"]:
            sys.stderr.write(
                f"chipbench: {self.cell['name']} needs {self.cell['chips']} "
                f"TPU chip(s); jax reports {len(devices)} "
                f"{devices[0].platform} device(s). --rehearsal runs toy "
                f"sizes elsewhere.\n"
            )
            sys.exit(3)
        self.peaks = peaks.lookup(devices[0].device_kind)
        self.watch_compiles()

    def memory_peak(self):
        import jax

        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()[: self.device["count"]]
        ]
        return int(max(peaks))

    def memory_in_use(self):
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        return {k: stats.get(k) for k in ("bytes_in_use", "bytes_limit")}

    # -- the program's counters ------------------------------------------

    def compile_count(self):
        """Programs the process has built so far: the engine's jit
        builds, and the registry's rows and their compiles."""
        from tensorframes_tpu import obs

        builds = self.counter_total("engine.jit_cache_builds_total")
        table = obs.programs.table()
        return builds + len(table) + _COMPILES[0] + sum(
            r["invocations"] - r["dispatches"] for r in table
        )

    def counter_total(self, name):
        from tensorframes_tpu import obs

        values = (obs.registry().snapshot().get(name) or {}).get("values", {})
        return sum(values.values())

    def watch_compiles(self):
        """Count what jax itself compiles or fetches from its cache."""
        import jax

        def on_event(event, duration, **_):
            if "compile" in event or "cache_retrieval" in event:
                _COMPILES[0] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def program_table(self):
        from tensorframes_tpu import obs

        return {r["name"]: r for r in obs.programs.table()}

    # -- tracing -----------------------------------------------------------

    @contextlib.contextmanager
    def capture(self, facts):
        """A profiler capture with the program's spans forwarded into it
        and collected; leaves ``facts['trace']`` and ``facts['spans']``."""
        import jax

        from tensorframes_tpu import obs
        from tensorframes_tpu.utils import profiling

        from chipbench import trace_reduce
        from chipbench.trace_reduce import WINDOW_SPAN

        spans = _SpanList()
        log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        obs.set_trace_sink(spans)
        try:
            with profiling.trace(log_dir):
                t0 = time.monotonic()
                with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                    yield
                t1 = time.monotonic()
            obs.set_trace_sink(None)
            events = spans.events()
            names = {e["name"] for e in events} | {WINDOW_SPAN}
            trace = trace_reduce.load(trace_reduce.find_xplane(log_dir), names)
            window = None
            marks = [e for e in trace["host"] if e[0] == WINDOW_SPAN]
            if marks:
                window = (marks[0][1], marks[0][1] + marks[0][2])
            facts["trace"] = trace_reduce.reduce(trace, window)
            facts["trace_window"] = (t0, t1)
            facts["spans"] = events
        finally:
            obs.set_trace_sink(None)
            shutil.rmtree(log_dir, ignore_errors=True)


class _SpanList:
    """A sink for ``obs.set_trace_sink``: keeps the span events."""

    def __init__(self):
        self._lines = []

    def write(self, line):
        self._lines.append(line)
        return len(line)

    def flush(self):
        pass

    def events(self):
        return [json.loads(l) for l in self._lines if l.strip()]


def metric_cells(entry, manifest):
    """The cells a manifest metric is read in."""
    if "workloads" in entry:
        return set(entry["workloads"])
    if "moves" in entry:
        moved = next(
            m for m in manifest["end_to_end"] if m["name"] == entry["moves"]
        )
        return metric_cells(moved, manifest)
    return {w["name"] for w in manifest["workloads"]}


def layer_metrics(run, facts):
    """Every per-layer metric the manifest lists for this cell, through
    its reader; a reader that finds nothing returns ``None`` and the
    metric is left out of the line."""
    out = {}
    for entry in run.manifest["per_layer"]:
        if run.cell["name"] not in metric_cells(entry, run.manifest):
            continue
        spec = load_json(HERE, "metrics", entry["name"] + ".json")
        reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
        value = reader.read(facts, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def main(argv=None):
    started = process_start()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON")
    ap.add_argument(
        "--control", action="store_true",
        help="also read the lower-precision control's number (notes)",
    )
    args = ap.parse_args(argv)

    manifest = load_json(ROOT, "BENCHMARK.json")
    listed = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in listed:
        ap.error(f"unknown workload {args.workload!r}; have {sorted(listed)}")
    cell = load_json(HERE, "workloads", args.workload + ".json")
    config = load_json(HERE, "configs", cell["config"] + ".json")
    if args.rehearsal:
        merge(cell, cell.get("rehearsal", {}))
        merge(config, config.get("rehearsal", {}))
    for item in args.set:
        key, _, raw = item.partition("=")
        scope, _, rest = key.partition(".")
        assign({"cell": cell, "config": config}[scope], rest, json.loads(raw))

    import tensorframes_tpu  # noqa: F401  (absent: no result, exit non-zero)

    run = Run(args, manifest, cell, config, started)
    run.mark("imports")
    run.claim_device()
    run.mark("device")
    driver = importlib.import_module(f"chipbench.drivers.{cell['driver']}")
    outcome = driver.run(run)

    facts = outcome["facts"]
    e2e = {}
    for entry in manifest["end_to_end"]:
        if cell["name"] in metric_cells(entry, manifest):
            e2e[entry["name"]] = {
                "value": outcome["end_to_end"][entry["name"]],
                "unit": entry["unit"],
            }
    checks = {
        k: {"value": v, "limit": lim} for k, (v, lim) in outcome["checks"].items()
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line = {
        "correct": bool(correct),
        "attempted": int(outcome["attempted"]),
        "failed": int(outcome["failed"]),
        "metrics": layer_metrics(run, facts) if run.trace else e2e,
        "device": dict(run.device, memory_peak_bytes=outcome["memory_peak_bytes"]),
    }
    if run.trace and facts.get("trace"):
        tr = facts["trace"]
        line["device"]["busy_s"] = tr["busy_s"]
        line["device"]["window_s"] = tr["window_s"]
        line["breakdown"] = {
            "device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"],
        }
    line["notes"] = dict(outcome.get("notes", {}), setup_phases_s=run.phases)
    if run.trace:  # beside an untraced run's, what tracing costs
        line["notes"]["end_to_end_while_traced"] = {
            k: v["value"] for k, v in e2e.items()
        }
    line["checks"] = checks
    for name, c in checks.items():
        sys.stderr.write(
            f"check {name}: value {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAILED'}\n"
        )
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
