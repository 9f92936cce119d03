"""From a profiler trace to numbers: device busy and idle time, time per
program, the operations that took most of it, and the idle gaps by what
the host was doing.

``load`` reads the ``.xplane.pb`` the jax profiler wrote into plain lists
(``jax.profiler.ProfileData`` needs nothing but jax); ``reduce`` works on
those lists alone, so it is tested on a recorded trace without a chip.
Times are seconds on the trace's own clock.

A device plane is one named ``/device:TPU:<n>``. Its line ``XLA Ops``
holds one event per operation that ran on the chip, its line
``XLA Modules`` one per executed program, named after the jitted function
(``jit_decode(...)``). Busy time is the union of the operations'
intervals. The host plane's events named like the program's obs spans
(``serve.decode_step``, ``engine.map_blocks``: ``obs/tracing.py`` forwards
them as ``TraceAnnotation`` while a capture is open) say what the host was
doing in a gap.
"""

import glob
import os
import re

_OP = re.compile(r"^%?[\w.\-]+ = (.*?) ([\w\-]+)\(")
_LAYOUT = re.compile(r"\{[^}]*\}")
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the annotation the harness puts around the traced window: it marks
#: the window's bounds and is no answer to "what was the host doing"
WINDOW_SPAN = "chipbench.window"


def find_xplane(log_dir):
    paths = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path, span_names=()):
    """``{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]}``; every event is ``[name, start_s, duration_s]``. Of the host
    plane only events whose name is in ``span_names`` are kept (a host
    trace holds hundreds of thousands of python frames)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    keep = set(span_names)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append(
                        [ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9]
                    )
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:") and keep:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in keep:
                        out["host"].append(
                            [ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9]
                        )
    return out


def union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _clip(events, lo, hi):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def op_kind(event_name):
    """``%fusion.184 = f32[8,1600]{1,0:T(8,128)} fusion(...)`` -> ``fusion
    f32[8,1600]``: the operations of the 48 layers of one program then
    add up under one name."""
    m = _OP.match(event_name)
    if not m:
        return event_name[:80]
    shape = _LAYOUT.sub("", m.group(1)).strip()
    return f"{m.group(2)} {shape}"[:120]


def program_name(event_name):
    """``jit_decode(1234567890)`` -> ``jit_decode``."""
    return event_name.split("(", 1)[0]


def reduce(trace, window=None, top=10):
    """The numbers of one traced window.

    ``window`` is ``(start_s, end_s)`` on the trace's clock; ``None``
    takes the span from the first to the last device event. Returns
    ``busy_s`` (mean over the device planes of the union of operation
    intervals), ``window_s``, ``idle_share``, ``programs`` (per program
    name: calls, total and mean seconds, summed over planes),
    ``device_ops`` (the ``top`` kinds of operation by total time: opcode
    and result shape, so that a program's layers add up) and
    ``idle_gaps`` (idle seconds by the host span that covered most of
    each gap; ``(no span)`` where none did), the last two as lists of
    ``[name, seconds]``. ``None`` where the trace has no device plane."""
    devices = trace["devices"]
    if not devices:
        return None
    if window is None:
        starts = [e[1] for d in devices.values() for e in d["ops"] + d["modules"]]
        ends = [e[1] + e[2] for d in devices.values() for e in d["ops"] + d["modules"]]
        if not starts:
            return None
        window = (min(starts), max(ends))
    lo, hi = window
    busy, programs, ops, gaps = [], {}, {}, {}
    spans = [e for e in trace["host"] if e[0] != WINDOW_SPAN]
    host = sorted(_clip(spans, lo, hi), key=lambda e: e[1])
    for dev in devices.values():
        source = dev["ops"] or dev["modules"]
        merged = union([(a, b) for _, a, b in _clip(source, lo, hi)])
        busy.append(sum(b - a for a, b in merged))
        for name, a, b in _clip(dev["ops"], lo, hi):
            kind = op_kind(name)
            ops[kind] = ops.get(kind, 0.0) + (b - a)
        for name, a, b in _clip(dev["modules"], lo, hi):
            rec = programs.setdefault(program_name(name), [0, 0.0])
            rec[0] += 1
            rec[1] += b - a
        edges = [lo] + [t for ab in merged for t in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                label = _covering(host, a, b)
                gaps[label] = gaps.get(label, 0.0) + (b - a)
    n = len(devices)
    busy_s = sum(busy) / n
    window_s = hi - lo
    rank = lambda d: [
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
    ]
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "programs": {
            k: {"calls": c, "total_s": t, "mean_s": t / c}
            for k, (c, t) in programs.items()
        },
        "device_ops": rank({k: v / n for k, v in ops.items()}),
        "idle_gaps": rank({k: v / n for k, v in gaps.items()}),
    }


def _covering(host, a, b):
    """The name of the host span that overlaps ``[a, b]`` most; the
    innermost (shortest) one among equals."""
    best, best_key = "(no span)", (0.0, 0.0)
    for name, s, e in host:
        if s >= b:
            break
        overlap = min(e, b) - max(s, a)
        if overlap > 0:
            key = (overlap, -(e - s))
            if key > best_key:
                best, best_key = name, key
    return best
