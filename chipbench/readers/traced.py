"""What the readers of a traced window's spans share: which spans lie
inside the window the trace holds, and a configuration with its model
file."""

import importlib
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spans_inside(facts):
    """The program's spans whose middle lies in the traced window. The
    sink opens before the profiler has started and closes after it has
    stopped, so only these are the calls the trace holds."""
    lo, hi = facts.get("trace_window") or (float("-inf"), float("inf"))
    return [
        e for e in facts.get("spans", [])
        if lo <= e.get("t_mono", lo) + 0.5 * e.get("dur_s", 0.0) < hi
    ]


def config_and_model(name):
    """``(configuration as published and cut, its model module)``."""
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        cfg = json.load(f)
    return cfg, importlib.import_module(f"chipbench.models.{cfg['family']}")
