"""The table of peaks, keyed by the literal ``device_kind`` jax reports."""

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def lookup(device_kind):
    """The peaks of one chip of this kind; an unknown kind is an error,
    never a default."""
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in {_PATH}; known: "
            f"{sorted(table)}"
        )
    return table[device_kind]
