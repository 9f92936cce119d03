"""A percentile, over the window's requests, of one stage time the server
echoes in every ``POST /generate`` response (``queue_wait_s``,
``prefill_s``, ``decode_s``)."""

from chipbench.stats import percentile


def read(facts, key, q, scale=1.0):
    values = [
        r["timing"][key] * scale
        for r in facts.get("measured", [])
        if key in (r.get("timing") or {})
    ]
    return percentile(values, q) if values else None
