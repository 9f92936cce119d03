"""What the HTTP layer adds: the client's wall for a request (sent to last
byte) less the ``total_s`` the server echoes in the same response."""

from chipbench.stats import percentile


def read(facts, q=50, scale=1.0):
    values = [
        (r["end"] - r["sent"] - r["timing"]["total_s"]) * scale
        for r in facts.get("measured", [])
        if r.get("done") and "total_s" in (r.get("timing") or {})
    ]
    return percentile(values, q) if values else None
