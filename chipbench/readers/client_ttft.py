"""A percentile of the time to first token at the client: first token
received less the time the request was due, over all requests due in the
window; a request that failed or never answered counts as the rest of the
run."""

from chipbench.stats import percentile


def read(facts, q):
    values = facts.get("ttft_ms")
    return percentile(values, q) if values else None
