"""The one statistic the drivers and readers share."""


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list: the smallest value
    with at least ``q`` percent of the values at or below it."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-len(s) * q // 100) - 1))]
