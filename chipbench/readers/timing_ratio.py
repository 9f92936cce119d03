"""A share over the window's requests: 100 x the sum of some keys of the
``timing`` object the server echoes in every ``POST /generate`` response,
over the sum of others (``wait_pages_s`` over ``wait_pages_s`` +
``wait_slots_s``). A key a response lacks counts nought in the numerator;
where no response carries any key of the denominator, or their sum is
nought, there is nothing to read."""


def read(facts, num, den):
    timings = [r.get("timing") or {} for r in facts.get("measured", [])]
    if not any(k in t for t in timings for k in den):
        return None
    total = sum(float(t.get(k, 0.0)) for t in timings for k in den)
    if total <= 0:
        return None
    part = sum(float(t.get(k, 0.0)) for t in timings for k in num)
    return 100.0 * part / total
