"""100 x one counter of the program's metric registry over another, as
they stand when the line is written (``serve.window_pages_released_total``
over ``serve.window_pages_allocated_total``: the share of the pages window
layers took that they gave back before their sequence ended). A program
without the counters, or one that never counted, leaves nothing to read."""


def read(facts, num, den):
    try:
        from tensorframes_tpu import obs

        snap = obs.registry().snapshot()
    except Exception:
        return None

    def total(name):
        return sum((snap.get(name) or {}).get("values", {}).values())

    if den not in snap or total(den) <= 0:
        return None
    return 100.0 * total(num) / total(den)
