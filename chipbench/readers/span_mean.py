"""The mean, over the traced window's spans named ``span``, of one
attribute (``occupancy`` of ``serve.decode_step``) or, with no attribute,
of the span's own host wall."""


def read(facts, span, attr=None, scale=1.0):
    events = [e for e in facts.get("spans", []) if e["name"] == span]
    if attr is not None:
        events = [e for e in events if attr in e.get("attrs", {})]
        values = [float(e["attrs"][attr]) for e in events]
    else:
        values = [e["dur_s"] for e in events]
    return sum(values) / len(values) * scale if values else None
