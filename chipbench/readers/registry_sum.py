"""The sum of some fields of the program registry's rows whose name
starts with ``program`` (``serve.``), as the registry stands when the
window closes: what loading those programs cost (``compile_s``), and
where it went (``trace_s``, ``lower_s``). A field no such row carries a
number for is nothing to read."""


def read(facts, program, fields, scale=1.0):
    reg = facts.get("registry")
    if not reg:
        return None
    values = [
        row[f]
        for name, row in reg["after"].items() if name.startswith(program)
        for f in fields if row.get(f) is not None
    ]
    return sum(values) * scale if values else None
