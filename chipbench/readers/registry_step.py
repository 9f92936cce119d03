"""Mean synced wall of one step program over the window: the program
registry's ``dispatch_s`` delta over its ``dispatches`` delta, for the
rows whose name starts with ``program`` (``serve.decode``)."""


def read(facts, program, scale=1.0):
    reg = facts.get("registry")
    if not reg:
        return None
    seconds = calls = 0.0
    for name, after in reg["after"].items():
        if not name.startswith(program):
            continue
        before = reg["before"].get(name, {"dispatch_s": 0.0, "dispatches": 0})
        seconds += (after["dispatch_s"] or 0.0) - (before["dispatch_s"] or 0.0)
        calls += after["dispatches"] - before["dispatches"]
    return seconds / calls * scale if calls > 0 else None
