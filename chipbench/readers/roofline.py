"""A program's share of its roofline: the least time the chip could take
for one call (the driver works it out from needed bytes and FLOPs and says
which bounds it) over the program's mean device time per call in the
trace. ``program`` is the jitted function's name as the trace has it."""


def read(facts, program, least):
    trace = facts.get("trace")
    floor = (facts.get("least_s") or {}).get(least)
    if not trace or not floor:
        return None
    calls = total = 0.0
    for name, rec in trace["programs"].items():
        if name == program or name.startswith(program):
            calls += rec["calls"]
            total += rec["total_s"]
    if calls == 0 or total <= 0:
        return None
    return 100.0 * floor["seconds"] / (total / calls)
