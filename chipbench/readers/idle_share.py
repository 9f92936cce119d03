"""The share of the traced window in which no operation ran on the
device (mean over the chips used)."""


def read(facts):
    trace = facts.get("trace")
    return 100.0 * trace["idle_share"] if trace else None
