"""The whole window's share of the chip's peak: the FLOPs the window's
work needs (from the configuration's shapes and the traffic's real
lengths; padding and recomputation do not count) over window seconds x
chips x peak FLOP/s."""


def read(facts):
    peaks, flops = facts.get("peaks"), facts.get("needed_flops")
    if not peaks or not flops:
        return None
    ceiling = facts["window_s"] * facts["chips"] * peaks["flops_per_s"]
    return 100.0 * flops / ceiling
