"""A chunked prefill program's share of its roofline, like divided by
like: the least time the chip could take for the work the traced calls
did (each ``span`` in the traced window says where its chunk started and
how many real tokens it held; the configuration's model file turns that
into needed FLOPs and bytes, and the larger of the two over the chip's
peaks is the call's floor) over the device time of those calls in the
trace. A prompt is several calls, so a floor per prompt over a time per
call (reader ``roofline``) would not do."""

from chipbench.readers.traced import config_and_model, spans_inside


def read(facts, program, span, config):
    trace, peaks = facts.get("trace"), facts.get("peaks")
    chunks = [
        e["attrs"] for e in spans_inside(facts)
        if e["name"] == span and "tokens" in e.get("attrs", {})
        and "start" in e["attrs"]
    ]
    if not trace or not peaks or not chunks:
        return None
    device_s = sum(
        rec["total_s"] for name, rec in trace["programs"].items()
        if name.startswith(program)
    )
    if device_s <= 0:
        return None
    cfg, model = config_and_model(config)
    least = 0.0
    for c in chunks:
        start, stop = int(c["start"]), int(c["start"]) + int(c["tokens"])
        least += max(
            model.sequence_flops(cfg, start, stop, 1) / peaks["flops_per_s"],
            model.chunk_bytes(cfg, start, stop - start)
            / peaks["hbm_bytes_per_s"],
        )
    return 100.0 * least / device_s
