"""Model FLOPs of the traced steps (``flops.step_flops``, from shapes)
over the traced window x chips x the chip's peak bf16 FLOP/s, in %."""

SPAN = "bench.dispatch"


def read(rec):
    if rec.trace is None or rec.peak_flops is None:
        return None
    steps = sum(1 for _, _, name in rec.trace.spans if name == SPAN)
    return (100.0 * rec.flops_per_step * steps
            / (rec.trace.window_s * rec.chips * rec.peak_flops))
