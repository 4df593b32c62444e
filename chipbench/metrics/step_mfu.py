"""Model FLOPs of the traced decode steps (matmuls with the LM head for
the active rows, attention at live length) over the summed host time of
those ``step_chunk`` calls, over the chip's bf16 peak, in %."""
from .. import flops


def read(run):
    ch = [c for c in run.loop.chunks if c.traced]
    t = sum(c.t1 - c.t0 for c in ch)
    if not ch or t <= 0:
        return None
    f = flops.decode_flops(run.cfg, sum(c.row_steps for c in ch),
                           sum(c.kv_tokens for c in ch))
    return 100.0 * f / t / run.peaks["bf16_flops_per_s"]
