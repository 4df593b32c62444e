"""Share of the roofline reached by the paged decode kernel in the traced
steps: the least time the chip could take for the bytes and FLOPs the
algorithm needs (K and V at each active row's live length), over the
device time of the kernel's events in the trace, in %."""
from .. import flops


def bound(run):
    ch = [c for c in run.loop.chunks if c.traced]
    kv = sum(c.kv_tokens for c in ch)
    rs = sum(c.row_steps for c in ch)
    t_bytes = flops.attn_bytes(run.cfg, kv, rs) / run.peaks["hbm_bytes_per_s"]
    t_flops = flops.attn_flops(run.cfg, kv) / run.peaks["bf16_flops_per_s"]
    return max(t_bytes, t_flops), ("bytes" if t_bytes >= t_flops else "flops")


def read(run):
    k = run.trace.get("kernel_s")
    if not k:
        return None
    t_min, _ = bound(run)
    return 100.0 * t_min / k
