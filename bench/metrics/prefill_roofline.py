"""The P chunk programs' share of their roofline: Σ over the window's
chunks of max(FLOPs / peak, bytes / HBM bandwidth), causal prefix only,
over the chunk programs' device time."""
from bench.common.readers import roofline

SOURCE = "device_trace"


def read(v):
    return roofline(v, "prefill", "prefill")
