"""The decode programs' share of their roofline: Σ over the window's
steps of max(FLOPs / peak, bytes / HBM bandwidth), live KV and routed
experts only, over the decode programs' device time."""
from bench.common.readers import roofline

SOURCE = "device_trace"


def read(v):
    return roofline(v, "decode", "decode")
