"""Model FLOPs of the decode steps in the traced window over the decode
programs' device time times the chip's bf16 peak."""
from bench.common.readers import mfu

SOURCE = "device_trace"


def read(v):
    return mfu(v, "decode", "decode")
