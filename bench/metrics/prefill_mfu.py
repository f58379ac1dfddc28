"""Model FLOPs of the prompt tokens prefilled in the traced window over
the P chunk programs' device time times the chip's bf16 peak."""
from bench.common.readers import mfu

SOURCE = "device_trace"


def read(v):
    return mfu(v, "prefill", "prefill")
