"""Device time of the D side's re-page programs per 1,000 prompt tokens
prefilled in the traced window."""
from bench.common.readers import prompt_ktok

SOURCE = "device_trace"


def read(v):
    dev, k = v.device_seconds("repage"), prompt_ktok(v)
    return None if dev is None or not k else 1000.0 * dev / k
