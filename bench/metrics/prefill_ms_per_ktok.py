"""Device time of the P engine's chunk programs per 1,000 prompt tokens
prefilled in the traced window."""
from bench.common.readers import prompt_ktok

SOURCE = "device_trace"


def read(v):
    dev, k = v.device_seconds("prefill"), prompt_ktok(v)
    return None if dev is None or not k else 1000.0 * dev / k
