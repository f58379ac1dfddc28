"""Device time per decode-step program in the traced window."""
SOURCE = "device_trace"


def read(v):
    dev, n = v.device_seconds("decode"), v.device_calls("decode")
    return None if dev is None or not n else 1000.0 * dev / n
