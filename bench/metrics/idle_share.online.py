"""Share of the traced window in which no operation ran on the device."""
from bench.common.readers import idle_share

SOURCE = "device_trace"


def read(v):
    return idle_share(v)
