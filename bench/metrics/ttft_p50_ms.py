"""Median time to first token over every request offered in the window,
from its scheduled arrival (host clock); failed requests count as misses."""
from bench.common.readers import ttft_ms

SOURCE = "host_clock"


def read(v):
    return ttft_ms(v, 50)
