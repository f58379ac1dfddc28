"""90th percentile over every request offered in the window of its queue
wait: from its scheduled arrival to the scheduler's first dispatch of it
(``Request.dispatch_time``); a request never dispatched is a miss."""
from bench.common.program_trace import stamp_gap_ms

SOURCE = "program_span"


def read(v):
    return stamp_gap_ms(v, "arrival_time", "dispatch_time", 90)
