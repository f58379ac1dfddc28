"""90th percentile over every request offered in the window of its
prefill flight: from the scheduler's first dispatch of it
(``Request.dispatch_time``) to its first token, through the P engine's
chunks and the handoff; a request without both stamps is a miss."""
from bench.common.program_trace import stamp_gap_ms

SOURCE = "program_span"


def read(v):
    return stamp_gap_ms(v, "dispatch_time", "first_token_time", 90)
