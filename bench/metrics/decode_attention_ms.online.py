"""Device time of the operations under the ``attention`` scope of the
decode programs in the traced window, per decode program."""
from bench.common import program_trace as PT

SOURCE = "device_trace"


def read(v):
    s = PT.summary(v)
    if not s or not s["decode_calls"] or "attention" not in s["scopes"]:
        return None
    return 1000.0 * s["scopes"]["attention"] / s["decode_calls"]
