"""Device-idle time under the program's ``pd.decode.*`` spans (block
tables and arguments, launch, logits fetch, host sampling, and the step's
own remainder) per decode program in the traced window."""
from bench.common import program_trace as PT

SOURCE = "program_span"


def read(v):
    s = PT.summary(v)
    idle = PT.idle_under(s, "pd.decode.")
    return None if idle is None or not s["decode_calls"] \
        else 1000.0 * idle / s["decode_calls"]
