"""Device-idle time under the program's ``pd.handoff.*`` spans (KV
extract, encode, stage, read, host→device, re-page dispatch, finalize)
per 1,000 prompt tokens prefilled in the traced window."""
from bench.common import program_trace as PT
from bench.common.readers import prompt_ktok

SOURCE = "program_span"


def read(v):
    idle, k = PT.idle_under(PT.summary(v), "pd.handoff."), prompt_ktok(v)
    return None if idle is None or not k else 1000.0 * idle / k
