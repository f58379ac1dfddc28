"""Host time the KV wire spends staging on the P side and reading on the
D side (``TransferStats.stage_seconds + read_seconds``) per 1,000 prompt
tokens prefilled in the window."""
SOURCE = "program_span"


def read(v):
    ts = v.outcome.transfer_stats
    tokens = sum(s.get("prefill_tokens", 0)
                 for s in v.outcome.engine_stats.values())
    if not ts or not tokens:
        return None
    return 1000.0 * (ts["stage_seconds"] + ts["read_seconds"]) \
        / (tokens / 1000.0)
