def read(run):
    steps = run.counters.get("decode_steps", 0)
    if not steps:
        return None
    return run.counters["tokens_committed"] / steps
