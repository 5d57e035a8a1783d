def read(run):
    return run.ledger["compiles"] + run.ledger["cache_hits"]
