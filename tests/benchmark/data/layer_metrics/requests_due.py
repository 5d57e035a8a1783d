def read(run):
    return len(run.due) or None
