def read(run):
    total = [(r.last - r.due) * 1e3 for r in run.due
             if r.last is not None and r.future.done
             and r.future.error is None]
    return run.stats.quantile(total, 0.50)
