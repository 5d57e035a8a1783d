def read(run):
    late = run.stats.lateness_ms([r.due for r in run.due],
                                 [r.sent for r in run.due])
    return run.stats.quantile(late, 0.90)
