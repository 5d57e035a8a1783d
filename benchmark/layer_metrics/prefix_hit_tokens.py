def read(run):
    t0, t1 = run.window
    sent = sum(len(r.request.prompt) for r in run.records
               if r.sent is not None and t0 <= r.sent < t1)
    if not sent or "prefix_blocks_reused" not in run.counters:
        return None
    block = run.cell.config["serving"]["block_size"]
    return 100.0 * run.counters["prefix_blocks_reused"] * block / sent
