"""Mean real requests per served batch (of max_batch rows), from the
scheduler's own counter."""


def read(run):
    return float(run.stats["batch_fill_mean"]) if run.stats["batches"] else None
