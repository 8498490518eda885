"""Host time per batch, ms: the scheduler's system time less its device
call time (TTFS encode, event packing, padding, readback, bookkeeping)."""


def read(run):
    st = run.stats
    if not st["batches"]:
        return None
    return 1e3 * (st["system_s"] - st["accelerator_s"]) / st["batches"]
