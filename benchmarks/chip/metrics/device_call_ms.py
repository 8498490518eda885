"""Event-program call time per batch, ms: the scheduler's host clock around
the runtime's forward, ended by block_until_ready (frame transfer, dispatch
and the fused kernel)."""


def read(run):
    st = run.stats
    if not st["batches"]:
        return None
    return 1e3 * st["accelerator_s"] / st["batches"]
