"""Mean timesteps the served rows ran, of T, from the scheduler's
``steps_served`` counter (``stats()["mean_steps"]``): what early exit
leaves of the full T."""


def read(run):
    st = run.stats
    if not st["batches"] or "mean_steps" not in st:
        return None
    return float(st["mean_steps"])
