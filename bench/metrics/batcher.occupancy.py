"""Real queries per engine batch over the window: the delta of the
collection's ``serving_requests_served`` over ``serving_batches_served``."""


def read(run):
    batches = run.stats_window["batches"]
    if batches <= 0:
        return None
    return run.stats_window["requests"] / batches
