"""Output tokens delivered inside the window, over the window's length."""
from bench import stats


def read(run):
    n = stats.in_window(run.rec.token_times(), run.t0, run.t0 + run.seconds)
    return n / run.seconds
