"""Blocking device-to-host reads per generated token (engine counters)."""


def read(run):
    c = run.counters
    return c["n_host_syncs"] / c["n_tokens"] if c["n_tokens"] else None
