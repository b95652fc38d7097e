"""95th percentile (nearest rank) of first_started_at - created_at over
every job of the window: the wait in admission, queue and DWRR."""
from chipbench.harness import percentile


def read(run):
    wait = [j.started - j.created for j in run.jobs if j.started is not None]
    return 1e3 * percentile(wait, 95) if wait else None
