"""95th percentile of the gateway's outstanding requests (submitted, not
finished: queued and running), sampled once per pump cycle in the window."""
from bench.stats import percentile


def read(run):
    v = [s[5] for s in run.steps if run.in_window(s[0])]
    return float(percentile(v, 95)) if v else None
