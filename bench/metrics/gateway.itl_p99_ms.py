"""99th percentile of every gap between consecutive streamed tokens of a
request whose later token lands in the window, on the gateway's clock: the
end-to-end ``itl_p99_ms``, read per layer in cells whose window holds too
few engine steps for that tail to take a bound."""
from bench import stats


def read(run):
    gaps = stats.itl_gaps(list(run.recs.values()), *run.window)
    return 1e3 * stats.percentile(gaps, 99) if gaps else None
