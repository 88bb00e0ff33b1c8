"""90th percentile of the time a request waits in the gateway's queues,
from its submission to its release to the engine (``t_release -
arrival`` of its timeline, gateway clock), over the requests due in the
window: the set ``ttft_p90_ms`` reads. A request not released by the
drain's end counts to that end."""
from bench import stats, timelines


def read(run):
    v = timelines.stage(run, "arrival", "t_release")
    return 1e3 * stats.percentile(v, 90) if v else None
