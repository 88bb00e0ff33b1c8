"""90th percentile of the time a released request waits in the engine's
queue for its first slot (``t_admit - t_release``, gateway clock), over
the requests due in the window; one not admitted by the drain's end
counts to that end."""
from bench import stats, timelines


def read(run):
    v = timelines.stage(run, "t_release", "t_admit")
    return 1e3 * stats.percentile(v, 90) if v else None
