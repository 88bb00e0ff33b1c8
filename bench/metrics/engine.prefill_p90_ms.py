"""90th percentile of prompt ingestion time, from a request's first slot
admission to the dispatch of the step carrying its last prompt token
(``t_prompt_done - t_admit``, gateway clock), over the requests due in the
window; one whose prompt is not done by the drain's end counts to that
end."""
from bench import stats, timelines


def read(run):
    v = timelines.stage(run, "t_admit", "t_prompt_done")
    return 1e3 * stats.percentile(v, 90) if v else None
