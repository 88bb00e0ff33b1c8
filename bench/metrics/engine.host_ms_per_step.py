"""Mean host control-plane time per engine step in the window
(``StepMetrics.host``: admission, paging, descriptor assembly, commit)."""


def read(run):
    v = [s[3] for s in run.steps if run.in_window(s[0])]
    return 1e3 * sum(v) / len(v) if v else None
