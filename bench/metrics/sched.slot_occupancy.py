"""Mean share of the engine's fixed slots holding a request, per step in
the window (``StepMetrics.active`` over the batch width)."""


def read(run):
    v = [s[1] / s[8] for s in run.steps if run.in_window(s[0])]
    return 100.0 * sum(v) / len(v) if v else None
