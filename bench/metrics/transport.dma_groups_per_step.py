"""Mean DMA groups the transport committed per step in the window
(``StepMetrics.dma_groups``)."""


def read(run):
    v = [s[4] for s in run.steps if run.in_window(s[0])]
    return sum(v) / len(v) if v else None
