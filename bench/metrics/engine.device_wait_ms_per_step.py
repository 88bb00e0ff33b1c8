"""Host time spent waiting on the device for a step's tokens
(``kvrm.engine.device_wait`` spans, around the readback's
``block_until_ready``) per engine step (``kvrm.engine.step`` spans), both
starting in the window."""
from bench import timelines


def read(run):
    steps = timelines.window_spans(run, timelines.STEP)
    if not steps:
        return None
    waits = timelines.window_spans(run, timelines.DEVICE_WAIT)
    return 1e3 * sum(t1 - t0 for t0, t1, _ in waits) / len(steps)
