"""Mean host time between engine steps: from the end of one
``kvrm.engine.step`` span to the start of the next, for consecutive steps
starting in the window. The gateway's pump, its release, and the event
loop's streams and clients run there."""
from bench import timelines


def read(run):
    steps = timelines.window_spans(run, timelines.STEP)
    if not steps or len(steps) < 2:
        return None
    gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
    return 1e3 * sum(gaps) / len(gaps)
