"""Share of the traced window in which no operation ran on the device
(one minus the union of device-op intervals, averaged over the chips)."""


def read(run):
    tr = run.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
