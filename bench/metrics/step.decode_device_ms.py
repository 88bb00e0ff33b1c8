"""Device time per decode-executor call in the traced window."""


def read(run):
    ex = (run.trace or {}).get("executors", {}).get("decode")
    return 1e3 * ex["seconds"] / ex["calls"] if ex and ex["calls"] else None
