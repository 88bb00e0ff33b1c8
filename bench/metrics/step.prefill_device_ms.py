"""Device time per prefill-chunk-executor call in the traced window."""


def read(run):
    ex = (run.trace or {}).get("executors", {}).get("prefill")
    return 1e3 * ex["seconds"] / ex["calls"] if ex and ex["calls"] else None
