"""Live KV bytes over reserved KV bytes, each summed over the window's
steps (``active_kv_bytes()`` / ``reserved_kv_bytes()``)."""


def read(run):
    act = res = 0
    for s in run.steps:
        if run.in_window(s[0]):
            act += s[6]
            res += s[7]
    return 100.0 * act / res if res else None
