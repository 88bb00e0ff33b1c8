"""Model operations done in the window over what the chips could do at
peak: every token decoded (lm head included) and every prompt token the
chunk executor ingested, with attention over the real positions
(``bench/flops.py``), divided by window x chips x peak bf16 FLOP/s."""
from bench import flops


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    if not peak:
        return None
    total = 0
    for t, ctxs in run.decode_calls:
        if run.in_window(t):
            total += sum(flops.decode_token_flops(run.mc, c) for c in ctxs)
    for t, rows in run.chunk_calls:
        if run.in_window(t):
            total += sum(flops.prefill_chunk_flops(run.mc, s, n)
                         for s, n in rows)
    span = run.window[1] - run.window[0]
    return 100.0 * total / (span * run.chips * peak) if total else None
