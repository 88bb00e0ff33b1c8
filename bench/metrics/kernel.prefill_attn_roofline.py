"""The chunked prefill kernel's share of its roofline: operations and
bytes of the prompt tokens ingested at their positions (from the
committed chunk descriptors) over the kernel's device time in the traced
window."""
from bench import flops, tracereduce


def read(run):
    return tracereduce.roofline_share(
        run, "chunked_prefill_attention",
        [flops.prefill_attn_cost(run.mc, rows)
         for t, rows in run.chunk_calls if run.in_window(t)])
