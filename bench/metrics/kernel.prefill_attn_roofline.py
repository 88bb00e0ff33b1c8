"""The prefill-chunk attention kernel's share of its roofline: operations
and bytes of the prompt tokens ingested at their positions (from the
committed chunk descriptors) over the kernel's device time in the traced
window. The kernel and its counts are the cell's architecture module's
(``chunked_prefill_attention`` for dense GQA)."""
from bench import tracereduce


def read(run):
    return tracereduce.roofline_share(
        run, run.arch.KERNELS["prefill"],
        [run.arch.prefill_attn_cost(run.mc, rows)
         for t, rows in run.chunk_calls if run.in_window(t)])
