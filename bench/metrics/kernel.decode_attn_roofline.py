"""The decode attention kernel's share of its roofline: the least time the
chip needs for the operations and bytes of the live contexts (each active
slot's cached tokens, from the committed descriptors) over the kernel's
device time in the traced window. The kernel and its counts are the cell's
architecture module's (``paged_decode_attention`` for dense GQA)."""
from bench import tracereduce


def read(run):
    return tracereduce.roofline_share(
        run, run.arch.KERNELS["decode"],
        [run.arch.decode_attn_cost(run.mc, ctxs)
         for t, ctxs in run.decode_calls if run.in_window(t)])
