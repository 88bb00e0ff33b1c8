"""The paged decode kernel's share of its roofline: the least time the
chip needs for the operations and bytes of the live contexts (each active
slot's cached tokens, from the committed descriptors) over the kernel's
device time in the traced window."""
from bench import flops, tracereduce


def read(run):
    return tracereduce.roofline_share(
        run, "paged_decode_attention",
        [flops.decode_attn_cost(run.mc, ctxs)
         for t, ctxs in run.decode_calls if run.in_window(t)])
