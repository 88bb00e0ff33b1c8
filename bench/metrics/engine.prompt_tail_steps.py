"""Mean decode steps a prompt spends ingesting one token a step (its
tokens below the chunk minimum, the last one included), over the requests
due in the window whose prompt was done by the drain's end."""
from bench import timelines


def read(run):
    tls = timelines.due_timelines(run)
    v = [tl["prompt_tail_steps"] for tl in tls or ()
         if tl["t_prompt_done"] is not None
         and tl["t_prompt_done"] <= run.t_end]
    return sum(v) / len(v) if v else None
