"""Share of the prefill-chunk executor's rows that carried prompt work:
the rows filled over the row width compiled and dispatched, summed over
the chunk calls whose ``kvrm.engine.prefill_assemble`` span starts in the
window, in %. A program that records no row width reads nothing."""
from bench import timelines

PREFILL_ASSEMBLE = "kvrm.engine.prefill_assemble"


def read(run):
    calls = [st for _, _, st in
             timelines.window_spans(run, PREFILL_ASSEMBLE) or ()
             if st.get("row_width")]
    if not calls:
        return None
    return (100.0 * sum(st["rows"] for st in calls)
            / sum(st["row_width"] for st in calls))
