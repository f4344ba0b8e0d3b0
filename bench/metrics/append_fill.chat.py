"""Share of an append call's rows that carry a prompt chunk, over the
window: the change in the engine's ``prefill_slot_ticks`` over the change
in ``prefill_calls`` times the grid's cells."""
import serve_metrics


def read(rec, red):
    return serve_metrics.append_fill(rec)
