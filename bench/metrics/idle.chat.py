"""Share of the traced window in which no operation ran on the chip."""
import serve_metrics


def read(rec, red):
    return serve_metrics.idle(red)
