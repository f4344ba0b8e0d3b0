"""Share of the traced window in which no operation ran on a chip, mean
over the cell's chips."""
import serve_metrics


def read(rec, red):
    return serve_metrics.idle(red)
