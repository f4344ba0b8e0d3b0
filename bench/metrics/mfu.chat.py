"""The serve step's share of the chip's peak over the traced window: the
least time each call of the window could take (``flops.serve_call_roofline_s``:
its weight and live-cache reads at peak bandwidth, or its tokens' operations
at peak rate, whichever is longer), summed, over the device's busy time."""
import serve_metrics


def read(rec, red):
    return serve_metrics.mfu(rec, red)
