"""100 x (1 - union of the device's op intervals / traced window), averaged
over chips."""
import xplane


def reduce(ctx, params):
    if ctx["trace"] is None:
        return None
    d = xplane.device_summary(ctx["trace"], "bench:window")["device"]
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
