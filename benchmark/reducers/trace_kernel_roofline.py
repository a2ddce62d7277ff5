"""The fused GF kernel's share of its roofline over the traced window: the
least time its calls' HBM traffic allows (bytes from the traced shapes over the
chip's HBM bandwidth, benchmark/kernelmodel.py) over the device time they took.
HBM-bound: on needed work the bytes, not the int8 operations, set the floor."""
import kernelmodel
from readers import kernel_events


def reduce(ctx, params):
    if ctx["trace"] is None:
        return None
    calls, _ = kernel_events(ctx["trace"])
    took = sum(t for _, t in calls)
    if not took:
        return None
    least = sum(kernelmodel.least_seconds(c, ctx["peaks"]) for c, _ in calls)
    executed = sum(c["ops"] for c, _ in calls) / ctx["peaks"]["int8_ops_per_s"]
    ctx["say"](kernel_calls=len(calls), kernel_s=took, least_hbm_s=least,
               executed_int8_ops_s=executed, bound="hbm")
    return 100.0 * least / took
