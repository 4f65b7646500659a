"""Share (%) of the HBM roofline reached by the fused lookup module
``jit__fused_pipeline``: the bytes no exact lookup can avoid
(``bench.roofline.lookup_bytes``, from the batch's shape and the index's
declared error bound) over peak bandwidth times the module's summed
device time in the traced window."""

from bench import roofline, trace


def read(run):
    if run.profile is None:
        return None
    secs, n = trace.module_time(run.profile, "jit__fused_pipeline",
                                run.trace_window)
    if n == 0:
        return None
    keys = n * run.traffic["bulk"]["batch_keys"]
    return roofline.hbm_roofline_pct(
        roofline.lookup_bytes(keys, run.err_bound), secs, run.device_kind)
