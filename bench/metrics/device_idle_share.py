"""device_idle_share (%): share of the traced window in which no operation
ran on device 0 (1 - union of its operation intervals / window)."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    return 100.0 * trace_reduce.idle_share(run.trace, 0)
