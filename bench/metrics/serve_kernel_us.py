"""serve_kernel_us (us): device time of the serve mega-kernel per call, on
device 0, from the profiler trace."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    d = trace_reduce.op_events(run.trace, trace_reduce.SERVE_KERNEL)
    return sum(d) / len(d) * 1e-3 if d else None
