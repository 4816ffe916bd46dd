"""dispatch_ms (ms): mean length of the scheduler's ``acam_fused_dispatch``
span (the tick's device dispatch and copy-back), from the host plane of the
profiler trace."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    d = trace_reduce.host_spans(run.trace, "acam_fused_dispatch")
    return sum(d) / len(d) * 1e-6 if d else None
