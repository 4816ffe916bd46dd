"""launch_us (us): mean length of the scheduler's ``sched.launch`` span (the
operands' uploads and the jitted tick's call, inside
``acam_fused_dispatch``), one per tick, from the host plane of the profiler
trace."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    d = trace_reduce.host_spans(run.trace, "sched.launch")
    return sum(d) / len(d) * 1e-3 if d else None
