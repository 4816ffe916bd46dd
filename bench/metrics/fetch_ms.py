"""fetch_ms (ms): mean length of the scheduler's ``sched.fetch`` span (the
wait for the device and the four copies back, inside
``acam_fused_dispatch``), one per tick, from the host plane of the profiler
trace."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    d = trace_reduce.host_spans(run.trace, "sched.fetch")
    return sum(d) / len(d) * 1e-6 if d else None
