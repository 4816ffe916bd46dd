"""pack_us (us): mean length of the scheduler's ``sched.pack`` span (pop,
placement lookup, the tick's numpy operands), one per tick, from the host
plane of the profiler trace."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    d = trace_reduce.host_spans(run.trace, "sched.pack")
    return sum(d) / len(d) * 1e-3 if d else None
