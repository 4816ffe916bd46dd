"""escalate_ms (ms): the service's ``acam.escalate`` time (the escalated
slots' pack, head tables, dense-head dispatch and copy-back) per tick: its
total over the number of ``sched.tick`` spans, so that it weighs how often
a tick escalates. From the host plane of the profiler trace."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    ticks = trace_reduce.host_spans(run.trace, "sched.tick")
    if not ticks:
        return None
    return sum(trace_reduce.host_spans(run.trace, "acam.escalate")) \
        / len(ticks) * 1e-6
