"""finalize_us (us): mean length of the service's ``acam.finalize`` span
(each response's disposition, latency and energy booked into the flight
recorder), one per step that answered, from the host plane of the profiler
trace."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    d = trace_reduce.host_spans(run.trace, "acam.finalize")
    return sum(d) / len(d) * 1e-3 if d else None
