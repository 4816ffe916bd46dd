"""collective_us (us): device time of the cross-shard collectives per tick,
on device 0: the operations named by ``trace_reduce.COLLECTIVE`` (the
margin reduce's all-gathers, the winner score's all-reduce) inside the
traced window, summed and divided by the window's ticks. Nothing on a run
whose program made no collective (one chip, an unsharded bank)."""
import trace_reduce


def read(run):
    if run.trace is None:
        return None
    d = trace_reduce.op_events(run.trace, trace_reduce.COLLECTIVE)
    ticks = run.window.ticks_in_window
    return sum(d) / ticks * 1e-3 if d and ticks else None
