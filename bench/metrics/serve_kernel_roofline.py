"""serve_kernel_roofline (%): the serve mega-kernel's least time over its
measured time. The least time of a tick is max(ops / int8 peak, bytes / HBM
bandwidth) of one chip, counted by `work.py` from the deployment and the
tick's requests; the measured time is the kernel's mean device time per
call, summed over the chips that run it (a sharded bank splits a tick's
work between them), from the profiler trace."""
import trace_reduce
import work


def read(run):
    if run.trace is None:
        return None
    per_chip = [trace_reduce.op_events(run.trace, trace_reduce.SERVE_KERNEL,
                                       device=i) for i in run.trace.ops]
    measured = sum(sum(d) / len(d) for d in per_chip if d) * 1e-9
    w = run.window
    ticks = [t for t in w.record.tick_tenants(w.tick0,
                                              w.tick0 + w.ticks_in_window)
             if t]
    if not measured or not ticks:
        return None
    peak = work.peaks(run.device_kind)
    least = [work.least_seconds(run.cfg, t, peak) for t in ticks]
    return 100.0 * (sum(least) / len(least)) / measured
