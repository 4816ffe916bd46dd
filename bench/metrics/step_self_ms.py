"""step_self_ms (ms): mean over the service's ``acam.step`` spans of the
span's length minus the union of its child spans (``sched.tick``,
``acam.escalate``, ``acam.finalize``): the cascade loop and the building of
the responses, and the benchmark's own tick recorder (`drive.record_ticks`),
which wraps the tick from outside ``sched.tick``. From the host plane of the
profiler trace."""

STEP = "acam.step"
CHILDREN = ("sched.tick", "acam.escalate", "acam.finalize")


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    steps, kids = [], []
    for s, e, name in run.trace.host:  # in start order
        if lo <= s and e <= hi:
            if name == STEP:
                steps.append((s, e))
            elif name in CHILDREN:
                kids.append((s, e))
    if not steps:
        return None
    total = k = 0
    for s, e in steps:
        while k < len(kids) and kids[k][0] < s:
            k += 1
        covered, end = 0, s
        while k < len(kids) and kids[k][0] < e:
            a, b = max(kids[k][0], end), min(kids[k][1], e)
            if b > a:
                covered += b - a
                end = b
            k += 1
        total += e - s - covered
    return total / len(steps) * 1e-6
