"""step_ms (ms): host time inside `ACAMService.step` (cascade, bookkeeping
and the scheduler tick), per step that dispatched in the window, by the
benchmark's own timer."""


def read(run):
    w = run.window
    return w.step_s / w.step_n * 1e3 if w.step_n else None
