"""submit_us (us): host time inside `ACAMService.submit`, per request sent
in the window, by the benchmark's own timer around each burst of calls."""


def read(run):
    w = run.window
    return w.submit_s / w.submit_n * 1e6 if w.submit_n else None
