"""requests_per_s (req/s): answers completed in the window over its length,
under the closed-loop (Offline) mix."""


def read(run):
    w = run.window
    return w.population / w.seconds if w.kind == "closed" else None
