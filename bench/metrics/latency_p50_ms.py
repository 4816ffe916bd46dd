"""latency_p50_ms (ms): the 50th percentile (nearest rank) of due time to
answer, over every request due in the open-loop (Server) window; a request
never answered counts as answered when the loop stopped."""
from drive import quantile


def read(run):
    w = run.window
    if w.kind != "poisson" or not w.population:
        return None
    return quantile(w.latencies_s(), 0.50) * 1e3
