"""The readers of the program's serving spans (`bench/metrics/` pack_us,
launch_us, fetch_ms, escalate_ms, finalize_us, step_self_ms), on a trace
recorded on the CPU: cifar10-k2-t64 at a test's size (`tinycell.tiny`) under
the offline mix, a 52 ms window of 21 full steps, each with an escalation.
Each reader finds its metric by name, as the benchmark does, and reads
nothing (without raising) from an untraced run or from a trace whose
program has no such spans."""
import dataclasses
import os

import pytest

import trace_reduce as tr
from tinycell import load_run

TRACE = tr.load(os.path.join(os.path.dirname(__file__), "data",
                             "tiny-offline-spans.xplane.pb"))
PROGRAM = ("acam.step", "sched.tick", "sched.pack", "acam_fused_dispatch",
           "sched.launch", "sched.fetch", "acam.escalate", "acam.finalize")


def mean(name: str) -> float:
    d = tr.host_spans(TRACE, name)
    return sum(d) / len(d)


def step_self() -> float:
    # the children of one step follow each other: their union is their sum
    kids = sum(sum(tr.host_spans(TRACE, n))
               for n in ("sched.tick", "acam.escalate", "acam.finalize"))
    steps = tr.host_spans(TRACE, "acam.step")
    return (sum(steps) - kids) / len(steps)


#: metric -> (its value in the recorded trace, the same from the spans)
CASES = {
    "pack_us": (66.59371428571428, lambda: mean("sched.pack") * 1e-3),
    "launch_us": (1200.1995714285713, lambda: mean("sched.launch") * 1e-3),
    "fetch_ms": (0.04559942857142857, lambda: mean("sched.fetch") * 1e-6),
    "escalate_ms": (0.7097150476190476, lambda: sum(tr.host_spans(
        TRACE, "acam.escalate")) / len(tr.host_spans(TRACE, "sched.tick"))
        * 1e-6),
    "finalize_us": (120.09495238095238,
                    lambda: mean("acam.finalize") * 1e-3),
    "step_self_ms": (0.11132199999999999, lambda: step_self() * 1e-6),
}


@dataclasses.dataclass
class Run:
    trace: object


@pytest.mark.parametrize("metric", sorted(CASES))
def test_span_reader(metric):
    value, from_spans = CASES[metric]
    bench = load_run()
    for mix in ("offline", "server"):
        assert bench.reader_path(f"{metric}.{mix}").endswith(
            f"metrics/{metric}.py")
    read = bench.reader(f"{metric}.offline")
    assert read(Run(TRACE)) == pytest.approx(value, rel=1e-12)
    assert read(Run(TRACE)) == pytest.approx(from_spans(), rel=1e-12)
    assert read(Run(None)) is None
    parent = dataclasses.replace(TRACE, host=[
        ev for ev in TRACE.host if ev[2] not in PROGRAM])
    assert read(Run(parent)) is None
    # the spans add up: a step is its tick, escalation, finalize and self
    # time, and the step's spans nest in the benchmark's own step timer
    assert {len(tr.host_spans(TRACE, n)) for n in PROGRAM} == {21}
    assert mean("acam.step") == pytest.approx(
        mean("sched.tick") + mean("acam.escalate") + mean("acam.finalize")
        + step_self(), rel=1e-12)
    assert mean("acam.step") <= mean("bench.step")
