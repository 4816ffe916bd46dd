"""Flight-recorder tests (PR 7): `repro.obs` and its serving-tier wiring.

Coverage layers:

  * registry primitives: labeled counters/gauges, the dual-view histogram
    (exact-from-buckets quantiles, cumulative-clears vs window-survives),
    the label-cardinality guard, kind-conflict detection;
  * request conservation (started == finished + in-flight) and the
    serving spans: a shared no-op with annotations off, and with them on
    every span of the served path, nested, in a CPU profiler trace;
  * energy ledger: fleet and per-tenant totals BIT-EXACT (`==`, not
    approx) with the left-fold sum over per-response attributions;
  * exporters: JSONL schema round-trip, torn-final-line tolerance,
    Prometheus exposition rendering + duplicate/cardinality validation;
  * service integration over the bursty trace harness: conservation
    totals == request counts across every disposition (ok/escalated/
    shed/expired), the queue-wait histogram, `reset_metrics()` exact
    clear/survive semantics, tick events reconcile with the registry,
    and telemetry on-vs-off serves bit-identical results with <5%
    latency overhead.
"""
import glob
import json
import os
import sys
import time

import numpy as np
import pytest

from repro.match.config import EngineConfig
from repro.obs import (DEFAULT_LATENCY_BUCKETS_MS, NO_SPAN, EnergyLedger,
                       FlightRecorder, JsonlEventLog, MetricsRegistry,
                       read_events, validate_event,
                       validate_prometheus_text)
from repro.obs.registry import MAX_LABEL_SETS, Histogram
from repro.serve import acam_service as svc_lib
from repro.serve import spec as spec_lib
from repro.serve.acam_service import AdmissionError, ClassifyRequest
from repro.serve.control import HybridService

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmarks")

N_FEATURES = 64
N_CLASSES = 6
N_TENANTS = 6
SLOTS = 16


def _traces():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import traces

    return traces


def _spec(slots=SLOTS, *, deadline_ms=None, shed_queue=None,
          obs=None) -> spec_lib.ServiceSpec:
    return spec_lib.ServiceSpec(
        registry=spec_lib.RegistrySpec(num_features=N_FEATURES),
        engine=EngineConfig(margin=True),
        mesh=spec_lib.MeshSpec(install=False),
        scheduler=spec_lib.SchedulerSpec(slots=slots),
        cascade=spec_lib.CascadeSpec(tau=8.0, tau_units="count",
                                     deadline_ms=deadline_ms,
                                     shed_queue=shed_queue),
        obs=obs if obs is not None else spec_lib.ObsSpec(),
    ).validate()


def _boot(spec):
    svc = HybridService.from_spec(spec)
    protos = {}
    for t in range(N_TENANTS):
        bank, head, p = svc_lib.make_synthetic_tenant(
            200 + t, num_classes=N_CLASSES, num_features=N_FEATURES)
        tid = f"tenant-{t}"
        svc.register_tenant(tid, bank, head=head)
        protos[tid] = p
    return svc, protos


def _mixed_requests(protos, per_tenant=12, *, noise=0.9, seed=3):
    rng = np.random.RandomState(seed)
    reqs = []
    for ti, (tid, p) in enumerate(protos.items()):
        feats, _ = svc_lib.sample_tenant_queries(
            seed + 31 * ti, p, per_tenant, noise=noise)
        reqs.extend(ClassifyRequest(tid, feats[i])
                    for i in range(per_tenant))
    return [reqs[i] for i in rng.permutation(len(reqs))]


# ---------------------------------------------------------------------------
# Registry primitives
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_labels_and_reset(self):
        reg = MetricsRegistry()
        c = reg.counter("x_total")
        c.inc()
        c.inc(2, tenant="a")
        c.inc(3, tenant="b")
        assert c.value() == 1 and c.value(tenant="a") == 2
        assert c.total() == 6
        reg.reset()
        assert c.total() == 0
        # label sets survive a reset (only the values clear)
        assert [(labels, v) for labels, v in c.items()] == \
            [({}, 0.0), ({"tenant": "a"}, 0.0), ({"tenant": "b"}, 0.0)]

    def test_gauge_reset_semantics(self):
        reg = MetricsRegistry()
        g = reg.gauge("depth")
        per_run = reg.gauge("fill_min", clear_on_reset=True)
        g.set(7)
        per_run.set_min(5)
        per_run.set_min(3)
        assert per_run.value() == 3
        reg.reset()
        assert g.value() == 7, "plain gauges must survive reset"
        assert per_run.value() == 0, "clear_on_reset gauges must not"

    def test_registered_twice_returns_same_family(self):
        reg = MetricsRegistry()
        assert reg.counter("x") is reg.counter("x")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x")

    def test_label_cardinality_guard(self):
        c = MetricsRegistry().counter("leak_total")
        for i in range(MAX_LABEL_SETS):
            c.inc(request=i)
        with pytest.raises(ValueError, match="cardinality"):
            c.inc(request=MAX_LABEL_SETS)

    def test_histogram_exact_quantiles(self):
        h = Histogram("lat", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 3.0, 8.0):
            h.observe(v)
        # one observation per bucket (incl. +Inf): the q-rank observation
        # lands on a bucket upper bound exactly, no estimation slack
        assert h.quantile(0.25) == 1.0
        assert h.quantile(0.5) == 2.0
        assert h.quantile(0.75) == 4.0
        assert h.quantile(1.0) == 4.0  # +Inf bucket clamps to last bound
        assert h.quantile(0.5, window=False) == h.quantile(0.5)

    def test_histogram_dual_view_reset(self):
        h = Histogram("lat", buckets=(1.0, 2.0, 4.0), window=8)
        for v in (0.5, 1.5, 3.0):
            h.observe(v)
        p50 = h.quantile(0.5)
        h.clear()
        assert h.count == 0 and sum(h.counts) == 0, "cumulative cleared"
        assert h.window_count == 3 and h.quantile(0.5) == p50, \
            "rolling window must survive (overload signal)"
        assert h.quantile(0.5, window=False) == 0.0

    def test_histogram_window_bounded(self):
        h = Histogram("lat", buckets=(1.0, 10.0), window=4)
        for _ in range(16):
            h.observe(0.5)
        for _ in range(4):
            h.observe(5.0)  # the window now holds ONLY the slow tail
        assert h.window_count == 4
        assert h.quantile(0.5) > 1.0
        assert h.count == 20, "cumulative view keeps everything"

    def test_histogram_rejects_bad_buckets(self):
        with pytest.raises(ValueError, match="increasing"):
            Histogram("h", buckets=(2.0, 1.0))
        with pytest.raises(ValueError, match="window"):
            Histogram("h", buckets=(1.0,), window=0)


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

#: each serving span and the span it nests in (`FlightRecorder.span`)
SPAN_PARENT = {
    "acam.step": None,
    "sched.tick": "acam.step",
    "sched.pack": "sched.tick",
    "acam_fused_dispatch": "sched.tick",
    "sched.launch": "acam_fused_dispatch",
    "sched.fetch": "acam_fused_dispatch",
    "acam.escalate": "acam.step",
    "acam.finalize": "acam.step",
}


def _response(disposition: str) -> svc_lib.ClassifyResponse:
    return svc_lib.ClassifyResponse(
        request_id=1, tenant_id="t0", pred=0, margin=1.0,
        escalated=disposition == "escalated", energy_j=0.0, latency_s=1e-3,
        error="deadline exceeded" if disposition == "expired" else None,
        shed=disposition == "shed")


def _host_spans(log_dir) -> list:
    """[(start_ns, end_ns, name, stats)] of the serving spans in the
    newest profiler trace under ``log_dir``, in start order."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                        dict(e.stats)) for e in line.events
                       if e.name in SPAN_PARENT)
    return sorted(out, key=lambda ev: ev[0])


class TestSpans:
    def test_open_close_conservation(self):
        rec = FlightRecorder()
        rec.record_submit()
        rec.record_submit()
        rec.finish_request(_response("ok"), 1e-9, 0.0)
        c = rec.conservation()
        assert c["started"] == 2 and c["finished"] == 1
        assert c["in_flight"] == 1
        assert c["by_disposition"] == {"ok": 1}
        assert rec.latency.count == 1
        rec.finish_request(_response("expired"), 0.0, 0.0)
        c = rec.conservation()
        assert c["finished"] == 2 and c["in_flight"] == 0
        assert c["by_disposition"] == {"ok": 1, "expired": 1}
        assert rec.latency.count == 1, "expired latency is not service"

    def test_unknown_disposition_rejected(self):
        rec = FlightRecorder()
        with pytest.raises(ValueError, match="disposition"):
            rec.record_finish("vanished")
        assert rec.conservation()["finished"] == 0

    def test_off_span_is_shared_noop_and_builds_nothing(self, monkeypatch):
        import jax

        def refuse(*args, **kwargs):
            raise AssertionError("TraceAnnotation built with spans off")

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
        svc, protos = _boot(_spec())
        assert not svc.obs.profile_annotations
        assert svc.obs.span("acam.step") is NO_SPAN
        assert svc.obs.span("sched.tick", tick_id=0, fill=1) is NO_SPAN
        responses = svc.serve(_mixed_requests(protos, per_tenant=4))
        assert len(responses) == 4 * N_TENANTS
        assert svc.metrics()["escalated"] > 0, "escalate span path ran"

    def test_traced_service_emits_nested_serving_spans(self, tmp_path):
        import jax

        svc, protos = _boot(_spec(obs=spec_lib.ObsSpec(
            profile_annotations=True, telemetry_dir=str(tmp_path))))
        reqs = _mixed_requests(protos)
        svc.serve(reqs)  # compiles the tick and the escalation buckets
        svc.reset_metrics()
        seq0 = svc.obs.tick_seq
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            responses = svc.serve(reqs)
        finally:
            jax.profiler.stop_trace()
        m = svc.metrics()
        spans = _host_spans(tmp_path / "trace")
        by_name = {n: [ev for ev in spans if ev[2] == n]
                   for n in SPAN_PARENT}
        # one sched.tick per tick, one acam.step and acam.finalize per step
        ticks = by_name["sched.tick"]
        assert len(ticks) == m["ticks"] == len(by_name["acam.step"]) > 1
        for name in ("sched.pack", "acam_fused_dispatch", "sched.launch",
                     "sched.fetch", "acam.finalize"):
            assert len(by_name[name]) == len(ticks), name
        # the tick's stats are the registry's tick ids and fills, and the
        # event log's tick lines carry the same ids
        assert [ev[3]["tick_id"] for ev in ticks] == \
            list(range(seq0, svc.obs.tick_seq))
        logged = [e["tick_id"] for e in read_events(tmp_path / "events.jsonl")
                  if e["kind"] == "tick"]
        assert logged[-len(ticks):] == [ev[3]["tick_id"] for ev in ticks]
        assert [ev[3]["fill"] for ev in ticks] == \
            list(svc.obs.fill_window)[-len(ticks):]
        assert sum(ev[3]["fill"] for ev in ticks) == len(responses)
        esc = by_name["acam.escalate"]
        assert len(esc) == m["escalation_dispatches"] > 0
        assert sum(ev[3]["n"] for ev in esc) == m["escalated"]
        assert all(ev[3]["bucket"] >= ev[3]["n"] for ev in esc)
        # every span lies inside one span of its parent's name
        for s, e, name, _ in spans:
            parent = SPAN_PARENT[name]
            if parent is not None:
                assert any(ps <= s and e <= pe
                           for ps, pe, _, _ in by_name[parent]), name


# ---------------------------------------------------------------------------
# Energy ledger
# ---------------------------------------------------------------------------

class TestEnergyLedger:
    def test_bit_exact_with_left_fold(self):
        rng = np.random.RandomState(0)
        ledger = EnergyLedger()
        energies = []
        for i in range(500):
            b = float(rng.uniform(1e-9, 3e-9))
            f = float(rng.uniform(0, 1e-7)) if i % 3 == 0 else 0.0
            ledger.add(f"t{i % 4}", b, f, escalated=bool(f))
            energies.append(b + f)
        total = 0.0
        for e in energies:
            total += e
        assert ledger.fleet_j() == total, "must be ==, not approx"
        assert ledger.backend_j() + ledger.frontend_j() == \
            pytest.approx(total)

    def test_fleet_summary(self):
        ledger = EnergyLedger()
        ledger.add("a", 1e-9, 0.0)
        ledger.add("a", 1e-9, 9e-8, escalated=True)
        ledger.add("b", 1e-9, 0.0, shed=True)
        f = ledger.fleet()
        assert f["requests"] == 3 and f["escalated"] == 1 and f["shed"] == 1
        assert f["total_nj"] == pytest.approx(93.0)
        assert f["backend_share"] == pytest.approx(3e-9 / 9.3e-8)
        per = ledger.per_tenant()
        assert set(per) == {"a", "b"}
        assert per["b"]["frontend_nj"] == 0.0
        ledger.clear()
        assert ledger.fleet_j() == 0.0 and ledger.per_tenant() == {}


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

class TestExporters:
    def test_jsonl_roundtrip(self, tmp_path):
        log = JsonlEventLog(tmp_path / "events.jsonl")
        log.emit("reshard", bank_shards_from=1, bank_shards_to=2)
        log.emit("device_loss", lost=[1], survivors=3)
        log.close()
        events = read_events(tmp_path / "events.jsonl")
        assert [e["kind"] for e in events] == ["reshard", "device_loss"]
        assert [e["seq"] for e in events] == [0, 1]

    def test_emit_validates_before_writing(self, tmp_path):
        log = JsonlEventLog(tmp_path / "events.jsonl")
        with pytest.raises(ValueError, match="missing fields"):
            log.emit("reshard", bank_shards_from=1)  # no ..._to
        with pytest.raises(ValueError, match="unknown event kind"):
            log.emit("made_up")
        log.close()
        assert read_events(tmp_path / "events.jsonl") == []

    def test_torn_final_line_tolerated(self, tmp_path):
        p = tmp_path / "events.jsonl"
        log = JsonlEventLog(p)
        log.emit("device_heal", restored=4)
        log.close()
        with open(p, "a") as fh:
            fh.write('{"kind": "tick", "ts"')  # SIGKILL mid-write
        events = read_events(p)
        assert len(events) == 1
        # ...but corruption BEFORE the final line fails loudly
        with open(p, "a") as fh:
            fh.write('\n{"kind": "device_heal", "restored": 1, '
                     '"ts": 0, "seq": 9}\n')
        with pytest.raises(ValueError, match="non-final"):
            read_events(p)

    def test_disabled_log_is_noop(self):
        log = JsonlEventLog(None)
        assert not log.enabled
        log.emit("snapshot", step=1, path="x")  # must not raise

    def test_prometheus_validator_catches_duplicates(self):
        with pytest.raises(ValueError, match="duplicate"):
            validate_prometheus_text("a_total 1\na_total 2\n")
        ok = validate_prometheus_text(
            'a_total{t="x"} 1\na_total{t="y"} 2\n')
        assert ok["series"] == 2


# ---------------------------------------------------------------------------
# FlightRecorder + service integration
# ---------------------------------------------------------------------------

class TestFlightRecorder:
    def test_default_construction(self):
        rec = FlightRecorder()
        assert rec.latency.buckets == DEFAULT_LATENCY_BUCKETS_MS
        assert not rec.events.enabled
        validate_prometheus_text(rec.render_prometheus())

    def test_obs_spec_validation(self):
        with pytest.raises(ValueError, match="increasing"):
            _spec(obs=spec_lib.ObsSpec(latency_buckets_ms=(2.0, 1.0)))
        with pytest.raises(ValueError, match="latency_window"):
            _spec(obs=spec_lib.ObsSpec(latency_window=0))

    def test_obs_spec_json_roundtrip(self):
        spec = _spec(obs=spec_lib.ObsSpec(
            latency_buckets_ms=(1.0, 5.0, 25.0), latency_window=64,
            profile_annotations=True))
        again = spec_lib.ServiceSpec.from_dict(json.loads(spec.to_json()))
        assert again == spec
        # snapshots written before the flight recorder existed still load
        legacy = spec.to_dict()
        del legacy["obs"]
        assert spec_lib.ServiceSpec.from_dict(legacy).obs == \
            spec_lib.ObsSpec()
        # ... and so do those written while spans were sampled per request
        sampled = spec.to_dict()
        sampled["obs"]["span_sample"] = 0.25
        assert spec_lib.ServiceSpec.from_dict(sampled) == spec

    def test_straggler_sink_feeds_health(self):
        svc, _ = _boot(_spec())
        assert svc.scheduler.monitor.sink is not None
        svc.obs.record_straggler({"deadline_s": 1.5}, {0: 2, 3: 1})
        h = svc.health()
        assert h["straggler_strikes"] == {0: 2, 3: 1}
        assert svc.obs.straggler_deadline.value() == 1.5


class TestServiceTelemetry:
    @pytest.fixture(scope="class")
    def served(self):
        svc, protos = _boot(_spec())
        reqs = _mixed_requests(protos)
        responses = svc.serve(reqs)
        return svc, reqs, responses

    def test_energy_ledger_bit_exact(self, served):
        svc, _, responses = served
        total = 0.0
        for r in responses:
            total += r.energy_j
        assert svc.obs.ledger.fleet_j() == total, \
            "fleet ledger must equal the response left-fold EXACTLY"
        for tid in {r.tenant_id for r in responses}:
            per = 0.0
            for r in responses:
                if r.tenant_id == tid:
                    per += r.energy_j
            assert svc.obs.ledger.tenant_j(tid) == per, tid
        fleet = svc.obs.ledger.fleet()
        assert fleet["requests"] == len(responses)
        # the paper's asymmetry shows through: escalations dominate joules
        assert fleet["backend_share"] < 0.5

    def test_span_counts_equal_request_counts(self, served):
        svc, reqs, responses = served
        c = svc.obs.conservation()
        m = svc.metrics()
        assert c["started"] == m["submitted"] == len(reqs)
        assert c["finished"] == m["completed"] == len(responses)
        assert c["in_flight"] == 0
        assert c["started"] == c["finished"] + c["in_flight"]
        assert c["by_disposition"].get("escalated", 0) == m["escalated"] > 0
        assert sum(c["by_disposition"].values()) == c["finished"]

    def test_queue_wait_counts_every_dispatched_request(self, served):
        svc, reqs, _ = served
        wait = svc.obs.queue_wait
        m = svc.metrics()
        assert wait.count == m["served"] == len(reqs)
        assert wait.sum >= 0.0
        # a burst served tick by tick: later ticks' requests waited longer
        assert wait.quantile(1.0, window=False) > 0.0
        assert "acam_queue_wait_ms_count" in svc.obs.render_prometheus()

    def test_conservation_survives_reset(self):
        svc, protos = _boot(_spec())
        reqs = _mixed_requests(protos, per_tenant=4)
        for r in reqs[:SLOTS + 3]:
            svc.submit(r)
        svc.step()  # one tick: 3 requests stay queued
        before = svc.obs.conservation()
        assert before["started"] == SLOTS + 3
        assert before["in_flight"] == svc.scheduler.qsize == 3
        svc.reset_metrics()
        assert svc.metrics()["submitted"] == 0, "the counter clears"
        assert svc.obs.conservation() == before, "the totals do not"
        svc.drain()
        after = svc.obs.conservation()
        assert after["started"] == after["finished"] == SLOTS + 3
        assert after["in_flight"] == 0

    def test_metrics_and_shed_check_read_same_quantile(self, served):
        svc, _, _ = served
        assert svc.metrics()["latency_p99_ms"] == \
            round(svc.obs.latency_quantile_ms(0.99), 3)

    def test_prometheus_export_of_live_service(self, served):
        svc, _, _ = served
        stats = validate_prometheus_text(svc.obs.render_prometheus())
        assert stats["families"] >= 20
        text = svc.obs.render_prometheus()
        assert "acam_request_latency_ms_bucket" in text
        assert 'acam_energy_joules_total{stage="backend"' in text

    def test_reset_metrics_exact_semantics(self, served):
        # runs LAST against the shared service: it mutates counters
        svc, _, _ = served
        svc.obs.queue_depth.set(7)  # pretend depth; must survive
        before = svc.obs.conservation()
        tick_seq = svc.obs.tick_seq
        assert svc.metrics()["completed"] > 0
        p50_window = svc.obs.latency_quantile_ms(0.5)
        assert p50_window > 0
        svc.reset_metrics()
        m = svc.metrics()
        # CLEARED: counters, cumulative histogram, ledger, fill aggregates
        for key in ("submitted", "completed", "escalated", "ticks",
                    "classify_dispatches", "energy_total_j", "min_fill",
                    "max_fill", "tick_time_s"):
            assert not m[key], (key, m[key])
        assert svc.obs.latency.count == 0
        assert svc.scheduler.stats.ticks == 0  # legacy mirror follows
        # SURVIVING: gauges, rolling window, conservation totals, tick ids
        assert svc.obs.queue_depth.value() == 7
        assert svc.obs.latency_quantile_ms(0.5) == p50_window, \
            "reset must never blind the shed_p99_ms overload signal"
        assert m["latency_p50_ms"] == round(p50_window, 3)
        assert svc.obs.conservation() == before
        assert svc.obs.tick_seq == tick_seq


class TestBurstyTraceTelemetry:
    """Conservation/energy/event accounting under the bursty Zipf trace
    with the overload policy armed — every disposition in one run."""

    @pytest.fixture(scope="class")
    def replayed(self, tmp_path_factory):
        tr = _traces()
        td = tmp_path_factory.mktemp("telemetry")
        # query_noise high enough that below-margin requests show up in
        # burst AND calm phases: all of ok/escalated/shed in one replay
        cfg = tr.TraceConfig(seed=1, tenants=N_TENANTS, classes=N_CLASSES,
                             num_features=N_FEATURES, requests=192,
                             burst=64, calm=6, phase_ticks=2,
                             query_noise=1.2)
        spec = _spec(shed_queue=2 * SLOTS,
                     obs=spec_lib.ObsSpec(telemetry_dir=str(td)))
        svc = HybridService.from_spec(spec)
        pool = tr.TenantPool(cfg)
        pool.register_all(svc)
        svc, stats = tr.replay(svc, tr.make_trace(cfg), pool)
        return svc, stats, td

    def test_conservation_across_dispositions(self, replayed):
        svc, stats, _ = replayed
        c = svc.obs.conservation()
        m = svc.metrics()
        assert c["started"] == m["submitted"] == stats["submitted"]
        assert c["finished"] == m["completed"] == stats["completed"]
        assert c["in_flight"] == svc.scheduler.qsize == 0
        assert c["by_disposition"].get("shed", 0) == m["shed"] > 0
        assert c["by_disposition"].get("escalated", 0) == m["escalated"] > 0
        assert c["by_disposition"].get("ok", 0) > 0

    def test_tick_events_reconcile_with_registry(self, replayed):
        svc, _, td = replayed
        events = read_events(td / "events.jsonl")  # validates every line
        ticks = [e for e in events if e["kind"] == "tick"]
        m = svc.metrics()
        assert sum(e["served"] + e["expired"] for e in ticks) \
            == m["completed"]
        assert sum(e["shed"] for e in ticks) == m["shed"]
        assert sum(1 for e in ticks if e["shed_mode"] and e["fill"]) \
            == m["load_shed_ticks"]
        total_j = sum(e["energy_j"] for e in ticks)
        assert total_j == pytest.approx(m["energy_total_j"], rel=1e-9)
        assert ticks[-1]["queue_depth"] == 0
        # dispatched ticks carry their tick id; the ids are unique
        ids = [e["tick_id"] for e in ticks if e["tick_id"] >= 0]
        assert len(ids) == len(set(ids)) == int(m["ticks"])

    def test_shed_flips_logged(self, replayed):
        svc, _, td = replayed
        events = read_events(td / "events.jsonl")
        on = sum(1 for e in events if e["kind"] == "shed_on")
        off = sum(1 for e in events if e["kind"] == "shed_off")
        assert on > 0, "burst phases must trip the overload policy"
        assert on - off in (0, 1)  # may end the trace still shedding


class TestDeadlineTelemetry:
    def test_expired_requests_close_spans(self):
        svc, protos = _boot(_spec(deadline_ms=1.0))
        reqs = _mixed_requests(protos, per_tenant=4)
        for r in reqs:
            svc.submit(r)
        time.sleep(0.01)  # everything queued is now past the 1ms deadline
        responses = svc.drain()
        assert all(r.error is not None and "deadline" in r.error
                   for r in responses)
        c = svc.obs.conservation()
        assert c["by_disposition"] == {"expired": len(reqs)}
        assert c["in_flight"] == 0
        m = svc.metrics()
        assert m["expired"] == m["failed"] == len(reqs)
        # expired latencies measure the deadline, not service: kept OUT of
        # the latency histogram
        assert svc.obs.latency.count == 0

    def test_rejections_counted_not_started(self):
        svc, protos = _boot(_spec())
        with pytest.raises(AdmissionError):
            svc.submit(ClassifyRequest("nobody", np.zeros(N_FEATURES)))
        c = svc.obs.conservation()
        assert c["started"] == 0
        assert svc.metrics()["rejected"] == 1


class TestTelemetryOverhead:
    def test_bit_identical_and_under_five_pct(self):
        """Telemetry observes, never steers: the full recorder (every
        serving span on + JSONL sink) must serve bit-identical
        preds/margins/escalations and cost <5% per-request latency vs
        spans-off/no-sink. Passes are
        INTERLEAVED (base, telemetry, base, telemetry, ...) and best-of-5
        so clock drift across the run — CPU frequency, GC pressure from
        earlier suite tests — hits both arms equally instead of reading
        as overhead."""
        import gc
        import tempfile

        def build(obs):
            # measured at the serving default (64 slots): the per-tick
            # JSONL write amortizes over a full micro-batch, which is the
            # regime the 5% budget is set for
            svc, protos = _boot(_spec(slots=64, obs=obs))
            reqs = _mixed_requests(protos, per_tenant=64)
            svc.serve(reqs)  # compiles every bucketed batch shape
            return svc, reqs

        def measure(svc, reqs):
            svc.reset_metrics()
            sig = [(r.tenant_id, r.pred, r.escalated, float(r.margin))
                   for r in svc.serve(reqs)]
            # the busy clock covers the whole step() — dispatch AND the
            # per-response telemetry bookkeeping under measurement
            return svc.obs.busy_seconds.value(), sig

        base_svc, base_reqs = build(spec_lib.ObsSpec())
        with tempfile.TemporaryDirectory() as td:
            tel_svc, tel_reqs = build(spec_lib.ObsSpec(
                telemetry_dir=td, profile_annotations=True))
            base_sig = tel_sig = None
            best = None
            # true overhead is ~2% (the BENCH row tracks it), so scheduler
            # noise can eat the 5% headroom in any single attempt; a real
            # regression fails ALL attempts, noise doesn't
            for _ in range(3):
                gc.collect()  # earlier tests' garbage stays out of the timing
                base_ts, tel_ts = [], []
                for _ in range(5):
                    base_t, base_sig = measure(base_svc, base_reqs)
                    tel_t, tel_sig = measure(tel_svc, tel_reqs)
                    base_ts.append(base_t)
                    tel_ts.append(tel_t)
                overhead = min(tel_ts) / min(base_ts)
                best = overhead if best is None else min(best, overhead)
                if best < 1.05:
                    break
        assert tel_sig == base_sig, \
            "telemetry flipped a served result (must be pure observation)"
        assert best < 1.05, \
            f"telemetry overhead {100 * (best - 1):.1f}% >= 5% " \
            "(best of 3 interleaved attempts)"
