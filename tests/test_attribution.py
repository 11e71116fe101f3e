"""Step-time attribution tests (observability tentpole, round 10).

Pins the span/attribution subsystem's guarantees: flight events pair
into nested per-step span trees; the bucket decomposition is EXACT
(disjoint intervals summing to the measured step time); the NTP-style
clock math recovers a known offset from min-RTT samples; the cross-rank
critical path descends into the gating rank's slowest spans and hops
across ranks at collectives; the Perfetto export round-trips through
``json``; the online :class:`AttributionWatch` flags per-bucket
regressions against its rolling median; and the new flight-recorder
surfaces (``dropped_events``, ``events_since``, monotonic stamps)
behave.  The committed golden dumps (``tests/data/attr_flight_*.json``)
anchor the end-to-end merge the same way ``flight_*.json`` anchors the
hang report.
"""

import json
import os

import pytest

from chainermn_tpu import observability as obs
from chainermn_tpu.observability import (
    AttributionWatch,
    BUCKETS,
    FlightRecorder,
    MetricsRegistry,
    attribute_step,
    attribution_report,
    build_step_trees,
    clock_handshake,
    critical_path,
    merge_ranks,
    offset_from_samples,
    reset_flight_recorder,
    span_summary,
    to_trace_events,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "tests", "data")


@pytest.fixture(autouse=True)
def clean_recorder():
    reset_flight_recorder()
    yield
    reset_flight_recorder()
    obs.disable()


def _stream(base=1000.0, rank=0, dcn_s=0.006):
    """One rank's synthetic two-step event stream with fixed clocks:
    per step 4ms data_load + 2ms host_put, then a device window holding
    a 4ms ICI stage and a ``dcn_s`` DCN stage."""
    evs = []
    seq = 0

    def ev(kind, ts, **f):
        nonlocal seq
        evs.append({"kind": kind, "ts": ts, "seq": seq, **f})
        seq += 1

    for it in (1, 2):
        t0 = base + (it - 1) * 0.1
        ev("phase", t0, phase="data_load", iteration=it - 1)
        ev("phase", t0 + 0.004, phase="host_put", iteration=it - 1)
        ev("phase", t0 + 0.006, phase="dispatch", iteration=it - 1)
        stage = dict(plan="hier", op="all-reduce", nbytes=4096)
        ev("plan_stage_begin", t0 + 0.007, stage=0, scope="intra",
           link="ici", **stage)
        ev("plan_stage_end", t0 + 0.011, stage=0, scope="intra",
           link="ici", **stage)
        ev("plan_stage_begin", t0 + 0.011, stage=1, scope="inter",
           link="dcn", op_seq=it, **stage)
        ev("plan_stage_end", t0 + 0.011 + dcn_s, stage=1, scope="inter",
           link="dcn", op_seq=it, **stage)
        ev("phase", t0 + 0.012 + dcn_s, phase="device_block",
           iteration=it - 1)
        ev("step", t0 + 0.016 + dcn_s, dur_s=0.016 + dcn_s, iteration=it)
    return evs


# ---- span trees -------------------------------------------------------------

class TestSpanTrees:
    def test_two_steps_with_nested_phases_and_stages(self):
        trees = build_step_trees(_stream(), rank=3)
        assert len(trees) == 2
        step = trees[0]
        assert step.kind == "step" and step.rank == 3
        assert step.dur_s == pytest.approx(0.022)
        phases = [c for c in step.children if c.kind == "phase"]
        assert [p.meta["phase"] for p in phases] == \
            ["data_load", "host_put", "dispatch", "device_block"]
        # plan stages nest under the dispatch phase they fall inside
        dispatch = phases[2]
        stages = [c for c in dispatch.children if c.kind == "plan_stage"]
        assert [s.meta["scope"] for s in stages] == ["intra", "inter"]
        assert stages[0].meta["link"] == "ici"
        assert stages[1].dur_s == pytest.approx(0.006)

    def test_offset_shifts_every_span(self):
        t0 = build_step_trees(_stream(), rank=0)[0]
        t1 = build_step_trees(_stream(), rank=0, offset=0.5)[0]
        for a, b in zip(t0.walk(), t1.walk()):
            assert b.t0 == pytest.approx(a.t0 + 0.5)
            assert b.t1 == pytest.approx(a.t1 + 0.5)

    def test_unmatched_begin_is_dropped(self):
        evs = _stream()
        evs = [e for e in evs if not (e["kind"] == "plan_stage_end"
                                      and e.get("stage") == 1)]
        trees = build_step_trees(evs)
        kinds = [sp.meta.get("scope") for t in trees for sp in t.walk()
                 if sp.kind == "plan_stage"]
        assert kinds == ["intra", "intra"]  # the inter begins never pair


# ---- bucket decomposition ---------------------------------------------------

class TestAttributeStep:
    def test_buckets_sum_exactly_and_split_links(self):
        step = build_step_trees(_stream())[0]
        a = attribute_step(step)
        assert set(a["buckets"]) == set(BUCKETS)
        assert a["sum_frac"] == pytest.approx(1.0)
        assert sum(a["buckets"].values()) == pytest.approx(a["step_s"])
        assert a["buckets"]["ici_comm"] == pytest.approx(0.004)
        assert a["buckets"]["dcn_comm"] == pytest.approx(0.006)
        assert a["buckets"]["host_input"] == pytest.approx(0.006)
        assert a["buckets"]["checkpoint"] == 0.0

    def test_bare_step_is_all_compute(self):
        evs = [{"kind": "step", "ts": 10.0, "dur_s": 0.02, "iteration": 1,
                "seq": 0}]
        a = attribute_step(build_step_trees(evs)[0])
        assert a["buckets"]["compute"] == pytest.approx(0.02)
        assert a["sum_frac"] == pytest.approx(1.0)


# ---- clock math -------------------------------------------------------------

class TestClockMath:
    def test_offset_recovered_from_min_rtt_sample(self):
        true_off = 0.25
        samples = []
        for i, rtt in enumerate((0.030, 0.002, 0.040)):
            t_send = 100.0 + i
            t_peer = t_send + rtt / 2 + true_off   # symmetric network
            samples.append((t_send, t_peer, t_send + rtt))
        off, rtt = offset_from_samples(samples)
        assert off == pytest.approx(true_off, abs=1e-9)
        assert rtt == pytest.approx(0.002)

    def test_handshake_degenerate_single_host(self):
        hs = clock_handshake(None)
        assert hs == {"rank": 0, "offset_s": 0.0, "rtt_s": 0.0, "rounds": 0}

    def test_negative_offset_recovered(self):
        # a peer clock RUNNING AHEAD yields a negative offset; the math
        # must not assume a sign
        true_off = -0.4
        samples = [(50.0, 50.0 + 0.001 + true_off, 50.002)]
        off, rtt = offset_from_samples(samples)
        assert off == pytest.approx(true_off, abs=1e-9)

    def test_merge_ranks_applies_negative_offset(self):
        base = merge_ranks({0: _stream(), 1: _stream(rank=1)})
        shifted = merge_ranks({0: _stream(), 1: _stream(rank=1)},
                              offsets={1: -0.35})
        for a, b in zip(base[0][0].walk(), shifted[0][0].walk()):
            assert b.t0 == pytest.approx(a.t0)  # rank 0 untouched
        for a, b in zip(base[1][0].walk(), shifted[1][0].walk()):
            assert b.t0 == pytest.approx(a.t0 - 0.35)
            assert b.t1 == pytest.approx(a.t1 - 0.35)
            assert b.t1 >= b.t0

    def test_offset_exceeding_span_durations_keeps_geometry(self):
        # a 5s skew dwarfs every ms-scale span: the shift must preserve
        # nesting and the exact bucket decomposition, not just ordering
        trees = merge_ranks({0: _stream(), 1: _stream(rank=1)},
                            offsets={1: 5.0})
        step = trees[1][0]
        kinds = [sp.kind for sp in step.walk()]
        ref = [sp.kind for sp in merge_ranks(
            {1: _stream(rank=1)})[1][0].walk()]
        assert kinds == ref  # same tree shape after the big shift
        a = attribute_step(step)
        assert a["sum_frac"] == pytest.approx(1.0)
        assert a["buckets"]["dcn_comm"] == pytest.approx(0.006)


# ---- cross-rank merge + critical path --------------------------------------

class TestCriticalPath:
    def test_descends_into_gating_rank_and_names_spans(self):
        trees = merge_ranks({0: _stream(dcn_s=0.006),
                             1: _stream(rank=1, dcn_s=0.012)})
        path = critical_path({r: steps[0] for r, steps in trees.items()})
        assert path[0]["rank"] == 1 and path[0]["kind"] == "step"
        # the path reaches rank 1's slow DCN hop and names it
        assert any(e["rank"] == 1 and e["kind"] == "plan_stage"
                   and e["dur_s"] == pytest.approx(0.012) for e in path)
        assert all("name" in e and "rank" in e for e in path)

    def test_collective_hop_blames_last_entrant(self):
        # rank 0 enters the inter stage 4ms late -> rank 1's wait is
        # attributed to rank 0 via the matching (kind, op, op_seq) span
        late = _stream(dcn_s=0.002)
        for e in late:
            if e["kind"].startswith("plan_stage") and e.get("stage") == 1:
                e["ts"] += 0.004
        trees = merge_ranks({0: late, 1: _stream(rank=1, dcn_s=0.006)})
        path = critical_path({r: steps[0] for r, steps in trees.items()})
        hops = [e for e in path if "blocked_by_rank" in e]
        assert hops and hops[0]["blocked_by_rank"] == 0

    def test_report_over_golden_dumps(self):
        dumps = [json.load(open(os.path.join(DATA, f"attr_flight_{r}.json")))
                 for r in (0, 1)]
        rep = attribution_report(
            {d["rank"]: d["events"] for d in dumps},
            offsets={d["rank"]: d["clock"]["offsets"]["0"]["offset_s"]
                     for d in dumps})
        assert rep["n_ranks"] == 2 and rep["n_steps"] == 2
        for st in rep["steps"]:
            for a in st["ranks"].values():
                assert a["sum_frac"] == pytest.approx(1.0, abs=1e-6)
            # rank 1's synthetic DCN hop is the slow one
            assert st["ranks"]["1"]["buckets"]["dcn_comm"] > \
                st["ranks"]["0"]["buckets"]["dcn_comm"]
        cp = rep["steps"][-1]["critical_path"]
        assert cp[0]["rank"] == 1
        assert any(e["kind"] == "plan_stage" for e in cp)


# ---- exports ----------------------------------------------------------------

class TestExports:
    def test_trace_events_round_trip(self):
        trees = merge_ranks({0: _stream(), 1: _stream(rank=1)})
        doc = json.loads(json.dumps(to_trace_events(trees)))
        xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert xs and all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
        assert {e["pid"] for e in xs} == {0, 1}
        # one metadata pair per rank names the process
        names = [e for e in doc["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"]
        assert {m["args"]["name"] for m in names} == {"rank0", "rank1"}
        # step and plan_stage ride distinct lanes
        tids = {e["cat"]: e["tid"] for e in xs}
        assert tids["step"] != tids["plan_stage"]

    def test_span_summary_top_spans(self):
        s = span_summary(_stream(), rank=0, k=2)
        assert s["steps"] == 2
        assert s["mean_step_s"] == pytest.approx(0.022)
        assert s["top_spans"] and len(s["top_spans"]) <= 2
        assert all(sp["kind"] != "step" for sp in s["top_spans"])
        assert s["top_spans"][0]["frac_of_step"] <= 1.0


# ---- online regression watch ------------------------------------------------

class TestAttributionWatch:
    def _attr(self, it, dcn=0.005):
        b = {k: 0.0 for k in BUCKETS}
        b.update(compute=0.010, dcn_comm=dcn)
        return {"rank": 0, "iteration": it, "step_s": sum(b.values()),
                "buckets": b, "sum_frac": 1.0}

    def test_flags_bucket_regression_once_baselined(self):
        reg = MetricsRegistry()
        fr = FlightRecorder()
        w = AttributionWatch(registry=reg, flight=fr, min_baseline=4,
                             factor=2.0, min_seconds=1e-3)
        for i in range(6):
            assert w.observe(self._attr(i)) == []
        flagged = w.observe(self._attr(6, dcn=0.050))
        assert [f["bucket"] for f in flagged] == ["dcn_comm"]
        assert flagged[0]["ratio"] == pytest.approx(10.0)
        assert reg.get("attribution_regressions_total").value(
            bucket="dcn_comm") == 1
        evs = [e for e in fr.snapshot()
               if e["kind"] == "attribution_regression"]
        assert evs and evs[0]["iteration"] == 6
        # gauges track the latest step either way
        assert reg.get("attribution_bucket_seconds").value(
            bucket="dcn_comm") == pytest.approx(0.050)

    def test_quiet_below_min_baseline_and_min_seconds(self):
        w = AttributionWatch(registry=MetricsRegistry(),
                             flight=FlightRecorder(), min_baseline=4,
                             min_seconds=1.0)
        for i in range(4):
            assert w.observe(self._attr(i)) == []
        # 10x bucket jump but below min_seconds -> not flagged
        assert w.observe(self._attr(4, dcn=0.050)) == []

    def test_factor_must_exceed_one(self):
        with pytest.raises(ValueError):
            AttributionWatch(registry=MetricsRegistry(),
                             flight=FlightRecorder(), factor=1.0)


# ---- flight recorder surfaces -----------------------------------------------

class TestRecorderSurfaces:
    def test_dropped_events_counts_ring_overwrites(self):
        fr = FlightRecorder(capacity=4)
        for i in range(10):
            fr.record("ev", i=i)
        assert fr.dropped_events == 6
        assert fr.collective_state()["dropped_events"] == 6

    def test_events_since_is_strictly_after(self):
        fr = FlightRecorder()
        fr.record("a")
        seq = fr.snapshot()[-1]["seq"]
        fr.record("b")
        fr.record("c")
        assert [e["kind"] for e in fr.events_since(seq)] == ["b", "c"]
        assert fr.events_since(10 ** 9) == []

    def test_events_carry_monotonic_stamps(self):
        fr = FlightRecorder()
        fr.record("x")
        ev = fr.snapshot()[0]
        assert "mono" in ev and ev["mono"] > 0
