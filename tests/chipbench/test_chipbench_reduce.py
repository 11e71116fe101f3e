"""chipbench/reduce_trace.py on hand-made events and on a small recorded
chip trace kept under chipbench/fixtures/."""

import os

import pytest

from chipbench import reduce_trace as rt
from chipbench import spec

FIXTURES = os.path.join(spec.HERE, "fixtures")


def test_union_and_subtract():
    merged = rt.union([[5, 7], [0, 2], [1, 3], [7, 8]])
    assert merged == [[0, 3], [5, 8]]
    assert rt.length(merged) == 6
    assert rt.subtract([[0, 10]], merged) == [[3, 5], [8, 10]]
    assert rt.subtract([[0, 2], [4, 9]], [[1, 5]]) == [[0, 1], [5, 9]]
    assert rt.subtract([[0, 2]], []) == [[0, 2]]


def test_busy_union_counts_overlap_once_and_ignores_wrappers():
    ops = [["while", 0, 100],          # a wrapper spanning its body
           ["fusion.1", 0, 40], ["fusion.2", 30, 30],   # overlap 30..40
           ["copy", 80, 20]]
    assert rt.busy_ns(ops) == 80       # 0..60 and 80..100, not 40+30+20+100
    assert rt.idle_share(ops) == pytest.approx(0.2)
    own = rt.self_times(ops)
    assert own["while"] == 10          # 100 less its children's 90
    assert own["fusion.1"] == 40 and own["copy"] == 20


def test_gaps_are_named_by_the_host_span_that_covers_them():
    events = {"devices": {"/device:TPU:0": [
                  ["a", 0, 10], ["b", 40, 10], ["c", 55, 10], ["d", 200, 5]]},
              "host_spans": [["dispatch", 8, 20], ["read_loss", 28, 14],
                             ["read_loss", 50, 6]]}
    gaps = rt.idle_gaps(events, count=3)
    assert [name for name, _ in gaps] == ["no_host_span", "dispatch",
                                          "read_loss"]
    assert gaps[0][1] == pytest.approx(135e-9)
    assert gaps[1][1] == pytest.approx(30e-9)
    out = rt.breakdown(events)
    assert len(out["device_ops"]) == 4 and len(out["idle_gaps"]) == 3


RECORDED = sorted(name for name in os.listdir(FIXTURES)
                  if name.endswith(".json.gz"))


@pytest.mark.parametrize("name", RECORDED)
def test_recorded_chip_trace_reduces(name):
    events = rt.load_events(os.path.join(FIXTURES, name))
    assert events["devices"] and events["host_spans"]
    for plane, ops in events["devices"].items():
        assert plane.startswith("/device:TPU:")
        busy = rt.busy_ns(ops)
        start, end = rt.window(ops)
        assert 0 < busy <= end - start
        assert busy <= sum(d for _, _, d in ops)
        assert 0.0 <= rt.idle_share(ops) < 1.0
        # self times partition the busy time of nested events
        assert sum(rt.self_times(ops).values()) >= busy * 0.999
    out = rt.breakdown(events)
    assert 1 <= len(out["device_ops"]) <= 10
    assert len(out["idle_gaps"]) <= 5
    assert rt.mean_busy_s(events) > 0 and rt.window_s(events) > 0


def test_the_recorded_one_chip_trace_reads_as_it_did_on_the_chip():
    """starcoder1b-t8192, two of the ten traced steps (PR 23's chip run)."""
    from chipbench.layer_metrics import flash_ms

    events = rt.load_events(os.path.join(
        FIXTURES, "starcoder1b-t8192.two-steps.json.gz"))
    ops = rt.first_device(events)
    assert rt.busy_ns(ops) / 1e6 == pytest.approx(602.98, abs=0.01)
    assert 100 * rt.idle_share(ops) == pytest.approx(0.41, abs=0.01)
    # 10 layers x (forward, dk/dv, dq), twice
    kernels = [name for name, _, _ in ops if flash_ms.is_flash(name)]
    assert len(kernels) == 2 * 3 * 10
    assert rt.time_of(ops, flash_ms.is_flash) / 2e6 == pytest.approx(
        102.87, abs=0.01)
    out = rt.breakdown(events)
    assert out["device_ops"][0][0].startswith("convolution_bitcast_fusion")
    # the device waits only while the host reads a loss
    assert {name for name, _ in out["idle_gaps"]} == {"read_loss"}
    # one chip: no event waits for a wire
    from chipbench.layer_metrics import collective_wait_ms
    assert not any(collective_wait_ms.is_wait(name) for name, _, _ in ops)
