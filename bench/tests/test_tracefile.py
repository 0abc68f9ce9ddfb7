"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle gaps by host span: on a hand-made trace, and on a small
trace recorded on a TPU v5e and kept as a fixture."""
import json
import pathlib

import pytest

import tracefile

FIXTURE = pathlib.Path(__file__).resolve().parent / "fixtures"

HAND = {
    "window": [0, 1000], "devices": 1,
    "ops": [[0, "fusion.1", 100, 300, {}],
            [0, "flash_attention.2", 250, 400, {}],   # overlaps the one before
            [0, "fusion.1", 600, 700, {}],
            [0, "decode_attention.1", 900, 1100, {}]],  # runs past the window
    "annotations": [["engine_step", 50, 450, {"n": 0}],
                    ["generator_wait", 450, 880, {}],
                    ["submit", 460, 470, {}]],
}


def test_busy_union_and_idle_share():
    # busy: [100, 400] + [600, 700] + [900, 1000] = 500 ns of 1000
    assert tracefile.busy_s(HAND) == pytest.approx(500e-9)
    assert tracefile.window_s(HAND) == pytest.approx(1000e-9)
    assert tracefile.idle_share(HAND) == pytest.approx(50.0)


def test_idle_gaps_by_innermost_host_span():
    assert tracefile.idle_gaps(HAND) == [(0, 100), (400, 600), (700, 900)]
    gaps = dict(tracefile.gaps_by_host_span(HAND))
    # (0,100) mid 50: engine_step; (400,600) mid 500 and (700,900) mid 800:
    # generator_wait (submit ends at 470)
    assert gaps["engine_step"] == pytest.approx(100e-9)
    assert gaps["generator_wait"] == pytest.approx(400e-9)


def test_enclosing_ops_left_out_of_the_longest():
    tr = {"window": [0, 100], "devices": 1, "ops": [], "annotations": []}
    tr["ops"] = [[0, "fusion.3", 20, 30, {}], [0, "while.1", 10, 90, {}],
                 [0, "copy.2", 40, 80, {}]]
    tracefile._mark_parents(tr["ops"])
    assert [n for n, _ in tracefile.top_ops(tr)] == ["copy.2", "fusion.3"]
    assert tracefile.busy_s(tr) == pytest.approx(80e-9)


def test_hlo_text_names():
    assert tracefile._HLO.match("%decode_attention.5 = bf16[6,1,2048]{2,1,0} x").group(1) \
        == "decode_attention.5"


def test_top_ops_and_matching():
    top = dict(tracefile.top_ops(HAND))
    assert top["fusion.1"] == pytest.approx(300e-9)
    assert top["flash_attention.2"] == pytest.approx(150e-9)
    ops = tracefile.ops_matching(HAND, lambda n, s: n.startswith("decode"))
    assert len(ops) == 1
    assert tracefile.within(HAND["ops"], 50, 450) == HAND["ops"][:2]


def _chip_traces():
    return sorted(FIXTURE.glob("*.trace.json"))


@pytest.mark.parametrize("path", _chip_traces(), ids=lambda p: p.name)
def test_recorded_trace(path):
    """A trace from the chip: device ops fall inside the window and inside
    the host annotations that caused them; the reduction's numbers are
    the ones written beside the fixture when it was cut."""
    tr = json.loads(path.read_text())
    want = json.loads(path.with_suffix("").with_suffix(".expect.json").read_text())
    assert tracefile.busy_s(tr) == pytest.approx(want["busy_s"], rel=1e-9)
    assert tracefile.idle_share(tr) == pytest.approx(want["idle_share"], rel=1e-9)
    assert [n for n, _ in tracefile.top_ops(tr)] == [n for n, _ in want["device_ops"]]
    assert [n for n, _ in tracefile.gaps_by_host_span(tr)] == [
        n for n, _ in want["idle_gaps"]]
    lo, hi = tr["window"]
    assert all(o[3] > lo and o[2] < hi for o in tr["ops"])
    assert 0 < tracefile.busy_s(tr) <= tracefile.window_s(tr)
