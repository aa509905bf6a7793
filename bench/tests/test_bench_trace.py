"""The trace reduction (bench/trace.py) on hand-made intervals and on a
small trace recorded on the chip (bench/tests/data/)."""
import glob
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _tr():
    # window 0..100 ns on two devices; device 0: a fusion 10..30 that a
    # nested op 15..20 sits inside, a kernel 40..50, a collective 60..90
    # half covered by a fusion 70..75; device 1: one op -10..20 that
    # starts before the window
    return {"window": [0.0, 100.0],
            "devices": {
                "0": [["fusion.1", 10, 20], ["copy.2", 15, 5],
                      ["_topk_kernel", 40, 10],
                      ["collective-permute-start.3", 60, 30],
                      ["fusion.4", 70, 5]],
                "1": [["fusion.1", -10, 30]]},
            "host": [["bench.window", 0, 100], ["bench.dispatch", 0, 35],
                     ["bench.wait", 35, 65]]}


def test_busy_union_and_idle_share():
    tr = _tr()
    assert trace.busy_ns(tr, "0") == 20 + 10 + 30
    assert trace.busy_ns(tr, "1") == 20
    assert trace.mean_busy_s(tr) == pytest.approx((60 + 20) / 2 / 1e9)
    assert trace.idle_pct(tr) == pytest.approx(100 * (1 - 40 / 100))


def test_kernel_time_by_name():
    t, n = trace.kernel_ns(_tr(), "0", r"_topk_kernel|_qdq_kernel")
    assert (t, n) == (10, 1)


def test_exposed_collective_time():
    # 30 ns of collective, 5 of them under fusion.4
    assert trace.exposed_ns(_tr(), "0") == 25


def test_breakdown_names_ops_and_gaps():
    b = trace.breakdown(_tr())
    ops = dict(b["device_ops"])
    # self time: fusion.4 runs nested inside the collective's interval
    assert ops["collective-permute-start"] == pytest.approx(25 / 2 / 1e9)
    assert ops["fusion"] == pytest.approx((15 + 5 + 20) / 2 / 1e9)
    gaps = b["idle_gaps"]
    # device 0 idles 0..10, 30..40, 50..60 and 90..100
    assert sorted(g[1] for g in gaps) == pytest.approx([1e-8] * 4)
    assert gaps and all(g[0].startswith("bench.") for g in gaps)


def _recorded():
    paths = sorted(glob.glob(os.path.join(DATA, "trace-*.json")))
    if not paths:
        pytest.fail("no recorded trace in bench/tests/data")
    with open(paths[0]) as f:
        return json.load(f)


def test_recorded_chip_trace_reduces_to_its_recorded_numbers():
    rec = _recorded()
    tr, want = rec["trace"], rec["reduced"]
    assert trace.mean_busy_s(tr) == pytest.approx(want["busy_s"])
    assert trace.idle_pct(tr) == pytest.approx(want["idle_pct"])
    t, n = trace.kernel_ns(tr, "0", want["kernel_pattern"])
    assert (t, n) == (pytest.approx(want["kernel_ns"]), want["kernel_count"])
    assert trace.exposed_ns(tr, "0") == pytest.approx(want["exposed_ns"])
    assert 0.0 <= trace.idle_pct(tr) < 100.0


def test_recorded_busy_union_against_a_bitmap():
    """The same busy time by painting every op onto a 10 ns grid."""
    import numpy as np
    tr = _recorded()["trace"]
    lo, hi = tr["window"]
    grid = np.zeros(int((hi - lo) // 10) + 1, bool)
    for _, start, dur in tr["devices"]["0"]:
        a = int(max(start - lo, 0) // 10)
        b = int(min(start + dur - lo, hi - lo) // 10)
        grid[a:b] = True
    ops = len(tr["devices"]["0"])
    assert trace.busy_ns(tr, "0") == pytest.approx(grid.sum() * 10,
                                                   abs=20 * ops)
