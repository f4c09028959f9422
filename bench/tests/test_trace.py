"""The trace reduction: clock alignment, busy union, idle share, kernel
events, the share of busy time in collectives, self times and named idle
gaps -- on a small hand-made trace whose answers are worked out below, and
on traces recorded on a TPU v5e (``data/``, as ``trace.collect`` read
them: each clock its own)."""
import pytest

import harness

T = harness.load_module(harness.BENCH / "trace.py")
DATA = harness.BENCH / "tests" / "data"
MS = 1_000_000   # ns


def ev(name, start_ms, end_ms):
    return T.Event(name, int(start_ms * MS), int(end_ms * MS))


@pytest.fixture
def hand():
    # Window 0-100 ms.  Device 0 runs a loop (10-40) holding two kernels
    # (12-20, 22-30), a fusion (50-60), an all-reduce (58-70) overlapping
    # it, and an op that starts before the window (-5 to 5).  Device 1
    # runs one op 0-30.
    dev0 = [ev("while.1", 10, 40), ev("ec_matmul_kernel", 12, 20),
            ev("ec_matmul_kernel", 22, 30), ev("fusion.3", 50, 60),
            ev("all-reduce.1", 58, 70), ev("copy.9", -5, 5)]
    dev1 = [ev("fusion.8", 0, 30)]
    host = [ev("bench.window", 0, 100), ev("bench.send", 0, 9),
            ev("bench.wait", 10, 44), ev("bench.input", 80, 95)]
    return T.Trace(window=(0, 100 * MS), host=host,
                   devices={"/device:TPU:0": dev0, "/device:TPU:1": dev1})


def test_busy_and_idle(hand):
    # device 0: [0,5] + [10,40] + [50,70] = 55 ms; device 1: 30 ms.
    assert T.busy_s(hand) == pytest.approx((55 + 30) / 2 * 1e-3)
    assert T.idle_share(hand) == pytest.approx(1 - 42.5 / 100)
    assert hand.window_s == pytest.approx(0.1)


def test_kernel_events(hand):
    hits = T.kernel_events(hand, r"ec_matmul")
    assert [e.seconds for e in hits["/device:TPU:0"]] == pytest.approx(
        [8e-3, 8e-3])
    assert hits["/device:TPU:1"] == []


def test_collective_share(hand):
    # all-reduce 58-70 is 12 ms of the two devices' 85 ms busy.
    assert T.busy_share_of(hand, r"all-reduce") == pytest.approx(12 / 85)
    assert T.busy_share_of(hand, r"all-gather") is None


def test_self_times_subtract_nested_ops(hand):
    st = T.self_times(hand.devices["/device:TPU:0"])
    assert st["while.1"] == pytest.approx(14e-3)
    assert st["ec_matmul_kernel"] == pytest.approx(16e-3)
    top = dict(T.top_ops(hand))
    # averaged over the two devices; copy.9 starts before the window.
    assert top["ec_matmul_kernel"] == pytest.approx(8e-3)
    assert "copy.9" not in top


def test_idle_gaps_named_by_host_span(hand):
    gaps = T.idle_gaps(hand)
    # device 0 idle: 5-10 (midpoint 7.5: the send), 40-50 (midpoint 45:
    # the wait has just ended), 70-100 (midpoint 85: input).
    assert gaps[0] == ["bench.input", pytest.approx(30e-3)]
    assert gaps[1] == ["outside any benchmark span", pytest.approx(10e-3)]
    assert gaps[2] == ["bench.send", pytest.approx(5e-3)]


def test_aligned_moves_each_device_to_the_first_send(hand):
    # The window's first send starts at 2 ms; device 0's first op at -5 ms
    # and device 1's at 0 ms: each device is shifted by its own offset.
    host = [ev("bench.input", -3, -1)] + [
        ev("bench.send", 2, 9) if e.name == "bench.send" else e
        for e in hand.host]
    tr = T.aligned(T.Trace(window=hand.window, host=host,
                           devices=hand.devices))
    assert [e.start for e in tr.devices["/device:TPU:0"]] == [
        e.start + 7 * MS for e in hand.devices["/device:TPU:0"]]
    assert tr.devices["/device:TPU:1"][0] == ev("fusion.8", 2, 32)
    assert tr.host == host and tr.window == hand.window


def test_aligned_needs_a_send(hand):
    host = [e for e in hand.host if e.name != "bench.send"]
    with pytest.raises(RuntimeError, match="bench.send"):
        T.aligned(T.Trace(window=hand.window, host=host,
                          devices=hand.devices))


RECORDED = sorted(DATA.glob("trace_*.json"))
tier1 = harness.load_module(harness.BENCH / "metrics" / "tier1_roofline.py")


def _busy_by_sweep(trace):
    """Busy seconds by a sweep over start and end points, apart from
    ``trace.merged``."""
    lo, hi = trace.window
    total = 0.0
    for evs in trace.devices.values():
        inside = [e for e in evs if e.end > lo and e.start < hi]
        points = sorted([(max(e.start, lo), 1) for e in inside]
                        + [(min(e.end, hi), -1) for e in inside])
        depth, since, busy = 0, None, 0
        for t, d in points:
            if depth == 0 and d == 1:
                since = t
            depth += d
            if depth == 0:
                busy += t - since
        total += busy * 1e-9
    return total / len(trace.devices)


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace_reduces(path):
    # A quarter of a second of a resident cell's traced window, recorded
    # on a TPU v5e, op names as read_xplane keeps them.
    tr = T.aligned(T.load(path))
    busy = T.busy_s(tr)
    assert busy == pytest.approx(_busy_by_sweep(tr), rel=1e-12)
    assert 0 < busy < tr.window_s
    assert T.idle_share(tr) == pytest.approx(1 - busy / tr.window_s)
    # the idle gaps and the busy intervals tile the window
    first = sorted(tr.devices)[0]
    gaps = T.idle_gaps(tr, count=10 ** 9)
    busy0 = sum(e - s for s, e in T.merged(tr.devices[first], tr.window))
    assert sum(g for _, g in gaps) + busy0 * 1e-9 == pytest.approx(
        tr.window_s, rel=1e-9)
    assert all(s >= 0 for _, s in T.top_ops(tr, count=10 ** 9))
    # one chip: no collective ran
    assert T.busy_share_of(tr, r"^%all-reduce") is None


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_tier1_kernel_events(path):
    tr = T.aligned(T.load(path))
    hits = T.kernel_events(tr, tier1.KERNEL)["/device:TPU:0"]
    names = {e.name.split(".")[0] for e in hits}
    assert names == {"%ec_matmul"}, names
    # every tier-1 call of a resident 32,768^2 image reads 8 GiB: 18 ms
    # here, the bytes bound being 10.5 ms
    assert all(17e-3 < e.seconds < 20e-3 for e in hits)
    cols = 64 if "mvm" in path.name else 1
    work = {"flop": 4 * 32768 ** 2 * cols,
            "bytes": 8 * 32768 ** 2 + 12 * 32768 * cols}
    least, bound = tier1.least_seconds(work, {"hbm_bytes_per_s": 819e9,
                                              "flops_bf16": 197e12})
    share = len(hits) * least / sum(e.seconds for e in hits)
    assert bound == "bytes" and 0.5 < share < 0.65


# The op that starts each request on the device, per recorded cell: the
# DAC scale of an MVM; the zero initial guess of a CG solve.
FIRST_OP = {"trace_v5e_resident_mvm_b64.json": "%abs_reduce_fusion fusion",
            "trace_v5e_resident_cg.json": "%broadcast_in_dim.1 broadcast"}


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_trace_aligned_follows_the_sends(path):
    # Aligned on the first request, every later request's first op starts
    # within a few tenths of a millisecond of the host's send; on the
    # profiler's own clocks they lay over half a millisecond off.
    def leads(tr):
        sends = sorted(e.start for e in tr.host if e.name == "bench.send")
        firsts = sorted(e.start for e in tr.devices["/device:TPU:0"]
                        if e.name == FIRST_OP[path.name])
        return [(f - s) * 1e-9 for s, f in zip(sends, firsts)]

    raw = T.load(path)
    aligned = leads(T.aligned(raw))
    assert len(aligned) >= 3 and aligned[0] == pytest.approx(0, abs=1e-6)
    assert all(-0.2e-3 < d < 0.3e-3 for d in aligned), aligned
    assert all(abs(d) > 0.5e-3 for d in leads(raw)), leads(raw)
