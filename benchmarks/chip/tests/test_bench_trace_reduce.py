"""The reduction from a profiler trace to busy time, idle gaps and their
host attribution, on a hand-made trace and on one recorded on the chip."""
import os

import pytest

import tiny  # noqa: F401  (puts the benchmark on sys.path)
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data")


def _ev(name, a, b):
    return tr.Ev(name, a, b - a)


def test_union_merges_overlaps_and_counts_busy_once():
    evs = [_ev("x", 0.0, 1.0), _ev("y", 0.5, 1.5), _ev("z", 2.0, 3.0)]
    assert tr.union(evs) == [(0.0, 1.5), (2.0, 3.0)]
    assert tr.busy_seconds({tr.OPS_LINE: evs}) == pytest.approx(2.5)


def test_idle_gaps_are_named_by_the_overlapping_host_span():
    t = tr.Trace(
        devices={"/device:TPU:0": {tr.OPS_LINE: [_ev("a", 0.0, 1.0),
                                                 _ev("b", 3.0, 4.0)]}},
        host=[_ev("bench.step", 0.0, 1.2), _ev("bench.wait", 1.2, 3.0)])
    gaps = tr.idle_gaps(t, 0.0, 5.0)
    assert [(round(a, 6), round(b, 6), n) for a, b, n in gaps] == [
        (1.0, 3.0, "bench.wait"), (4.0, 5.0, "outside bench spans")]
    bd = tr.breakdown(t, 0.0, 5.0)
    assert bd["idle_gaps"][0][0] == "bench.wait"
    assert bd["device_ops"][0][1] == pytest.approx(1.0)


# the recorded trace is of mixtral-8x7b-disagg.chat: 2 layers, so two
# paged-attention calls inside every run of the decode step
RECORDED_LAYERS = 2


def test_recorded_chip_trace_reduces():
    path = tr.find_xplane(RECORDED)
    if path is None:
        pytest.skip("no trace recorded on the chip is committed under "
                    "tests/data yet (record one with run.py --trace 1 "
                    "--seconds 2 --keep-trace benchmarks/chip/tests/data)")
    t = tr.load(path)
    assert t.devices, "no TPU plane in the recorded trace"
    lines = next(iter(t.devices.values()))
    lo, hi = tr.window(t)
    busy = tr.busy_seconds(lines)
    assert 0 < busy <= hi - lo
    import layerctx
    steps = tr.matching(lines[tr.MODULES_LINE], layerctx.DECODE_MODULE)
    kernels = tr.matching(lines[tr.OPS_LINE], layerctx.PAGED_KERNEL)
    assert steps and kernels
    # the kernel's name matches its own calls and nothing else of the step
    for st in steps:
        inside = [k for k in kernels
                  if k.start >= st.start and k.end <= st.end]
        assert len(inside) == RECORDED_LAYERS, (st.name, len(inside))
    assert len(kernels) <= RECORDED_LAYERS * (len(steps) + 2)
    assert any(h.name == "bench.step" for h in t.host)
    bd = tr.breakdown(t, lo, hi)
    assert 0 < len(bd["device_ops"]) <= 10
    assert all(s > 0 for _, s in bd["idle_gaps"])
