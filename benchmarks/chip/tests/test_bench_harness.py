"""One cell's loop driven through the harness's functions at a toy size on
the CPU: the open loop, the record, the end-to-end arithmetic and the
reference check. Timings here are CPU timings: nothing is printed."""
import numpy as np
import pytest

import tiny


def _run(monkeypatch, seed, seconds=3.0):
    import harness
    import workload
    conf = tiny.install(monkeypatch)
    traffic = workload.load_traffic("tiny")
    b = harness.build(conf, traffic, seed)
    harness.warm_up(b, seed)
    reqs = workload.generate(traffic, rate=tiny.RATE, seconds=seconds,
                             seed=seed, vocab=b.dims["vocab"],
                             n_adapters=b.dims["n_adapters"])
    rec = harness.drive(b, reqs, seconds, 30.0)
    harness.release(b)
    return b, rec


def test_open_loop_window_serves_every_request(monkeypatch):
    import e2e
    b, rec = _run(monkeypatch, 2**31 + 12345)
    assert rec.sent and all(e2e.done(s) for s in rec.sent)
    assert rec.compiles_in_window == 0
    # sends are open-loop: each went out at or after its scheduled time
    assert all(s.submitted >= s.sched for s in rec.sent)
    # the first token comes after the send, the rest in order
    for s in rec.sent:
        assert s.stamps[0] >= s.sched and s.stamps == sorted(s.stamps)
        assert len(s.tokens) == s.req.output_len
    m = e2e.metrics(rec, setup_s=1.0)
    assert set(m) == {"ttft_p90_ms", "tpot_p90_ms", "output_tokens_per_s",
                      "slo_attainment", "setup_s"}
    assert all(np.isfinite(v) and v > 0 for v in m.values())


def test_served_tokens_agree_with_the_reference(monkeypatch):
    import check
    import spec
    b, rec = _run(monkeypatch, 77)
    picked = check.sample(rec, 77)
    assert max(len(s.tokens) for s in picked) == \
        max(len(s.tokens) for s in rec.sent)
    g = check.gaps(b, picked, pad_to=spec.max_len(b.conf, b.longest))
    verdict = check.decide(rec, g["served"], {"p98_logit_gap": 0.05})
    assert verdict["correct"], verdict["checks"]


def test_same_work_for_every_seed():
    import workload
    tr = workload.load_traffic("chat")
    a = workload.generate(tr, rate=5.0, seconds=30.0, seed=1, vocab=32000,
                          n_adapters=8)
    b = workload.generate(tr, rate=5.0, seconds=30.0, seed=2**33 + 7,
                          vocab=32000, n_adapters=8)

    def work_of(rs):
        return (sorted(len(r.prompt) for r in rs),
                sorted(r.output_len for r in rs),
                sorted(np.bincount([r.adapter for r in rs], minlength=8)))

    assert len(a) == len(b) == 150
    assert work_of(a) == work_of(b)
    # the same schedule: sizes and send times in the same order
    assert [(r.offset, len(r.prompt), r.output_len) for r in a] == \
        [(r.offset, len(r.prompt), r.output_len) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert max(r.offset for r in a) < 30.0
    assert sum(np.diff([0.0] + [r.offset for r in a])) == \
        pytest.approx(max(r.offset for r in a))
