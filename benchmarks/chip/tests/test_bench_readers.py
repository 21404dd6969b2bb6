"""Every per-layer reader on a trace of four chips: with four identical
``/device:TPU:<n>`` planes each reads what it reads on one of them, so no
reader counts a chip twice or leaves one out."""
import pytest

import tiny
import harness
import layerctx
import peaks
import spec
import trace_reduce as tr
import workload
from test_bench_scopes import _instruction, _profile

DECODE = "jit__fused_paged_fn"


def _ev(name, a, b):
    return tr.Ev(name, a, b - a)


def _plane():
    """Two decode-step runs of 10 ms and 12 ms, each with a paged-attention
    call per layer, a hook and an expert operation; a prefill between."""
    runs, ops = [], []
    for i, t in enumerate((1.000, 1.030)):
        dur = 0.010 + 0.002 * i
        runs.append(_ev(f"{DECODE}(7)", t, t + dur))
        ops += [_ev("_paged_attention_call.3", t, t + 0.001),
                _ev("_paged_attention_call.4", t + 0.001, t + 0.002),
                _ev("fusion.1", t + 0.002, t + 0.005),
                _ev("fusion.2", t + 0.005, t + dur)]
    runs.append(_ev("jit_prefill_chunk(3)", 1.050, 1.060))
    ops.append(_ev("fusion.9", 1.050, 1.060))
    return {tr.OPS_LINE: ops, tr.MODULES_LINE: runs}


def _record():
    reqs = [workload.Request(0.0, [1] * 20, 4, 0),
            workload.Request(0.01, [1] * 30, 4, 1)]
    sent = [harness.Sent(r, 0.9 + r.offset) for r in reqs]
    for s, adm in zip(sent, (0.95, 0.96)):
        s.admitted = adm
    rounds = [harness.Round(0.995, 1.012, 0, [21, 31]),
              harness.Round(1.025, 1.044, 0, [22, 32]),
              harness.Round(1.048, 1.062, 1, [])]
    return harness.Record(sent, rounds, 0.9, 1.1, 1.2, 0, (0.99, 1.07))


def _ctx(n_planes, out_dir, monkeypatch):
    import run
    conf = tiny._read("tiny.json")
    lay = spec.layout(conf)
    names = {f"{DECODE}(7)": [
        _instruction(1, "fusion.1", "jit(_fused_paged_fn)/lora_hook/dot"),
        _instruction(2, "fusion.2", "jit(_fused_paged_fn)/moe_experts/dot"),
        _instruction(3, "_paged_attention_call.3",
                     "jit(_fused_paged_fn)/attention/pallas_call"),
        _instruction(4, "_paged_attention_call.4",
                     "jit(_fused_paged_fn)/attention/pallas_call")]}
    (out_dir / "trace").mkdir(parents=True)
    (out_dir / "trace" / "t.xplane.pb").write_bytes(_profile(names))
    monkeypatch.setattr(run, "OUT_DIR", out_dir)
    trace = tr.Trace({f"/device:TPU:{i}": _plane()
                      for i in range(n_planes)}, [])
    return layerctx.LayerContext(_record(), lay.dims(conf),
                                 peaks.peak("TPU v5 lite"), 1, trace, lay)


def _read_all(ctx):
    bench = spec.load_benchmark()
    return {e["name"]: layerctx.load_reader(e["name"]).read(ctx)
            for e in bench["per_layer"]}


def test_four_identical_chips_read_as_one(monkeypatch, tmp_path):
    one = _read_all(_ctx(1, tmp_path / "one", monkeypatch))
    four = _read_all(_ctx(4, tmp_path / "four", monkeypatch))
    assert all(v is not None for v in one.values()), one
    assert four == pytest.approx(one, rel=1e-12)
    assert one["decode_step_device_ms"] == pytest.approx(11.0)
    assert one["hook_device_ms"] == pytest.approx(3.0)
    assert one["expert_device_ms"] == pytest.approx(6.0)
