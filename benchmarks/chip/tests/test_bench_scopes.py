"""The decode step's device time split by the program's named scopes
(scopes.py) and the two readers built on it, on hand-made traces and
profiles and on traces recorded on the chip: ``data/`` from the program
before it named its scopes, ``scoped/`` from the program with them."""
import os
import shutil

import pytest

import tiny  # noqa: F401  (puts the benchmark on sys.path)
import scopes
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))
UNSCOPED_TRACE = os.path.join(HERE, "data")
SCOPED_TRACE = os.path.join(HERE, "scoped")
DECODE = "jit__fused_paged_fn"


def _ev(name, a, b):
    return tr.Ev(name, a, b - a)


def _trace(ops, runs):
    return tr.Trace(devices={"/device:TPU:0": {tr.OPS_LINE: ops,
                                               tr.MODULES_LINE: runs}},
                    host=[])


def test_scope_of_takes_the_innermost_named_scope():
    assert scopes.scope_of(
        "jit(f)/moe_experts/lora_hook/dot_general") == "lora_hook"
    assert scopes.scope_of(
        "jit(_fused_paged_fn)/attention/jit(_paged_attention_call)/"
        "pallas_call") == "attention"
    assert scopes.scope_of("jit(f)/jit(_take)/gather") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED
    assert scopes.scope_of(None) == scopes.UNSCOPED


def test_decode_split_joins_each_run_to_its_own_program():
    # two decode buckets reuse the name "fusion.1" for different work,
    # and a prefill run in between is not a decode step
    runs = [_ev(f"{DECODE}(11)", 0.0, 1.0), _ev(f"{DECODE}(22)", 2.0, 3.0),
            _ev("jit_prefill_chunk(5)", 4.0, 5.0)]
    ops = [_ev("fusion.1", 0.0, 0.4), _ev("copy.2", 0.4, 0.8),
           _ev("fusion.9", 0.8, 1.0),
           _ev("fusion.1", 2.0, 2.5), _ev("fusion.3", 2.5, 3.0),
           _ev("fusion.1", 4.0, 5.0)]
    names = {
        f"{DECODE}(11)": {"fusion.1": "jit(_fused_paged_fn)/lora_hook/x",
                          "copy.2": "jit(_fused_paged_fn)/moe_experts/y"},
        f"{DECODE}(22)": {"fusion.1": "jit(_fused_paged_fn)/attention/x",
                          "fusion.3": "jit(_fused_paged_fn)/lm_head/z"},
        "jit_prefill_chunk(5)": {
            "fusion.1": "jit(prefill_chunk)/while/body/moe_experts/z"},
    }
    split = scopes.decode_split(_trace(ops, runs), names)
    assert split == pytest.approx({"lora_hook": 0.2, "moe_experts": 0.2,
                                   "attention": 0.25, "lm_head": 0.25,
                                   scopes.UNSCOPED: 0.1})


def test_decode_split_reads_nothing_without_named_scopes():
    runs = [_ev(f"{DECODE}(1)", 0.0, 1.0)]
    ops = [_ev("fusion.1", 0.0, 1.0)]
    plain = {f"{DECODE}(1)": {"fusion.1": "jit(_fused_paged_fn)/dot"}}
    assert scopes.decode_split(_trace(ops, runs), plain) is None
    assert scopes.decode_split(_trace(ops, []), plain) is None


# ---- a profile's /host:metadata plane, encoded by hand ---------------- #
def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _f(field, value):
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def _instruction(iid, name, op_name=None, operands=()):
    msg = _f(1, name) + _f(35, iid)
    if op_name:
        msg += _f(7, _f(2, op_name))          # OpMetadata.op_name
    if operands:
        msg += _f(36, b"".join(_varint(o) for o in operands))   # packed
    return msg


def _profile(programs):
    """XSpace bytes: one metadata plane holding an HloProto per program,
    beside a device plane the reader must skip."""
    stat = _f(1, 7) + _f(2, "Hlo Proto")      # XStatMetadata id, name
    plane = _f(2, "/host:metadata") + _f(5, _f(1, 7) + _f(2, stat))
    for i, (name, instrs) in enumerate(programs.items()):
        comp = _f(1, "main") + b"".join(_f(2, ins) for ins in instrs)
        hlo = _f(1, _f(1, name) + _f(3, comp))
        meta = _f(1, i + 1) + _f(2, name) + _f(5, _f(1, 7) + _f(6, hlo))
        plane += _f(4, _f(1, i + 1) + _f(2, meta))
    other = _f(2, "/device:TPU:0")
    return _f(1, other) + _f(1, plane)


def test_program_op_names_charge_a_compiler_instruction_to_its_user(
        tmp_path):
    lora = "jit(_fused_paged_fn)/lora_hook/dot_general"
    instrs = [
        _instruction(4, "copy-start.4"),
        _instruction(2, "copy-done.2", operands=[4]),
        _instruction(1, "fusion.1", lora, operands=[2, 3]),
        _instruction(3, "param.3", "view[1]"),
        _instruction(5, "constant.5"),        # no metadata, no user
    ]
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(_profile({f"{DECODE}(9)": instrs}))
    got = scopes.program_op_names(str(path))
    assert got == {f"{DECODE}(9)": {"fusion.1": lora, "copy-done.2": lora,
                                     "copy-start.4": lora,
                                     "param.3": "view[1]"}}
    # without inheritance only the instructions' own metadata counts
    own = scopes.program_op_names(str(path), inherit=False)
    assert own == {f"{DECODE}(9)": {"fusion.1": lora, "param.3": "view[1]"}}


# ------------------------ traces recorded on the chip ------------------ #
def _reader(name):
    import layerctx
    return layerctx.load_reader(name)


def _ctx_over(path, monkeypatch, tmp_path):
    """A LayerContext over the recorded trace, with the profile where
    run.py leaves a traced run's."""
    import layerctx
    import run
    dest = tmp_path / "trace"
    dest.mkdir()
    shutil.copy(path, dest)
    monkeypatch.setattr(run, "OUT_DIR", tmp_path)
    return layerctx.LayerContext(None, {}, {}, 1, tr.load(path))


def test_program_without_named_scopes_reads_nothing(monkeypatch, tmp_path):
    path = tr.find_xplane(UNSCOPED_TRACE)
    ctx = _ctx_over(path, monkeypatch, tmp_path)
    assert scopes.decode_split(ctx.trace,
                               scopes.program_op_names(path)) is None
    assert _reader("hook_device_ms").read(ctx) is None
    assert _reader("expert_device_ms").read(ctx) is None


def test_recorded_scoped_trace_splits_the_whole_decode_step(monkeypatch,
                                                            tmp_path):
    path = tr.find_xplane(SCOPED_TRACE)
    ctx = _ctx_over(path, monkeypatch, tmp_path)
    split = scopes.decode_split(ctx.trace, scopes.program_op_names(path))
    assert split is not None
    for scope in scopes.SCOPES:
        assert split.get(scope, 0.0) > 0, (scope, split)
    step_ms = _reader("decode_step_device_ms").read(ctx)
    total_ms = 1e3 * sum(split.values())
    assert abs(total_ms - step_ms) <= 0.02 * step_ms
    assert 1e3 * split.get(scopes.UNSCOPED, 0.0) < 0.05 * step_ms
    hook = _reader("hook_device_ms").read(ctx)
    expert = _reader("expert_device_ms").read(ctx)
    assert hook == pytest.approx(1e3 * split["lora_hook"])
    assert expert == pytest.approx(1e3 * split["moe_experts"])
    assert 0 < hook < step_ms and 0 < expert < step_ms


def test_recorded_scoped_trace_separates_inherited_time(monkeypatch,
                                                       tmp_path):
    """Each scope's device time a decode-step run on the recorded trace
    (37 runs of bucket 4), from its instructions' own metadata and from
    what metadata-less instructions inherit from their users: without
    the inheritance 7.5 % of the step is unscoped, with it 0.01 %."""
    path = tr.find_xplane(SCOPED_TRACE)
    ctx = _ctx_over(path, monkeypatch, tmp_path)
    full = scopes.decode_split(ctx.trace, scopes.program_op_names(path))
    own = scopes.decode_split(ctx.trace,
                              scopes.program_op_names(path, inherit=False))
    ms = {k: 1e3 * v for k, v in own.items()}
    assert ms == pytest.approx({"lora_hook": 6.8921, "moe_experts": 7.5306,
                                "attention": 1.9438, "lm_head": 0.3599,
                                "moe_router": 0.0331,
                                scopes.UNSCOPED: 1.3583}, abs=1e-3)
    inherited = {k: 1e3 * (full[k] - own.get(k, 0.0)) for k in scopes.SCOPES}
    assert inherited == pytest.approx({"lora_hook": 1.1739,
                                       "moe_experts": 0.0145,
                                       "attention": 0.1670, "lm_head": 0.0,
                                       "moe_router": 0.0007}, abs=1e-3)
    assert sum(own.values()) == pytest.approx(sum(full.values()))
    line = scopes.report(full, own, ctx)
    assert "lora_hook 8.066 ms" in line and "own 6.892, inherited 1.174" \
        in line and "unscoped before inheritance 1.358 ms" in line


def test_recorded_scoped_trace_holds_the_existing_reduction():
    """What test_bench_trace_reduce checks of the trace of the program
    before its scopes holds on the trace of the program with them."""
    path = tr.find_xplane(SCOPED_TRACE)
    import layerctx
    t = tr.load(path)
    lines = next(iter(t.devices.values()))
    lo, hi = tr.window(t)
    assert 0 < tr.busy_seconds(lines) <= hi - lo
    steps = tr.matching(lines[tr.MODULES_LINE], layerctx.DECODE_MODULE)
    kernels = tr.matching(lines[tr.OPS_LINE], layerctx.PAGED_KERNEL)
    assert steps and kernels
    for st in steps:        # 2 layers: two paged-attention calls a step
        inside = [k for k in kernels
                  if k.start >= st.start and k.end <= st.end]
        assert len(inside) == 2, (st.name, len(inside))
    assert any(h.name == "bench.step" for h in t.host)
    bd = tr.breakdown(t, lo, hi)
    assert 0 < len(bd["device_ops"]) <= 10
    assert all(s > 0 for _, s in bd["idle_gaps"])
