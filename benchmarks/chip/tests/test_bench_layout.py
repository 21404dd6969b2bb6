"""A configuration chooses its layout and its reference by file: the
``moe_lm`` layout draws the same bits as before it moved, and a second
layout (``shared_expert/``, a toy with a shared expert) is drawn, sized
by fit.py and run through its reference with no edit to a harness
file."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import tiny
import spec
import weights

HERE = os.path.dirname(os.path.abspath(__file__))
TOY = os.path.join(HERE, "shared_expert", "toy.json")

# weights.checksum of tiny.json's draw at seed 0, taken on the CPU from
# the draw as it was before the layouts moved into layouts/
PINNED = {
    "embed": 1605918563, "final_norm": 239477810,
    "layers.attn.wk": 1020858817, "layers.attn.wo": 1756279186,
    "layers.attn.wq": 2219937881, "layers.attn.wv": 3237268682,
    "layers.ln1": 1050086143, "layers.ln2": 1090842055,
    "layers.moe.down": 2718502691, "layers.moe.gate": 532655247,
    "layers.moe.router": 3960981020, "layers.moe.up": 2094618738,
    "lm_head": 4140782082,
    "adapters.down.A": 2652091467, "adapters.down.B": 1969773492,
    "adapters.gate.A": 2515537967, "adapters.gate.B": 2844950821,
    "adapters.up.A": 3836087664, "adapters.up.B": 1184219112,
}


def _toy():
    with open(TOY) as f:
        return json.load(f)


def test_moe_lm_draw_keeps_its_bits():
    got = weights.checksum(*weights.make(tiny._read("tiny.json"), 0))
    assert got == PINNED


def test_layout_added_as_files_is_drawn_and_referenced():
    conf = _toy()
    lay = spec.layout(conf)
    assert os.path.dirname(lay.__file__) == os.path.dirname(TOY)
    m = lay.dims(conf)
    params, adapters = weights.make(conf, 5)
    moe = params["layers"]["moe"]
    L, d, fs = m["n_layers"], m["d_model"], m["d_shared"]
    assert moe["shared_gate"].shape == (L, d, fs)
    assert moe["shared_down"].shape == (L, fs, d)
    cfg = lay.model_config(conf)
    assert (cfg.n_experts, cfg.top_k, cfg.d_ff) == \
        (m["n_experts"], m["top_k"], m["d_ff"])

    ref = spec.load_module(conf["reference"])
    toks = np.random.default_rng(5).integers(0, m["vocab"], 30)
    rows = np.arange(10, 30)
    lg = ref.logits(params, adapters, m, toks, 12, 2, rows, pad_to=32)
    assert lg.shape == (len(rows), m["vocab"]) and np.isfinite(lg).all()
    # with the shared expert's output projection zeroed the toy computes
    # moe_lm's layer: the shared expert is all that sets them apart
    off = dict(params, layers=dict(params["layers"], moe=dict(
        moe, shared_down=moe["shared_down"] * 0)))
    plain = ref.logits(off, adapters, m, toks, 12, 2, rows, pad_to=32)
    base = spec.load_module("reference/moe_lm.py").logits(
        off, adapters, m, toks, 12, 2, rows, pad_to=32)
    np.testing.assert_allclose(plain, base, rtol=0, atol=1e-5)
    assert np.max(np.abs(lg - plain)) > 0.1

    moe_lm = spec.load_module("layouts/moe_lm.py")
    assert lay.decode_step_flops(m, [5, 9]) - moe_lm.decode_step_flops(
        m, [5, 9]) == L * 2 * 6 * d * fs


def test_fit_sizes_a_layout_the_program_cannot_serve():
    p = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(HERE), "fit.py"),
         "--config", TOY], env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 3, p.stderr[-3000:]
    assert "the program cannot serve layout tests/shared_expert/layout.py" \
        in p.stderr
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["program_serves"] is False
    conf = _toy()
    drawn = {k: int(np.prod(v[0])) * 2
             for k, v in weights.leaves(conf).items()}
    res = out["resident"]["0"]
    assert res["weights"] == sum(v for k, v in drawn.items()
                                 if not k.startswith("adapters."))
    assert res["adapter_pool"] == sum(v for k, v in drawn.items()
                                      if k.startswith("adapters."))


def test_harness_stops_before_building_a_layout_the_program_lacks(
        monkeypatch):
    import harness
    import workload
    from repro.serving import api

    def never(*a, **kw):
        raise AssertionError("build_system reached")

    monkeypatch.setattr(api, "build_system", never)
    with pytest.raises(RuntimeError,
                       match="program cannot serve layout "
                             "tests/shared_expert/layout.py"):
        harness.build(_toy(), workload.load_traffic("chat"), 5)
