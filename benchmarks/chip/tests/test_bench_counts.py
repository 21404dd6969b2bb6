"""The useful-work counts come from the model and the live rows alone
(the ``moe_lm`` layout's ``decode_step_flops`` and
``paged_attention_work``)."""
import json
import os

import tiny  # noqa: F401  (puts the benchmark on sys.path)
import spec

HERE = os.path.dirname(os.path.abspath(__file__))


moe_lm = spec.load_module("layouts/moe_lm.py")


def _dims(name):
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           f"{name}.json")) as f:
        return moe_lm.dims(json.load(f))


def test_decode_flops_add_over_live_rows():
    m = _dims("mixtral-8x7b-disagg")
    rows = [17, 300, 1024]
    assert moe_lm.decode_step_flops(m, rows) == sum(
        moe_lm.decode_step_flops(m, [c]) for c in rows)
    assert moe_lm.decode_step_flops(m, []) == 0


def test_decode_flops_count_top_k_experts_not_the_dropless_buffer():
    m = _dims("qwen3-30b-a3b-disagg")
    d, ff, K, r = m["d_model"], m["d_ff"], m["top_k"], m["rank"]
    one = moe_lm.decode_step_flops(m, [1])
    two = moe_lm.decode_step_flops(m, [2])
    # one more key attended: attention's 4 H hd per layer, nothing else
    assert two - one == 4 * m["n_heads"] * m["head_dim"] * m["n_layers"]
    # a row's expert work is top_k experts' three GEMMs and three hooks
    per_layer_experts = K * 6 * d * ff + K * 6 * r * (d + ff)
    assert one > m["n_layers"] * per_layer_experts
    assert one < m["n_layers"] * per_layer_experts * m["n_experts"] / K


def test_paged_attention_work_reads_the_real_context_only():
    m = _dims("mixtral-8x7b-disagg")
    f1, b1 = moe_lm.paged_attention_work(m, [100])
    f2, b2 = moe_lm.paged_attention_work(m, [200])
    kv_per_key = 2 * m["n_kv_heads"] * m["head_dim"] * 2
    assert b2 - b1 == 100 * kv_per_key
    assert f2 == 2 * f1


def test_rounds_record_live_rows_not_the_bucket(monkeypatch):
    import harness
    import workload
    conf = tiny.install(monkeypatch)
    traffic = workload.load_traffic("tiny")
    b = harness.build(conf, traffic, 11)
    harness.warm_up(b, 11)
    reqs = workload.generate(traffic, rate=tiny.RATE, seconds=3.0, seed=11,
                             vocab=b.dims["vocab"],
                             n_adapters=b.dims["n_adapters"])
    rec = harness.drive(b, reqs, 3.0, 30.0)
    harness.release(b)
    # the rounds hold one context per live row: each request's prompt
    # length plus the tokens it had, never a padded bucket's rows
    got = sorted(c for r in rec.rounds for c in r.contexts)
    want = sorted(s.plen + j for s in rec.sent for j in range(len(s.tokens)))
    assert got == want and got
    for r in rec.rounds:
        assert b.layout.decode_step_flops(b.dims, r.contexts) == sum(
            b.layout.decode_step_flops(b.dims, [c]) for c in r.contexts)
