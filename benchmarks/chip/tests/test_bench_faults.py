"""``correct`` comes out false when the timed path is broken, and the fp8
control reads above the limit that sound runs stay under (toy size, CPU).

The run is driven through ``run.execute`` with the look for a chip
skipped, so the whole path after it is the one the benchmark runs."""
import pytest

import tiny

# the toy cell's limit on the 98th percentile of the logit gap: sound CPU
# runs read 0 (seeds 7-12, 77, 3000000001), the fp8 control 0.23 to 0.80.
# Their widest gaps overlap (sound up to 2.4, control from 1.8): a sound
# run has at most one token over 0.1, the control 22 to 47.
TINY_LIMIT = 0.05


def _execute(monkeypatch, seed):
    tiny.install(monkeypatch, limit=TINY_LIMIT)
    import run
    args = run.parse(["--workload", tiny.CELL, "--seed", str(seed),
                      "--seconds", "3", "--trace", "0"])
    return run.execute(args, check_device=False)


def test_sound_run_is_correct(monkeypatch):
    res = _execute(monkeypatch, 3000000001)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serving import engine
    step = engine.Engine.step

    def altered(self):
        vocab = self.cfg.vocab_size
        return {rid: (t + 1) % vocab for rid, t in step(self).items()}

    monkeypatch.setattr(engine.Engine, "step", altered)
    res = _execute(monkeypatch, 3000000002)
    assert not res["correct"]
    assert res["checks"]["p98_logit_gap"]["value"] > TINY_LIMIT


def test_adapter_left_out_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.transport import fused

    def no_delta(self, hook, layer, rows, adapter_ids, expert_ids):
        d_out = (self.up_B if hook == "up" else self.down_B).shape[-1]
        return jnp.zeros((rows.shape[0], d_out), jnp.float32)

    import jax
    monkeypatch.setattr(fused.DeviceLoraView, "compute", no_delta)
    jax.clear_caches()          # retrace the fused step with the fault
    try:
        res = _execute(monkeypatch, 3000000003)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"]
    assert res["checks"]["p98_logit_gap"]["value"] > TINY_LIMIT


def _patch_decode_step(monkeypatch, fault):
    """Run the fused paged decode step, then ``fault(tok, k_in, v_in,
    k_out, v_out)`` on what it returns."""
    import jax
    from repro.transport import fused
    step = jax.jit(fused._fused_paged_fn, static_argnames=("cfg",))

    def broken(params, cfg, k_pool, v_pool, *rest):
        tok, k, v = step(params, cfg, k_pool, v_pool, *rest)
        return fault(tok, k_pool, v_pool, k, v)

    monkeypatch.setattr(fused, "_fused_paged", broken)


def test_decode_step_returning_its_state_unchanged_is_not_correct(
        monkeypatch):
    # the step's tokens come back, its KV writes do not
    _patch_decode_step(monkeypatch,
                       lambda tok, k_in, v_in, k, v: (tok, k_in, v_in))
    res = _execute(monkeypatch, 3000000004)
    assert not res["correct"]
    assert res["checks"]["p98_logit_gap"]["value"] > TINY_LIMIT


def test_half_of_the_decode_rows_left_out_is_not_correct(monkeypatch):
    # the second half of the rows is answered with the first half's tokens
    import jax.numpy as jnp

    def half(tok, k_in, v_in, k, v):
        h = (tok.shape[0] + 1) // 2
        return jnp.concatenate([tok[:h], tok[:tok.shape[0] - h]]), k, v

    _patch_decode_step(monkeypatch, half)
    res = _execute(monkeypatch, 3000000005)
    assert not res["correct"]
    assert res["checks"]["p98_logit_gap"]["value"] > TINY_LIMIT


@pytest.mark.parametrize("seed", [7, 8, 9])
def test_fp8_control_reads_above_the_limit(monkeypatch, seed):
    import check
    import harness
    import spec
    import workload
    conf = tiny.install(monkeypatch)
    traffic = workload.load_traffic("tiny")
    b = harness.build(conf, traffic, seed)
    harness.warm_up(b, seed)
    reqs = workload.generate(traffic, rate=tiny.RATE, seconds=3.0, seed=seed,
                             vocab=b.dims["vocab"],
                             n_adapters=b.dims["n_adapters"])
    rec = harness.drive(b, reqs, 3.0, 30.0)
    harness.release(b)
    g = check.gaps(b, check.sample(rec, seed),
                   pad_to=spec.max_len(conf, b.longest), control=True)
    limits = {"p98_logit_gap": TINY_LIMIT}
    assert check.decide(rec, g["served"], limits)["correct"]
    verdict = check.decide(rec, g["control"], limits)
    assert not verdict["correct"]
    assert verdict["checks"]["p98_logit_gap"]["value"] > TINY_LIMIT
