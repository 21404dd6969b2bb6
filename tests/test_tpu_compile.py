"""The served path's Pallas kernels compile for one TPU v5e chip at
Mixtral-8x7B's published widths.

The chip is described, not attached: each case lowers a kernel's jitted
wrapper with Mosaic (``interpret=False``) for one device of a described
``v5e:2x2`` topology and checks that the compiled program holds the kernel
(``tpu_custom_call``). Nothing runs. The topology is described only inside
the fixture, so that importing this file loads no TPU library.
"""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

CFG = get_config("mixtral-8x7b")
N_ADAPTERS, BATCH, PAGE, N_PAGES = 8, 8, 16, 128
# decode rows reaching an expert hook: dropless capacity (batch * top_k)
# for each of the experts
EXPERT_ROWS = BATCH * CFG.top_k * CFG.n_experts


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler to describe it with
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back without one:
        # keep the persistent cache out of these compiles
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _case(name, dev):
    """(jitted wrapper, argument shapes) at the served widths."""
    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)

    d, ff, r, E = CFG.d_model, CFG.d_ff, CFG.lora_rank, CFG.n_experts
    N, T, R = N_ADAPTERS, BATCH, EXPERT_ROWS
    i32 = jnp.int32
    if name == "bgmv":
        return ops._bgmv_call, (s(T, d), s(N, d, r), s(N, r, d),
                                s(T, dtype=i32))
    if name == "bgmv_expert_gate_up":
        return ops._bgmv_expert_call, (s(R, d), s(N, E, d, r),
                                       s(N, E, r, ff), s(R, dtype=i32),
                                       s(R, dtype=i32))
    if name == "bgmv_expert_down":
        return ops._bgmv_expert_call, (s(R, ff), s(N, E, ff, r),
                                       s(N, E, r, d), s(R, dtype=i32),
                                       s(R, dtype=i32))
    KV, hd = CFG.n_kv_heads, CFG.head_dim
    pool = s(N_PAGES, PAGE, KV, hd)
    return ops._paged_attention_call, (
        s(T, KV, CFG.n_heads // KV, hd), pool, pool,
        s(T, N_PAGES // T, dtype=i32), s(T, dtype=i32))


@pytest.mark.parametrize("name", ["bgmv", "bgmv_expert_gate_up",
                                  "bgmv_expert_down", "paged_attention"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, args = _case(name, one_chip)
    compiled = fn.lower(*args, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
