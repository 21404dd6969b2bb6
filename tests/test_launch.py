"""The entry points: the serving driver (``repro.launch.serve``), the
compile-cache placement they share, and ``chip_smoke.py``'s phases at the
reduced size on the CPU (kernels in interpret mode)."""
import dataclasses
import importlib.util
import pathlib

import jax
import pytest

from repro.launch import compile_cache, serve

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


chip_smoke = _load_chip_smoke()


@pytest.fixture
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()        # later tests in this process cache as before


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(from_env, tmp_path, monkeypatch,
                                 restore_cache_dir):
    """$JAX_COMPILATION_CACHE_DIR when set; else one fixed, git-ignored
    directory in the checkout."""
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(ROOT / ".jax_compile_cache")
        assert ".jax_compile_cache/" in \
            (ROOT / ".gitignore").read_text().split()
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_model_config_cuts_depth_only():
    full = serve.model_config("mixtral-8x7b")
    cut = serve.model_config("mixtral-8x7b", layers=2)
    assert cut == dataclasses.replace(full, n_layers=2)
    assert (cut.d_model, cut.d_ff, cut.n_experts, cut.vocab_size) == \
        (4096, 14336, 8, 32000)
    with pytest.raises(ValueError):
        serve.model_config("mixtral-8x7b", layers=33)


@pytest.mark.parametrize("mode", ["coupled", "disagg"])
def test_serve_main_reduced(mode, tmp_path, monkeypatch, capsys,
                            restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert serve.main(["--arch", "mixtral-8x7b", "--reduced", "--mode", mode,
                       "--requests", "2", "--adapters", "2"]) == 0
    out = capsys.readouterr().out
    assert "served batch=2 adapters=[0, 1]" in out


def test_chip_smoke_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture(scope="module")
def reduced_model():
    cfg = serve.model_config("mixtral-8x7b", reduced=True)
    params, pool = serve.init_model(cfg, chip_smoke.N_ADAPTERS)
    return cfg, params, pool


@pytest.mark.parametrize("name", ["bgmv q", "bgmv k", "bgmv o",
                                  "bgmv_expert gate", "bgmv_expert down",
                                  "paged_attention"])
def test_chip_smoke_kernel_check_interpret(name, reduced_model, monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    cfg, _, pool = reduced_model
    kernel, oracle, args = chip_smoke.kernel_cases(cfg, pool)[name]
    assert chip_smoke.check_kernel(name, kernel, oracle, args) < 1e-5


@pytest.mark.parametrize("disaggregated,transport",
                         [(False, "host"), (True, "host"), (True, "fused")])
def test_chip_smoke_phase_reduced(disaggregated, transport, reduced_model):
    cfg, params, pool = reduced_model
    requests = serve.make_requests(cfg, chip_smoke.N_REQUESTS,
                                   chip_smoke.N_ADAPTERS,
                                   prompt_lens=chip_smoke.PROMPT_LENS)
    with chip_smoke.CompileClock() as clock:
        toks = chip_smoke.run_phase("reduced", cfg, params, pool, requests,
                                    clock, disaggregated=disaggregated,
                                    transport=transport)
    assert [len(t) for t in toks] == \
        [chip_smoke.NEW_TOKENS] * chip_smoke.N_REQUESTS
    assert clock.events > 0
