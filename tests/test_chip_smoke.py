"""chip_smoke.py's phases at smoke size on the CPU, its refusal to run
without a TPU, and the compile-cache helper every entry point calls.

The phases are the same functions the chip run calls; here the kernels run
in the Pallas interpreter (passed explicitly) and the serving steps take
the CPU's XLA paths, so nothing asserts a Mosaic kernel."""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke as cs  # noqa: E402
from repro.launch import compile_cache  # noqa: E402


def test_kernel_phase_at_smoke_size():
    cs.kernel_phase(cs.KernelConfig(arch="paper-demo", smoke=True, rows=4,
                                    block_size=8, max_blocks=5,
                                    prefill_len=256, interpret=True))


def test_serve_phase_at_smoke_size(capsys):
    cs.serve_phase(cs.ServeConfig(arch="paper-demo", smoke=True, requests=4,
                                  prompt_len=32, prefix_len=24, gen=8,
                                  slots=4, block_size=8))
    out = capsys.readouterr().out
    assert "serve greedy: requests=4/4 tokens=32" in out
    assert "serve seeded: requests=4/4 tokens=32" in out
    assert "token_agreement=" in out


def test_chip_smoke_refuses_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, env=env, cwd=ROOT,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "platform=cpu" in r.stdout


@pytest.fixture()
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(env_dir, monkeypatch, cache_dir_config):
    if env_dir is None:
        monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
        got = compile_cache.enable_compile_cache()
        assert got == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    else:
        monkeypatch.setenv(compile_cache.CACHE_ENV, env_dir)
        before = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == before
