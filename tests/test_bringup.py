"""``chip_smoke.py`` and the compile cache, rehearsed on the CPU.

The smoke script itself only runs on a TPU; here its phases run at the
smoke width of llama3.2-1b with interpret-mode Pallas, so wrong paths,
arguments and control flow show up without the chip.
"""
from __future__ import annotations

import os
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402

from repro import api  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

INTERPRET = kops.KernelPolicy(mode="pallas", interpret=True)


def test_chip_smoke_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "needs a TPU" in out.err


def test_chip_smoke_one_chip_phases_on_cpu(capsys):
    chip_smoke.one_chip(api.get_smoke(chip_smoke.ARCH), INTERPRET)
    out = capsys.readouterr().out
    assert "all done" in out and "tolerance" in out


@pytest.mark.slow
def test_chip_smoke_tensor_parallel_phase_on_cpu():
    from conftest import run_multidevice
    out = run_multidevice(f"""
        import sys
        sys.path.insert(0, {os.path.abspath(ROOT)!r})
        import chip_smoke
        from repro import api
        from repro.kernels import ops as kops
        chip_smoke.tensor_parallel(
            api.get_smoke(chip_smoke.ARCH),
            kops.KernelPolicy(mode="pallas", interpret=True), 2)
    """, devices=2)
    assert "identical on 1 and 2 devices" in out


def test_pallas_kernels_reads_named_custom_calls():
    from repro.roofline.hlo import pallas_kernels
    hlo = ('  %nq_decode_megakernel.3 = bf16[4,1,2048]{2,1,0} custom-call('
           '%a), custom_call_target="tpu_custom_call", x\n'
           '  %nq_fused_lowrank_matmul = f32[1]{0} custom-call(%b), '
           'custom_call_target="tpu_custom_call"\n'
           '  %nq_fused_lowrank_matmul.7 = f32[1]{0} custom-call(%b), '
           'custom_call_target="tpu_custom_call"\n'
           '  %fusion.1 = f32[1]{0} fusion(%c), kind=kLoop\n')
    assert pallas_kernels(hlo) == {"nq_decode_megakernel": 1,
                                   "nq_fused_lowrank_matmul": 2}


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_follows_env(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
