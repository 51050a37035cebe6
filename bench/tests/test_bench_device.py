"""The harness refuses to measure on the CPU and on a chip it has no
peaks for, and prints no result either way."""
import io

import _paths  # noqa: F401
import pytest

from bench.lib import device, harness, spec


def test_cpu_is_refused(capsys):
    with pytest.raises(SystemExit) as e:
        device.check(chips=1, need_accelerator=True)
    assert e.value.code != 0
    assert capsys.readouterr().out == ""


def test_whole_run_on_cpu_prints_nothing():
    out = io.StringIO()
    with pytest.raises(SystemExit) as e:
        harness.run("qwen3-4b.decode", 1, 1.0, False, out=out,
                    err=io.StringIO())
    assert e.value.code != 0 and out.getvalue() == ""


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")
    assert spec.peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_too_few_chips_is_refused():
    with pytest.raises(SystemExit):
        device.check(chips=64, need_accelerator=False)
