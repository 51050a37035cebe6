"""The `gather_walk_share.decode` reader on hand-made contexts: the share
of the block tables' pages the paged gather walked in the traced window,
and None where the window holds no walk."""
import types

import _paths  # noqa: F401
import pytest

from bench.lib import metrics, spec, trace


def ctx(stats):
    return metrics.Context(
        conf=spec.config("qwen1.5-0.5b"),
        cell=spec.cell("qwen1.5-0.5b.decode"),
        peaks=spec.peaks("TPU v5 lite"),
        trace=trace.Summary([], [], [], (0, 10**9), 1),
        driver=types.SimpleNamespace(
            steps=[], records={},
            engine=types.SimpleNamespace(admission_step={})),
        window=(0.0, 1.0), traced=(0.0, 1.0), stats=stats)


def read(c):
    return spec.load_module("metrics", "gather_walk_share.decode").read(c)


def test_gather_walk_share_from_counters():
    c = ctx(({"gather_pages_live": 100, "gather_pages_table": 480},
             {"gather_pages_live": 430, "gather_pages_table": 1440}))
    assert read(c) == pytest.approx(100 * 330 / 960)


def test_gather_walk_share_none_without_walk():
    same = {"gather_pages_live": 7, "gather_pages_table": 96}
    assert read(ctx((same, same))) is None
