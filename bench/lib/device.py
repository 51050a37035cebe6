"""The chip the run measures: found, checked against the cell, named."""
from __future__ import annotations

import os
import sys

from bench.lib import spec

CACHE_DIR = spec.ROOT / ".jax_cache"


class NoAccelerator(SystemExit):
    pass


def check(chips: int, need_accelerator: bool = True) -> dict:
    """The device block of the result line, plus the peaks of its kind.
    Exits non-zero, printing nothing on stdout, without an accelerator,
    with fewer chips than the cell asks for, or with a device kind the
    peaks table does not hold."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if need_accelerator and d.platform == "cpu":
        print("bench: JAX found no accelerator, only the CPU",
              file=sys.stderr)
        raise NoAccelerator(3)
    if len(devs) < chips:
        print(f"bench: the cell needs {chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        raise NoAccelerator(3)
    try:
        peaks = spec.peaks(d.device_kind) if need_accelerator else {}
    except KeyError as e:
        print(f"bench: {e.args[0]}", file=sys.stderr)
        raise NoAccelerator(3)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "peaks": peaks}


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout unless JAX_COMPILATION_CACHE_DIR names one. Every program
    is kept, however fast it compiled, so a second run compiles none."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes() -> int:
    import jax
    peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dv in jax.local_devices()]
    return int(max(peaks))
