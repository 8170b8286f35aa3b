"""Machine metadata stamped into the ``BENCH_zoo.json`` record.

Timings are meaningless without knowing what they ran on, and a host's
throughput is bounded by its *physical* cores, not by
``jax.device_count()`` (the ``--xla_force_host_platform_device_count``
flag happily splits one core into eight "devices").  The writer calls
:func:`machine_metadata` once and embeds the result under a ``"machine"``
key.
"""
from __future__ import annotations

import os
import platform
import sys


def machine_metadata() -> dict:
    """Environment fingerprint for benchmark records (JSON-serialisable)."""
    import jax

    devices = jax.devices()
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else None,
        "device_count": len(devices),
        "cpu_count": os.cpu_count(),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
        "platform": platform.platform(),
        "python": sys.version.split()[0],
    }
