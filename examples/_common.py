"""Shared example setup: the examples run on whatever backend JAX
selects. On a TPU machine that is the chip; for the virtual CPU mesh run
them as the tests run (tests/conftest.py)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/train_llama_hybrid.py
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))       # repo root on sys.path


def setup():
    import jax
    d = jax.devices()[0]
    print(f"devices: {jax.device_count()} x {d.platform} ({d.device_kind})")
    return jax
