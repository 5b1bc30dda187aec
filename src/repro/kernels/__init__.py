"""Pallas TPU kernels: compiled for the chip by default, validated on the
CPU with ``interpret=True`` against their oracles, and compiled for a
described TPU v5e in ``tests/test_tpu_compile.py``.

Each kernel package: <name>.py (pl.pallas_call + BlockSpec tiling),
ops.py (jit'd public wrapper), ref.py (pure-jnp oracle).
"""
