"""Pallas TPU kernels for the model zoo's compute hot-spots.

Layout per kernel: <name>.py holds the pl.pallas_call + BlockSpec tiling;
ops.py is the dispatching wrapper (pallas | blockwise-jnp | ref); ref.py the
pure-jnp oracle. Kernels run in interpret mode off-TPU (their default
there), and tests/test_tpu_compile.py compiles them for a described v5e.
"""
from . import ops, ref
