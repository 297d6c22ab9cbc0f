"""Kernels for the model zoo's compute hot-spots.

ops.py holds the entry points the models call; each chooses its kernel from
what the call shows (shapes, mask, platform), never by name.  ref.py holds
the plain jnp forms: the tests' oracles, and what ops runs for RMSNorm and
decode attention.  decode_attention.py, rmsnorm.py and mamba_scan.py are
standalone Pallas kernels that no path calls yet; their tests run them in
interpret mode off the TPU (their default there), and
tests/test_tpu_compile.py compiles them for a described v5e.
"""
from . import ops, ref
