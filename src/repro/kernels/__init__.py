"""Pallas TPU kernels for the framework's compute hot-spots.

The paper itself has no kernel-level contribution (its kernels come from
open-source suites); these are the perf-critical layers of the *framework*:
flash_attention (blocked online softmax), rwkv6 (WKV recurrence), rmsnorm.
Each package has kernel.py (pl.pallas_call + BlockSpec), ops.py (jit'd
wrapper; callers off the TPU pass ``interpret=True``) and ref.py (pure-jnp
oracle).
"""
