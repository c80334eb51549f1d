"""jit'd wrapper: any leading shape.  The kernel compiles for the TPU;
callers off the TPU (the CPU tests) pass ``interpret=True``."""
from __future__ import annotations

import functools

import jax

from .kernel import rmsnorm_2d


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-6, interpret: bool = False):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    y = rmsnorm_2d(x2, scale, eps=eps, interpret=interpret)
    return y.reshape(shape)
