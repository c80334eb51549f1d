"""JAX implementations of the 33 benchmark kernels (paper §V-B).

Kernels are pure functions of the device values of their argument list (in
argument order, including output placeholders) and return the new values of
their writable arguments — the executor installs results into the
ManagedArray handles.  Taken/derived from the open-source suites the paper
cites (CUDA samples, LightSpMV, cuda-gaussian-blur, Kepler reduction post).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


# ---------------------------------------------------------------- VEC ----
@jax.jit
def k_square(x, _y):
    return x * x


@jax.jit
def k_reduce_diff(y1, y2, _z):
    return jnp.sum(y1 - y2)[None]


# ---------------------------------------------------------------- B&S ----
def _ndtr(x):
    return 0.5 * (1.0 + lax.erf(x / jnp.sqrt(jnp.asarray(2.0, x.dtype))))


@jax.jit
def k_black_scholes(s, _out):
    """European call, CUDA-samples parameterization, in the dtype of ``s``."""
    dt = s.dtype
    K = jnp.asarray(60.0, dt)
    r = jnp.asarray(0.035, dt)
    sigma = jnp.asarray(0.2, dt)
    T = jnp.asarray(1.0, dt)
    sqrt_t = jnp.sqrt(T)
    d1 = (jnp.log(s / K) + (r + 0.5 * sigma * sigma) * T) / (sigma * sqrt_t)
    d2 = d1 - sigma * sqrt_t
    return s * _ndtr(d1) - K * jnp.exp(-r * T) * _ndtr(d2)


# ---------------------------------------------------------------- IMG ----
def _gauss_1d(ksize: int, sigma: float) -> np.ndarray:
    """Normalized 1-D Gaussian; the 2-D kernel is its outer product."""
    ax = np.arange(ksize) - (ksize - 1) / 2.0
    g = np.exp(-(ax ** 2) / (2.0 * sigma ** 2))
    return (g / g.sum()).astype(np.float32)


def _sep_conv2d_same(img, col, row):
    """img: (H, W) cross-correlated, zero-padded to the same size, with the
    separable kernel ``outer(col, row)`` -- as ``lax.conv_general_dilated``
    with ``padding="SAME"`` computes it, but as shifted multiply-adds.  A
    one-channel 2-D convolution puts its size-1 feature dim minor, and the
    TPU's (8, 128) tiling then pads it 128-fold: at IMG's published size
    (9796^2) XLA asks for 49 GB of HBM for one 3x3 blur."""
    def along(x, taps, axis):
        k = len(taps)
        pad = [(0, 0), (0, 0)]
        pad[axis] = ((k - 1) // 2, k // 2)
        xp = jnp.pad(x, pad)
        n = x.shape[axis]
        return sum(float(t) * lax.slice_in_dim(xp, i, i + n, axis=axis)
                   for i, t in enumerate(taps) if t != 0)

    return along(along(img, row, 1), col, 0)


@functools.partial(jax.jit, static_argnames=("ksize", "sigma"))
def k_gaussian_blur(img, _out, *, ksize: int, sigma: float):
    g = _gauss_1d(ksize, sigma)
    return _sep_conv2d_same(img, g, g)


@jax.jit
def k_sobel(img, _out):
    # gx = [[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]] = outer(smooth, diff), gy = gx.T
    smooth, diff = (1.0, 2.0, 1.0), (-1.0, 0.0, 1.0)
    ex = _sep_conv2d_same(img, smooth, diff)
    ey = _sep_conv2d_same(img, diff, smooth)
    g = jnp.sqrt(ex * ex + ey * ey)
    return g / (jnp.max(g) + 1e-6)


@jax.jit
def k_extend_mask(mask, _out):
    """Dilate + normalize the edge mask (paper's `extend` kernel)."""
    m = lax.reduce_window(mask, -jnp.inf, lax.max, (5, 5), (1, 1), "SAME")
    lo, hi = jnp.min(m), jnp.max(m)
    return (m - lo) / (hi - lo + 1e-6)


@jax.jit
def k_unsharpen(img, blur, _out):
    return jnp.clip(img + 0.5 * (img - blur), 0.0, 1.0)


@jax.jit
def k_combine(sharp, blur_med, mask, _out):
    return sharp * mask + blur_med * (1.0 - mask)


@jax.jit
def k_combine_low(comb, blur_low, mask, _out):
    return comb * mask + blur_low * (1.0 - mask)


# ----------------------------------------------------------------- ML ----
@jax.jit
def k_nb_scores(x, feat_logprob, class_logprior, _out):
    """Categorical Naive-Bayes log-posteriors — the tall-matrix low-IPC
    kernel of §V-F (rows >> classes)."""
    return x @ feat_logprob.T + class_logprior[None, :]


@jax.jit
def k_ridge_scores(x, w, b, _out):
    return x @ w.T + b[None, :]


@jax.jit
def k_softmax_norm(scores, _out):
    m = jnp.max(scores, axis=1, keepdims=True)
    e = jnp.exp(scores - m)
    return e / jnp.sum(e, axis=1, keepdims=True)


@jax.jit
def k_ensemble_avg(p1, p2, _out):
    return jnp.argmax(0.5 * (p1 + p2), axis=1).astype(jnp.int32)


# --------------------------------------------------------------- HITS ----
@jax.jit
def k_spmv(vals, cols, rows, x, _y):
    """CSR-ish SpMV (COO row index + segment_sum), LightSpMV-derived."""
    n = _y.shape[0]
    return jax.ops.segment_sum(vals * x[cols], rows, num_segments=n)


@jax.jit
def k_l2_norm(x, _out):
    return jnp.sqrt(jnp.sum(x * x))[None]


@jax.jit
def k_divide(x, norm, _out):
    return x / (norm[0] + 1e-12)


# ----------------------------------------------------------------- DL ----
@functools.partial(jax.jit, static_argnames=("stride",))
def k_conv_relu_pool(x, w, _out, *, stride: int = 1):
    """x: (N,C,H,W), w: (O,C,k,k) -> conv + relu + 2x2 maxpool."""
    y = lax.conv_general_dilated(x, w, (stride, stride), "SAME")
    y = jnp.maximum(y, 0.0)
    return lax.reduce_window(y, -jnp.inf, lax.max, (1, 1, 2, 2),
                             (1, 1, 2, 2), "VALID")


@jax.jit
def k_dense_embed(x, w, _out):
    flat = x.reshape((x.shape[0], -1))
    return jnp.tanh(flat @ w)


@jax.jit
def k_concat_dense(e1, e2, w, _out):
    h = jnp.concatenate([e1, e2], axis=1) @ w
    return 1.0 / (1.0 + jnp.exp(-h))


# ======================================================================
# Declared GrFunctions (the polyglot frontend surface, paper §III-IV)
# ======================================================================
# Access modes are declared exactly once, here with the kernel; the
# benchmark builders then call these like plain functions — per-call
# const/out annotation boilerplate is gone.  ``with_options`` attaches the
# per-call cost model / occupancy / display name without forking identity.
from ..core.frontend import function as _gr_function

SQUARE = _gr_function(k_square, modes=("const", "out"), outputs=0,
                      name="SQ")
REDUCE_DIFF = _gr_function(k_reduce_diff, modes=("const", "const", "out"),
                           name="RED")
BLACK_SCHOLES = _gr_function(k_black_scholes, modes=("const", "out"),
                             outputs=0, name="BS")
BLUR_S = _gr_function(functools.partial(k_gaussian_blur, ksize=3, sigma=1.0),
                      modes=("const", "out"), name="BLUR_S")
BLUR_M = _gr_function(functools.partial(k_gaussian_blur, ksize=7, sigma=2.5),
                      modes=("const", "out"), name="BLUR_M")
BLUR_L = _gr_function(functools.partial(k_gaussian_blur, ksize=13, sigma=5.0),
                      modes=("const", "out"), name="BLUR_L")
SOBEL = _gr_function(k_sobel, modes=("const", "out"), name="SOBEL")
EXTEND_MASK = _gr_function(k_extend_mask, modes=("const", "out"),
                           name="EXTEND")
UNSHARPEN = _gr_function(k_unsharpen, modes=("const", "const", "out"),
                         name="UNSHARP")
COMBINE = _gr_function(k_combine, modes=("const", "const", "const", "out"),
                       name="COMBINE")
COMBINE_LOW = _gr_function(k_combine_low,
                           modes=("const", "const", "const", "out"),
                           name="COMBINE_LOW")
NB_SCORES = _gr_function(k_nb_scores,
                         modes=("const", "const", "const", "out"), name="NB")
RIDGE_SCORES = _gr_function(k_ridge_scores,
                            modes=("const", "const", "const", "out"),
                            name="RIDGE")
SOFTMAX_NORM = _gr_function(k_softmax_norm, modes=("const", "out"),
                            name="SOFTMAX")
ENSEMBLE_AVG = _gr_function(k_ensemble_avg, modes=("const", "const", "out"),
                            name="ARGMAX")
SPMV = _gr_function(k_spmv,
                    modes=("const", "const", "const", "const", "out"),
                    name="SPMV",
                    lint_shapes=(((8,), np.float32), ((8,), np.int32),
                                 ((8,), np.int32), ((8,), np.float32),
                                 ((8,), np.float32)))
L2_NORM = _gr_function(k_l2_norm, modes=("const", "out"), name="NORM")
# DIVIDE never reads the prior value of its destination (pure x/norm
# store); ``inout`` here forced a spurious prefetch of dead data.  The
# WAR edges against this iteration's SpMV readers come from the *write*
# and are identical under ``out``.
DIVIDE = _gr_function(k_divide, modes=("const", "const", "out"),
                      name="DIV")
CONV_RELU_POOL = _gr_function(k_conv_relu_pool,
                              modes=("const", "const", "out"), name="CONV",
                              lint_shapes=(((1, 1, 8, 8), np.float32),
                                           ((1, 1, 3, 3), np.float32),
                                           ((1, 1, 4, 4), np.float32)))
DENSE_EMBED = _gr_function(k_dense_embed, modes=("const", "const", "out"),
                           name="DENSE")
CONCAT_DENSE = _gr_function(k_concat_dense,
                            modes=("const", "const", "const", "out"),
                            name="HEAD",
                            lint_shapes=(((8, 4), np.float32),
                                         ((8, 4), np.float32),
                                         ((8, 1), np.float32),
                                         ((8, 1), np.float32)))
