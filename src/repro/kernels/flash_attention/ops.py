"""jit'd public wrapper: (B, S, H, hd) layout, block sizes from the shapes.
The kernel compiles for the TPU; callers off the TPU (the CPU tests) pass
``interpret=True``."""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import LANES, flash_attention_grouped

BLOCK_ROWS = 5120        # most (position, head) rows of one query block
BLOCK_K = 1024           # most keys of one K/V tile
CHUNK_ROWS = 512         # most rows of one score tile


def _pow2_blocks(n: int, most: int):
    """Powers of two that divide ``n``, largest first, at most ``most``."""
    b = 1
    while b * 2 <= min(n, most) and n % (b * 2) == 0:
        b *= 2
    while b >= 1:
        yield b
        b //= 2


def _chunk(rows: int) -> int:
    """The most rows of a score tile that divide the block's ``rows``: a
    multiple of 16, at most ``CHUNK_ROWS``."""
    return max(c for c in range(16, min(rows, CHUNK_ROWS) + 1, 16)
               if rows % c == 0)


def block_sizes(Sq: int, Sk: int, group: int) -> Optional[Tuple[int, int]]:
    """(block_q, block_k) for ``Sq`` queries of ``group`` heads over ``Sk``
    keys, or None where the TPU's tiling cannot split them: a K/V tile of
    whole 128-lane key columns and a query block of whole 128-row groups of
    (position, head) rows.  The query block holds at most ``BLOCK_ROWS``
    rows unless one 128-row group needs more positions."""
    bk = next((b for b in _pow2_blocks(Sk, BLOCK_K) if b % LANES == 0), None)
    bq = next((b for b in _pow2_blocks(Sq, Sq) if (b * group) % LANES == 0
               and b * group <= max(BLOCK_ROWS, LANES * group)), None)
    if bk is None or bq is None:
        return None
    return bq, bk


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "with_lse", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: Optional[int] = None,
                    block_k: Optional[int] = None, with_lse: bool = False,
                    interpret: bool = False):
    """q: (B, Sq, H, hd); k/v: (B, Sk, Hkv, hd) -> (B, Sq, H, hd), and with
    ``with_lse`` each query's log-sum-exp of its scaled (softcapped)
    scores, (B, Hkv, H // Hkv, Sq) float32.  ``block_q``/``block_k``
    override the block sizes ``block_sizes`` picks."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    bq, bk = block_sizes(Sq, Sk, g) or (None, None)
    bq, bk = block_q or bq, block_k or bk
    if not (bq and bk):
        raise ValueError(f"no TPU tiling for {Sq} queries of {g} heads over "
                         f"{Sk} keys")
    # Rows ordered (position, head) under each KV head: with one KV head
    # this is q's own order.
    qs = (q * hd ** -0.5).astype(q.dtype).reshape(B, Sq, Hkv, g, hd)
    qs = jnp.swapaxes(qs, 1, 2).reshape(B, Hkv, Sq * g, hd)
    kt, vt = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
    res = flash_attention_grouped(
        qs, kt, vt, group=g, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, chunk=_chunk(bq * g), with_lse=with_lse,
        interpret=interpret)
    o = res[0] if with_lse else res
    o = jnp.swapaxes(o.reshape(B, Hkv, Sq, g, hd), 1, 2).reshape(
        B, Sq, H, hd)
    if not with_lse:
        return o
    lse = jnp.swapaxes(res[1].reshape(B, Hkv, Sq, g), 2, 3)
    return o, lse
