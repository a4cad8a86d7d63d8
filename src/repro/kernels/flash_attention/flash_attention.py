"""Pallas TPU kernel: causal GQA flash attention (forward).

VMEM-tiled online-softmax attention: queries are processed in (BQ, hd)
blocks; K/V stream through VMEM in (BK, hd) slices inside a fori_loop
with running (m, l, acc) statistics. Causal + sliding-window masking
prunes K blocks entirely outside the visible range (the loop upper bound
is derived from the query block index, so local-attention layers touch
O(window) keys). Supports gemma2 logit softcapping and GQA by mapping
each query head to its KV head in the BlockSpec index map.

Block sizes default to MXU-aligned (128) tiles; head_dim is the minor
dimension of every matmul so the systolic array runs at full width for
hd in {64, 128, 256}.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, window,
                  softcap, bq, bk, sk):
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * scale           # (BQ, hd)
    nkb = sk // bk
    if causal:
        # highest k block any query in this q block can see
        nkb = jnp.minimum(nkb, (qi + 1) * bq // bk + ((qi + 1) * bq % bk != 0))
    lo = 0
    if window is not None:
        lo = jnp.maximum(0, (qi * bq - window + 1) // bk)

    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(kb * bk, bk), :].astype(jnp.float32)
        v = v_ref[pl.ds(kb * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (BQ, BK)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(lo, nkb, body, (m0, l0, a0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "scale", "bq", "bk",
                     "interpret"))
def flash_attention_fwd(q, k, v, *, causal=True, window=None, softcap=None,
                        scale=None, bq=DEFAULT_BQ, bk=DEFAULT_BK,
                        interpret=False):
    """q: (B, Sq, Hq, hd); k, v: (B, Sk, Hkv, hd). Returns (B, Sq, Hq, hd).

    Sq % bq == 0 and Sk % bk == 0 required (ops.py pads).
    """
    B, Sq, Hq, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, Sk, bq, bk)
    assert Hq % Hkv == 0
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    qt = q.transpose(0, 2, 1, 3)  # (B, Hq, Sq, hd)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (B, Hq, Sq // bq)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bk=bk, sk=Sk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((pl.squeezed, pl.squeezed, bq, hd),
                         lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((pl.squeezed, pl.squeezed, Sk, hd),
                         lambda b, h, i, hkv=Hkv, hq=Hq: (b, h * hkv // hq, 0, 0)),
            pl.BlockSpec((pl.squeezed, pl.squeezed, Sk, hd),
                         lambda b, h, i, hkv=Hkv, hq=Hq: (b, h * hkv // hq, 0, 0)),
        ],
        out_specs=pl.BlockSpec((pl.squeezed, pl.squeezed, bq, hd),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, hd), q.dtype),
        interpret=interpret, name="flash_attention",
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
