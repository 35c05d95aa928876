"""Pallas TPU flash-attention (causal, GQA) forward kernel.

Layout: q (B, Kv, G, S, hd); k, v (B, Kv, S, hd).  Grid (B, Kv, nq, nk) with
the kv-block dim innermost and "arbitrary" semantics: the online-softmax
running state (acc, m, denom) lives in VMEM scratch and is carried across kv
blocks; the output block is written once on the last kv iteration.

BlockSpec / VMEM budget (defaults bq = bk = 256, hd = 128, G <= 8):
  q block  (G*bq, hd) f32      = 1.0 MB
  k, v     (bk, hd)   f32      = 0.25 MB
  scores   (G*bq, bk) f32      = 2.0 MB
  acc      (G*bq, hd) f32      = 1.0 MB        => ~5 MB << 16 MB VMEM
MXU alignment: contraction dims are hd (128) and bk (multiple of 128);
row count G*bq is a multiple of 8.

Causality: kv blocks strictly above the diagonal are predicated off with
``pl.when`` — unlike the XLA fallback, no masked-out FLOPs are issued.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, d_ref, *,
               bq: int, bk: int, nk: int, G: int, scale: float,
               causal: bool):
    ki = pl.program_id(3)
    qi = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        d_ref[...] = jnp.zeros_like(d_ref)

    q_first = qi * bq
    k_first = ki * bk
    live = (k_first <= q_first + bq - 1) if causal else True

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32).reshape(G * bq, -1)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale      # (G*bq, bk)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (G * bq, bk), 0) % bq
            cols = jax.lax.broadcasted_iota(jnp.int32, (G * bq, bk), 1)
            mask = (q_first + rows) >= (k_first + cols)
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                  # (G*bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                               # (G*bq, bk)
        corr = jnp.exp(m_prev - m_new)
        d_ref[...] = d_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        den = jnp.maximum(d_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / den).reshape(
            G, bq, -1).astype(o_ref.dtype)


def flash_attention_grouped(q, k, v, *, block_q: int = 256,
                            block_k: int = 256, causal: bool = True,
                            interpret: bool = False):
    """q: (B, Kv, G, S, hd); k, v: (B, Kv, S, hd) -> out like q."""
    B, Kv, G, S, hd = q.shape
    bq = min(block_q, S)
    bk = min(block_k, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    scale = hd ** -0.5

    kernel = functools.partial(_fa_kernel, bq=bq, bk=bk, nk=nk, G=G,
                               scale=scale, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, Kv, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, bq, hd), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, bq, hd),
                               lambda b, h, i, j: (b, h, 0, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, hd), jnp.float32),
            pltpu.VMEM((G * bq, 1), jnp.float32),
            pltpu.VMEM((G * bq, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
