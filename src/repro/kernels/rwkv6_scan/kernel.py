"""Pallas TPU RWKV-6 WKV chunked-scan kernel.

Recurrence (per head, state S in R^{hd x hd}, decay w_t in (0,1)^hd):
    y_t = r_t^T (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T

Grid (B, H, nc) with the chunk dim innermost and "arbitrary" semantics: the
state is VMEM scratch carried across chunks (sequential in time, parallel
over batch and heads).  Within a chunk the recurrence is stepped with a
``fori_loop`` over C timesteps; each step is rank-1 VPU work on the
(hd, hd) state tile.  HBM traffic is one read of (r,k,v,w) and one write of
y per chunk — the memory-bound optimum — while the XLA fallback in
``repro.models.rwkv6`` re-materialises state per segment for autodiff.

VMEM (defaults C=128, hd=64): 4 chunk tiles (C, hd) f32 = 128 KB, state
(hd, hd) f32 = 16 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_ref, *, C: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0, 0].astype(jnp.float32)          # (C, hd)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)             # (hd,)

    def step(t, carry):
        S, y = carry                             # (hd, hd), (C, hd)
        rt, kt, vt, wt = r[t], k[t], v[t], w[t]  # (hd,)
        kv = kt[:, None] * vt[None, :]           # (hd, hd)
        att = S + u[:, None] * kv
        yt = rt @ att                            # (hd,)
        S = wt[:, None] * S + kv
        return S, jax.lax.dynamic_update_index_in_dim(y, yt, t, 0)

    S, y = jax.lax.fori_loop(0, C, step,
                             (s_ref[...], jnp.zeros((C, r.shape[1]),
                                                    jnp.float32)))
    s_ref[...] = S
    y_ref[0, 0] = y.astype(y_ref.dtype)


def rwkv6_wkv(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """r,k,v,w: (B, H, S, hd); u: (H, hd) -> y (B, H, S, hd)."""
    B, H, S, hd = r.shape
    C = min(chunk, S)
    assert S % C == 0, (S, C)
    kernel = functools.partial(_wkv_kernel, C=C)
    spec = pl.BlockSpec((1, 1, C, hd), lambda b, h, c: (b, h, c, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, S // C),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec((1, hd), lambda b, h, c: (h, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(r.shape, r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
