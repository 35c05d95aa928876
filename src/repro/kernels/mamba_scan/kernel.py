"""Pallas TPU Mamba (S6) selective-scan kernel.

Recurrence (per channel block, state h in R^{di_b x ds}):
    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) B_t
    y_t = h_t C_t^T + D * x_t

Grid (B, n_di, nc): chunk dim innermost ("arbitrary") with the state in VMEM
scratch; channel blocks are parallel (A, D, and the state are sliced per
channel block; B_t/C_t are shared across channel blocks).  Within a chunk a
``fori_loop`` steps C timesteps of elementwise VPU work on the (di_b, ds)
state tile.

VMEM (defaults C=128, di_b=512, ds=16): x/dt tiles (C, di_b) f32 = 512 KB,
B/C tiles (C, ds) = 8 KB, state (di_b, ds) = 32 KB, A (di_b, ds) = 32 KB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref,
                  *, C: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)             # (C, di_b)
    dt = dt_ref[0].astype(jnp.float32)           # (C, di_b)
    Bm = b_ref[0].astype(jnp.float32)            # (C, ds)
    Cm = c_ref[0].astype(jnp.float32)            # (C, ds)
    A = a_ref[...].astype(jnp.float32)           # (di_b, ds)
    D = d_ref[...].astype(jnp.float32)           # (1, di_b)

    def step(t, carry):
        h, y = carry                             # (di_b, ds), (C, di_b)
        dA = jnp.exp(dt[t][:, None] * A)         # (di_b, ds)
        dBx = (dt[t] * x[t])[:, None] * Bm[t][None, :]
        h = dA * h + dBx
        yt = h @ Cm[t] + D[0] * x[t]             # (di_b,)
        return h, jax.lax.dynamic_update_index_in_dim(y, yt, t, 0)

    h, y = jax.lax.fori_loop(
        0, C, step, (h_ref[...], jnp.zeros_like(x)))
    h_ref[...] = h
    y_ref[0] = y.astype(y_ref.dtype)


def mamba_ssm(x, dt, Bmat, Cmat, A, D, *, chunk: int = 128,
              block_di: int = 512, interpret: bool = False):
    """x, dt: (B, S, di); Bmat, Cmat: (B, S, ds); A: (di, ds); D: (di,).

    Returns y (B, S, di)."""
    B, S, di = x.shape
    ds = Bmat.shape[-1]
    C = min(chunk, S)
    dib = min(block_di, di)
    assert S % C == 0 and di % dib == 0, (S, C, di, dib)
    kernel = functools.partial(_mamba_kernel, C=C)
    return pl.pallas_call(
        kernel,
        grid=(B, di // dib, S // C),
        in_specs=[
            pl.BlockSpec((1, C, dib), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, C, dib), lambda b, i, c: (b, c, i)),
            pl.BlockSpec((1, C, ds), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((1, C, ds), lambda b, i, c: (b, c, 0)),
            pl.BlockSpec((dib, ds), lambda b, i, c: (i, 0)),
            pl.BlockSpec((1, dib), lambda b, i, c: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, C, dib), lambda b, i, c: (b, c, i)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((dib, ds), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, Bmat, Cmat, A, D.reshape(1, di))
