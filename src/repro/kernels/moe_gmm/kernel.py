"""Pallas TPU grouped matmul (MoE expert FFN): x (E, C, d) @ w (E, d, f).

Grid (E, C/bc, f/bf, d/bd): classic tiled matmul per expert with a VMEM f32
accumulator carried across the contraction (innermost, "arbitrary") dim;
the output tile is written once on the last contraction step.

BlockSpec / VMEM (defaults bc=128, bf=128, bd=512):
  x tile (bc, bd) bf16 = 128 KB;  w tile (bd, bf) = 128 KB;
  acc    (bc, bf) f32  = 64 KB    — MXU-aligned (128 x 128 output tile).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, nd: int):
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[0], w_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def moe_gmm(x, w, *, block_c: int = 128, block_f: int = 128,
            block_d: int = 512, interpret: bool = False):
    """x: (E, C, d); w: (E, d, f) -> (E, C, f) in x.dtype."""
    E, C, d = x.shape
    _, _, f = w.shape
    bc, bf, bd = min(block_c, C), min(block_f, f), min(block_d, d)
    assert C % bc == 0 and f % bf == 0 and d % bd == 0, (C, f, d, bc, bf, bd)
    nd = d // bd
    kernel = functools.partial(_gmm_kernel, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, C // bc, f // bf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, i, j, k: (e, i, k)),
            pl.BlockSpec((1, bd, bf), lambda e, i, j, k: (e, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e, i, j, k: (e, i, j)),
        out_shape=jax.ShapeDtypeStruct((E, C, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
