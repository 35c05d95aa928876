"""Fused VPC datapath megakernel: firewall -> NAT -> ChaCha20 in ONE Pallas
launch (the paper's "schedule the chain once" insight, §4.2, taken to the
kernel level).

The composed ComputeBackend path runs the three NTs as separate XLA ops:
each one round-trips the packet batch through HBM.  Here a tile of ``bn``
packets is DMA'd into VMEM once and all three NTs run over it in a single
pass — LPM verdict, header rewrite, keystream generation and payload XOR —
with the deny verdict applied at egress in the same pass, so headers and
payload never leave VMEM between NTs.  The grid walks the packet axis;
Pallas's grid pipeline double-buffers the HBM->VMEM tile fetches, so tile
``i+1`` streams in while tile ``i`` computes (the VPU-era version of the
sNIC keeping packet state on-chip across operators).

Layout per grid step (all u32 unless noted):

  headers (bn, 5)  [src, dst, sport, dport, proto]
  payload (bn, 16) one 64-byte ChaCha block per packet
  ctr     (bn, 1)  per-packet keystream counter (part of packet state so
                   batches coalesce without changing any ciphertext)
  rules   (1, R) x4: prefixes, masks, mask popcounts, allow bits
  key (1, 8), nonce (1, 3)

Bit-exactness contract: identical output to ``repro.serving.vpc.vpc_chain``
(see ref.py and tests/test_compute_runtime.py).  All arithmetic is integer,
so equality is exact, not allclose.

Firewall tie-breaking note: the reference resolves equal-length prefix hits
with ``argmax`` (first index wins).  A lane argmax is awkward on the VPU, so
we rank rules by the unique priority ``mlen * R + (R - 1 - idx)`` and take
the allow bit of the max-priority hit — the same winner by construction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas.tpu import CompilerParams

from repro.kernels.chacha20.core import chacha_rounds, init_state


def _vpc_datapath_kernel(prefixes_ref, masks_ref, mlen_ref, rallow_ref,
                         key_ref, nonce_ref, nat_ref, headers_ref,
                         payload_ref, ctr_ref, allow_ref, hout_ref, pout_ref,
                         *, bn: int, n_rules: int, salt: int):
    headers = headers_ref[...]                            # (bn, 5) u32

    # ---- NT 1: firewall (longest-prefix match on dst, default allow) ----
    dst = headers[:, 1][:, None]                          # (bn, 1)
    masks = masks_ref[...]                                # (1, R) u32
    hit = (dst & masks) == prefixes_ref[...]              # (bn, R)
    mlen = mlen_ref[...].astype(jnp.int32)                # (1, R)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (1, n_rules), 1)
    prio = jnp.where(hit, mlen * n_rules + (n_rules - 1 - ridx), -1)
    best = jnp.max(prio, axis=1, keepdims=True)           # (bn, 1)
    # the verdict stays int32 (0/1) until egress: Mosaic cannot lower a
    # reduction or select over bool vectors (i8 -> i1 truncation).  Hit
    # priorities are unique, so ``prio == best`` with ``best >= 0`` picks
    # exactly the winning rule; ``best < 0`` is "no hit" -> default allow.
    rallow = rallow_ref[...].astype(jnp.int32)            # (1, R) 0/1
    win_allow = jnp.max(jnp.where(prio == best, rallow, 0), axis=1,
                        keepdims=True)                    # (bn, 1)
    allow_i = jnp.where(best >= 0, win_allow, 1)          # (bn, 1) int32
    allow = allow_i != 0                                  # (bn, 1) mask

    # ---- NT 2: NAT source rewrite (flow-hash port, fixed ip) ----
    # ``x << 16`` is taken as two shifts of 8: on a v5e, Mosaic lowers a
    # lone u32 shift-left by 16 through a float path that flushes results
    # whose bits read as an f32 denormal or NaN (~1 in 128 values wrong).
    sport_hi = (headers[:, 2] << jnp.uint32(8)) << jnp.uint32(8)
    flow = headers[:, 0] ^ (headers[:, 1] * jnp.uint32(2654435761)) \
        ^ sport_hi ^ headers[:, 3] ^ headers[:, 4]
    new_port = ((flow * jnp.uint32(salt)) >> jnp.uint32(16)) \
        & jnp.uint32(0xFFFF)
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, 5), 1)
    nat_h = jnp.where(col == 0, nat_ref[0, 0], headers)
    nat_h = jnp.where(col == 2, new_port[:, None], nat_h)

    # ---- NT 3: ChaCha20 keystream generated in-VMEM, XOR at egress ----
    ctr = ctr_ref[...][:, 0]                              # (bn,) u32
    key = key_ref[...]                                    # (1, 8)
    nonce = nonce_ref[...]                                # (1, 3)
    init = init_state([key[0, w] for w in range(8)],
                      [nonce[0, w] for w in range(3)], ctr)
    s = chacha_rounds(init)
    payload = payload_ref[...]                            # (bn, 16)

    # ---- egress: apply the firewall verdict in the same pass ----
    allow_ref[...] = allow_i.astype(jnp.uint32)
    hout_ref[...] = jnp.where(allow, nat_h, headers)
    for w in range(16):
        ks = s[w] + init[w]                               # final add
        pout_ref[:, w] = jnp.where(allow[:, 0], payload[:, w] ^ ks,
                                   jnp.uint32(0))


def vpc_datapath_kernel_call(headers, payload, ctr, prefixes, masks, mlen,
                             rallow, key, nonce, nat_ip, *, salt: int,
                             block_n: int = 256, interpret: bool = False):
    """Raw fused launch.  All inputs preprocessed (see ops.py); N must be a
    multiple of the chosen tile size ``bn``.  ``nat_ip`` is a (1, 1) u32
    array (a kernel input, not a static, so deployments rebind it at
    runtime like every other param)."""
    N = headers.shape[0]
    R = prefixes.shape[0]
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    kernel = functools.partial(_vpc_datapath_kernel, bn=bn, n_rules=R,
                               salt=salt)
    rule_spec = pl.BlockSpec((1, R), lambda i: (0, 0))
    allow_u32, hout, pout = pl.pallas_call(
        kernel,
        grid=(N // bn,),
        in_specs=[
            rule_spec,                                    # prefixes
            rule_spec,                                    # masks
            rule_spec,                                    # mlen
            rule_spec,                                    # rallow
            pl.BlockSpec((1, 8), lambda i: (0, 0)),       # key
            pl.BlockSpec((1, 3), lambda i: (0, 0)),       # nonce
            pl.BlockSpec((1, 1), lambda i: (0, 0)),       # nat_ip
            pl.BlockSpec((bn, 5), lambda i: (i, 0)),      # headers
            pl.BlockSpec((bn, 16), lambda i: (i, 0)),     # payload
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),      # ctr
        ],
        out_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 5), lambda i: (i, 0)),
            pl.BlockSpec((bn, 16), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.uint32),
            jax.ShapeDtypeStruct((N, 5), jnp.uint32),
            jax.ShapeDtypeStruct((N, 16), jnp.uint32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(prefixes.reshape(1, R), masks.reshape(1, R), mlen.reshape(1, R),
      rallow.reshape(1, R), key.reshape(1, 8), nonce.reshape(1, 3),
      nat_ip.reshape(1, 1), headers, payload, ctr.reshape(N, 1))
    return allow_u32, hout, pout
