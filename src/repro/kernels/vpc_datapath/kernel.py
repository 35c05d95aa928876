"""Fused VPC datapath megakernel: firewall -> NAT -> ChaCha20 in ONE Pallas
launch (the paper's "schedule the chain once" insight, §4.2, taken to the
kernel level).

The composed ComputeBackend path runs the three NTs as separate XLA ops:
each one round-trips the packet batch through HBM.  Here a tile of ``bn``
packets is DMA'd into VMEM once and all three NTs run over it in a single
pass — LPM verdict, header rewrite, keystream generation and payload XOR —
with the deny verdict applied at egress in the same pass, so headers and
payload never leave VMEM between NTs.

The grid is (packet tiles, rule tiles), the rule axis innermost.  The
packet blocks ignore the rule axis, so each is fetched once and written
back once; the rule table streams through VMEM a tile at a time while the
packets stay resident, and Pallas's grid pipeline double-buffers every
fetch, so the next rule tile (or packet tile) streams in while this one
computes.  VMEM use is therefore bounded by the rule tile, not by the
table: a tenant may hold any number of rules.

Layout per grid step (all 32-bit):

  headers (bn, 5)  u32 [src, dst, sport, dport, proto]
  payload (bn, 16) u32 one 64-byte ChaCha block per packet
  ctr     (bn, 1)  u32 per-packet keystream counter (part of packet state so
                   batches coalesce without changing any ciphertext)
  rules   (3 * chunks, lanes) i32: one rule tile, ``chunks`` rows each of
          prefixes, masks and keys (``ops.rule_slab``), one DMA
  best    (bn, lanes) i32 VMEM scratch: the running best key per packet
          and lane, carried across the rule tiles
  key (1, 8), nonce (1, 3), nat_ip (1, 1) u32

Firewall: each rule carries one key, ``2 * (mlen * R + R - 1 - idx) +
allow``, unique per rule and ordered as the reference ranks hits: the
longest prefix wins, the first index wins a tie, and the low bit is the
verdict.  A scan is then one masked max: ``max(where(hit, key, -1))``; pad
rules carry key -1 and never win.  The scan walks the rule tile ``lanes``
rules at a time over ``SCAN_ROWS`` packets, so its operands stay in vector
registers, and folds lanes together only once, after the last rule tile.

Bit-exactness contract: identical output to ``repro.serving.vpc.vpc_chain``
(see ref.py and tests/test_compute_runtime.py).  All arithmetic is integer,
so equality is exact, not allclose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.chacha20.core import chacha_rounds, init_state

#: packets a firewall scan holds in registers at once
SCAN_ROWS = 128
#: a rule tile of at most this many lane rows is scanned in straight-line
#: code, a longer one in a loop (Mosaic unrolls a loop wholly or not at
#: all): at 1,024 rules, 8 rows, a 16,384-packet launch took 0.681 ms
#: unrolled against 0.705 looped on a v5e
UNROLL_CHUNKS = 16


def _firewall_scan(rules_ref, dst, best_ref, *, chunks: int, rows: int):
    """Fold one rule tile into the running best key of every packet: for
    each block of ``rows`` packets, the tile's ``chunks`` rule rows in turn,
    one elementwise ``max(best, where(hit, key, -1))`` each."""
    bn, lanes = best_ref.shape
    for r in range(0, bn, rows):
        m = min(rows, bn - r)
        d = jnp.broadcast_to(dst[r:r + m], (m, lanes))

        def chunk(c, best, d=d):
            prefix = rules_ref[pl.ds(c, 1), :]                # (1, lanes)
            mask = rules_ref[pl.ds(chunks + c, 1), :]
            key = rules_ref[pl.ds(2 * chunks + c, 1), :]
            return jnp.maximum(best, jnp.where((d & mask) == prefix, key, -1))

        best_ref[r:r + m, :] = jax.lax.fori_loop(
            0, chunks, chunk, best_ref[r:r + m, :],
            unroll=chunks <= UNROLL_CHUNKS)


def _vpc_datapath_kernel(rules_ref, key_ref, nonce_ref, nat_ref, headers_ref,
                         payload_ref, ctr_ref, allow_ref, hout_ref, pout_ref,
                         best_ref, *, chunks: int, rows: int, salt: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _reset():
        best_ref[...] = jnp.full(best_ref.shape, -1, jnp.int32)

    # ---- NT 1: firewall (longest-prefix match on dst, default allow) ----
    # the scan runs in int32: & and == see the same bits either way
    dst = jax.lax.bitcast_convert_type(headers_ref[:, 1:2], jnp.int32)
    _firewall_scan(rules_ref, dst, best_ref, chunks=chunks, rows=rows)

    @pl.when(j == pl.num_programs(1) - 1)
    def _egress():
        _nat_chacha_egress(best_ref, key_ref, nonce_ref, nat_ref,
                           headers_ref, payload_ref, ctr_ref, allow_ref,
                           hout_ref, pout_ref, salt=salt)


def _nat_chacha_egress(best_ref, key_ref, nonce_ref, nat_ref, headers_ref,
                       payload_ref, ctr_ref, allow_ref, hout_ref, pout_ref,
                       *, salt: int):
    """The firewall's verdict from the best keys, then NAT, ChaCha20 and
    egress over the packet tile, after its last rule tile."""
    bn = headers_ref.shape[0]
    headers = headers_ref[...]                            # (bn, 5) u32
    best = jnp.max(best_ref[...], axis=1, keepdims=True)  # (bn, 1) int32
    # the verdict stays int32 (0/1) until egress: Mosaic cannot lower a
    # reduction or select over bool vectors (i8 -> i1 truncation).
    # ``best < 0`` is "no hit" -> default allow; else the key's low bit.
    allow_i = jnp.where(best >= 0, best & 1, 1)           # (bn, 1) int32
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


def vpc_datapath_kernel_call(headers, payload, ctr, rules, key, nonce,
                             nat_ip, *, salt: int, block_n: int = 256,
                             interpret: bool = False):
    """Raw fused launch.  All inputs preprocessed (see ops.py); N must be a
    multiple of the chosen tile size ``bn``.  ``rules`` is the rule slab
    ``(tiles, 3 * chunks, lanes)`` int32 of ``ops.rule_slab``: one rule
    tile a grid step along the grid's inner axis.  ``nat_ip`` is a (1, 1) u32 array (a kernel input,
    not a static, so deployments rebind it at runtime like every other
    param)."""
    N = headers.shape[0]
    tiles, rows3, lanes = rules.shape
    chunks = rows3 // 3
    bn = min(block_n, N)
    assert N % bn == 0, (N, bn)
    kernel = functools.partial(_vpc_datapath_kernel, chunks=chunks,
                               rows=min(SCAN_ROWS, bn), salt=salt)

    def fixed(shape):
        return pl.BlockSpec(shape, lambda i, j: (0, 0))

    def per_packet(width):
        return pl.BlockSpec((bn, width), lambda i, j: (i, 0))

    allow_u32, hout, pout = pl.pallas_call(
        kernel,
        grid=(N // bn, tiles),
        in_specs=[
            pl.BlockSpec((None, rows3, lanes), lambda i, j: (j, 0, 0)),
            fixed((1, 8)),                                # key
            fixed((1, 3)),                                # nonce
            fixed((1, 1)),                                # nat_ip
            per_packet(5),                                # headers
            per_packet(16),                               # payload
            per_packet(1),                                # ctr
        ],
        out_specs=[per_packet(1), per_packet(5), per_packet(16)],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1), jnp.uint32),
            jax.ShapeDtypeStruct((N, 5), jnp.uint32),
            jax.ShapeDtypeStruct((N, 16), jnp.uint32),
        ],
        scratch_shapes=[pltpu.VMEM((bn, lanes), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rules, key.reshape(1, 8), nonce.reshape(1, 3), nat_ip.reshape(1, 1),
      headers, payload, ctr.reshape(N, 1))
    return allow_u32, hout, pout
