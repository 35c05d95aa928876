"""Jittable wrapper for the fused VPC datapath megakernel.

Handles everything the raw kernel keeps static: the rule tile's size and
the rule slab (one key per rule, the table padded to whole tiles), default
per-packet counters, padding the packet axis to a tile multiple, backend
selection (interpret off-TPU), and slicing the pad rows back off.  The
result triple matches ``vpc_chain`` bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import SCAN_ROWS, vpc_datapath_kernel_call


def _popcount32(masks):
    """Per-mask set-bit count, identical to the reference firewall's
    ``unpackbits`` expression (pure u32 arithmetic, jit-safe)."""
    x = masks.astype(jnp.uint32)
    x = x - ((x >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> jnp.uint32(2))
                                        & jnp.uint32(0x33333333))
    x = (x + (x >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return (x * jnp.uint32(0x01010101)) >> jnp.uint32(24)


#: lanes of a rule tile's rows: the firewall scans a tile this many rules
#: at a time
LANES = 128
#: largest rule tile: a table holding more streams through the kernel in
#: tiles of at most this many rules
MAX_RULE_TILE = 16384
#: live ``(SCAN_ROWS, LANES)`` int32 values of one scan step: the masked
#: address, the hit select and the running best
SCAN_VALUES = 3


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def rule_tile_for(n_rules: int) -> int:
    """Rules per tile for a table of ``n_rules``: the whole table, rounded
    up to whole lane rows, while it fits ``MAX_RULE_TILE``; else the fewest
    tiles of at most that size, as even as whole lane rows allow."""
    tiles = -(-max(n_rules, 1) // MAX_RULE_TILE)
    return _round_up(-(-max(n_rules, 1) // tiles), LANES)


def rule_slab(rules, tile: int):
    """The rule table as the kernel streams it: ``(tiles, 3 * tile //
    LANES, LANES)`` int32, each tile one sublane-dense block of its
    prefixes, masks and keys, ``tile // LANES`` rows each.

    A rule's key is ``2 * (mlen * R + R - 1 - idx) + allow``: one number
    that ranks hits as the reference does (the longest prefix, then the
    first index) and carries the verdict in its low bit.  The table is
    padded to whole tiles with rules of key -1, which never win."""
    prefixes, masks, allow = rules
    n = prefixes.shape[0]
    if n * 66 >= 2 ** 31:                  # 2 * (32 * R + R) must stay int32
        raise ValueError(f"{n} rules overflow the int32 rule keys")
    idx = jnp.arange(n, dtype=jnp.int32)
    mlen = _popcount32(masks).astype(jnp.int32)
    keys = 2 * (mlen * n + (n - 1 - idx)) + allow.astype(jnp.int32)
    tiles = -(-n // tile)
    pad = tiles * tile - n
    as_i32 = functools.partial(jax.lax.bitcast_convert_type,
                               new_dtype=jnp.int32)
    slab = jnp.stack([
        jnp.pad(as_i32(prefixes.astype(jnp.uint32)), (0, pad)),
        jnp.pad(as_i32(masks.astype(jnp.uint32)), (0, pad)),
        jnp.pad(keys, (0, pad), constant_values=-1)])      # (3, tiles*tile)
    chunks = tile // LANES
    return slab.reshape(3, tiles, chunks, LANES).transpose(1, 0, 2, 3) \
        .reshape(tiles, 3 * chunks, LANES)


def _block_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of a 32-bit ``(rows, cols)`` block, laid out in (8, 128)
    tiles."""
    return 4 * _round_up(rows, 8) * _round_up(cols, LANES)


def vmem_tile_bytes(block_n: int = 256, tile: int = MAX_RULE_TILE) -> int:
    """Worst-case VMEM residency of one grid step, from the kernel's
    BlockSpecs and scratch, every block in its (8, 128) layout: the packet
    tiles (headers, payload, ctr in; verdict, headers, payload out), the
    rule tile of ``tile`` rules and the key, nonce and NAT address, all
    double-buffered; the ``(block_n, LANES)`` running best; and the scan's
    ``SCAN_VALUES`` ``(SCAN_ROWS, LANES)`` intermediates.  The admission
    verifier sums this per fused branch against
    ``core.vmem.VMEM_BUDGET_BYTES``."""
    packets = sum(_block_bytes(block_n, w) for w in (5, 16, 1, 1, 5, 16))
    params = _block_bytes(1, 8) + _block_bytes(1, 3) + _block_bytes(1, 1)
    rule = _block_bytes(3 * (tile // LANES), LANES)
    scan = SCAN_VALUES * _block_bytes(min(SCAN_ROWS, block_n), LANES)
    return 2 * (packets + params + rule) + _block_bytes(block_n, LANES) \
        + scan


def vpc_datapath(headers, payload, rules, key, nonce,
                 nat_ip: int = 0x0A000001, counter0: int = 1, ctr=None,
                 salt: int = 0x9e3779b9, block_n: int = 256,
                 interpret: bool | None = None):
    """Fused firewall -> NAT -> ChaCha20 over a packet batch, one kernel
    launch.  Same signature contract as ``vpc_chain``: headers (N, 5) u32,
    payload (N, 16) u32 -> (allow (N,) bool, new_headers, ciphertext).

    ``ctr``: optional (N,) u32 per-packet keystream counters (defaults to
    ``counter0 + arange(N)``, the ``vpc_chain`` convention).  ``nat_ip`` and
    ``counter0`` may be traced values — nothing here is a compile-time
    static except the tile sizes.  A traced 0-d ``counter0`` is the
    streaming dispatch ring's per-slot counter base: the ring ships one u32
    per slot and the counter run is synthesized here, on device, inside the
    jitted program (pad rows take counters past the batch; their output is
    sliced off with the other pad rows)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    N = headers.shape[0]
    if N == 0:                  # empty batch: nothing to launch
        return (jnp.zeros((0,), bool), headers, payload)
    if ctr is None:
        ctr = jnp.asarray(counter0, jnp.uint32) \
            + jnp.arange(N, dtype=jnp.uint32)
    tile = rule_tile_for(rules[0].shape[0])
    bn = min(block_n, N)
    pad = (-N) % bn
    if pad:
        headers = jnp.pad(headers, ((0, pad), (0, 0)))
        payload = jnp.pad(payload, ((0, pad), (0, 0)))
        ctr = jnp.pad(ctr, (0, pad))
    allow_u32, hout, pout = vpc_datapath_kernel_call(
        headers, payload, ctr, rule_slab(rules, tile),
        key.astype(jnp.uint32), nonce.astype(jnp.uint32),
        jnp.asarray(nat_ip, jnp.uint32), salt=salt, block_n=bn,
        interpret=interpret)
    return (allow_u32[:N, 0] != 0, hout[:N], pout[:N])
