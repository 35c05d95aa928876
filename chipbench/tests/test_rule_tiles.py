"""The megakernel with its rule table streamed in tiles equals both
references: the program's own ``vpc_datapath_ref`` and the benchmark's
numpy ``reference.chain``, in interpret mode, on packet tiles of 8 and
rule tiles of 128 rules (``MAX_RULE_TILE`` lowered to 128).  The cases put
the table below one tile, across a ragged last tile and across several
whole tiles, break equal-length ties across two tiles, and leave packets
with no hit beside pad rules.  One case takes tiles of 17 lane rows, which
the kernel scans in a loop where it unrolls shorter ones."""
from __future__ import annotations

from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference

TILE = 128
#: rules a tile in the rolled-loop case: one lane row past ``UNROLL_CHUNKS``
LONG_TILE = 17 * TILE
N = 24
NAT_IP = 0x0A000007


def masks_of(mlen):
    mlen = np.asarray(mlen)
    return ((0xFFFFFFFF << (32 - mlen)) & 0xFFFFFFFF).astype(np.uint32)


def packets(seed, dst=None):
    rng = np.random.default_rng(seed)
    h = rng.integers(0, 2 ** 32, (N, 5), dtype=np.uint32)
    if dst is not None:
        h[:, 1] = dst
    return h, rng.integers(0, 2 ** 32, (N, 16), dtype=np.uint32)


def hitting(n_rules, seed):
    """Packets and ``n_rules`` rules, half of them prefixes of the packets'
    own destinations, so most packets hit several rules."""
    h, p = packets(seed)
    rng = np.random.default_rng(seed + 1)
    masks = masks_of(rng.integers(8, 25, n_rules))
    pre = rng.integers(0, 2 ** 32, n_rules, dtype=np.uint32)
    own = rng.random(n_rules) < 0.5
    pre[own] = h[rng.integers(0, N, own.sum()), 1]
    return h, p, (pre & masks, masks, rng.random(n_rules) < 0.5)


def ties_across_tiles():
    """Two tiles of rules: rules 100 and 200 are one /16 (deny, then
    allow), rules 50 and 130 another (allow, then deny): the first wins.
    Rule 250, a /24 in the second tile, outranks rule 20's /16 in the
    first.  The other rules match none of the packets."""
    n = 2 * TILE
    pre = np.full(n, 0xC0000000, np.uint32)
    mlen = np.full(n, 24)
    allow = np.zeros(n, bool)
    for i, (net, bits, ok) in {100: (0x0A010000, 16, False),
                               200: (0x0A010000, 16, True),
                               50: (0x0A020000, 16, True),
                               130: (0x0A020000, 16, False),
                               20: (0x0A030000, 16, False),
                               250: (0x0A030300, 24, True)}.items():
        pre[i], mlen[i], allow[i] = net, bits, ok
    dst = np.resize(np.array([0x0A010203, 0x0A020304, 0x0A030304,
                              0x0A030404, 0x0B000000], np.uint32), N)
    h, p = packets(7, dst)
    return h, p, (pre, masks_of(mlen), allow), \
        np.resize([False, True, True, False, True], N)


def no_hit():
    """130 rules (a ragged second tile, padded with 126 pad rules), none
    matching: every packet is allowed."""
    rng = np.random.default_rng(9)
    masks = masks_of(rng.integers(8, 25, 130))
    pre = (0x0A000000 | rng.integers(0, 2 ** 24, 130,
                                     dtype=np.uint32)) & masks
    h, p = packets(9, rng.integers(0xC0000000, 0xC0FFFFFF, N,
                                   dtype=np.uint32))
    return h, p, (pre, masks, np.zeros(130, bool)), [True] * N


def match_all_last():
    """A real /0 deny as the last rule of a padded tile: it matches every
    packet and, unlike the pad rules beside it, wins where nothing longer
    hits."""
    h, p, (pre, masks, allow) = hitting(70, seed=11)
    pre[-1], masks[-1], allow[-1] = 0, 0, False
    return h, p, (pre, masks, allow), None


CASES = {
    "below-one-tile": lambda: (*hitting(50, seed=1), None),
    "ragged-last-tile": lambda: (*hitting(300, seed=3), None),
    "several-whole-tiles": lambda: (*hitting(4 * TILE, seed=5), None),
    "tie-across-tiles": ties_across_tiles,
    "no-hit-beside-pad-rules": no_hit,
    "match-all-beside-pad-rules": match_all_last,
    "rolled-scan-loop": lambda: (*hitting(2 * LONG_TILE - 5, seed=15), None),
}


@pytest.mark.parametrize("case", CASES)
def test_rule_tiled_kernel_equals_both_references(case, monkeypatch):
    from repro.kernels.vpc_datapath import ops, vpc_datapath, vpc_datapath_ref
    from repro.kernels.vpc_datapath.kernel import UNROLL_CHUNKS
    tile = LONG_TILE if case == "rolled-scan-loop" else TILE
    assert (tile // ops.LANES > UNROLL_CHUNKS) == (tile == LONG_TILE)
    monkeypatch.setattr(ops, "MAX_RULE_TILE", tile)
    h, p, (pre, masks, allow), want_allow = CASES[case]()
    pre = pre & masks
    rng = np.random.default_rng(13)
    key = rng.integers(0, 2 ** 32, 8, dtype=np.uint32)
    nonce = rng.integers(0, 2 ** 32, 3, dtype=np.uint32)
    rules = (jnp.asarray(pre), jnp.asarray(masks), jnp.asarray(allow))
    args = (jnp.asarray(h), jnp.asarray(p), rules, jnp.asarray(key),
            jnp.asarray(nonce))
    got = vpc_datapath(*args, nat_ip=NAT_IP, block_n=8, interpret=True)
    program = vpc_datapath_ref(*args, nat_ip=NAT_IP)
    numpy = reference.chain(
        ("firewall", "nat", "chacha20"), h, p,
        SimpleNamespace(prefixes=pre, masks=masks, allow=allow,
                        nat_ip=NAT_IP, key=key, nonce=nonce))
    for i, k in enumerate(("allow", "headers", "payload")):
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(program[i]), err_msg=k)
        np.testing.assert_array_equal(np.asarray(got[i]), numpy[k],
                                      err_msg=k)
    if want_allow is not None:
        np.testing.assert_array_equal(np.asarray(got[0]), want_allow)
    else:                 # the hitting tables decide both ways
        assert 0 < int(np.asarray(got[0]).sum()) < N
