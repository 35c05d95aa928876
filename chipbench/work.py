"""The work a chain must do per packet, counted from its semantics.

These counts are the benchmark's, not the program's: they do not change
when the program changes how it implements a chain, so a roofline share
read against them stays comparable across implementations.
"""
from __future__ import annotations

HEADER_BYTES = 20          # five u32 words
PAYLOAD_BYTES = 64         # one ChaCha20 block
VERDICT_BYTES = 1
#: bytes of one packet on the wire, as the share of delivered bytes counts it
WIRE_BYTES = HEADER_BYTES + PAYLOAD_BYTES


def chain_bytes(nts) -> int:
    """Bytes a chain must read and write per packet: the header in where an
    NT reads it, the header out where NAT rewrites it, the payload in and
    out where ChaCha20 encrypts it, and one byte of verdict where a
    firewall decides.  The rule table and the per-packet counter are
    implementation detail and are not counted."""
    nts = tuple(nts)
    b = 0
    if "firewall" in nts or "nat" in nts:
        b += HEADER_BYTES
    if "nat" in nts:
        b += HEADER_BYTES
    if "chacha20" in nts:
        b += 2 * PAYLOAD_BYTES
    if "firewall" in nts:
        b += VERDICT_BYTES
    return b
