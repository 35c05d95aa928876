"""Plain numpy reference of the VPC network tasks, the benchmark's yardstick.

It is written from the semantics alone and imports nothing of the program
under test, so a change to the program cannot move it:

  firewall  longest-prefix match of the destination address against the
            tenant's rule table; the longest matching mask wins, the first
            rule wins a tie; a packet that matches no rule is allowed.
  nat       source rewrite: the source address becomes the tenant's NAT
            address, the source port a 16-bit hash of the 5-tuple.
  chacha20  RFC 8439 ChaCha20 block function; each packet's 64-byte payload
            is one block, XORed with the keystream of its own counter.
  egress    where the chain has a firewall, a denied packet leaves with its
            original header and a zeroed payload.

Headers are ``(N, 5)`` u32 ``[src, dst, sport, dport, proto]``; payloads are
``(N, 16)`` u32.  All arithmetic is u32 and wraps, so results are exact.
"""
from __future__ import annotations

import numpy as np

U32 = np.uint32
CONSTANTS = (0x61707865, 0x3320646E, 0x79622D32, 0x6B206574)
#: multiplier of the flow hash's 5-tuple mix and of the port hash
FLOW_MUL = 2654435761
PORT_SALT = 0x9E3779B9


def popcount32(x: np.ndarray) -> np.ndarray:
    """Set bits of each u32, as int32."""
    bits = np.unpackbits(np.ascontiguousarray(x, dtype=U32).view(np.uint8))
    return bits.reshape(-1, 32).sum(axis=1).astype(np.int32)


def firewall(headers: np.ndarray, prefixes: np.ndarray, masks: np.ndarray,
             allow: np.ndarray, chunk: int = 2048) -> np.ndarray:
    """Verdict of each packet, ``(N,)`` bool."""
    dst = headers[:, 1].astype(U32)
    mlen = popcount32(masks)
    masks = masks.astype(U32)
    prefixes = prefixes.astype(U32)
    allow = allow.astype(bool)
    out = np.empty(len(dst), bool)
    for i in range(0, len(dst), chunk):
        hit = (dst[i:i + chunk, None] & masks[None, :]) == prefixes[None, :]
        score = np.where(hit, mlen[None, :], -1)
        best = score.argmax(axis=1)           # first index wins a tie
        out[i:i + chunk] = np.where(hit.any(axis=1), allow[best], True)
    return out


def nat(headers: np.ndarray, nat_ip: int) -> np.ndarray:
    """Rewritten headers, ``(N, 5)`` u32."""
    h = headers.astype(U32)
    flow = h[:, 0] ^ (h[:, 1] * U32(FLOW_MUL)) ^ (h[:, 2] << U32(16)) \
        ^ h[:, 3] ^ h[:, 4]
    port = ((flow * U32(PORT_SALT)) >> U32(16)) & U32(0xFFFF)
    out = h.copy()
    out[:, 0] = U32(nat_ip)
    out[:, 2] = port
    return out


def _rotl(x: np.ndarray, n: int) -> np.ndarray:
    return (x << U32(n)) | (x >> U32(32 - n))


def _quarter(s: list, a: int, b: int, c: int, d: int) -> None:
    s[a] = s[a] + s[b]
    s[d] = _rotl(s[d] ^ s[a], 16)
    s[c] = s[c] + s[d]
    s[b] = _rotl(s[b] ^ s[c], 12)
    s[a] = s[a] + s[b]
    s[d] = _rotl(s[d] ^ s[a], 8)
    s[c] = s[c] + s[d]
    s[b] = _rotl(s[b] ^ s[c], 7)


def chacha20(payload: np.ndarray, key: np.ndarray, nonce: np.ndarray,
             ctr: np.ndarray) -> np.ndarray:
    """``payload`` XOR the ChaCha20 keystream block of each row's counter."""
    n = len(payload)
    init = [np.full(n, c, U32) for c in CONSTANTS]
    init += [np.full(n, k, U32) for k in np.asarray(key, U32)]
    init.append(np.asarray(ctr, U32))
    init += [np.full(n, w, U32) for w in np.asarray(nonce, U32)]
    s = list(init)
    for _ in range(10):
        _quarter(s, 0, 4, 8, 12)
        _quarter(s, 1, 5, 9, 13)
        _quarter(s, 2, 6, 10, 14)
        _quarter(s, 3, 7, 11, 15)
        _quarter(s, 0, 5, 10, 15)
        _quarter(s, 1, 6, 11, 12)
        _quarter(s, 2, 7, 8, 13)
        _quarter(s, 3, 4, 9, 14)
    ks = np.stack([s[w] + init[w] for w in range(16)], axis=1)
    return payload.astype(U32) ^ ks


def counters(counter0: int, n: int) -> np.ndarray:
    """The keystream counters ``counter0, counter0 + 1, ...`` mod 2**32."""
    return ((counter0 + np.arange(n, dtype=np.uint64)) % (1 << 32)).astype(U32)


def chain(nts, headers: np.ndarray, payload: np.ndarray, tenant,
          counter0: int = 1) -> dict:
    """Run the chain ``nts`` (NT names in order) on one batch.

    ``tenant`` carries ``prefixes``, ``masks``, ``allow``, ``nat_ip``,
    ``key`` and ``nonce``.  Returns ``headers``, ``payload`` and, where the
    chain has a firewall, ``allow``."""
    h, p, verdict = headers.astype(U32), payload.astype(U32), None
    for name in nts:
        if name == "firewall":
            v = firewall(h, tenant.prefixes, tenant.masks, tenant.allow)
            verdict = v if verdict is None else verdict & v
        elif name == "nat":
            h = nat(h, tenant.nat_ip)
        elif name == "chacha20":
            p = chacha20(p, tenant.key, tenant.nonce,
                         counters(counter0, len(p)))
        else:
            raise ValueError(f"no reference for NT {name!r}")
    out = {"headers": h, "payload": p}
    if verdict is not None:
        out["headers"] = np.where(verdict[:, None], h, headers.astype(U32))
        out["payload"] = np.where(verdict[:, None], p, U32(0))
        out["allow"] = verdict
    return out
