"""Packets delivered per second, in millions: every packet whose result was
ready inside the window, over the window's whole length (64-byte packets,
RFC 2544's smallest frame, where the per-packet cost rules)."""


def read(r):
    if r.window_s <= 0:
        return None
    return sum(r.window.delivered) / r.window_s / 1e6
