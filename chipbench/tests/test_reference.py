"""The benchmark's numpy reference equals the program on every chain
template: ``vpc_chain``, the composed XLA path and the fused megakernel
(in interpret mode), with the keystream counter restarting in each batch
and running on across batches."""
from __future__ import annotations

import functools
import operator

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, spec
from chipbench.cell import make_packets, make_tenants, seed_rng

TEMPLATES = [("firewall",), ("firewall", "nat"), ("nat",),
             ("firewall", "nat", "chacha20")]
SIZES = (40, 24)                  # two batches; the second is padded


def tenant(chain, seed=3, rules=32):
    cfg = spec.load_config("vpc8-r1k")
    cfg["deployment"].update(rules_per_tenant=rules, tenants=1,
                             chains={">>".join(chain): 1})
    return make_tenants(cfg, seed)[0]


def batches(seed=3):
    rng = seed_rng(seed, 0, 2)
    return [make_packets(rng, n) for n in SIZES]


def run_program(chain, t, stream, use_fused):
    from repro.api import VPC_SPECS, ComputeBackend, Platform, nt
    plat = Platform(ComputeBackend(use_fused=use_fused), specs=VPC_SPECS)
    expr = functools.reduce(operator.rshift, [nt(n) for n in chain])
    dep = plat.tenant(t.name, weight=1.0).deploy(expr,
                                                 params=t.params(stream))
    for h, p in batches():
        dep.inject(headers=h, payload=p)
    plat.run()
    return plat.report().tenants[t.name].outputs


def check(outs, chain, t, stream):
    assert len(outs) == len(SIZES)
    c0 = 1
    for out, (h, p) in zip(outs, batches()):
        want = reference.chain(chain, h, p, t, c0 if stream else 1)
        assert set(want) <= set(out)
        for k, w in want.items():
            np.testing.assert_array_equal(np.asarray(out[k]), w, err_msg=k)
        c0 += len(h)


@pytest.mark.parametrize("stream", [False, True],
                         ids=["counter-per-batch", "counter-runs-on"])
@pytest.mark.parametrize("chain", TEMPLATES, ids=">>".join)
def test_reference_equals_composed_path(chain, stream):
    t = tenant(chain)
    check(run_program(chain, t, stream, use_fused=False), chain, t, stream)


@pytest.mark.parametrize("stream", [False, True],
                         ids=["counter-per-batch", "counter-runs-on"])
def test_reference_equals_megakernel(stream):
    chain = TEMPLATES[-1]
    t = tenant(chain)
    check(run_program(chain, t, stream, use_fused=True), chain, t, stream)


@pytest.mark.parametrize("counter0", [1, 2 ** 32 - 5])
def test_reference_equals_vpc_chain(counter0):
    from repro.serving.vpc import vpc_chain
    t = tenant(TEMPLATES[-1], seed=9)
    h, p = batches(seed=9)[0]
    allow, nh, ct = vpc_chain(
        jnp.asarray(h), jnp.asarray(p),
        (jnp.asarray(t.prefixes), jnp.asarray(t.masks),
         jnp.asarray(t.allow)),
        jnp.asarray(t.key), jnp.asarray(t.nonce), nat_ip=t.nat_ip,
        counter0=counter0)
    want = reference.chain(TEMPLATES[-1], h, p, t, counter0)
    np.testing.assert_array_equal(np.asarray(allow), want["allow"])
    np.testing.assert_array_equal(np.asarray(nh), want["headers"])
    np.testing.assert_array_equal(np.asarray(ct), want["payload"])


def test_firewall_longest_prefix_then_first_rule_then_default_allow():
    masks = np.array([0xFF000000, 0xFFFF0000, 0xFFFF0000, 0xFFFFFF00],
                     np.uint32)
    prefixes = np.array([0x0A000000, 0x0A010000, 0x0A010000, 0x0B010100],
                        np.uint32)
    allow = np.array([True, False, True, True])
    headers = np.zeros((4, 5), np.uint32)
    headers[:, 1] = [0x0A010203, 0x0A020304, 0x0C000000, 0x0B0101FF]
    got = reference.firewall(headers, prefixes, masks, allow)
    # /16 beats /8 and its first rule (deny) wins the tie; /8 allows;
    # no rule matches; the /24 allows
    assert got.tolist() == [False, True, True, True]


def test_chacha20_rfc8439_block():
    """RFC 8439 section 2.3.2's test vector."""
    key = np.frombuffer(bytes(range(32)), "<u4")
    nonce = np.frombuffer(bytes.fromhex("000000090000004a00000000"), "<u4")
    ks = reference.chacha20(np.zeros((1, 16), np.uint32), key, nonce,
                            np.array([1], np.uint32))
    assert ks[0, :4].tolist() == [0xE4E7F110, 0x15593BD1, 0x1FDD0F50,
                                  0xC47120A3]


@pytest.mark.parametrize("chain,nbytes", zip(TEMPLATES, (21, 41, 40, 169)),
                         ids=lambda x: ">>".join(x) if isinstance(x, tuple)
                         else str(x))
def test_chain_bytes_per_packet(chain, nbytes):
    """Header in and out, payload in and out, one byte of verdict: what
    each chain must move, whatever implements it."""
    from chipbench.work import chain_bytes
    assert chain_bytes(chain) == nbytes
