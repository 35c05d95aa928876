"""Fused VPC datapath megakernel + async ComputeBackend runtime.

Covers the ISSUE-2 acceptance surface: bit-exactness of ``vpc_datapath``
vs ``vpc_chain`` across bucket-straddling batch sizes (incl. N=1 and
non-powers-of-two), a flat jit-trace count across 50 mixed-size injects,
donation/aliasing safety (run twice, same result), wire-field-only
throughput accounting, and the composed fallback for chains with no
registered megakernel.
"""
from __future__ import annotations

import numpy as np
import pytest
import jax.numpy as jnp

from repro import obs
from repro.api import (ComputeBackend, ComputeNT, Platform, VPC_SPECS,
                       bucket_size, nt)
from repro.serving.vpc import make_packets, make_rules, vpc_chain

VPC = nt("firewall") >> nt("nat") >> nt("chacha20")
RULES = make_rules(32, seed=2)
KEY = jnp.arange(8, dtype=jnp.uint32) * 3 + 1
NONCE = jnp.arange(3, dtype=jnp.uint32) + 7
PARAMS = {"firewall": {"rules": RULES}, "nat": {"nat_ip": 0x0A000001},
          "chacha20": {"key": KEY, "nonce": NONCE}}


def assert_matches_chain(out, h, p):
    allow, newh, ct = vpc_chain(h, p, RULES, KEY, NONCE)
    np.testing.assert_array_equal(np.asarray(out["allow"]), np.asarray(allow))
    np.testing.assert_array_equal(np.asarray(out["headers"]),
                                  np.asarray(newh))
    np.testing.assert_array_equal(np.asarray(out["payload"]), np.asarray(ct))


def vpc_platform(**backend_kw):
    plat = Platform(ComputeBackend(**backend_kw), specs=VPC_SPECS)
    dep = plat.tenant("t").deploy(VPC, params=PARAMS)
    return plat, dep


# ========================================================== megakernel ====
class TestVpcDatapathKernel:
    @pytest.mark.parametrize("N", [1, 9])   # N=1 edge + non-power-of-two
    def test_bit_exact_vs_vpc_chain(self, N):
        from repro.kernels.vpc_datapath import vpc_datapath, vpc_datapath_ref
        h, p = make_packets(N, seed=N)
        a0, h0, c0 = vpc_chain(h, p, RULES, KEY, NONCE)
        for a, nh, ct in (vpc_datapath_ref(h, p, RULES, KEY, NONCE),
                          vpc_datapath(h, p, RULES, KEY, NONCE,
                                       interpret=True)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(a0))
            np.testing.assert_array_equal(np.asarray(nh), np.asarray(h0))
            np.testing.assert_array_equal(np.asarray(ct), np.asarray(c0))

    def test_multi_tile_grid_and_explicit_ctr(self):
        """Counter offsets must track the global packet index across grid
        tiles, and an explicit per-packet ctr overrides the default."""
        from repro.kernels.vpc_datapath import vpc_datapath, vpc_datapath_ref
        N = 16
        h, p = make_packets(N, seed=3)
        a, nh, ct = vpc_datapath(h, p, RULES, KEY, NONCE, block_n=8,
                                 interpret=True)
        assert_matches_chain({"allow": a, "headers": nh, "payload": ct}, h, p)
        ctr = jnp.uint32(1000) + jnp.arange(N, dtype=jnp.uint32)
        a1, h1, c1 = vpc_datapath(h, p, RULES, KEY, NONCE, ctr=ctr,
                                  block_n=8, interpret=True)
        a2, h2, c2 = vpc_datapath_ref(h, p, RULES, KEY, NONCE, ctr=ctr)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        assert not np.array_equal(np.asarray(c1), np.asarray(ct))

    def test_empty_batch(self):
        from repro.kernels.vpc_datapath import vpc_datapath
        h = jnp.zeros((0, 5), jnp.uint32)
        p = jnp.zeros((0, 16), jnp.uint32)
        a, nh, ct = vpc_datapath(h, p, RULES, KEY, NONCE, interpret=True)
        assert a.shape == (0,)
        assert nh.shape == (0, 5) and ct.shape == (0, 16)

    def test_firewall_lpm_tie_break(self):
        """Overlapping prefixes: the longest mask must win, and among
        equal-length hits the first rule (regression for the unsigned
        ``-1`` sentinel wrap that let non-hitting rules outrank hits)."""
        from repro.kernels.vpc_datapath import vpc_datapath
        rules = (jnp.asarray([0x0A000000, 0x0A010000, 0x0A010000],
                             jnp.uint32),
                 jnp.asarray([0xFF000000, 0xFFFF0000, 0xFFFF0000],
                             jnp.uint32),
                 jnp.asarray([True, False, True]))
        h = jnp.asarray([[1, 0x0A010203, 2, 3, 4],     # /16 deny beats /8
                         [1, 0x0A220203, 2, 3, 4],     # only /8 allow hits
                         [1, 0x0B000000, 2, 3, 4]],    # no hit -> allow
                        jnp.uint32)
        p = jnp.zeros((3, 16), jnp.uint32)
        from repro.serving.vpc import firewall
        np.testing.assert_array_equal(
            np.asarray(firewall(h, rules)), [False, True, True])
        a, _, _ = vpc_datapath(h, p, rules, KEY, NONCE, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), [False, True, True])


# ============================================================= runtime ====
class TestComputeRuntime:
    @pytest.mark.parametrize("use_fused", [True, False])
    def test_bucket_straddling_sizes_bit_exact(self, use_fused):
        """Sizes on both sides of bucket boundaries (incl. N=1 and
        non-powers-of-two) through pad + mask + slice-back."""
        plat, dep = vpc_platform(use_fused=use_fused)
        # buckets 8, 8, 16 (+128 on the cheap composed path); interpret-mode
        # megakernel compiles dominate test time, so the fused variant keeps
        # to two buckets
        sizes = [1, 7, 9] if use_fused else [1, 7, 9, 100]
        batches = []
        for i, n in enumerate(sizes):
            h, p = make_packets(n, seed=i)
            batches.append((h, p))
            dep.inject(headers=h, payload=p)
            plat.run()                    # run per inject: no coalescing
        rep = plat.report()["t"]
        assert len(rep.outputs) == len(sizes)
        for (h, p), out in zip(batches, rep.outputs):
            assert_matches_chain(out, h, p)
        fused_n = plat.backend.stats["fused_dispatches"]
        assert fused_n == (len(sizes) if use_fused else 0)

    def test_coalescing_same_dag_injects(self):
        """Multiple pending injects dispatch once and stay bit-exact (the
        keystream counter is per-packet state, so merging cannot change any
        ciphertext)."""
        plat, dep = vpc_platform(use_fused=False)
        batches = []
        for i, n in enumerate([7, 9, 1]):
            h, p = make_packets(n, seed=10 + i)
            batches.append((h, p))
            dep.inject(headers=h, payload=p)
        plat.run()
        be = plat.backend
        assert be.stats["dispatches"] == 1
        assert be.stats["coalesced_batches"] == 3
        rep = plat.report()["t"]
        assert len(rep.outputs) == 3      # un-coalesced back to per-inject
        for (h, p), out in zip(batches, rep.outputs):
            assert_matches_chain(out, h, p)

    def test_mixed_signature_results_stay_in_inject_order(self):
        """Batches that cannot coalesce (extra field) split into separate
        dispatch groups but results must still come back in inject order.
        Coalescing only merges *consecutive* entries of the fair service
        order (a later same-signature batch must not jump the queue), so
        the [sig_a, sig_b, sig_a] pattern is three dispatches."""
        plat, dep = vpc_platform(use_fused=False)
        marks = []
        for i, n in enumerate([7, 9, 1]):
            h, p = make_packets(n, seed=20 + i)
            if i == 1:               # different signature: its own group
                tag = jnp.full((n,), i, jnp.int32)
                dep.inject(headers=h, payload=p, tag=tag)
            else:
                dep.inject(headers=h, payload=p)
            marks.append((n, h))
        plat.run()
        rep = plat.report()["t"]
        assert plat.backend.stats["dispatches"] == 3
        for (n, h), out in zip(marks, rep.outputs):   # sizes 7, 9, 1 differ
            assert out["headers"].shape[0] == n
        assert "tag" in rep.outputs[1] and "tag" not in rep.outputs[0]

    def test_compile_cache_flat_across_50_mixed_size_injects(self):
        """Jit trace count across 50 mixed-size runs must be <= number of
        distinct buckets, not ~number of batches."""
        plat, dep = vpc_platform(use_fused=False)
        sizes = [3, 10, 100, 7, 9] * 10               # 50 injects
        buckets = {bucket_size(n) for n in sizes}
        assert len(buckets) == 3
        for i, n in enumerate(sizes):
            h, p = make_packets(n, seed=i)
            dep.inject(headers=h, payload=p)
            plat.run()
        be = plat.backend
        assert be.stats["batches"] == 50
        assert be.stats["runs"] == 50
        assert be.stats["traces"] <= len(buckets)
        assert len(plat.report()["t"].outputs) == 50

    def test_donation_no_aliasing_run_twice(self):
        """Donated dispatch must never consume caller-owned arrays: inject
        the same arrays twice (and run twice) -> identical results, inputs
        intact."""
        h, p = make_packets(7, seed=5)
        h_copy, p_copy = np.asarray(h).copy(), np.asarray(p).copy()
        plat, dep = vpc_platform(use_fused=False, donate=True)
        dep.inject(headers=h, payload=p)
        plat.run()
        dep.inject(headers=h, payload=p)  # same arrays again
        plat.run()
        rep = plat.report()["t"]
        assert len(rep.outputs) == 2
        for k in ("allow", "headers", "payload"):
            np.testing.assert_array_equal(np.asarray(rep.outputs[0][k]),
                                          np.asarray(rep.outputs[1][k]))
        np.testing.assert_array_equal(np.asarray(h), h_copy)
        np.testing.assert_array_equal(np.asarray(p), p_copy)
        assert_matches_chain(rep.outputs[0], h, p)

    def test_report_counts_wire_bytes_only(self):
        """Gbps accounting: headers + payload only; the allow mask, ctr and
        validity mask must not inflate throughput."""
        plat, dep = vpc_platform(use_fused=False)
        h, p = make_packets(9, seed=1)
        dep.inject(headers=h, payload=p)
        plat.run()
        rep = plat.report()
        tr = rep["t"]
        assert tr.pkts_done == 9
        assert tr.bytes_done == 9 * (5 + 16) * 4      # wire fields only
        assert rep.duration_ns > 0
        assert tr.gbps == pytest.approx(
            tr.bytes_done * 8 / rep.duration_ns, rel=1e-6)
        assert rep.extra["compiles"] == plat.backend.stats["traces"] >= 1

    def test_custom_nt_falls_back_to_composed(self):
        """A chain containing an unregistered-for-fusion NT must run on the
        composed path and still produce correct output."""
        def scrub(state, params):
            return {"payload": state["payload"] & jnp.uint32(0xFFFF)}

        be = ComputeBackend(use_fused=True)
        be.register_nt(ComputeNT("scrub", scrub, writes=("payload",)))
        from repro.core.nt import NTSpec
        specs = dict(VPC_SPECS, scrub=NTSpec("scrub"))
        plat = Platform(be, specs=specs)
        dep = plat.tenant("t").deploy(
            nt("firewall") >> nt("scrub"),
            params={"firewall": {"rules": RULES}})
        h, p = make_packets(16, seed=8)
        dep.inject(headers=h, payload=p)
        plat.run()
        assert be.stats["fused_dispatches"] == 0
        out = plat.report()["t"].outputs[0]
        from repro.serving.vpc import firewall
        allow = np.asarray(firewall(h, RULES))
        expect = np.where(allow[:, None], np.asarray(p) & 0xFFFF, 0)
        np.testing.assert_array_equal(np.asarray(out["payload"]), expect)

    def test_pad_to_never_returns_caller_buffer(self):
        from repro.api.compute_backend import _pad_to
        x = jnp.arange(8)
        y = _pad_to(x, 8)
        assert y is not x
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))

    def test_bucket_size_powers_of_two(self):
        assert [bucket_size(n) for n in (1, 8, 9, 100, 256, 257)] == \
            [8, 8, 16, 128, 256, 512]

    @pytest.mark.parametrize("sizes, bucket, pad", [
        ([1024] * 3, 4096, 1024),          # 3072 rows padded to 4096
        ([16384] * 2, 32768, 0),           # an exact fit pads nothing
    ])
    def test_launch_counts_bucket_and_pad_rows(self, sizes, bucket, pad):
        plat, dep = vpc_platform(use_fused=False)
        for i, n in enumerate(sizes):
            h, p = make_packets(n, seed=30 + i)
            dep.inject(headers=h, payload=p)
        plat.run()
        stats = plat.backend.stats
        assert stats["dispatches"] == 1
        assert (stats["rows_launched"], stats["pad_rows"]) == (bucket, pad)

    @pytest.mark.parametrize("firewall", [True, False])
    def test_launches_count_rule_pairs(self, firewall, tmp_path):
        """``stats["rule_pairs"]`` and each launch span's ``rule_pairs``
        argument are the launch's real rows times the deployment's rules,
        0 for a chain with no firewall; pad rows count for nothing."""
        import jax
        from jax.profiler import ProfileData
        plat = Platform(ComputeBackend(use_fused=False), specs=VPC_SPECS)
        chain = VPC if firewall else nt("nat") >> nt("chacha20")
        dep = plat.tenant("t").deploy(chain, params=PARAMS)
        sizes = [7, 3, 8]                 # one bucket: one compile
        jax.profiler.start_trace(str(tmp_path))
        for i, n in enumerate(sizes):
            h, p = make_packets(n, seed=90 + i)
            dep.inject(headers=h, payload=p)
            plat.run()                    # one launch per run
        jax.profiler.stop_trace()
        n_rules = RULES[0].shape[0] if firewall else 0
        assert plat.backend.stats["rule_pairs"] == sum(sizes) * n_rules
        [path] = tmp_path.glob("**/*.xplane.pb")
        spans = [dict(e.stats)["rule_pairs"]
                 for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for e in line.events
                 if e.name == obs.LAUNCH]
        assert spans == [n * n_rules for n in sizes]

    @pytest.mark.parametrize("stream", [False, True])
    def test_run_fills_the_phase_totals(self, stream):
        """Both engines time the same five spans, and only those."""
        plat, dep = vpc_platform(use_fused=False, stream=stream)
        for i, n in enumerate([7, 9]):
            h, p = make_packets(n, seed=40 + i)
            dep.inject(headers=h, payload=p)
        plat.run()
        totals = plat.backend.span_s
        assert list(totals) == list(obs.PHASES) == [
            "repro.sched.order", "repro.compute.stage",
            "repro.compute.launch", "repro.compute.sync",
            "repro.compute.split"]
        assert all(v > 0 for v in totals.values()), totals
        assert plat.backend.stats["rows_launched"] == 16


# ======================================================== host staging ====
def np_packets(n, seed):
    h, p = make_packets(n, seed=seed)
    return np.asarray(h), np.asarray(p)


#: bytes a staged row sends to the device: headers, payload, ctr, valid
ROW_BYTES = 5 * 4 + 16 * 4 + 4 + 1


class TestHostStaging:
    @pytest.mark.parametrize("use_fused", [True, False])
    def test_numpy_groups_bit_exact(self, use_fused):
        """Host arrays staged at coalesced and bucket-straddling sizes
        match ``vpc_chain``."""
        plat, dep = vpc_platform(use_fused=use_fused)
        # the fused variant keeps to buckets 8 and 16 (interpret mode);
        # the composed one adds a 3 x 1024 group padded to 4096
        groups = [[1], [7], [9]] + ([[4, 5]] if use_fused
                                    else [[1024] * 3])
        sent = []
        for g, sizes in enumerate(groups):
            for i, n in enumerate(sizes):
                h, p = np_packets(n, seed=50 + 10 * g + i)
                sent.append((h, p))
                dep.inject(headers=h, payload=p)
            plat.run()                    # one dispatch group per run
        rep = plat.report()["t"]
        assert len(rep.outputs) == len(sent)
        for (h, p), out in zip(sent, rep.outputs):
            assert_matches_chain(out, h, p)
        stats = plat.backend.stats
        assert stats["dispatches"] == len(groups)
        assert stats["fused_dispatches"] == (len(groups) if use_fused else 0)

    @pytest.mark.parametrize("sizes, bucket", [
        ([9], 16),
        ([1024] * 3, 4096),
    ])
    def test_host_path_counts_groups_and_bytes(self, sizes, bucket):
        plat, dep = vpc_platform(use_fused=False)
        for i, n in enumerate(sizes):
            h, p = np_packets(n, seed=60 + i)
            dep.inject(headers=h, payload=p)
        plat.run()
        stats = plat.backend.stats
        assert stats["dispatches"] == 1
        assert stats["h2d_bytes"] == bucket * ROW_BYTES

    @pytest.mark.parametrize("kinds", [("jax", "jax"), ("numpy", "jax")])
    def test_device_arrays_are_staged_on_the_host(self, kinds):
        """A group that carries a ``jax.Array`` (alone, or beside numpy
        batches) is read back and staged like host data: one group, its
        bucket's bytes sent once, and it still matches."""
        plat, dep = vpc_platform(use_fused=False)
        sent = []
        for i, (kind, n) in enumerate(zip(kinds, [7, 9])):
            h, p = make_packets(n, seed=70 + i)
            if kind == "numpy":
                h, p = np.asarray(h), np.asarray(p)
            sent.append((h, p))
            dep.inject(headers=h, payload=p)
        plat.run()
        stats = plat.backend.stats
        assert stats["dispatches"] == 1 and stats["coalesced_batches"] == 2
        assert stats["h2d_bytes"] == 16 * ROW_BYTES
        for (h, p), out in zip(sent, plat.report()["t"].outputs):
            assert_matches_chain(out, h, p)

    def test_numpy_caller_buffers_not_donated_run_twice(self):
        """Host staging copies caller arrays into fresh buffers: the same
        numpy arrays run twice give identical results, inputs intact."""
        h, p = np_packets(7, seed=5)
        h_copy, p_copy = h.copy(), p.copy()
        plat, dep = vpc_platform(use_fused=False, donate=True)
        for _ in range(2):
            dep.inject(headers=h, payload=p)
            plat.run()
        rep = plat.report()["t"]
        assert len(rep.outputs) == 2
        assert plat.backend.stats["h2d_bytes"] == 2 * 8 * ROW_BYTES
        for k in ("allow", "headers", "payload"):
            np.testing.assert_array_equal(np.asarray(rep.outputs[0][k]),
                                          np.asarray(rep.outputs[1][k]))
        np.testing.assert_array_equal(h, h_copy)
        np.testing.assert_array_equal(p, p_copy)
        assert_matches_chain(rep.outputs[0], h, p)

    def test_stream_engine_reuses_its_ring_slots(self):
        """The streaming engine stages in its ring slots through the same
        fill: no slot is allocated after warm-up, and every group counts
        its bucket's bytes."""
        plat, dep = vpc_platform(use_fused=False, stream=True, ring_depth=2,
                                 max_inflight=1)
        be = plat.backend
        h, p = np_packets(8, seed=80)

        def feed(k):
            return be.inject_stream(
                (("t", dep.uid, {"headers": h, "payload": p})
                 for _ in range(k)), epoch_batches=1)

        feed(3)
        allocs = be.ring.allocs
        assert feed(9) == 9
        assert be.ring.allocs == allocs
        assert be.stats["dispatches"] == 12
        assert be.stats["h2d_bytes"] == 12 * 8 * ROW_BYTES
        for out in plat.report()["t"].outputs:
            assert_matches_chain(out, h, p)
