"""The static-analysis plane: admission verifier fixture corpus, linter
rule fixtures, baseline mechanics, and the HLO text tools."""
import textwrap

import numpy as np
import pytest

from repro.analysis import Baseline, Diagnostic, Severity
from repro.analysis.diagnostics import render_text, sort_diags
from repro.analysis.hlo import format_buffers, grep_lines, top_buffers
from repro.analysis.linter import lint_paths, lint_source
from repro.analysis.verifier import AdmissionError, admit, verify
from repro.api import ComputeBackend, Platform, SimBackend, VPC_SPECS
from repro.api.compute_backend import ComputeNT
from repro.api.dag import DagError, nt
from repro.core.nt import NTDag, NTSpec


def rules_of(diags):
    return [d.rule for d in diags]


# ===================================================== bad-DAG fixture corpus
class TestVerifierStructure:
    def test_cycle_within_branch(self):
        dag = NTDag(1, "a", ((("firewall", "nat", "firewall"),),))
        assert "V-CYCLE" in rules_of(verify(dag))

    def test_cycle_across_stages(self):
        dag = NTDag(1, "a", ((("firewall",),), (("nat",),),
                             (("firewall",),)))
        diags = verify(dag)
        assert "V-CYCLE" in rules_of(diags)
        [d] = [d for d in diags if d.rule == "V-CYCLE"]
        assert "stage2" in d.subject

    def test_parallel_branches_may_share_no_nt_upstream(self):
        # the same NT in two parallel branches of ONE stage is not a cycle
        dag = NTDag(1, "a", ((("firewall",), ("firewall",)),))
        assert "V-CYCLE" not in rules_of(verify(dag))

    def test_arity_empty_dag(self):
        assert rules_of(verify(NTDag(1, "a", ()))) == ["V-ARITY"]

    def test_arity_empty_branch(self):
        dag = NTDag(1, "a", ((("firewall",), ()),))
        assert "V-ARITY" in rules_of(verify(dag))

    def test_arity_empty_stage_marks_tail_unreachable(self):
        dag = NTDag(1, "a", ((("firewall",),), (), (("nat",),)))
        rules = rules_of(verify(dag))
        assert "V-ARITY" in rules and "V-UNREACHABLE" in rules

    def test_non_string_entry(self):
        dag = NTDag(1, "a", (((42,),),))
        assert "V-ARITY" in rules_of(verify(dag))

    def test_strict_admit_raises_admission_error(self):
        dag = NTDag(1, "a", ((("firewall", "firewall"),),))
        with pytest.raises(AdmissionError) as ei:
            admit(dag, "a", strict=True)
        assert any(d.rule == "V-CYCLE" for d in ei.value.diagnostics)
        # AdmissionError IS a DagError: existing handling keeps working
        assert isinstance(ei.value, DagError)

    def test_warn_only_admit_returns_diagnostics(self):
        dag = NTDag(1, "a", ((("firewall", "firewall"),),))
        diags = admit(dag, "a", strict=False)
        assert "V-CYCLE" in rules_of(diags)


class TestVerifierSignatures:
    def _backend(self, **nts):
        be = ComputeBackend(use_fused=False)
        be.nts.update(nts)
        return be

    def test_read_without_producer(self):
        be = self._backend(
            needs_meta=ComputeNT("needs_meta", lambda s, p: {},
                                 writes=("x",), reads=("metadata",)))
        dag = NTDag(1, "a", ((("needs_meta",),),))
        diags = verify(dag, backend=be)
        assert "V-SIGNATURE" in rules_of(diags)
        [d] = [d for d in diags if d.rule == "V-SIGNATURE"]
        assert "metadata" in d.message

    def test_shape_break_on_edge(self):
        be = self._backend(
            producer=ComputeNT("producer", lambda s, p: {},
                               writes=("foo",),
                               schema=(("foo", (4,), "f32"),)),
            consumer=ComputeNT("consumer", lambda s, p: {},
                               writes=("bar",), reads=("foo",),
                               schema=(("foo", (8,), "f32"),)))
        dag = NTDag(1, "a", ((("producer",),), (("consumer",),)))
        diags = verify(dag, backend=be)
        [d] = [d for d in diags if d.rule == "V-SIGNATURE"]
        assert "shape break on edge producer -> consumer" in d.message

    def test_fork_join_write_conflict(self):
        be = self._backend()
        dag = NTDag(1, "a", ((("firewall",), ("firewall",)),))
        diags = verify(dag, backend=be)
        [d] = [d for d in diags if d.rule == "V-SIGNATURE"]
        assert "both write" in d.message

    def test_vmem_tile_over_budget(self):
        be = self._backend(
            huge=ComputeNT("huge", lambda s, p: {}, writes=("x",),
                           tile_bytes=32 << 20))
        dag = NTDag(1, "a", ((("huge",),),))
        diags = verify(dag, backend=be)
        assert "V-BUDGET-VMEM" in rules_of(diags)
        assert all(d.severity == Severity.ERROR for d in diags
                   if d.rule == "V-BUDGET-VMEM")

    def test_vpc_chain_tiles_fit(self):
        be = self._backend()
        dag = NTDag(1, "a", ((("firewall", "nat", "chacha20"),),))
        assert "V-BUDGET-VMEM" not in rules_of(verify(dag, backend=be))

    def test_single_rule_block_of_20k_rules_over_budget(self):
        """A megakernel that held a 20,000-rule table as one block, scanned
        whole, would need ``(256, 20000)`` intermediates: over the budget,
        as the chip's compiler found for such a kernel (out of VMEM from
        17,500 rules at 256-packet tiles)."""
        from repro.core.vmem import VMEM_BUDGET_BYTES
        from repro.kernels.vpc_datapath.ops import LANES, vmem_tile_bytes
        lanes = -(-20000 // LANES) * LANES
        # one (256, R) int32 intermediate, beside the four (1, R) rule
        # rows, each padded to 8 sublanes and double-buffered
        whole = 4 * 256 * lanes + 2 * 4 * 4 * 8 * lanes
        assert whole > VMEM_BUDGET_BYTES >= vmem_tile_bytes(256)
        be = self._backend(
            fw1=ComputeNT("fw1", lambda s, p: {}, writes=("allow",),
                          tile_bytes=whole))
        dag = NTDag(1, "a", ((("fw1",),),))
        assert "V-BUDGET-VMEM" in rules_of(verify(dag, backend=be))

    def test_rule_tiled_firewall_admits_100k_rules(self):
        """The firewall's count is the kernel's at its largest rule tile,
        which bounds its VMEM for a table of any size: a 100,000-rule
        tenant is admitted in strict mode, and its own tile fits the
        count."""
        from repro.api.compute_backend import BUILTIN_COMPUTE_NTS as NTS
        from repro.kernels.vpc_datapath.ops import (MAX_RULE_TILE,
                                                    rule_tile_for,
                                                    vmem_tile_bytes)
        assert NTS["firewall"].tile_bytes + NTS["chacha20"].tile_bytes == \
            vmem_tile_bytes(256, MAX_RULE_TILE)
        assert rule_tile_for(100_000) <= MAX_RULE_TILE
        assert vmem_tile_bytes(256, rule_tile_for(100_000)) <= \
            vmem_tile_bytes(256, MAX_RULE_TILE)
        rng = np.random.default_rng(0)
        masks = np.full(100_000, 0xFFFF0000, np.uint32)
        rules = (rng.integers(0, 2 ** 32, 100_000, dtype=np.uint32) & masks,
                 masks, rng.random(100_000) < 0.5)
        plat = Platform(ComputeBackend(use_fused=True), specs=VPC_SPECS,
                        strict=True)
        plat.tenant("t").deploy(
            nt("firewall") >> nt("nat") >> nt("chacha20"),
            params={"firewall": {"rules": rules}, "nat": {"nat_ip": 1},
                    "chacha20": {"key": np.arange(8, dtype=np.uint32),
                                 "nonce": np.arange(3, dtype=np.uint32)}})
        assert "V-BUDGET-VMEM" not in rules_of(plat.admission_log)


class TestVerifierResources:
    def test_capacity_warning_not_error(self):
        # chacha20's service model (80 Gbps) is below the declared 100 Gbps
        # line: a provisioning smell, never a rejection
        be = ComputeBackend(use_fused=False)
        dag = NTDag(1, "a", ((("firewall", "nat", "chacha20"),),))
        diags = verify(dag, backend=be, specs=VPC_SPECS)
        caps = [d for d in diags if d.rule == "V-CAPACITY"]
        assert caps and all(d.severity == Severity.WARNING for d in caps)
        assert "chacha20" in caps[0].message

    def test_state_budget_warning(self):
        specs = {"bigtable": NTSpec("bigtable", state_bytes=1 << 30)}
        dag = NTDag(1, "a", ((("bigtable",),),))
        diags = verify(dag, specs=specs)
        [d] = [d for d in diags if d.rule == "V-BUDGET-STATE"]
        assert d.severity == Severity.WARNING
        assert "swap" in d.message

    def test_cross_tenant_stateful_nt_rejected(self):
        specs = {"conntrack": NTSpec("conntrack", state_bytes=1 << 20)}
        plat = Platform(SimBackend(specs=specs), specs=specs)
        plat.tenant("alice").deploy(nt("conntrack"))
        with pytest.raises(AdmissionError) as ei:
            plat.tenant("bob").deploy(nt("conntrack"))
        assert any(d.rule == "V-ISOLATION" for d in ei.value.diagnostics)

    def test_shared_stateful_nt_admits_across_tenants(self):
        specs = {"pool": NTSpec("pool", state_bytes=1 << 20, shared=True)}
        plat = Platform(SimBackend(specs=specs), specs=specs)
        plat.tenant("alice").deploy(nt("pool"))
        dep = plat.tenant("bob").deploy(nt("pool"))       # no raise
        assert dep.uid

    def test_same_tenant_stateful_redeploy_admits(self):
        specs = {"conntrack": NTSpec("conntrack", state_bytes=1 << 20)}
        plat = Platform(SimBackend(specs=specs), specs=specs)
        t = plat.tenant("alice")
        t.deploy(nt("conntrack"))
        t.deploy(nt("conntrack"))                         # no raise


class TestAdmissionAtDeploy:
    def test_existing_vpc_dag_admits_in_strict_mode(self):
        plat = Platform(SimBackend(specs=VPC_SPECS), specs=VPC_SPECS)
        dep = plat.tenant("alice").deploy(
            nt("firewall") >> nt("nat") >> nt("chacha20"))
        assert dep.uid == 1
        # the capacity warning is logged, not raised
        assert any(d.rule == "V-CAPACITY" for d in plat.admission_log)
        assert not any(d.severity == Severity.ERROR
                       for d in plat.admission_log)

    def test_warn_only_platform_deploys_bad_dag(self):
        plat = Platform(SimBackend(specs=VPC_SPECS), specs=VPC_SPECS,
                        strict=False)
        dag = NTDag(99, "alice", ((("firewall", "firewall"),),))
        plat.tenant("alice")
        # deploy the raw NTDag through the tenant API: warn-only admits
        plat.tenants["alice"].deploy(dag)
        assert any(d.rule == "V-CYCLE" for d in plat.admission_log)

    def test_per_deploy_strict_override(self):
        plat = Platform(SimBackend(specs=VPC_SPECS), specs=VPC_SPECS,
                        strict=False)
        dag = NTDag(99, "alice", ((("firewall", "firewall"),),))
        plat.tenant("alice")
        with pytest.raises(AdmissionError):
            plat.tenants["alice"].deploy(dag, strict=True)


# ================================================================ the linter
LINT_FIXTURES = {
    "L-HOSTSYNC": """
        import jax
        def f(items):
            out = []
            for x in items:
                out.append(x.block_until_ready())
            return out
    """,
    "L-JITCACHE": """
        import jax
        def f(fns, x):
            for fn in fns:
                x = jax.jit(fn)(x)
            return x
    """,
    "L-DONATE": """
        import jax
        def build(step):
            return jax.jit(step)
    """,
    "L-NONDET": """
        import time
        def now():
            return time.time()
    """,
    "L-RING": """
        import jax
        def feed(items, device):
            for b in items:
                launch(jax.device_put(b, device))
    """,
    "L-SYNTAX": """
        def broken(:
    """,
}
LINT_PATHS = {
    "L-HOSTSYNC": "src/repro/api/x.py",
    "L-JITCACHE": "src/repro/api/x.py",
    "L-DONATE": "src/repro/api/some_backend.py",
    "L-NONDET": "src/repro/core/x.py",
    "L-RING": "src/repro/api/some_backend.py",
    "L-SYNTAX": "src/repro/api/x.py",
}


class TestLinter:
    @pytest.mark.parametrize("rule", sorted(LINT_FIXTURES))
    def test_seeded_fixture_detected(self, rule):
        src = textwrap.dedent(LINT_FIXTURES[rule])
        diags = lint_source(src, LINT_PATHS[rule])
        assert rule in rules_of(diags), render_text(diags)

    def test_sync_module_calls_in_loop(self):
        src = textwrap.dedent("""
            import jax
            import numpy as np
            def f(xs):
                return [np.asarray(x) for x in xs]
        """)
        assert "L-HOSTSYNC" in rules_of(lint_source(src, "src/repro/a.py"))

    def test_int_over_subscript_in_loop(self):
        src = textwrap.dedent("""
            import jax
            def f(tok, n):
                return [int(tok[j]) for j in range(n)]
        """)
        assert "L-HOSTSYNC" in rules_of(lint_source(src, "src/repro/a.py"))

    def test_shape_subscript_not_flagged(self):
        src = textwrap.dedent("""
            import jax
            def f(batch):
                return [int(v.shape[0]) for v in batch]
        """)
        assert lint_source(src, "src/repro/a.py") == []

    def test_non_jax_file_int_subscript_silent(self):
        src = textwrap.dedent("""
            def f(rows):
                return [int(r[0]) for r in rows]
        """)
        assert lint_source(src, "src/repro/a.py") == []

    def test_noqa_suppresses(self):
        src = textwrap.dedent("""
            import jax
            def f(items):
                return [x.item() for x in items]  # noqa: L-HOSTSYNC
        """)
        assert lint_source(src, "src/repro/a.py") == []

    def test_donate_only_in_dispatch_files(self):
        src = textwrap.dedent("""
            import jax
            def build(step):
                return jax.jit(step)
        """)
        assert "L-DONATE" not in rules_of(
            lint_source(src, "src/repro/launch/notes.py"))
        assert "L-DONATE" in rules_of(
            lint_source(src, "src/repro/serving/thing.py"))

    def test_ring_slot_transfer_exempt(self):
        src = textwrap.dedent("""
            import jax
            def feed(items, ring):
                for b in items:
                    slot = ring.acquire(b)
                    launch(jax.device_put(slot.staging, None))
        """)
        assert "L-RING" not in rules_of(
            lint_source(src, "src/repro/api/some_backend.py"))

    def test_ring_scoped_to_dispatch_files(self):
        src = textwrap.dedent(LINT_FIXTURES["L-RING"])
        assert "L-RING" not in rules_of(
            lint_source(src, "src/repro/core/sim.py"))

    def test_ring_outside_loop_silent(self):
        src = textwrap.dedent("""
            import jax
            def pin(state, device):
                return jax.device_put(state, device)
        """)
        assert "L-RING" not in rules_of(
            lint_source(src, "src/repro/api/some_backend.py"))

    def test_hostsync_ring_drain_exempt(self):
        src = textwrap.dedent("""
            import jax
            def drain(inflight):
                while wrapped(inflight):
                    jax.block_until_ready(inflight[0].out)
        """)
        assert "L-HOSTSYNC" not in rules_of(
            lint_source(src, "src/repro/api/some_backend.py"))
        plain = textwrap.dedent("""
            import jax
            def drain(outs):
                for o in outs:
                    jax.block_until_ready(o)
        """)
        assert "L-HOSTSYNC" in rules_of(
            lint_source(plain, "src/repro/api/some_backend.py"))

    def test_nondet_scoped_to_core(self):
        src = textwrap.dedent("""
            import time
            def now():
                return time.time()
        """)
        assert "L-NONDET" not in rules_of(
            lint_source(src, "src/repro/launch/x.py"))

    def test_src_tree_is_lint_clean_against_baseline(self):
        diags = lint_paths(["src"])
        base = Baseline.load("analysis_baseline.json")
        fresh = base.new(diags)
        assert fresh == [], render_text(fresh)


# =========================================================== baseline gating
class TestBaseline:
    def _d(self, rule, subject):
        return Diagnostic(rule, Severity.ERROR, subject, "msg")

    def test_grandfathers_counts_per_key(self):
        old = [self._d("L-X", "a.py:10"), self._d("L-X", "a.py:20")]
        base = Baseline.from_diags(old)
        assert base.new(old) == []
        extra = old + [self._d("L-X", "a.py:30")]
        assert len(base.new(extra)) == 1

    def test_line_numbers_do_not_churn(self):
        base = Baseline.from_diags([self._d("L-X", "a.py:10")])
        assert base.new([self._d("L-X", "a.py:999")]) == []

    def test_new_rule_fails(self):
        base = Baseline.from_diags([self._d("L-X", "a.py:10")])
        assert len(base.new([self._d("L-Y", "a.py:10")])) == 1

    def test_roundtrip(self, tmp_path):
        p = tmp_path / "b.json"
        base = Baseline.from_diags([self._d("L-X", "a.py:10")])
        base.save(p)
        assert Baseline.load(p).counts == base.counts
        assert Baseline.load(tmp_path / "missing.json").counts == {}

    def test_render_and_sort(self):
        diags = [Diagnostic("B", Severity.WARNING, "b", "warn"),
                 Diagnostic("A", Severity.ERROR, "a", "err")]
        assert sort_diags(diags)[0].rule == "A"
        text = render_text(diags)
        assert "1 error(s), 1 warning(s)" in text


# ============================================================= HLO text tools
HLO_SAMPLE = """\
HloModule jit_step

fused_computation {
  %p0 = f32[32768,4096]{1,0} parameter(0)
  %big = f32[32768,4096]{1,0} add(%p0, %p0)
  %big2 = f32[32768,4096]{1,0} add(%p0, %p0)
  %huge = bf16[65536,8192]{1,0} convert(%big)
  %small = f32[8]{0} constant(0)
  ROOT %all-reduce = f32[32768,4096]{1,0} all-reduce(%big)
}
"""


class TestHloTools:
    def test_grep_lines_matches_and_limits(self):
        assert len(grep_lines(HLO_SAMPLE, "f32", limit=2)) == 2
        lines = grep_lines(HLO_SAMPLE, "all-reduce")
        assert len(lines) == 1 and "all-reduce" in lines[0]
        assert grep_lines(HLO_SAMPLE, "nothing-matches") == []

    def test_top_buffers_sizes_and_threshold(self):
        bufs = dict(top_buffers(HLO_SAMPLE, min_bytes=1e6))
        # keys are the raw op token (args included, matching the original
        # tool); the two identical adds aggregate into one row
        assert bufs["add(%p0, f32[32768,4096]"] == 2 * 32768 * 4096 * 4
        assert bufs["convert(%big) bf16[65536,8192]"] == 65536 * 8192 * 2
        assert not any(k.endswith("f32[8]") for k in bufs)
        # raising the floor drops everything
        assert top_buffers(HLO_SAMPLE, min_bytes=1e13) == []

    def test_format_buffers(self):
        text = format_buffers(top_buffers(HLO_SAMPLE, min_bytes=1e6))
        assert "GB" in text and "convert" in text


# ================================================================== CLI gate
class TestCli:
    def test_lint_cli_baseline_gate(self, tmp_path):
        from repro.analysis.__main__ import main
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(LINT_FIXTURES["L-HOSTSYNC"]))
        base = tmp_path / "base.json"
        # no baseline: the seeded violation fails the gate
        assert main(["lint", str(bad), "--baseline", str(base)]) == 1
        # enumerate it; the same tree now passes
        assert main(["lint", str(bad), "--baseline", str(base),
                     "--update-baseline"]) == 0
        assert main(["lint", str(bad), "--baseline", str(base)]) == 0

    def test_lint_cli_json_artifact(self, tmp_path):
        import json

        from repro.analysis.__main__ import main
        bad = tmp_path / "bad.py"
        bad.write_text(textwrap.dedent(LINT_FIXTURES["L-JITCACHE"]))
        out = tmp_path / "diags.json"
        main(["lint", str(bad), "--baseline",
              str(tmp_path / "none.json"), "--json", str(out)])
        data = json.loads(out.read_text())
        assert data and data[0]["rule"] == "L-JITCACHE"

    def test_typecheck_skips_without_mypy(self, monkeypatch):
        import shutil as _sh

        from repro.analysis.__main__ import main
        monkeypatch.setattr(_sh, "which", lambda _: None)
        assert main(["typecheck"]) == 0
