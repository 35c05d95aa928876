"""Sharding-rule and distribution tests on small in-process meshes.

These run with the default single CPU device for rule/unit checks and use a
subprocess with forced host devices for real multi-device pjit execution
(numerical equivalence of sharded vs single-device training steps).
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch import steps as ST

SRC = str(Path(__file__).resolve().parents[1] / "src")


class FakeMesh:
    """Just enough Mesh surface for the spec builders."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


class TestParamSpecs:
    def setup_method(self):
        from repro.parallel import sharding as SH
        self.SH = SH
        self.mesh = FakeMesh({"data": 16, "model": 16})

    def _specs(self, arch):
        cfg = configs.get_config(arch)
        params = ST.abstract_params(cfg)
        return params, self.SH.param_specs(params, self.mesh)

    def test_dense_rules(self):
        params, specs = self._specs("yi-6b")
        # stacked layers: leading None then (fsdp, TP)
        assert specs["layers"]["attn"]["wq"]["w"] == P(None, "data", "model")
        assert specs["layers"]["attn"]["wo"]["w"] == P(None, "model", "data")
        assert specs["layers"]["mlp"]["down"]["w"] == P(None, "model", "data")
        assert specs["layers"]["norm1"]["g"] == P(None, None)
        assert specs["embed"]["table"] == P("model", None)
        assert specs["head"]["w"] == P("data", "model")

    def test_moe_rules(self):
        params, specs = self._specs("grok-1-314b")
        assert specs["layers"]["moe"]["gate"] == P(None, None, "data", "model")
        assert specs["layers"]["moe"]["down"] == P(None, None, "model", "data")
        assert specs["layers"]["moe"]["router"]["w"] == P(None, None, None)

    def test_nondivisible_dims_dropped(self):
        # granite vocab 49155 is not divisible by 16: spec must drop the axis
        params, specs = self._specs("granite-moe-1b-a400m")
        assert specs["embed"]["table"] == P(None, None)

    def test_every_leaf_divides(self):
        import numpy as np
        for arch in configs.ARCH_NAMES:
            cfg = configs.get_config(arch)
            params = ST.abstract_params(cfg)
            specs = self.SH.param_specs(params, self.mesh)

            def check(path, leaf, spec):
                for dim, ax in zip(leaf.shape, tuple(spec)):
                    if ax is None:
                        continue
                    axes = ax if isinstance(ax, tuple) else (ax,)
                    n = int(np.prod([self.mesh.shape[a] for a in axes]))
                    assert dim % n == 0, (arch, path, leaf.shape, spec)
            jax.tree_util.tree_map_with_path(
                lambda p, l, s: check(p, l, s), params, specs,
                is_leaf=lambda x: hasattr(x, "shape"))

    def test_cache_specs_batch_vs_seq(self):
        from repro.parallel import sharding as SH
        cfg = configs.get_config("yi-6b")
        cache = ST.abstract_cache(cfg, 128, 1024)
        specs = SH.cache_specs(cfg, cache, self.mesh, 128)
        leaves = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        # batch sharded over data, seq over model
        assert P(None, "data", "model", None, None) in leaves
        # B=1: batch unshardable -> seq over everything
        cache1 = ST.abstract_cache(cfg, 1, 1024)
        specs1 = SH.cache_specs(cfg, cache1, self.mesh, 1)
        leaves1 = jax.tree_util.tree_leaves(
            specs1, is_leaf=lambda x: isinstance(x, P))
        assert P(None, None, ("data", "model"), None, None) in leaves1


class TestShardedExecution:
    """Sharded training step == single-device step, bit-for-bit-ish."""

    @pytest.mark.parametrize("arch", ["yi-6b", "granite-moe-1b-a400m"])
    def test_sharded_step_matches_single(self, arch):
        script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {SRC!r})
import jax, numpy as np
import jax.numpy as jnp
from repro import configs
from repro.data import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import adamw
from repro.parallel import sharding as SH, ctx as pctx

cfg = configs.get_tiny_config({arch!r}).replace(scan_layers=True)
params = init_params(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params)
batch = SyntheticLM(cfg, 8, 64, seed=0).batch(0)
step = make_train_step(cfg, lr=1e-3)

# single device
p1, o1, m1 = jax.jit(step)(params, opt, batch)
l1 = float(m1["loss"])

# sharded 4x2
mesh = make_mesh((4, 2), ("data", "model"))
pspec = SH.param_specs(params, mesh)
with mesh, pctx.policy(mesh):
    sharded = jax.jit(step, in_shardings=(
        SH.to_shardings(pspec, mesh),
        type(o1)(m=SH.to_shardings(pspec, mesh),
                 v=SH.to_shardings(pspec, mesh),
                 count=jax.sharding.NamedSharding(
                     mesh, jax.sharding.PartitionSpec())),
        SH.to_shardings(SH.batch_specs(batch, mesh), mesh)))
    p2, o2, m2 = sharded(params, opt, batch)
l2 = float(m2["loss"])
assert abs(l1 - l2) < 5e-4, (l1, l2)
# updated params agree
d = max(float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
assert d < 5e-3, d
print("SHARDED_OK", l1, l2, d)
"""
        r = subprocess.run([sys.executable, "-c", script],
                           capture_output=True, text=True, timeout=560,
                           env={**os.environ, "PYTHONPATH": SRC})
        assert "SHARDED_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]


class TestDryrunArtifacts:
    """The committed dry-run records cover every applicable cell x mesh."""

    DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"

    def test_all_cells_present(self):
        if not self.DIR.exists():
            pytest.skip("dry-run artifacts not generated yet")
        missing = []
        for a, s, ok, _ in configs.all_cells():
            for m in ("single", "multi"):
                if not (self.DIR / f"{a}__{s}__{m}.json").exists():
                    missing.append((a, s, m))
        assert not missing, missing

    def test_records_sane(self):
        import json
        if not self.DIR.exists():
            pytest.skip("dry-run artifacts not generated yet")
        for fn in self.DIR.glob("*.json"):
            rec = json.loads(fn.read_text())
            assert rec["cost"].get("flops", 0) > 0, fn.name
            assert rec["n_chips"] in (256, 512), fn.name
            if rec["mesh"] == "multi":
                assert rec["n_chips"] == 512
