"""Dry-run machinery tests on a small forced-device mesh (subprocess):
lower+compile one representative cell per family on a 4x2 mesh and check
the JSON record pipeline + collective parser."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
  %ag = bf16[64,128]{1,0} all-gather(bf16[8,128]{1,0} %x), dimensions={0}
  %ar.1 = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%sum
  %rs = f32[32]{0} reduce-scatter(f32[256]{0} %z), dimensions={0}
  %cp = u32[16]{0} collective-permute(u32[16]{0} %w)
  %notacoll = f32[2]{0} add(f32[2]{0} %a, f32[2]{0} %b)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 256 * 4
    assert out["reduce-scatter"] == 256 * 4
    assert out["collective-permute"] == 16 * 4
    assert out["counts"]["all-gather"] == 1
    assert out["total"] == sum(v for k, v in out.items()
                               if k not in ("counts", "total"))


@pytest.mark.parametrize("arch,shape", [
    ("yi-6b", "decode_32k"),            # dense serve, fsdp_only arch
    ("jamba-v0.1-52b", "train_4k"),     # hybrid+MoE+EP train
])
def test_small_mesh_cell_compiles(arch, shape, tmp_path):
    """The same run_cell path used for the 512-chip dry-run compiles tiny
    reduced configs on an in-process 4x2 mesh."""
    script = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, {SRC!r})
import json
from pathlib import Path
from repro import configs

# shrink the arch (keep family structure) and the shape
cfg = configs.get_tiny_config({arch!r}).replace(scan_layers=True)
if not cfg.is_homogeneous():
    cfg = cfg.replace(scan_layers=False)
orig_get, orig_shapes = configs.get_config, dict(configs.SHAPES)
configs.get_config = lambda a: cfg if a == {arch!r} else orig_get(a)
from repro.configs.base import ShapeConfig
sh = orig_shapes[{shape!r}]
configs.SHAPES[{shape!r}] = ShapeConfig(sh.name, 256, 8, sh.kind)

import repro.launch.dryrun as DR
from repro.launch.mesh import make_mesh
DR.make_mesh_by_name = lambda name: make_mesh((4, 2), ("data", "model"))
rec = DR.run_cell({arch!r}, {shape!r}, "single",
                  out_dir=Path({str(tmp_path)!r}), verbose=False)
assert rec["cost"]["flops"] > 0
assert rec["memory"]["temp_size_in_bytes"] is not None
print("CELL_OK", rec["collectives"]["total"])
"""
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=560,
                       env={**os.environ, "PYTHONPATH": SRC})
    assert "CELL_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-3000:]
