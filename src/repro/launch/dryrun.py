import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes, record memory/cost/collective analysis.

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k \
      --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Outputs one JSON per cell under experiments/dryrun/.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh  # noqa: E402
from repro.launch.steps import input_specs  # noqa: E402
from repro.parallel import sharding as SH  # noqa: E402
from repro.parallel import ctx as pctx  # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "c64": 8, "c128": 16}
_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"\b(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in (per-device) HLO."""
    out = {k: 0 for k in ("all-gather", "all-reduce", "reduce-scatter",
                          "all-to-all", "collective-permute")}
    count = {k: 0 for k in out}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[-1][:40]:
            continue
        kind = m.group(1)
        # shapes on the line: first = result, rest = operands
        shapes = list(_SHAPE_RE.finditer(line))
        if not shapes:
            continue
        args_part = line[m.end():]
        op_shapes = list(_SHAPE_RE.finditer(args_part))
        if op_shapes:
            out[kind] += sum(_shape_bytes(s) for s in op_shapes)
        else:                       # fallback: use the result shape
            out[kind] += _shape_bytes(shapes[0])
        count[kind] += 1
    out["counts"] = count
    out["total"] = sum(v for k, v in out.items() if k != "counts")
    return out


def make_mesh_by_name(mesh_name: str):
    """single | multi | "DxM" (custom data x model, 256 or 512 chips)."""
    if mesh_name in ("single", "multi"):
        return make_production_mesh(multi_pod=(mesh_name == "multi"))
    d, m = (int(x) for x in mesh_name.split("x"))
    return make_mesh((d, m), ("data", "model"))


def run_cell(arch: str, shape_name: str, mesh_name: str,
             out_dir: Path = OUT_DIR, verbose: bool = True) -> dict:
    mesh = make_mesh_by_name(mesh_name)
    n_chips = mesh.devices.size
    cell = input_specs(arch, shape_name)
    t0 = time.time()

    pmode = cell.kind          # train | prefill | decode
    in_specs = []
    for i, a in enumerate(cell.args):
        if i == 0 and cell.kind in ("train", "prefill", "decode"):
            in_specs.append(SH.param_specs(a, mesh, mode=pmode,
                                           fsdp_only=cell.cfg.fsdp_only,
                                           moe_ep=cell.cfg.moe_ep))
        elif cell.kind == "train" and i == 1:
            pspec = SH.param_specs(cell.args[0], mesh, mode=pmode,
                                   fsdp_only=cell.cfg.fsdp_only,
                                   moe_ep=cell.cfg.moe_ep)
            in_specs.append(type(a)(m=pspec, v=pspec,
                                    count=jax.sharding.PartitionSpec()))
        elif cell.kind == "decode" and i == 1:
            in_specs.append(SH.cache_specs(cell.cfg, a, mesh,
                                           cell.shape.global_batch))
        elif isinstance(a, dict):
            in_specs.append(SH.batch_specs(
                a, mesh, all_axes=(pmode == "train"
                                   and cell.cfg.fsdp_only),
                seq_over_model=(cell.kind == "prefill"
                                and cell.cfg.fsdp_only)))
        else:
            in_specs.append(jax.sharding.PartitionSpec())
    in_shardings = SH.to_shardings(tuple(in_specs), mesh)

    with mesh, pctx.policy(mesh, dp_all_axes=(pmode == "train"
                                              and cell.cfg.fsdp_only)):
        jitted = jax.jit(cell.step, in_shardings=in_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_rec = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        mem_rec[k] = getattr(mem, k, None)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0] if cost else {}
    cost_rec = {k: float(v) for k, v in cost.items()
                if isinstance(v, (int, float)) and
                (k in ("flops", "bytes accessed", "optimal_seconds")
                 or k.startswith("bytes accessed"))}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    n_hlo_lines = hlo.count("\n")
    del hlo

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": cell.kind, "n_chips": int(n_chips),
        "seq_len": cell.shape.seq_len,
        "global_batch": cell.shape.global_batch,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem_rec, "cost": cost_rec, "collectives": coll,
        "hlo_lines": n_hlo_lines,
        "params_total": cell.cfg.param_counts()["total"],
        "params_active": cell.cfg.active_param_counts(),
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    fn = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    fn.write_text(json.dumps(rec, indent=1))
    if verbose:
        arg_gb = (mem_rec.get("argument_size_in_bytes") or 0) / 1e9
        tmp_gb = (mem_rec.get("temp_size_in_bytes") or 0) / 1e9
        print(f"[dryrun] {arch} x {shape_name} x {mesh_name}: "
              f"compile={t_compile:.1f}s args/dev={arg_gb:.2f}GB "
              f"temp/dev={tmp_gb:.2f}GB flops/dev={cost_rec.get('flops', 0):.3g} "
              f"coll/dev={coll['total']/1e9:.3f}GB", flush=True)
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=str(OUT_DIR))
    args = ap.parse_args(argv)
    out = Path(args.out)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = []
    if args.all:
        for a, s, ok, why in configs.all_cells(include_skipped=True):
            if ok:
                cells.append((a, s))
            else:
                print(f"[dryrun] SKIP {a} x {s}: {why}")
    else:
        shapes = [args.shape] if args.shape else list(configs.SHAPES)
        archs = [args.arch] if args.arch else configs.ARCH_NAMES
        for a in archs:
            cfg = configs.get_config(a)
            for s in shapes:
                ok, why = configs.shape_applicable(cfg, configs.SHAPES[s])
                if ok:
                    cells.append((a, s))
                else:
                    print(f"[dryrun] SKIP {a} x {s}: {why}")

    failures = []
    for a, s in cells:
        for m in meshes:
            fn = out / f"{a}__{s}__{m}.json"
            if args.skip_existing and fn.exists():
                print(f"[dryrun] cached {fn.name}")
                continue
            try:
                run_cell(a, s, m, out)
            except Exception as e:  # noqa: BLE001
                failures.append((a, s, m, repr(e)))
                print(f"[dryrun] FAIL {a} x {s} x {m}: {e!r}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"[dryrun] {len(failures)} failures:")
        for f in failures:
            print("   ", f)
        return 1
    print(f"[dryrun] all {len(cells) * len(meshes)} cells compiled OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
