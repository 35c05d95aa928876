"""End-to-end training driver.

Runs any assigned architecture (full or ``tiny:`` reduced config) with the
production substrate: sharded step, checkpoint/restart, synthetic data
pipeline, optional gradient compression, and failure injection for the
fault-tolerance tests.

  PYTHONPATH=src python -m repro.launch.train --arch tiny:yi-6b --steps 50 \
      --batch 8 --seq 128 --mesh 1x1 --ckpt /tmp/ck

Fault tolerance: ``--crash-at N`` raises after step N (simulating a node
loss); rerunning the same command restores from the latest checkpoint and
continues — examples/fault_tolerance.py drives the full kill/restart cycle,
including restarting onto a different mesh shape (elastic re-mesh).
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import numpy as np

from repro import configs
from repro.checkpoint import CheckpointManager
from repro.data import SyntheticLM
from repro.launch.mesh import make_mesh
from repro.launch.steps import abstract_params, make_train_step, moment_dtype_for
from repro.optim import adamw
from repro.optim.compress import GradCompressor
from repro.parallel import ctx as pctx
from repro.parallel import sharding as SH


def parse_mesh(spec: str):
    parts = [int(x) for x in spec.split("x")]
    n = int(np.prod(parts))
    avail = len(jax.devices())
    assert n <= avail, f"mesh {spec} needs {n} devices, have {avail}"
    if len(parts) == 2:
        return make_mesh(tuple(parts), ("data", "model"))
    return make_mesh(tuple(parts), ("pod", "data", "model"))


def get_cfg(name: str):
    if name.startswith("tiny:"):
        return configs.get_tiny_config(name[5:])
    return configs.get_config(name)


class Trainer:
    """Owns params/opt state, the jitted step, and the checkpoint manager."""

    def __init__(self, cfg, mesh, ckpt_dir=None, *, lr=3e-4,
                 compress="none", seed=0, keep=3):
        self.cfg, self.mesh = cfg, mesh
        self.compressor = GradCompressor(compress)
        self._dp_all = cfg.fsdp_only
        with mesh, pctx.policy(mesh, dp_all_axes=self._dp_all):
            params = jax.jit(
                lambda k: __import__("repro.models", fromlist=["m"]
                                     ).init_params(k, cfg),
                out_shardings=SH.to_shardings(
                    SH.param_specs(abstract_params(cfg), mesh,
                                   fsdp_only=cfg.fsdp_only,
                                   moe_ep=cfg.moe_ep), mesh))(
                jax.random.PRNGKey(seed))
            opt = adamw.init(params, moment_dtype_for(cfg))
        self.params, self.opt = params, opt
        self.pspecs = SH.param_specs(abstract_params(cfg), mesh,
                                     fsdp_only=cfg.fsdp_only,
                                     moe_ep=cfg.moe_ep)
        self.step_fn = self._build_step(lr)
        self.ckpt = CheckpointManager(ckpt_dir, keep=keep) if ckpt_dir else None
        self.step = 0

    def _build_step(self, lr):
        base = make_train_step(self.cfg, lr=lr)
        compressor = self.compressor

        if compressor.method == "none":
            def stepc(params, opt, ef, batch):
                p, o, m = base(params, opt, batch)
                return p, o, ef, m
        else:
            from repro.models import model as MD

            def stepc(params, opt, ef, batch):
                (loss, m), grads = jax.value_and_grad(
                    MD.apply_train, has_aux=True)(params, self.cfg, batch)
                grads, ef, cm = compressor.compress(grads, ef)
                params, opt, om = adamw.update(grads, opt, params, lr=lr)
                return params, opt, ef, {**m, **om, **cm}

        with self.mesh, pctx.policy(self.mesh, dp_all_axes=self._dp_all):
            return jax.jit(stepc, donate_argnums=(0, 1, 2))

    # ----------------------------------------------------------- training --
    def restore_if_any(self):
        if self.ckpt and self.ckpt.latest_step() is not None:
            tree = {"params": self.params, "opt": self.opt}
            shardings = {
                "params": SH.to_shardings(self.pspecs, self.mesh),
                "opt": type(self.opt)(
                    m=SH.to_shardings(self.pspecs, self.mesh),
                    v=SH.to_shardings(self.pspecs, self.mesh),
                    count=jax.sharding.NamedSharding(
                        self.mesh, jax.sharding.PartitionSpec())),
            }
            restored, extra = self.ckpt.restore(None, tree, shardings)
            self.params, self.opt = restored["params"], restored["opt"]
            self.step = int(extra["step"])
            return True
        return False

    def run(self, steps: int, batch: int, seq: int, *, seed=0,
            ckpt_every=10, crash_at=None, log_every=10, log=print):
        data = SyntheticLM(self.cfg, batch, seq, seed=seed)
        ef = self.compressor.init(self.params)
        losses = []
        with self.mesh, pctx.policy(self.mesh, dp_all_axes=self._dp_all):
            bspecs = SH.batch_specs(data.batch(0), self.mesh,
                                    all_axes=self._dp_all)
            t0 = time.time()
            while self.step < steps:
                from repro.data import place
                b = place(data.batch(self.step), self.mesh, bspecs)
                self.params, self.opt, ef, m = self.step_fn(
                    self.params, self.opt, ef, b)
                self.step += 1
                # keep the loss device-side: converting every step would
                # block the dispatch pipeline once per iteration; the whole
                # history crosses to the host once at return
                losses.append(m["loss"])
                if self.step % log_every == 0 or self.step == steps:
                    # logging sync is deliberate and amortized over
                    # log_every steps
                    log(f"step {self.step:5d} "
                        f"loss {float(m['loss']):.4f} "          # noqa: L-HOSTSYNC
                        f"gnorm {float(m['grad_norm']):.3f} "    # noqa: L-HOSTSYNC
                        f"({(time.time() - t0):.1f}s)")
                if self.ckpt and (self.step % ckpt_every == 0
                                  or self.step == steps):
                    self.ckpt.save(self.step,
                                   {"params": self.params, "opt": self.opt},
                                   extra={"step": self.step})
                if crash_at is not None and self.step >= crash_at:
                    if self.ckpt:
                        self.ckpt.wait()
                    raise RuntimeError(f"injected failure at step {self.step}")
        if self.ckpt:
            self.ckpt.wait()
        return [float(x) for x in losses]   # ONE device->host pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny:yi-6b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--compress", default="none",
                    choices=["none", "int8", "topk"])
    ap.add_argument("--crash-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_cfg(args.arch)
    mesh = parse_mesh(args.mesh)
    tr = Trainer(cfg, mesh, args.ckpt, lr=args.lr, compress=args.compress,
                 seed=args.seed)
    if tr.restore_if_any():
        print(f"[train] restored from step {tr.step}")
    losses = tr.run(args.steps, args.batch, args.seq, seed=args.seed,
                    ckpt_every=args.ckpt_every, crash_at=args.crash_at)
    print(f"[train] done: first loss {losses[0]:.4f} "
          f"last loss {losses[-1]:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
