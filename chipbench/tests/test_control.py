"""The comparison that decides ``correct`` fails what it has to fail.

Each run here is a whole cell at a small size on the CPU (``tiny.py``),
the look for a chip skipped, with the timed path broken underneath:
the control (the firewall's scan skipped), and each fault a packet path
can have, in the batch and in the streaming engine: a step that hands back
its input unchanged, half of each batch left unprocessed, one shard of the
fleet never run, one answer altered where it is produced.  A sound run of
the same cell reads correct."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from chipbench import spec
from chipbench.control import CONTROL_BACKEND, control_nts
from chipbench.tests import tiny

BENCH = spec.load_benchmark()
VPC = spec.find_cell(BENCH, "vpc8-r1k.backlog")
POISSON = spec.find_cell(BENCH, "vpc8-r1k.poisson80")
STREAM = spec.find_cell(BENCH, "vpc8-r1k.stream")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # whole 1024-rule tables, as in the cells, so that about a fifth of the
    # packets are denied and skipping the scan shows
    return tiny.make_root(tmp_path_factory.mktemp("tiny"), rules=1024,
                          batch=32)


def run(cell, root, seed=11, **kw):
    kw.setdefault("backend_kw", {})
    return tiny.run(cell, root, False, root / "trace", seed=seed,
                    seconds=0.2, **kw)


def _launch_with(fault):
    """``ComputeBackend._launch`` with ``fault(state_in, out)`` applied to
    every launch's output."""
    from repro.api.compute_backend import ComputeBackend
    real = ComputeBackend._launch

    def launch(self, dep, batches, bucket, state, dev):
        before = {k: v for k, v in state.items()
                  if k in ("headers", "payload")}
        before = jax.tree.map(jnp.copy, before)   # the program donates
        return fault(before, real(self, dep, batches, bucket, state, dev))
    return launch


def unchanged(before, out):
    return {**out, **before}


def half_left_out(before, out):
    res = dict(out)
    for k, v in before.items():
        h = v.shape[0] // 2
        res[k] = out[k].at[h:].set(v[h:])
    return res


def one_answer_altered(before, out):
    return {**out, "payload": out["payload"].at[0, 0].add(jnp.uint32(1))}


@pytest.mark.parametrize("cell", [VPC, POISSON, STREAM],
                         ids=lambda c: c["name"])
def test_sound_run_is_correct(cell, root):
    assert run(cell, root)["correct"]


@pytest.mark.parametrize("cell", [VPC, POISSON, STREAM],
                         ids=lambda c: c["name"])
def test_control_is_not_correct(cell, root):
    out = run(cell, root, nts=control_nts(), backend_kw=CONTROL_BACKEND)
    assert not out["correct"]
    assert out["checks"]["mismatched_words"]["value"] > 0


FAULTS = [unchanged, half_left_out, one_answer_altered]


def run_with_fault(cell, fault, root, monkeypatch):
    from repro.api.compute_backend import ComputeBackend
    monkeypatch.setattr(ComputeBackend, "_launch", _launch_with(fault))
    out = run(cell, root)
    assert not out["correct"]
    assert out["checks"]["mismatched_words"]["value"] > 0


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, root, monkeypatch):
    run_with_fault(VPC, fault, root, monkeypatch)


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_in_the_streaming_engine_is_not_correct(fault, root,
                                                      monkeypatch):
    """The same faults where the streaming engine launches: it calls the
    same ``_launch`` from its dispatch ring."""
    run_with_fault(STREAM, fault, root, monkeypatch)


def test_shard_never_run_is_not_correct(root, monkeypatch):
    """The fleet's coordinator leaves one chip's shard out of ``run()``:
    its tenants' packets never come back."""
    from repro.api.compute_backend import ComputeBackend
    real = ComputeBackend.run

    def run_but_c3(self, *a, **kw):
        if self.name != "c3":
            real(self, *a, **kw)
    monkeypatch.setattr(ComputeBackend, "run", run_but_c3)
    out = run(tiny.FLEET, root)
    assert not out["correct"]
    assert out["failed"] > 0
    assert out["checks"]["bad_batches"]["value"] > 0
