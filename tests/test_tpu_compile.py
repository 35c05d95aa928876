"""The VPC datapath compiles for a TPU v5e chip.

Interpret mode accepts programs the chip's compiler (Mosaic) refuses, so
these tests compile the megakernel and the runtime's jitted fused program
with ``interpret=False`` for a described v5e topology: the TPU compiler
runs here without a chip attached.  Nothing executes.  All compiles stay in
this one file and in this process: only one process at a time may load
the TPU compiler library.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.api.compute_backend import ComputeBackend, _vpc_fused_factory
from repro.kernels.vpc_datapath import vpc_datapath


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # can never be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.uint32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _rules(sharding, n_rules):
    return (_spec(sharding, (n_rules,)), _spec(sharding, (n_rules,)),
            _spec(sharding, (n_rules,), jnp.bool_))


@pytest.mark.parametrize("n,n_rules", [(8, 32), (16384, 1024),
                                       (65536, 1024), (16384, 100000),
                                       (8192, 100000)])
def test_vpc_datapath_compiles_for_v5e(one_chip, n, n_rules):
    """At 100,000 rules the table streams through the kernel in rule
    tiles; a single block of it would not fit VMEM."""
    def step(headers, payload, rules, key, nonce):
        return vpc_datapath(headers, payload, rules, key, nonce,
                            interpret=False)

    compiled = jax.jit(step).lower(
        _spec(one_chip, (n, 5)), _spec(one_chip, (n, 16)),
        _rules(one_chip, n_rules), _spec(one_chip, (8,)),
        _spec(one_chip, (3,))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_fused_runtime_program_compiles_for_v5e(one_chip, monkeypatch):
    """The program ComputeBackend dispatches for ``firewall >> nat >>
    chacha20`` at one pad bucket.  The kernel wrapper picks interpret mode
    from ``jax.default_backend()``, which is the CPU here, so the test
    reports the chip's backend to it."""
    _compile_fused_program(one_chip, monkeypatch, n_rules=1024)


def test_fused_runtime_program_compiles_for_v5e_at_100k_rules(one_chip,
                                                               monkeypatch):
    """The same program for a tenant of 100,000 rules, whose table the
    kernel streams in rule tiles."""
    _compile_fused_program(one_chip, monkeypatch, n_rules=100000)


def _compile_fused_program(one_chip, monkeypatch, n_rules):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    bucket = 16384
    params = {"firewall": {"rules": _rules(one_chip, n_rules)},
              "nat": {"nat_ip": _spec(one_chip, (), jnp.int32)},
              "chacha20": {"key": _spec(one_chip, (8,)),
                           "nonce": _spec(one_chip, (3,))}}
    state = {"headers": _spec(one_chip, (bucket, 5)),
             "payload": _spec(one_chip, (bucket, 16)),
             "ctr": _spec(one_chip, (bucket,)),
             "valid": _spec(one_chip, (bucket,), jnp.bool_)}
    program = _vpc_fused_factory(params)
    assert program is not None
    jitted = ComputeBackend(use_fused=True)._jit(program)
    compiled = jitted.lower(state, params).compile()
    assert "tpu_custom_call" in compiled.as_text()
