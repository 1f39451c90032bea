"""The main path's jitted programs, compiled for a described TPU v5e at
the sizes a deployment runs — no chip attached, nothing executed.

The TPU compiler is installed beside the CPU backend and compiles for
a topology that is described, not attached: what it refuses here (a
program that does not fit 16 GB of HBM, a kernel that cannot be
partitioned over the mesh) it would refuse on the chip.  A compile that
passes is a compile, not a chip run; `chip_smoke.py` is the chip run.

The topology is described inside a module-scoped fixture, never at
import: the first process to describe it holds libtpu for its lifetime,
so under xdist only the worker that is handed this file may do it, and
it compiles in its own process.  Keep every such test in THIS file.
"""

import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from emqx_tpu.ops.match_kernel import (
    decide_batch,
    match_batch,
    match_batch_compact,
    rules_eval_batch,
)

# shipped kernel widths (config.BrokerEngineConfig)
F_WIDTH, M_CAP = 16, 128
# a 10M-subscription automaton at the engine's power-of-two capacity
# classes (0.70 nodes and 0.26 buckets a subscription, rounded up)
N_NODES, N_BUCKETS, LEVELS = 8_388_608, 4_194_304, 8
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    # a TPU executable written to the persistent cache from here cannot
    # be read back without a chip: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, **static):
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **static).compile()
    mem = compiled.memory_analysis()
    print(
        f"{fn.__name__}: compiled in {time.perf_counter() - t0:.1f}s, "
        f"arguments {mem.argument_size_in_bytes / 1e6:.1f} MB, "
        f"temporaries {mem.temp_size_in_bytes / 1e6:.1f} MB, "
        f"code {mem.generated_code_size_in_bytes / 1e6:.1f} MB"
    )
    total = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes + mem.generated_code_size_in_bytes
    )
    assert total < HBM_BYTES
    return compiled, mem


def _match_args(sharding, batch, nodes=N_NODES, buckets=N_BUCKETS,
                levels=LEVELS):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return (
        s((buckets, 16), jnp.int32),     # fp_rows
        s((nodes, 8), jnp.int32),        # node_rows
        s((), jnp.uint32),               # salt
        s((batch, levels), jnp.int32),   # tokens
        s((batch,), jnp.int32),          # lengths
        s((batch,), jnp.bool_),          # dollar
    )


@pytest.mark.parametrize("batch", [16, 4096])
def test_match_batch_compact_10m_subs(one_chip, batch):
    _, mem = _compile(
        match_batch_compact, *_match_args(one_chip, batch),
        f_width=F_WIDTH, m_cap=M_CAP, c_cap=8 * batch,  # the first rung
    )
    # the tables are counted at their logical size (32 B and 64 B a
    # row); what the resident layout pads them to only the chip's
    # memory_stats() says
    assert mem.argument_size_in_bytes >= N_NODES * 32 + N_BUCKETS * 64


@pytest.mark.parametrize("nodes,buckets,levels", [
    (262_144, 262_144, 9),   # the base: 171,117 nodes, 7 id levels + root
    (4_096, 2_048, 17),      # the live delta: scans `max_levels` + 1
], ids=["base", "delta"])
def test_match_batch_compact_plus_100k_at_32_lanes(one_chip, nodes, buckets,
                                                   levels):
    """`plus-100k` (PR 33) runs the kernel at `f_width` 32, a shape no
    other deployment compiles: a sort of 64 candidates a level and a
    32 x 32 `in_prev` compare, at a full window of 4,096."""
    _compile(
        match_batch_compact,
        *_match_args(one_chip, 4096, nodes, buckets, levels),
        f_width=32, m_cap=M_CAP, c_cap=8 * 4096,
    )


@pytest.mark.parametrize("batch", [16, 4096])
def test_match_batch_dense_10m_subs(one_chip, batch):
    _compile(
        match_batch, *_match_args(one_chip, batch),
        f_width=F_WIDTH, m_cap=M_CAP,
    )


def test_decide_batch_1m_deliveries(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows, n, b = 1 << 16, 1 << 20, 4096  # window 4096 x fan-out 256
    _compile(
        decide_batch,
        s((rows,), jnp.int8), s((rows,), jnp.bool_),
        s((rows,), jnp.bool_), s((rows,), jnp.bool_),
        s((n,), jnp.int32), s((n,), jnp.int32), s((n,), jnp.int32),
        s((b,), jnp.int8), s((b,), jnp.bool_), s((b,), jnp.int32),
    )


def test_rules_eval_batch_128_rules(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    r, steps, p, lits, w = 128, 16, 8, 8, 4096
    prog = s((r, steps), jnp.int32)
    _compile(
        rules_eval_batch,
        prog, prog, prog, prog, prog, s((r, steps), jnp.float32),
        s((lits,), jnp.int32), s((r,), jnp.int32),
        s((p, w), jnp.float32), s((p, w), jnp.int32),
        s((p, w), jnp.bool_), s((p, w), jnp.bool_),
    )


def test_sharded_match_four_chips(topo):
    from emqx_tpu.parallel.sharded import sharded_match

    k, nodes, buckets, batch = 4, 2_097_152, 1_048_576, 4096
    mesh = Mesh(np.array(topo.devices[:k]).reshape(k, 1), ("sub", "pub"))

    def s(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec)
        )

    t0 = time.perf_counter()
    compiled = sharded_match.lower(
        mesh,
        s((k, buckets, 16), jnp.int32, P("sub")),
        s((k, nodes, 8), jnp.int32, P("sub")),
        s((k,), jnp.uint32, P("sub")),
        s((batch, LEVELS), jnp.int32, P("pub")),
        s((batch,), jnp.int32, P("pub")),
        s((batch,), jnp.bool_, P("pub")),
        f_width=F_WIDTH, m_cap=M_CAP,
    ).compile()
    mem = compiled.memory_analysis()
    print(
        f"sharded_match: compiled in {time.perf_counter() - t0:.1f}s, "
        f"arguments {mem.argument_size_in_bytes / 1e6:.1f} MB a device"
    )
    # each device holds its quarter of the stack, not the whole of it
    stack = k * (buckets * 64 + nodes * 32)
    assert mem.argument_size_in_bytes < stack / k * 1.1
    # the one collective: the psum of match counts over `sub`
    assert "all-reduce" in compiled.as_text()
