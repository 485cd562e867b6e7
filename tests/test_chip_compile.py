"""Compile the main path's programs and the Pallas kernels for a TPU v5e.

The chip is described, not attached: ``jax.experimental.topologies`` gives
a v5e device the TPU compiler targets from a host without a TPU, so a program
the chip's compiler would refuse (a kernel over its VMEM budget, a tile it
cannot lay out) fails here, before any chip run.  Nothing executes.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library, and the test workers
must all collect the same tests.  The persistent compile cache is off
around these compiles (an entry written for a described chip cannot be
read back here).
"""

import os

import pytest

import jax
import jax.numpy as jnp

#: chains per problem in the batched-annealer compile (FabricOptions default)
CHAINS = 16


@pytest.fixture(scope="module")
def chip():
    """One v5e chip's sharding, with the persistent compile cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _synthetic(size):
    from repro.fabric import FabricSpec, lower, synthetic_netlist
    spec = FabricSpec(rows=size, cols=size)
    return lower(synthetic_netlist(spec, seed=4), spec)


def _is_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


def test_batch_annealer_compiles_32x32(chip):
    from repro.fabric.place import _build_batch_annealer, batch_signature

    s_pad, n_pad, d_pad, e_pad, k_pad = batch_signature(_synthetic(32), 32)
    run = _build_batch_annealer(s_pad, n_pad, d_pad, e_pad, k_pad, 0.02,
                                "delta")
    rows = 4 * CHAINS                         # 4 problems x their chains
    compiled = run.lower(
        _spec(chip, (rows, 2), jnp.uint32),
        _spec(chip, (rows, e_pad), jnp.int32),
        _spec(chip, (rows, e_pad, 2), jnp.float32),
        _spec(chip, (rows, n_pad, d_pad), jnp.int32),
        _spec(chip, (rows, n_pad, d_pad), jnp.bool_),
        _spec(chip, (rows, e_pad, k_pad), jnp.int32),
        _spec(chip, (rows, 5), jnp.int32),
        _spec(chip, (rows,), jnp.float32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 << 30


def _while_body_ops(hlo: str) -> set:
    """Opcodes of every instruction the compiled program's ``while`` body
    runs, through the fusions and other computations it calls."""
    import re

    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.-]+) .*\{$", line)
        if head and not line.startswith(" "):
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    body, = set(re.findall(r" while\(.*body=%?([\w.-]+)", hlo))
    ops, seen, todo = set(), set(), [body]
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for line in comps[c]:
            op = re.search(r"= [^=]*? ([a-z][\w-]*)\(", line)
            if op:
                ops.add(op.group(1))
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.-]+)", line)
    return ops


def test_batch_annealer_loop_body_has_no_gather_or_scatter(chip):
    """The annealer at the ml16 cells' first dispatch (6 problems x 16
    chains, signature (2048, 64, 2, 512, 4)) compiles its loop body to
    compares, selects and reductions: no indexed read or write per chain
    step, which a TPU runs one element at a time."""
    from repro.fabric.place import _build_batch_annealer

    s_pad, n_pad, d_pad, e_pad, k_pad = 2048, 64, 2, 512, 4
    run = _build_batch_annealer(s_pad, n_pad, d_pad, e_pad, k_pad, 0.02,
                                "delta")
    rows = 6 * CHAINS
    compiled = run.lower(
        _spec(chip, (rows, 2), jnp.uint32),
        _spec(chip, (rows, e_pad), jnp.int32),
        _spec(chip, (rows, e_pad, 2), jnp.float32),
        _spec(chip, (rows, n_pad, d_pad), jnp.int32),
        _spec(chip, (rows, n_pad, d_pad), jnp.bool_),
        _spec(chip, (rows, e_pad, k_pad), jnp.int32),
        _spec(chip, (rows, 5), jnp.int32),
        _spec(chip, (rows,), jnp.float32)).compile()
    ops = _while_body_ops(compiled.as_text())
    assert {"compare", "select"} <= ops
    assert not ops & {"gather", "scatter"}, sorted(ops)


def test_batch_stepper_compiles_fig11_bucket(chip):
    from repro.sim.cycle import _ARITY_PAD, _build_batch_stepper

    # the one bucket every Fig. 11 pair lands in at 16x16 (the floors)
    sig = (64, 4, 32, 64, 512, 64, 32, 1, 256, 4, 3, 2)
    ip, up, ep, sp, wp, lp, cp, op_, _, _, K, B = sig
    ops = ("nop", "add", "ashr", "max", "mul")
    n = 14                                    # Fig. 11 (variant, app) pairs
    i32 = jnp.int32
    fields = [((n,), i32), ((n, 2), i32), ((n, ip, up), i32),
              ((n, ip, up, _ARITY_PAD), i32), ((n, cp), jnp.float32),
              ((n, ip), i32), ((n, ep), i32), ((n, wp), i32),
              ((n, sp), i32), ((n, sp), i32), ((n, lp), i32),
              ((n, lp), i32), ((n, lp), i32), ((n, op_), i32),
              ((n, op_), i32), ((n, B, K, ep), jnp.float32)]
    run = _build_batch_stepper(sig, ops)
    run.lower(*(_spec(chip, s, d) for s, d in fields)).compile()


def test_hpwl_pallas_compiles_128x128(chip):
    from repro.kernels.pnr_cost import hpwl_pallas

    p = _synthetic(128)
    n, d = p.net_pins.shape
    compiled = hpwl_pallas.lower(
        _spec(chip, (p.n_entities, 2), jnp.float32),
        _spec(chip, (n, d), jnp.int32),
        _spec(chip, (n, d), jnp.bool_), interpret=False).compile()
    assert _is_kernel(compiled)


def test_hpwl_delta_pallas_compiles(chip):
    from repro.kernels.pnr_cost import hpwl_delta_pallas

    p = _synthetic(64)
    n, d = p.net_pins.shape
    e, k = p.ent_nets.shape
    compiled = hpwl_delta_pallas.lower(
        _spec(chip, (e, 2), jnp.float32), _spec(chip, (e,), jnp.int32),
        _spec(chip, (n, d), jnp.int32), _spec(chip, (n, d), jnp.bool_),
        _spec(chip, (n,), jnp.float32), _spec(chip, (2 * k,), jnp.int32),
        _spec(chip, (), jnp.int32), _spec(chip, (), jnp.int32),
        interpret=False).compile()
    assert _is_kernel(compiled)


def test_alu_step_pallas_compiles_full_op_table(chip):
    from repro.kernels.sim_step import ALU_IMPLS, alu_step_pallas, op_table

    ops = op_table(sorted(ALU_IMPLS))
    lanes = 4096
    operand = _spec(chip, (8, lanes), jnp.float32)
    compiled = alu_step_pallas.lower(
        _spec(chip, (lanes,), jnp.int32), operand, operand, operand, ops,
        interpret=False).compile()
    assert _is_kernel(compiled)


def test_pe_kernel_compiles(chip):
    from repro.graphir import pattern_from_spec
    from repro.graphir.graph import free_in_ports
    from repro.kernels import make_pe_kernel

    # conv + relu: mul -> add -> max(., const)
    pattern = pattern_from_spec([("mul", (-1, -1)), ("add", (0, -1)),
                                 ("const", ()), ("max", (1, 2))])
    run = make_pe_kernel(pattern, interpret=False)
    x = _spec(chip, (512, 512), jnp.float32)
    compiled = run.lower(*[x] * len(free_in_ports(pattern))).compile()
    assert _is_kernel(compiled)
