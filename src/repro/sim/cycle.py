"""Cycle-accurate functional simulator of a scheduled CGRA array.

Executes a placed, routed, modulo-scheduled mapping over time: every tile
runs in lockstep, one ``jax.lax.scan`` step per clock cycle, with the whole
machine state held in dense arrays so input batches vectorize for free.

Machine model (the register set the scheduler's arithmetic assumes —
see :mod:`repro.sim.schedule`):

* ``ext``   — one streaming register per array input signal, refreshed with
  the next iteration's word every II cycles by its io_in tile;
* ``sig``   — one output register per PE-produced signal, loaded when the
  producing instance fires;
* ``wire``  — one pipeline register per (net, tile) hop of every routed
  tree (per-track: nets sharing a channel keep separate registers), shifted
  unconditionally every cycle — a value physically ripples down its route;
* ``latch`` — one input FIFO per (consumer tile, signal),
  ``spec.latch_depth`` iterations deep, capturing the arriving word the
  cycle it lands (slot = iteration mod depth) while the consumer reads the
  slot of the iteration it is executing — operand skew up to
  ``depth x II`` survives, exactly what the scheduler assumed;
* ``tmp``   — combinational values inside a firing tile: each instance's
  covered app nodes execute as a short micro-op program (topological order,
  at most ``n_steps`` per tile), all tiles dispatching their step-``u``
  opcode simultaneously through :mod:`repro.kernels.sim_step`.

Because instances execute the *application* nodes they cover (not the
merged-PE pattern — the datapath validator already proved those equal),
simulated outputs must bit-match :func:`repro.graphir.interp.interpret`
whenever the op set is IEEE-exact, which is the entire paper suite.  A
mismatch means the mapping, placement, routing, or schedule is wrong —
this simulator is the end-to-end correctness oracle the static pipeline
never had.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..core.mapper import Mapping
from ..graphir.graph import Graph
from ..graphir.ops import OPS
from ..fabric.netlist import Netlist
from ..fabric.place import Placement
from .schedule import ModuloSchedule

_ARITY_PAD = 3


@dataclass
class SimProgram:
    """A scheduled design lowered to the dense arrays the scan consumes."""

    app_name: str
    ii: int
    latency: int
    n_inst: int
    n_steps: int                      # micro-ops per tile (padded)
    ops: Tuple[str, ...]              # opcode table (0 = nop)
    # tile micro-code
    opcodes: np.ndarray               # (n_inst, n_steps) int32
    op_src: np.ndarray                # (n_inst, n_steps, 3) int32 (operand ix)
    # operand space = [latch | const | tmp]
    n_latch: int
    n_const: int
    const_pool: np.ndarray            # (n_const,) float32
    # schedule times
    fire_time: np.ndarray             # (n_inst,) int32
    ext_time: np.ndarray              # (n_ext,) int32
    # wires: src space = [sig | ext | wire]
    n_sig: int
    n_ext: int
    n_wire: int
    wire_src: np.ndarray              # (n_wire,) int32
    # producers
    sig_tmp: np.ndarray               # (n_sig,) int32 into tmp-flat
    sig_owner: np.ndarray             # (n_sig,) int32 instance index
    # latches
    latch_wire: np.ndarray            # (n_latch,) int32 wire index
    latch_time: np.ndarray            # (n_latch,) int32 first capture cycle
    latch_owner: np.ndarray           # (n_latch,) int32 consumer instance
    latch_depth: int                  # FIFO slots per latch
    # outputs
    out_wire: np.ndarray              # (n_out,) int32 wire index
    out_time: np.ndarray              # (n_out,) int32 first capture cycle
    out_cols: List[int]               # graph.outputs -> capture column
    input_names: List[str]            # per ext index
    schedule: ModuloSchedule = None
    _cache: Dict[Tuple, Any] = field(default_factory=dict, repr=False)

    @property
    def n_out(self) -> int:
        return len(self.out_wire)

    def total_cycles(self, iterations: int) -> int:
        return self.latency + (iterations - 1) * self.ii

    def summary(self) -> str:
        return (f"SimProgram[{self.app_name}: II={self.ii} "
                f"latency={self.latency} tiles={self.n_inst} "
                f"steps={self.n_steps} wires={self.n_wire} "
                f"latches={self.n_latch}]")

    # _cache holds jitted steppers — process-local, unpicklable.  Dropping
    # it on pickle makes SimPrograms storable in the explore DiskStore; a
    # restored program just recompiles its stepper on first simulate().
    def __getstate__(self) -> Dict[str, Any]:
        state = dict(self.__dict__)
        state["_cache"] = {}
        return state

    def __setstate__(self, state: Dict[str, Any]) -> None:
        self.__dict__.update(state)


def check_cycle_budget(prog: SimProgram, iterations: int,
                       max_cycles: Optional[int], *,
                       metrics=None) -> None:
    """Refuse (pre-dispatch) to simulate a program over its cycle cap.

    Raises :class:`repro.errors.BudgetExceeded` when ``max_cycles`` is
    set and ``prog.total_cycles(iterations)`` exceeds it — checked before
    any scan launches, so an over-budget program degrades to a structured
    failure instead of burning the budget it already exceeds.  No-op when
    ``max_cycles`` is None (the default).
    """
    if max_cycles is None:
        return
    total = prog.total_cycles(iterations)
    if total > max_cycles:
        if metrics is not None:
            metrics.inc("sim.budget_exhausted")
        from ..errors import BudgetExceeded
        raise BudgetExceeded(
            f"simulation of {prog.app_name} needs {total} cycles "
            f"(> sim_max_cycles={max_cycles})",
            total_cycles=total, max_cycles=max_cycles,
            iterations=iterations, ii=prog.ii, latency=prog.latency)


@dataclass
class SimResult:
    outputs: np.ndarray               # (B, K, n_graph_outputs) float32
    ii: int
    min_ii: int
    latency: int
    cycles: int
    iterations: int
    n_fires: int                      # PE invocations actually issued
    active_frac: float                # fires / (cycles * tiles)
    backend: str

    def throughput_ops_per_cycle(self, total_ops: int) -> float:
        return total_ops / self.ii


def lower_program(mapping: Mapping, app: Graph, netlist: Netlist,
                  placement: Placement,
                  schedule: ModuloSchedule) -> SimProgram:
    """Lower a scheduled design into a :class:`SimProgram`.

    Route timing comes from the schedule itself
    (:attr:`ModuloSchedule.net_timing` / ``hop_time``), so the simulator
    executes exactly the register chains the scheduler reasoned about.
    """
    if mapping.unmapped:
        raise ValueError(f"cannot simulate: unmapped nodes {mapping.unmapped}")
    if mapping.offloaded:
        raise NotImplementedError(
            "time-domain simulation requires fully PE-mapped graphs "
            f"(offloaded macros: {mapping.offloaded})")

    from ..kernels.sim_step import op_table

    coords = placement.coords
    cell_kind = {name: c.kind for name, c in netlist.cells.items()}
    inst_of_cell = {name: c.instance for name, c in netlist.cells.items()
                    if c.kind == "pe"}

    # -- signal spaces ------------------------------------------------------
    ext_sigs: List[int] = []
    for c in sorted(netlist.io_cells, key=lambda c: c.name):
        if c.kind == "io_in":
            ext_sigs.extend(c.signals)
    ext_sigs.sort()
    ext_ix = {s: i for i, s in enumerate(ext_sigs)}
    pe_sigs = sorted(n.signal for n in netlist.nets
                     if cell_kind[n.driver] == "pe")
    sig_ix = {s: i for i, s in enumerate(pe_sigs)}
    n_sig, n_ext = len(pe_sigs), len(ext_sigs)

    # -- wires: one register per (net, non-driver tile), timed exactly as
    # the scheduler published (ModuloSchedule.net_timing/net_src) ----------
    wire_ix: Dict[Tuple[str, Tuple[int, int]], int] = {}
    wire_src: List[int] = []
    timings = schedule.net_timing
    for net in sorted(netlist.nets, key=lambda n: n.name):
        nt = timings[net.name]
        drv_src = (sig_ix[net.signal]
                   if schedule.net_src[net.name][0] == "pe"
                   else n_sig + ext_ix[net.signal])
        for tile in sorted(nt.depth, key=lambda t: (nt.depth[t], t)):
            if tile == nt.driver:
                continue
            wire_ix[(net.name, tile)] = len(wire_src)
            parent = nt.parent[tile]
            if parent == nt.driver:
                wire_src.append(drv_src)
            else:
                wire_src.append(n_sig + n_ext
                                + wire_ix[(net.name, parent)])
    n_wire = len(wire_src)

    # -- latches: one per (consumer pe cell, signal) ------------------------
    latch_ix: Dict[Tuple[str, int], int] = {}
    latch_wire: List[int] = []
    latch_time: List[int] = []
    latch_owner: List[int] = []
    for net in sorted(netlist.nets, key=lambda n: n.name):
        nt = timings[net.name]
        for sink in net.sinks:
            if cell_kind[sink] != "pe":
                continue
            tile = coords[sink]
            latch_ix[(sink, net.signal)] = len(latch_wire)
            latch_wire.append(wire_ix[(net.name, tile)])
            latch_time.append(schedule.hop_time[(net.name, tile)])
            latch_owner.append(inst_of_cell[sink])
    n_latch = len(latch_wire)

    # -- constants -----------------------------------------------------------
    const_nodes = sorted(n for n, op in app.nodes.items() if op == "const")
    const_ix = {n: i for i, n in enumerate(const_nodes)}
    const_pool = np.asarray([float(app.attr(n, "value", 0.0))
                             for n in const_nodes], np.float32)
    n_const = len(const_nodes)

    # -- per-instance micro-code --------------------------------------------
    topo_pos = {n: i for i, n in enumerate(app.topo_order())}
    n_inst = mapping.n_pes
    per_inst_nodes = [sorted(inst.covered, key=topo_pos.get)
                      for inst in mapping.instances]
    n_steps = max((len(ns) for ns in per_inst_nodes), default=1)
    used_ops = sorted({app.nodes[n] for ns in per_inst_nodes for n in ns})
    ops = op_table(used_ops)
    code_of = {name: k for k, name in enumerate(ops)}

    def operand(i: int, tmp_of: Dict[int, int], cell: str,
                node: int, port: int) -> int:
        src = app.in_edges(node)[port]
        if src in tmp_of:
            return n_latch + n_const + i * n_steps + tmp_of[src]
        op = app.nodes[src]
        if op == "const":
            return n_latch + const_ix[src]
        # external operand (graph input or another tile's value)
        if (cell, src) not in latch_ix:
            raise AssertionError(
                f"no latch for signal {src} at {cell}: netlist/route mismatch")
        return latch_ix[(cell, src)]

    opcodes = np.zeros((n_inst, n_steps), np.int32)
    op_src = np.zeros((n_inst, n_steps, _ARITY_PAD), np.int32)
    for i, nodes in enumerate(per_inst_nodes):
        cell = f"pe{i}"
        tmp_of: Dict[int, int] = {}
        for u, node in enumerate(nodes):
            op = app.nodes[node]
            opcodes[i, u] = code_of[op]
            for port in range(OPS[op].arity):
                op_src[i, u, port] = operand(i, tmp_of, cell, node, port)
            tmp_of[node] = u

    # -- producers -----------------------------------------------------------
    sig_tmp = np.zeros((n_sig,), np.int32)
    sig_owner = np.zeros((n_sig,), np.int32)
    home = {}
    for i, inst in enumerate(mapping.instances):
        for n in inst.covered:
            home[n] = i
    for s, ix in sig_ix.items():
        i = home[s]
        sig_owner[ix] = i
        sig_tmp[ix] = i * n_steps + per_inst_nodes[i].index(s)

    # -- schedule times ------------------------------------------------------
    fire_time = np.asarray([schedule.start[("pe", i)]
                            for i in range(n_inst)], np.int32)
    ext_time = np.asarray([schedule.start[("in", s)] for s in ext_sigs],
                          np.int32)

    # -- output captures ----------------------------------------------------
    out_wire: List[int] = []
    out_time: List[int] = []
    cap_col: Dict[int, int] = {}
    for net in sorted(netlist.nets, key=lambda n: n.name):
        for sink in net.sinks:
            if cell_kind[sink] != "io_out":
                continue
            cap_col[net.signal] = len(out_wire)
            out_wire.append(wire_ix[(net.name, coords[sink])])
            out_time.append(schedule.hop_time[(net.name, coords[sink])])
    missing = [o for o in app.outputs if o not in cap_col]
    if missing:
        raise ValueError(f"graph outputs with no io_out capture: {missing} "
                         "(pass-through inputs/consts are not simulable)")
    out_cols = [cap_col[o] for o in app.outputs]

    input_names = [str(app.attr(s, "name", f"in{s}")) for s in ext_sigs]
    return SimProgram(
        app_name=mapping.app_name, ii=schedule.ii, latency=schedule.latency,
        n_inst=n_inst, n_steps=n_steps, ops=ops,
        opcodes=opcodes, op_src=op_src,
        n_latch=n_latch, n_const=n_const, const_pool=const_pool,
        fire_time=fire_time, ext_time=ext_time,
        n_sig=n_sig, n_ext=n_ext, n_wire=n_wire,
        wire_src=np.asarray(wire_src, np.int32),
        sig_tmp=sig_tmp, sig_owner=sig_owner,
        latch_wire=np.asarray(latch_wire, np.int32),
        latch_time=np.asarray(latch_time, np.int32),
        latch_owner=np.asarray(latch_owner, np.int32),
        latch_depth=schedule.latch_depth,
        out_wire=np.asarray(out_wire, np.int32),
        out_time=np.asarray(out_time, np.int32),
        out_cols=out_cols, input_names=input_names, schedule=schedule)


# ---------------------------------------------------------------------------
# execution
# ---------------------------------------------------------------------------
def _coerce_inputs(prog: SimProgram, inputs) -> np.ndarray:
    """Normalize to (B, K, n_ext) float32 in ext-signal order."""
    if isinstance(inputs, dict):
        cols = []
        for name in prog.input_names:
            if name not in inputs:
                raise KeyError(f"missing input {name!r}")
            cols.append(np.asarray(inputs[name], np.float32))
        arr = np.stack(cols, axis=-1)
    else:
        arr = np.asarray(inputs, np.float32)
    if arr.ndim == 2:
        arr = arr[None]
    if arr.ndim != 3 or arr.shape[-1] != prog.n_ext:
        raise ValueError(f"inputs must be (B, K, {prog.n_ext}); "
                         f"got {arr.shape}")
    return arr


def _build_stepper(prog: SimProgram, iterations: int, backend: str,
                   interpret: Optional[bool]):
    import jax
    import jax.numpy as jnp

    from ..kernels.sim_step import alu_step_jnp, alu_step_pallas

    K = iterations
    ii = prog.ii
    U = prog.n_steps
    opcodes = jnp.asarray(prog.opcodes)
    op_src = jnp.asarray(prog.op_src)
    const_pool = jnp.asarray(prog.const_pool)
    fire_time = jnp.asarray(prog.fire_time)
    ext_time = jnp.asarray(prog.ext_time)
    wire_src = jnp.asarray(prog.wire_src)
    sig_tmp = jnp.asarray(prog.sig_tmp)
    sig_owner = jnp.asarray(prog.sig_owner)
    latch_wire = jnp.asarray(prog.latch_wire)
    latch_time = jnp.asarray(prog.latch_time)
    latch_owner = jnp.asarray(prog.latch_owner)
    out_wire = jnp.asarray(prog.out_wire)
    out_time = jnp.asarray(prog.out_time)
    n_out = prog.n_out
    D = prog.latch_depth
    # tmp-flat positions written at micro-op step u: instance i -> i*U + u
    step_slots = jnp.asarray(
        np.arange(prog.n_inst, dtype=np.int32)[None, :] * U
        + np.arange(U, dtype=np.int32)[:, None])          # (U, n_inst)

    def periodic(c, t0):
        """(active now, iteration index) for a period-II event train."""
        d = c - t0
        k = d // ii
        live = (d >= 0) & (d % ii == 0) & (k < K)
        return live, jnp.clip(k, 0, K - 1)

    def dispatch(codes, a, b, c3):
        if backend == "pallas":
            return alu_step_pallas(codes, a, b, c3, prog.ops,
                                   interpret=interpret)
        return alu_step_jnp(codes, a, b, c3, prog.ops)

    def step(carry, c):
        ext, sig, wire, latch, outbuf, inputs = carry
        B = ext.shape[0]

        # each consumer reads the FIFO slot of the iteration it executes
        fire, fire_k = periodic(c, fire_time)                 # (n_inst,)
        rd = fire_k[latch_owner] % D                          # (n_latch,)
        latch_view = jnp.take_along_axis(
            latch, rd[None, :, None], axis=2)[:, :, 0]        # (B, n_latch)

        # tiles compute (all in lockstep; results committed only on fire).
        # one operand buffer [latch | const | tmp] per cycle: each micro-op
        # step writes its results into the tmp slice in place
        constb = jnp.broadcast_to(const_pool, (B, prog.n_const))
        operands = jnp.concatenate(
            [latch_view, constb,
             jnp.zeros((B, prog.n_inst * U), jnp.float32)], axis=1)
        tmp_off = prog.n_latch + prog.n_const
        for u in range(U):
            a = operands[:, op_src[:, u, 0]]
            b = operands[:, op_src[:, u, 1]]
            c3 = operands[:, op_src[:, u, 2]]
            r = dispatch(opcodes[:, u], a, b, c3)
            operands = operands.at[:, tmp_off + step_slots[u]].set(r)

        sig_new = jnp.where(fire[sig_owner],
                            operands[:, tmp_off + sig_tmp], sig)

        ext_live, ext_k = periodic(c, ext_time)               # (n_ext,)
        stream = inputs[:, ext_k, jnp.arange(prog.n_ext)]     # (B, n_ext)
        ext_new = jnp.where(ext_live, stream, ext)

        src_vec = jnp.concatenate([sig, ext, wire], axis=1)
        wire_new = src_vec[:, wire_src]

        l_live, l_k = periodic(c, latch_time)
        wr = l_k % D                                          # (n_latch,)
        arriving = wire[:, latch_wire]                        # (B, n_latch)
        cur = jnp.take_along_axis(latch, wr[None, :, None], axis=2)[:, :, 0]
        written = jnp.where(l_live, arriving, cur)
        latch_new = latch.at[:, jnp.arange(prog.n_latch), wr].set(written)

        o_live, o_k = periodic(c, out_time)
        vals = wire[:, out_wire]
        cols = jnp.arange(n_out)
        prev = outbuf[:, o_k, cols]
        outbuf = outbuf.at[:, o_k, cols].set(jnp.where(o_live, vals, prev))

        return (ext_new, sig_new, wire_new, latch_new, outbuf, inputs), None

    cycles = prog.total_cycles(K)

    def run(inputs):
        import jax.numpy as jnp
        B = inputs.shape[0]
        carry = (jnp.zeros((B, prog.n_ext), jnp.float32),
                 jnp.zeros((B, prog.n_sig), jnp.float32),
                 jnp.zeros((B, prog.n_wire), jnp.float32),
                 jnp.zeros((B, prog.n_latch, D), jnp.float32),
                 jnp.zeros((B, K, n_out), jnp.float32),
                 inputs)
        carry, _ = jax.lax.scan(step, carry, jnp.arange(cycles))
        return carry[4]

    return jax.jit(run), cycles


# ---------------------------------------------------------------------------
# cross-program batching: many (variant, app) simulations in one dispatch
# ---------------------------------------------------------------------------
#: sentinel start time for padded periodic events — they never fire
_NEVER = 1 << 30


#: per-dimension lower bounds for the bucket key, sized so the programs a
#: 16x16-class array typically produces all land in ONE bucket: compile
#: count — not padded-lane arithmetic — dominates wall clock on a sweep,
#: so small programs trade padding for sharing the compiled scan.  Floors
#: are static constants, so a program's bucket (and therefore its padded
#: lowering and outputs) still depends only on the program itself.
_SIG_FLOORS = (64, 4, 32, 64, 512, 64, 32, 1, 256)


def sim_signature(prog: SimProgram, iterations: int,
                  batch: int) -> Tuple[int, ...]:
    """Static shape key two programs must share to ride one vmapped scan.

    Every dimension pads to its power-of-two bucket
    (:func:`repro.kernels.tiling.pow2_bucket`), floored by
    :data:`_SIG_FLOORS` — tiles, micro-op steps, I/O streams,
    signal/wire/latch registers, output captures, and the total cycle
    count — so the key (and therefore both the compiled program and a
    program's simulated outputs) depends only on the program itself,
    never on its groupmates.
    """
    from ..kernels.tiling import pow2_bucket as b

    dims = (prog.n_inst, prog.n_steps, prog.n_ext, prog.n_sig, prog.n_wire,
            prog.n_latch, prog.n_const, prog.n_out,
            prog.total_cycles(iterations))
    return tuple(max(b(d), f) for d, f in zip(dims, _SIG_FLOORS)) \
        + (prog.latch_depth, iterations, batch)


def _pad_program(prog: SimProgram, sig: Tuple[int, ...],
                 code_of: Dict[str, int]) -> Dict[str, np.ndarray]:
    """Lower one program onto the bucket shapes of ``sig``.

    Operand/wire indices are remapped into the padded address spaces,
    opcodes into the group's shared table; padded periodic events start at
    ``_NEVER`` so they never fire, and padded register slots are only ever
    read by other padding (real index tables reference real entries only).
    """
    ip, up, ep, sp, wp, lp, cp, op_, _, _, _, _ = sig
    n_l, n_c, n_s = prog.n_latch, prog.n_const, prog.n_steps

    lut = np.asarray([code_of[name] for name in prog.ops], np.int32)
    opcodes = np.zeros((ip, up), np.int32)
    opcodes[:prog.n_inst, :n_s] = lut[prog.opcodes]

    # operand space [latch | const | tmp] -> [latch(lp) | const(cp) | tmp]
    v = prog.op_src
    tmp_off = v - n_l - n_c
    remapped = np.where(
        v < n_l, v,
        np.where(v < n_l + n_c, lp + (v - n_l),
                 lp + cp + (tmp_off // n_s) * up + tmp_off % n_s))
    op_src = np.zeros((ip, up, _ARITY_PAD), np.int32)
    op_src[:prog.n_inst, :n_s] = remapped

    # wire sources [sig | ext | wire] -> [sig(sp) | ext(ep) | wire]
    w = prog.wire_src
    wire_src = np.zeros((wp,), np.int32)
    wire_src[:prog.n_wire] = np.where(
        w < prog.n_sig, w,
        np.where(w < prog.n_sig + prog.n_ext, sp + (w - prog.n_sig),
                 sp + ep + (w - prog.n_sig - prog.n_ext)))

    sig_tmp = np.zeros((sp,), np.int32)
    sig_tmp[:prog.n_sig] = ((prog.sig_tmp // n_s) * up + prog.sig_tmp % n_s)
    sig_owner = np.zeros((sp,), np.int32)   # padded sigs may latch tile 0's
    sig_owner[:prog.n_sig] = prog.sig_owner  # value; nothing ever reads them

    def pad_time(src: np.ndarray, n: int) -> np.ndarray:
        out = np.full((n,), _NEVER, np.int32)
        out[:src.shape[0]] = src
        return out

    def pad_ix(src: np.ndarray, n: int) -> np.ndarray:
        out = np.zeros((n,), np.int32)
        out[:src.shape[0]] = src
        return out

    const_pool = np.zeros((cp,), np.float32)
    const_pool[:n_c] = prog.const_pool
    return dict(
        ii=np.int32(prog.ii),
        dims=np.asarray([n_s, prog.n_inst], np.int32),
        opcodes=opcodes, op_src=op_src, const_pool=const_pool,
        fire_time=pad_time(prog.fire_time, ip),
        ext_time=pad_time(prog.ext_time, ep),
        wire_src=wire_src, sig_tmp=sig_tmp, sig_owner=sig_owner,
        latch_wire=pad_ix(prog.latch_wire, lp),
        latch_time=pad_time(prog.latch_time, lp),
        latch_owner=pad_ix(prog.latch_owner, lp),
        out_wire=pad_ix(prog.out_wire, op_),
        out_time=pad_time(prog.out_time, op_))


#: field order of the stacked arrays fed to the batched stepper
_BATCH_FIELDS = ("ii", "dims", "opcodes", "op_src", "const_pool",
                 "fire_time", "ext_time", "wire_src", "sig_tmp", "sig_owner",
                 "latch_wire", "latch_time", "latch_owner", "out_wire",
                 "out_time")


@functools.lru_cache(maxsize=64)
def _build_batch_stepper(sig: Tuple[int, ...], ops: Tuple[str, ...]):
    """One compiled vmapped scan for every program of one bucket signature.

    Unlike :func:`_build_stepper` (which bakes one program's register
    counts, II, and schedule times into the compiled code as constants),
    the batched step takes them all as *data*: II drives the periodic
    event trains, the schedule-time tables are gathered arrays, and the
    per-program micro-op/tile counts mask the padded dispatch lanes
    (:func:`repro.kernels.sim_step.alu_step_masked`).  Real lanes execute
    exactly the arithmetic of the per-program stepper, so outputs are
    bit-identical to :func:`simulate` per program.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels.sim_step import alu_step_masked

    ip, up, ep, sp, wp, lp, cp, op_, cycles, D, K, B = sig
    tmp_off = lp + cp
    step_slots = jnp.asarray(
        np.arange(ip, dtype=np.int32)[None, :] * up
        + np.arange(up, dtype=np.int32)[:, None])             # (up, ip)

    def one(ii, dims, opcodes, op_src, const_pool, fire_time, ext_time,
            wire_src, sig_tmp, sig_owner, latch_wire, latch_time,
            latch_owner, out_wire, out_time, inputs):
        n_steps, n_inst = dims[0], dims[1]
        lane_act = jnp.arange(ip) < n_inst                    # (ip,)

        def periodic(c, t0):
            d = c - t0
            k = d // ii
            live = (d >= 0) & (d % ii == 0) & (k < K)
            return live, jnp.clip(k, 0, K - 1)

        def step(carry, c):
            ext, sig, wire, latch, outbuf = carry
            fire, fire_k = periodic(c, fire_time)             # (ip,)
            rd = fire_k[latch_owner] % D                      # (lp,)
            latch_view = jnp.take_along_axis(
                latch, rd[None, :, None], axis=2)[:, :, 0]    # (B, lp)

            constb = jnp.broadcast_to(const_pool, (B, cp))
            operands = jnp.concatenate(
                [latch_view, constb,
                 jnp.zeros((B, ip * up), jnp.float32)], axis=1)
            for u in range(up):
                a = operands[:, op_src[:, u, 0]]
                b = operands[:, op_src[:, u, 1]]
                c3 = operands[:, op_src[:, u, 2]]
                r = alu_step_masked(opcodes[:, u], a, b, c3, ops,
                                    lane_act & (u < n_steps))
                operands = operands.at[:, tmp_off + step_slots[u]].set(r)

            sig_new = jnp.where(fire[sig_owner],
                                operands[:, tmp_off + sig_tmp], sig)

            ext_live, ext_k = periodic(c, ext_time)           # (ep,)
            stream = inputs[:, ext_k, jnp.arange(ep)]         # (B, ep)
            ext_new = jnp.where(ext_live, stream, ext)

            src_vec = jnp.concatenate([sig, ext, wire], axis=1)
            wire_new = src_vec[:, wire_src]

            l_live, l_k = periodic(c, latch_time)
            wr = l_k % D                                      # (lp,)
            arriving = wire[:, latch_wire]                    # (B, lp)
            cur = jnp.take_along_axis(
                latch, wr[None, :, None], axis=2)[:, :, 0]
            written = jnp.where(l_live, arriving, cur)
            latch_new = latch.at[:, jnp.arange(lp), wr].set(written)

            o_live, o_k = periodic(c, out_time)
            vals = wire[:, out_wire]
            cols = jnp.arange(op_)
            prev = outbuf[:, o_k, cols]
            outbuf = outbuf.at[:, o_k, cols].set(
                jnp.where(o_live, vals, prev))

            return (ext_new, sig_new, wire_new, latch_new, outbuf), None

        carry = (jnp.zeros((B, ep), jnp.float32),
                 jnp.zeros((B, sp), jnp.float32),
                 jnp.zeros((B, wp), jnp.float32),
                 jnp.zeros((B, lp, D), jnp.float32),
                 jnp.zeros((B, K, op_), jnp.float32))
        carry, _ = jax.lax.scan(step, carry, jnp.arange(cycles))
        return carry[4]

    return jax.jit(jax.vmap(one))


def simulate_batch(progs: List[SimProgram], inputs_list,
                   *, backend: str = "jax",
                   metrics=None) -> List[SimResult]:
    """Simulate many programs in ONE vmapped ``lax.scan`` dispatch.

    All programs must share one :func:`sim_signature` (group by it first)
    and all input sets one (batch, iterations) shape; the union of the
    group's opcode tables drives one shared ALU dispatch.  Cycles beyond a
    program's real count execute harmlessly (no capture fires past
    iteration K-1), padded events never fire, and padded lanes retire
    zeros — so per-program outputs are bit-identical to :func:`simulate`
    on that program alone, regardless of which programs share the
    dispatch.

    Bucket provenance lands in ``metrics`` (default: the global registry):
    one ``sim.dispatch`` tick plus ``sim.bucket_programs`` /
    ``sim.bucket_cycles`` histogram observations per call, and the
    dispatch runs under a ``sim.dispatch`` span naming the bucket.
    """
    import jax.numpy as jnp

    from ..kernels.sim_step import op_table
    from ..obs import span
    from ..obs.metrics import global_registry

    if backend != "jax":
        raise ValueError("simulate_batch supports backend='jax' only "
                         "(the pallas tile-step kernel is per-program)")
    if len(progs) != len(inputs_list):
        raise ValueError("inputs_list must match progs 1:1")
    arrs = [_coerce_inputs(p, x) for p, x in zip(progs, inputs_list)]
    B, K, _ = arrs[0].shape
    for a in arrs:
        if a.shape[:2] != (B, K):
            raise ValueError("all input sets must share one (B, K) shape; "
                             f"got {a.shape[:2]} vs {(B, K)}")
    sigs = {sim_signature(p, K, B) for p in progs}
    if len(sigs) != 1:
        raise ValueError(f"programs span {len(sigs)} sim signatures; "
                         "group by sim_signature() first")
    sig = next(iter(sigs))

    reg = metrics if metrics is not None else global_registry()
    reg.inc("sim.dispatch")
    reg.observe("sim.bucket_programs", len(progs))
    reg.observe("sim.bucket_cycles", sig[8])

    ops = op_table(sorted(set().union(*(p.ops for p in progs)) - {"nop"}))
    code_of = {name: k for k, name in enumerate(ops)}
    padded = [_pad_program(p, sig, code_of) for p in progs]
    stacked = [jnp.asarray(np.stack([d[k] for d in padded]))
               for k in _BATCH_FIELDS]
    inputs = np.zeros((len(progs), B, K, sig[2]), np.float32)
    for i, (p, a) in enumerate(zip(progs, arrs)):
        inputs[i, :, :, :p.n_ext] = a

    with span("sim.dispatch", bucket="x".join(str(d) for d in sig),
              programs=len(progs)):
        run = _build_batch_stepper(sig, ops)
        outbuf = np.asarray(run(*stacked, jnp.asarray(inputs)))

    results = []
    for i, p in enumerate(progs):
        cycles = p.total_cycles(K)
        n_fires = K * p.n_inst
        results.append(SimResult(
            outputs=outbuf[i][:, :, p.out_cols], ii=p.ii,
            min_ii=p.schedule.min_ii, latency=p.latency, cycles=cycles,
            iterations=K, n_fires=n_fires,
            active_frac=n_fires / max(1, cycles * p.n_inst),
            backend="jax-batch"))
    return results


def simulate(prog: SimProgram, inputs, *, backend: str = "jax",
             interpret: Optional[bool] = None) -> SimResult:
    """Run `prog` over `inputs` and return per-iteration outputs.

    inputs: dict name -> (K,) or (B, K) arrays, or an (B, K, n_ext) /
    (K, n_ext) array in ext-signal order.  K = loop iterations; new
    iterations are issued every II cycles (software pipelining), so the
    run itself verifies the modulo schedule is hazard-free.
    backend: ``"jax"`` (vmapped ``lax.switch`` dispatch) or ``"pallas"``
    (tile-step kernel from :mod:`repro.kernels.sim_step`).
    """
    import jax.numpy as jnp

    arr = _coerce_inputs(prog, inputs)
    B, K, _ = arr.shape
    key = (K, backend, interpret)
    if key not in prog._cache:
        prog._cache[key] = _build_stepper(prog, K, backend, interpret)
    run, cycles = prog._cache[key]
    outbuf = np.asarray(run(jnp.asarray(arr)))
    outputs = outbuf[:, :, prog.out_cols]
    n_fires = K * prog.n_inst
    return SimResult(
        outputs=outputs, ii=prog.ii, min_ii=prog.schedule.min_ii,
        latency=prog.latency, cycles=cycles, iterations=K,
        n_fires=n_fires,
        active_frac=n_fires / max(1, cycles * prog.n_inst),
        backend=backend)
