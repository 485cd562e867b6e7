"""Plain reference semantics the benchmark holds the program to.

Nothing here imports the program.  Straightforward checks:

* ``evaluate`` runs an application graph, given as the ``Graph.to_dict``
  blob of ``suites/*.json``, elementwise over NumPy arrays in float32 (the
  datapath word the configurations state).  ``precision="bfloat16"``
  rounds every value to bfloat16 after each operation: the lower-precision
  control that ``correct`` must reject.
* ``hpwl`` is the half-perimeter wirelength of a placement, summed exactly
  in integers (``"bfloat16"`` accumulates it in bfloat16: the control).
* ``placement_violations`` and ``route_violations`` check a placement and
  its routes against the mesh the configuration states: cells on tiles of
  their kind, no shared tile, every sink reached through adjacent channels,
  no channel over its track count.
* ``blind_routes`` routes a net on shortest paths with no regard for the
  other nets: a router that drops the track-count guarantee (the control
  for routing, which has no precision to lower).
"""

from __future__ import annotations

from typing import Dict, List, Mapping, Sequence, Tuple

import numpy as np

Coord = Tuple[int, int]

#: the intermediate representation's elementwise semantics
#: (``sel``: port 0 = predicate, 1 = false value, 2 = true value;
#: shifts scale by powers of two)
SEMANTICS = {
    "add": lambda a, b: a + b,
    "sub": lambda a, b: a - b,
    "neg": lambda a: -a,
    "abs": lambda a: np.abs(a),
    "mul": lambda a, b: a * b,
    "mac": lambda a, b, c: a * b + c,
    "div": lambda a, b: a / b,
    "recip": lambda a: 1.0 / a,
    "shl": lambda a, b: a * np.exp2(b),
    "shr": lambda a, b: a / np.exp2(b),
    "ashr": lambda a, b: a / np.exp2(b),
    "eq": lambda a, b: a == b,
    "neq": lambda a, b: a != b,
    "lt": lambda a, b: a < b,
    "lte": lambda a, b: a <= b,
    "gt": lambda a, b: a > b,
    "gte": lambda a, b: a >= b,
    "min": np.minimum,
    "max": np.maximum,
    "and": np.logical_and,
    "or": np.logical_or,
    "xor": np.logical_xor,
    "not": np.logical_not,
    "sign": np.sign,
    "sel": lambda c, f, t: np.where(c != 0, t, f),
    "floor": np.floor,
    "round": np.round,
}


def _rounder(precision: str):
    if precision == "float32":
        return lambda x: np.asarray(x, np.float32)
    if precision == "bfloat16":
        import ml_dtypes
        return lambda x: np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def _topo_order(nodes: Mapping[str, str], edges: Sequence) -> List[int]:
    indeg = {int(n): 0 for n in nodes}
    succs: Dict[int, List[int]] = {n: [] for n in indeg}
    for s, d, _ in edges:
        indeg[d] += 1
        succs[s].append(d)
    ready = sorted(n for n, k in indeg.items() if k == 0)
    order = []
    while ready:
        n = ready.pop()
        order.append(n)
        for d in succs[n]:
            indeg[d] -= 1
            if indeg[d] == 0:
                ready.append(d)
    if len(order) != len(indeg):
        raise ValueError("graph has a cycle")
    return order


def evaluate(graph: Mapping, feed: Mapping[str, np.ndarray],
             precision: str = "float32") -> List[np.ndarray]:
    """Values of the graph's outputs, in ``graph["outputs"]`` order.

    ``feed`` maps each input node's ``name`` attribute to a float32 array;
    an input missing from it reads zeros.
    """
    rnd = _rounder(precision)
    nodes = graph["nodes"]
    attrs = graph.get("attrs", {})
    ins: Dict[int, Dict[int, int]] = {}
    for s, d, p in graph["edges"]:
        ins.setdefault(d, {})[p] = s
    shape = np.shape(next(iter(feed.values()))) if feed else ()
    vals: Dict[int, np.ndarray] = {}
    for n in _topo_order(nodes, graph["edges"]):
        op = nodes[str(n)]
        a = attrs.get(str(n), {})
        if op == "input":
            x = feed.get(str(a.get("name")))
            vals[n] = rnd(np.zeros(shape, np.float32) if x is None else x)
        elif op == "const":
            vals[n] = rnd(np.full(shape, a["value"], np.float32))
        elif op == "output":
            vals[n] = vals[ins[n][0]]
        else:
            args = [vals[ins[n][p]] for p in sorted(ins.get(n, {}))]
            vals[n] = rnd(SEMANTICS[op](*args))
    return [vals[o] for o in graph["outputs"]]


# ---------------------------------------------------------------------------
# the mesh
# ---------------------------------------------------------------------------
def is_pe(t: Coord, rows: int, cols: int) -> bool:
    return 0 <= t[0] < cols and 0 <= t[1] < rows


def is_io(t: Coord, rows: int, cols: int) -> bool:
    x, y = t
    if y in (-1, rows):
        return 0 <= x < cols
    if x in (-1, cols):
        return 0 <= y < rows
    return False


def channel(a: Coord, b: Coord, rows: int, cols: int,
            channel_width: int, io_capacity: int) -> int:
    """Tracks of the directed channel a -> b; 0 where there is none.

    PE tiles connect to their four neighbours; an I/O site connects only
    to the PE tile next to it, through ``io_capacity`` tracks."""
    a_io, b_io = is_io(a, rows, cols), is_io(b, rows, cols)
    a_pe, b_pe = is_pe(a, rows, cols), is_pe(b, rows, cols)
    if not ((a_pe or a_io) and (b_pe or b_io)) or (a_io and b_io):
        return 0
    if abs(a[0] - b[0]) + abs(a[1] - b[1]) != 1:
        return 0
    return io_capacity if (a_io or b_io) else channel_width


def hpwl(nets: Sequence[Sequence[Coord]], precision: str = "exact") -> float:
    """Sum over nets of the pins' bounding-box half-perimeter."""
    per_net = []
    for pins in nets:
        xs = [p[0] for p in pins]
        ys = [p[1] for p in pins]
        per_net.append((max(xs) - min(xs)) + (max(ys) - min(ys)))
    if precision == "exact":
        return float(sum(per_net))
    rnd = _rounder(precision)
    total = np.float32(0.0)
    for v in per_net:
        total = rnd(total + np.float32(v))
    return float(total)


def placement_violations(cells: Mapping[str, str],
                         coords: Mapping[str, Coord],
                         rows: int, cols: int) -> int:
    """Cells unplaced, on a tile of the wrong kind, or sharing a tile.

    ``cells`` maps a cell name to its kind (``pe`` or an I/O kind)."""
    bad = 0
    seen: Dict[Coord, str] = {}
    for name, kind in cells.items():
        t = coords.get(name)
        if t is None:
            bad += 1
            continue
        t = (int(t[0]), int(t[1]))
        ok = is_pe(t, rows, cols) if kind == "pe" else is_io(t, rows, cols)
        bad += (not ok) + (t in seen)
        seen[t] = name
    return bad


def route_violations(nets: Sequence[Tuple[Coord, Sequence[Coord],
                                          Sequence[Tuple[Coord, Coord]]]],
                     rows: int, cols: int, channel_width: int,
                     io_capacity: int) -> Tuple[int, int]:
    """(violations, wirelength) of a routing.

    ``nets``: (driver tile, sink tiles, directed edges) per net.  A
    violation is an edge that is no channel, a sink the driver does not
    reach along the net's own edges, or one track of overuse on a channel.
    The wirelength counts each net's distinct channels.
    """
    bad = 0
    usage: Dict[Tuple[Coord, Coord], int] = {}
    wirelength = 0
    for driver, sinks, edges in nets:
        own = {(tuple(a), tuple(b)) for a, b in edges}
        wirelength += len(own)
        succ: Dict[Coord, List[Coord]] = {}
        for a, b in own:
            if channel(a, b, rows, cols, channel_width, io_capacity) == 0:
                bad += 1
            succ.setdefault(a, []).append(b)
            usage[(a, b)] = usage.get((a, b), 0) + 1
        reached = {tuple(driver)}
        todo = [tuple(driver)]
        while todo:
            for b in succ.get(todo.pop(), ()):
                if b not in reached:
                    reached.add(b)
                    todo.append(b)
        bad += sum(tuple(s) not in reached for s in sinks)
    for (a, b), used in usage.items():
        cap = channel(a, b, rows, cols, channel_width, io_capacity)
        if cap:
            bad += max(0, used - cap)
    return bad, wirelength


def _inward(t: Coord, rows: int, cols: int) -> Coord:
    return (min(max(t[0], 0), cols - 1), min(max(t[1], 0), rows - 1))


def blind_routes(driver: Coord, sinks: Sequence[Coord], rows: int,
                 cols: int) -> List[Tuple[Coord, Coord]]:
    """Each sink reached from the driver along x, then y, through the PE
    tile next to an I/O site; the net's channels, each once."""
    edges = set()
    d = _inward(driver, rows, cols)
    if d != tuple(driver):
        edges.add((tuple(driver), d))
    for sink in sinks:
        s = _inward(sink, rows, cols)
        x, y = d
        while x != s[0]:
            nx = x + (1 if s[0] > x else -1)
            edges.add(((x, y), (nx, y)))
            x = nx
        while y != s[1]:
            ny = y + (1 if s[1] > y else -1)
            edges.add(((x, y), (x, ny)))
            y = ny
        if s != tuple(sink):
            edges.add((s, tuple(sink)))
    return sorted(edges)


def random_hpwl(cell_kinds: Sequence[str], nets: Sequence[Sequence[int]],
                rows: int, cols: int, rng: np.random.Generator,
                draws: int = 8) -> float:
    """Mean HPWL of ``draws`` uniformly random legal placements: what an
    annealer that moved nothing would report, on average.

    ``nets`` hold indices into ``cell_kinds``."""
    pe_tiles = np.array([(x, y) for y in range(rows) for x in range(cols)])
    io_sites = np.array([(x, -1) for x in range(cols)]
                        + [(x, rows) for x in range(cols)]
                        + [(-1, y) for y in range(rows)]
                        + [(cols, y) for y in range(rows)])
    kinds = np.array([k == "pe" for k in cell_kinds])
    n_pe, n_io = int(kinds.sum()), int((~kinds).sum())
    total = 0.0
    for _ in range(draws):
        xy = np.zeros((len(cell_kinds), 2), np.int64)
        xy[kinds] = pe_tiles[rng.permutation(len(pe_tiles))[:n_pe]]
        xy[~kinds] = io_sites[rng.permutation(len(io_sites))[:n_io]]
        for pins in nets:
            p = xy[list(pins)]
            total += float(np.ptp(p[:, 0]) + np.ptp(p[:, 1]))
    return total / draws
