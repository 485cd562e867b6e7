"""Fabric place-and-route: map DSE variants onto an N x M CGRA array.

The paper's loop (mine -> merge -> map -> cost) stops at the single-PE
level; this subsystem models the array.  Given a
:class:`~repro.core.mapper.Mapping` and a :class:`FabricSpec`, it extracts
the inter-tile netlist, places cells with JAX-batched simulated annealing,
routes every net over the mesh, and prices the result at array level —
exposing the tradeoff the per-tile model cannot see: fewer, bigger PEs mean
fewer tiles and shorter routes.

    from repro.fabric import FabricSpec, place_and_route
    pnr = place_and_route(dp, mapping, app, FabricSpec(rows=8, cols=8))
    print(pnr.cost.row())
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ..core.mapper import Mapping
from ..core.pe import Datapath
from ..graphir.graph import Graph
from .arch import FabricSpec, manhattan
from .cost import FabricCost, attach_fabric, evaluate_fabric
from .cluster import Clustering, partition
from .netlist import Cell, Net, Netlist, extract_netlist, synthetic_netlist
from .options import FabricOptions
from .place import HierPlacement, Placement, PlacementProblem, \
    anneal_chains, anneal_jax_batch, anneal_python, batch_signature, \
    chain_placements, lower, net_incidence, place, place_hierarchical
from .route import RouteResult, RoutedNet, route_first_legal, route_nets

__all__ = [
    "FabricSpec", "FabricOptions", "manhattan", "Cell", "Net", "Netlist",
    "extract_netlist", "synthetic_netlist", "Placement", "PlacementProblem",
    "HierPlacement", "Clustering", "partition",
    "lower", "net_incidence", "place", "place_hierarchical",
    "anneal_jax_batch", "anneal_python", "batch_signature",
    "RouteResult", "RoutedNet", "route_first_legal", "route_nets",
    "FabricCost", "evaluate_fabric", "attach_fabric", "PnRResult",
    "place_and_route",
]


@dataclass
class PnRResult:
    spec: FabricSpec
    netlist: Netlist
    placement: Placement
    routes: RouteResult
    cost: FabricCost


def place_and_route(dp: Datapath, mapping: Mapping, app: Graph,
                    spec: Optional[FabricSpec] = None, *,
                    backend: str = "jax", chains: int = 16,
                    sweeps: int = 32, seed: int = 0, nonce: int = 0,
                    auto_size: bool = True, pe_name: str = "PE",
                    score_mode: str = "delta",
                    max_states: Optional[int] = None,
                    pnr_mode: str = "flat", metrics=None) -> PnRResult:
    """Full flow: netlist -> place -> route -> array-level cost.

    The flat anneal's chains (:func:`anneal_chains`, keyed by ``nonce``)
    are routed cheapest first and the first legal routing is served
    (:func:`route_first_legal`), the rule the explore pipeline's grouped
    placer applies: at equal options and nonce this returns, bit for bit,
    what :func:`repro.explore.pipeline.pnr_grouped` gives the pair.
    Raises :class:`~repro.errors.Unroutable` when no chain routes.
    ``metrics`` receives the anneal's and the router's observations.

    ``pnr_mode="hierarchical"`` runs :func:`place_hierarchical` (cluster ->
    detail -> deblock) instead of the flat single-level anneal — worth it
    for mega-fabrics, pure overhead for the small arrays single mapped
    apps produce.  Its one placement is the only candidate routed.
    """
    spec = spec or FabricSpec()
    netlist = extract_netlist(mapping, app, spec)
    if auto_size:
        spec = spec.fit(len(netlist.pe_cells), len(netlist.io_cells))
    if pnr_mode == "hierarchical":
        if backend != "jax":
            raise ValueError("pnr_mode='hierarchical' requires the jax "
                             "backend")
        candidates = [place_hierarchical(netlist, spec, chains=chains,
                                         sweeps=sweeps, seed=seed,
                                         score_mode=score_mode,
                                         max_states=max_states,
                                         metrics=metrics)]
    elif pnr_mode == "flat":
        p = lower(netlist, spec)
        slots, costs = anneal_chains(p, backend=backend, chains=chains,
                                     sweeps=sweeps, seed=seed,
                                     score_mode=score_mode, nonce=nonce,
                                     max_states=max_states, metrics=metrics)
        candidates = chain_placements(p, slots, costs, backend=backend,
                                      sweeps=sweeps)
    else:
        raise ValueError(f"unknown pnr_mode {pnr_mode!r}")
    placement, routes = route_first_legal(
        netlist, candidates, spec, label=f"{pe_name}/{mapping.app_name}",
        metrics=metrics)
    fc = evaluate_fabric(dp, mapping, netlist, placement, routes, spec,
                         pe_name=pe_name)
    return PnRResult(spec, netlist, placement, routes, fc)
