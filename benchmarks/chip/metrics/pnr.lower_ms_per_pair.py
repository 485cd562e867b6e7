"""Host wall time in ``pnr.lower`` spans (netlist extraction, array fit,
lowering and the anneal budget check of every pair, before any dispatch)
per pair placed."""


def read(w):
    t = w.span_s("pnr.lower")
    return 1e3 * t / w.pairs if t and w.pairs else None
