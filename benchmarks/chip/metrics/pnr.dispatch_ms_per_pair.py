"""Host wall time in ``pnr.dispatch`` spans (the batched anneal, from
padding the problems to reading the placements back) per pair placed."""


def read(w):
    t = w.span_s("pnr.dispatch")
    return 1e3 * t / w.pairs if t and w.pairs else None
