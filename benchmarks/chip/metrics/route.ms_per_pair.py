"""Host wall time in ``pnr.pair`` spans (routing and fabric costing of one
placed pair) per pair."""


def read(w):
    t = w.span_s("pnr.pair")
    return 1e3 * t / w.pairs if t and w.pairs else None
