"""Host wall time in ``pnr.pack`` spans (padding the problems to their
bucket, chain init and key derivation, before the annealer's program is
called) per pair placed."""


def read(w):
    t = w.span_s("pnr.pack")
    return 1e3 * t / w.pairs if t and w.pairs else None
