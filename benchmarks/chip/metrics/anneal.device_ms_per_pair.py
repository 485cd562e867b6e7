"""Device time of the batched annealer's program (``jit_chain``) per pair
placed, from the trace's program line."""


def read(w):
    t = w.module_s("jit_chain")
    return 1e3 * t / w.pairs if t and w.pairs else None
