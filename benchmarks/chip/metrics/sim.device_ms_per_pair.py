"""Device time of the batched sim stepper's program (``jit_one``) per
simulated pair, from the trace's program line."""


def read(w):
    t = w.module_s("jit_one")
    return 1e3 * t / w.sim_pairs if t and w.sim_pairs else None
