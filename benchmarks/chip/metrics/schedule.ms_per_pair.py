"""Host wall time in ``schedule`` spans (modulo scheduling and lowering)
per simulated pair."""


def read(w):
    t = w.span_s("schedule")
    return 1e3 * t / w.sim_pairs if t and w.sim_pairs else None
