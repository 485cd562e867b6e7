"""Host wall time in ``sim.dispatch`` spans (the batched stepper, from
padding the programs to reading the outputs back) per simulated pair."""


def read(w):
    t = w.span_s("sim.dispatch")
    return 1e3 * t / w.sim_pairs if t and w.sim_pairs else None
