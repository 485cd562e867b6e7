"""Device time of the annealer's program (``jit_chain``) per chain step it
ran (``pnr.anneal.steps_run``: problems x chains x bucketed trip count)."""


def read(w):
    t = w.module_s("jit_chain")
    _, run = w.hist.get("pnr.anneal.steps_run", (0, 0.0))
    return 1e9 * t / run if t and run else None
