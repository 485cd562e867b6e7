"""Share of the annealer's loop steps that can move a cell: the program's
``pnr.anneal.steps_real`` over ``pnr.anneal.steps_run`` (each problem's
own step count against the bucketed trip count the device runs)."""


def read(w):
    _, real = w.hist.get("pnr.anneal.steps_real", (0, 0.0))
    _, run = w.hist.get("pnr.anneal.steps_run", (0, 0.0))
    return 100.0 * real / run if real and run else None
