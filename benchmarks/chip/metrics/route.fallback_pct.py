"""Share of the pairs served from a chain other than the cheapest, whose
routing left a channel over its track count: 100 x the mean of the
program's ``pnr.route.fell_back`` (1 or 0 a served pair)."""


def read(w):
    m = w.hist_mean("pnr.route.fell_back")
    return None if m is None else 100.0 * m
