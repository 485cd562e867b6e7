"""Nets the router ripped up and rerouted per pair served: the mean of the
program's ``pnr.route.ripups`` (one observation a served pair, summed over
the placements it tried)."""


def read(w):
    return w.hist_mean("pnr.route.ripups")
