"""(PE, app) pairs explored and checked per second, over the whole window."""


def read(w):
    return w.pairs / w.seconds if w.seconds > 0 and w.pairs else None
