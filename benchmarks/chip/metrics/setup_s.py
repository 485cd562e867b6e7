"""Process start to the first measured request, compilation included."""


def read(w):
    return w.setup_s
