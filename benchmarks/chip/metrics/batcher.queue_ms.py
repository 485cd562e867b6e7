"""Mean time a window request waited in the batcher's admission queue
(the service's ``serve.time_in_queue_ms`` histogram)."""


def read(w):
    return w.hist_mean("serve.time_in_queue_ms")
