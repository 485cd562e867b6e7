"""Host time of the NDJSON front end per request: ``serve.decode``
(``json.loads`` and ``parse_request_line``) plus ``serve.encode`` (the
response's ``to_dict``, ``json.dumps`` and write), over the requests of
the slice."""


def read(w):
    decode, encode = w.span_s("serve.decode"), w.span_s("serve.encode")
    t = (decode or 0.0) + (encode or 0.0)
    return 1e3 * t / len(w.latencies) if t and w.latencies else None
