"""Explorer-as-a-service: async front-end + cross-request batching.

Many concurrent clients submit app-graph exploration requests (in-
process coroutines, or newline-delimited JSON over a socket / stdio);
the service deduplicates them against the Explorer's content-keyed memo
store — repeat requests answer from cache in milliseconds without
touching JAX — and continuously batches the rest: pending (variant,
app) pairs from *different* requests are grouped by pow2 bucket
signature and flushed through the batch-first pnr/schedule/simulate
stages together: at once when no batch is executing, else as one batch
when the running one returns (an opt-in ``max_wait_ms`` linger holds a
batch that is not full for more same-config requests).

Bit-identity guarantee: a request's records are byte-identical whether
it is served solo, batched with strangers, or answered from cache —
the pipeline's content-key memoization and content-nonce seeding make
results independent of dispatch grouping (``python -m repro.serve
--smoke`` asserts this end to end).

Entry points::

    from repro.serve import ExploreService
    async with ExploreService(store="memo/") as svc:
        resp = await svc.explore("r1", apps, config)

    python -m repro.serve --port 7341 --store memo/     # NDJSON server
    python -m repro.serve --smoke                       # CI smoke

The token-decode LM demo that used to live here moved to
:mod:`repro.serve.lm_engine`; the package-level ``ServeEngine`` /
``Request`` names (and ``repro.serve.engine``) still resolve but warn
``DeprecationWarning``.
"""

from .batcher import ContinuousBatcher, QueueFull
from .frontend import ExploreService
from .protocol import (PROTOCOL_SCHEMA, ProtocolError, ServeRequest,
                       ServeResponse, encode_request, parse_request_line,
                       request_key)

__all__ = [
    "ContinuousBatcher", "QueueFull",
    "ExploreService",
    "PROTOCOL_SCHEMA", "ProtocolError", "ServeRequest", "ServeResponse",
    "encode_request", "parse_request_line", "request_key",
    # deprecated LM-demo names, resolved lazily with a warning:
    "Request", "ServeEngine",
]


def __getattr__(name):
    if name in ("Request", "ServeEngine"):
        import warnings
        warnings.warn(
            f"repro.serve.{name} is deprecated: the LM demo moved to "
            f"repro.serve.lm_engine (repro.serve now names the "
            f"exploration serving subsystem)",
            DeprecationWarning, stacklevel=2)
        from . import lm_engine
        return getattr(lm_engine, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
