"""CLI for explorer-as-a-service.

::

    python -m repro.serve --smoke                  # CI self-check
    python -m repro.serve --port 7341 --store memo/
    python -m repro.serve --stdio < requests.jsonl

The smoke is the serving layer's load-bearing CI assertion: it serves
the same requests solo, batched with strangers, from cache, and over
the wire protocol, and requires the records to be **byte-identical**
in all four paths — while the batched path performs strictly fewer JAX
dispatches than the solo runs summed.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from typing import Dict, List

from ..graphir.graph import Graph
from .frontend import ExploreService
from .protocol import encode_request


def _smoke_requests():
    """Four overlapping client requests over three small apps (the
    explore smoke's Fig. 3 convolution plus two MAC/add kernels)."""
    from ..explore.__main__ import _smoke_case
    from ..graphir import trace_scalar

    apps, cfg = _smoke_case()
    conv = apps["conv"]

    def fir4(i0, i1, i2, i3, w0, w1, w2, w3):
        return ((i0 * w0) + (i1 * w1)) + ((i2 * w2) + (i3 * w3))

    def blur4(a, b, c, d, w):
        return (((a + b) + (c + d)) * w)

    fir = trace_scalar(fir4, ["i0", "i1", "i2", "i3",
                              "w0", "w1", "w2", "w3"])
    blur = trace_scalar(blur4, ["a", "b", "c", "d", "w"])
    cfg = cfg.replace(on_error="isolate")   # what the service runs under
    clients = [
        ("r1", {"conv": conv}),
        ("r2", {"conv": conv, "fir": fir}),
        ("r3", {"fir": fir, "blur": blur}),
        ("r4", {"conv": conv, "blur": blur}),
    ]
    return clients, cfg


def _solo_lines(apps: Dict[str, Graph], cfg) -> tuple:
    """Ground truth: one fresh solo Explorer run -> (record line bytes,
    dispatch count)."""
    from ..explore import Explorer
    ex = Explorer(apps, cfg)
    res = ex.run()
    assert not res.failures, f"solo run degraded: {res.failures}"
    lines = [json.dumps(r.to_dict()) for r in res.records()]
    return lines, ex.stats["pnr_dispatch"] + ex.stats["sim_dispatch"]


async def _smoke_async() -> int:
    clients, cfg = _smoke_requests()

    solo: Dict[str, List[str]] = {}
    solo_dispatches = 0
    for rid, apps in clients:
        solo[rid], n = _solo_lines(apps, cfg)
        solo_dispatches += n
        assert solo[rid], f"solo {rid} produced no records"

    async with ExploreService(max_batch_apps=4, max_wait_ms=100,
                              queue_limit=16) as svc:
        # -- N concurrent clients, batched across requests ---------------
        resps = await asyncio.gather(*[
            svc.explore(rid, apps, cfg) for rid, apps in clients])
        for (rid, _apps), resp in zip(clients, resps):
            assert resp.ok, f"{rid} failed: {resp.error}"
            assert not resp.cached, f"{rid} unexpectedly cached"
            assert resp.record_lines() == solo[rid], \
                f"bit-identity violated for {rid}: batched != solo"
            assert not resp.failures, f"{rid} degraded: {resp.failures}"
        stats = svc.metrics.view()
        served_dispatches = (stats["pnr_dispatch"] + stats["sim_dispatch"])
        assert served_dispatches < solo_dispatches, (
            f"no cross-request amortization: served {served_dispatches} "
            f"dispatches vs {solo_dispatches} solo")
        n_apps = len({n for _rid, apps in clients for n in apps})
        assert stats["mine"] == n_apps, (
            f"expected {n_apps} unique mines across all requests, "
            f"got {stats['mine']}")

        # -- cache hit: same content, new rid, zero new dispatches --------
        rid2, apps2 = clients[1]
        resp = await svc.explore("r2-again", apps2, cfg)
        assert resp.ok and resp.cached, "repeat request missed the cache"
        assert resp.record_lines() == solo[rid2], \
            "bit-identity violated: cached != solo"
        after = stats["pnr_dispatch"] + stats["sim_dispatch"]
        assert after == served_dispatches, "cache hit dispatched JAX work"
        assert resp.elapsed_ms < 1000, \
            f"cache hit took {resp.elapsed_ms:.1f} ms"

        # -- wire protocol round trip -------------------------------------
        server = await svc.serve_tcp("127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write((json.dumps(
            encode_request("wire-1", apps2, cfg)) + "\n").encode())
        writer.write(b'{"this is": "not a request"}\n')
        await writer.drain()
        writer.write_eof()
        line1 = json.loads(await reader.readline())
        line2 = json.loads(await reader.readline())
        by_ok = {d["ok"]: d for d in (line1, line2)}
        assert set(by_ok) == {True, False}, f"unexpected replies: {by_ok}"
        assert by_ok[True]["id"] == "wire-1" and by_ok[True]["cached"]
        assert [json.dumps(r) for r in by_ok[True]["records"]] \
            == solo[rid2], "bit-identity violated: wire != solo"
        writer.close()
        await writer.wait_closed()
        server.close()
        await server.wait_closed()

        cache_ms = svc.metrics.histogram("serve.cache_hit_ms")
        print(f"# serve smoke OK: {len(clients)} clients bit-identical "
              f"(solo == batched == cached == wire), "
              f"{served_dispatches} batched dispatches vs "
              f"{solo_dispatches} solo, {stats['mine']}/"
              f"{sum(len(a) for _r, a in clients)} apps mined, "
              f"cache hits {cache_ms.count} "
              f"(mean {cache_ms.mean:.2f} ms)")
    return 0


async def _serve_async(args) -> int:
    svc = ExploreService(store=args.store,
                         max_batch_apps=args.max_batch_apps,
                         max_wait_ms=args.max_wait_ms,
                         queue_limit=args.queue_limit)
    async with svc:
        if args.stdio:
            await svc.serve_stdio()
            return 0
        server = await svc.serve_tcp(args.host, args.port)
        port = server.sockets[0].getsockname()[1]
        print(f"# repro.serve listening on {args.host}:{port} "
              f"(max_batch_apps={args.max_batch_apps}, "
              f"max_wait_ms={args.max_wait_ms}, "
              f"queue_limit={args.queue_limit}, "
              f"store={args.store or 'in-memory'})", flush=True)
        async with server:
            await server.serve_forever()
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.serve",
        description="Exploration serving: NDJSON front-end with "
                    "cross-request continuous batching")
    ap.add_argument("--smoke", action="store_true",
                    help="fast end-to-end self check (CI): bit-identity "
                         "solo == batched == cached == wire")
    ap.add_argument("--stdio", action="store_true",
                    help="serve NDJSON on stdin/stdout until EOF")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=7341,
                    help="TCP port (0 picks a free one)")
    ap.add_argument("--store", default=None, metavar="DIR",
                    help="persistent DiskStore directory (default: "
                         "in-memory, cache dies with the process)")
    ap.add_argument("--max-batch-apps", type=int, default=8,
                    help="flush a batch once this many distinct apps "
                         "are pending (default 8)")
    ap.add_argument("--max-wait-ms", type=float, default=50.0,
                    help="flush the oldest ticket after this long even "
                         "if the batch is not full (default 50)")
    ap.add_argument("--queue-limit", type=int, default=32,
                    help="bounded admission queue: tickets beyond this "
                         "wait at the door (default 32)")
    args = ap.parse_args(argv)
    try:
        if args.smoke:
            return asyncio.run(_smoke_async())
        return asyncio.run(_serve_async(args))
    except KeyboardInterrupt:
        return 130
    except AssertionError as e:
        print(f"error: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    from ..compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
