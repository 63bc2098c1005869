#!/usr/bin/env python3
"""Run the standalone batch-verification service — no beacon node.

Front-end for ``lighthouse_tpu.serve``: builds the full verifier ladder
through the shared construction path (``serve/stack.py`` — the same
wiring ``bn --serve-port`` embeds), compiles the device programs for
every batch size it serves, starts the tick pump and the
Beacon-API-shaped HTTP edge, and serves until interrupted.  Tenants
submit with::

    curl -X POST http://127.0.0.1:5053/eth/v1/verify/batch \\
         -d '{"tenant": "vc-7", "deadline_ms": 250, "sets": [...]}'

and poll ``GET /eth/v1/verify/batch/<request_id>`` for verdicts.

Usage:
    python tools/serve.py --port 5053
    python tools/serve.py --port 5053 --bls-backend jax
    python tools/serve.py --port 0 --flush-margin 0.005
    python tools/serve.py --port 5053 --rate 500 --burst 1000
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def build_parser() -> argparse.ArgumentParser:
    from lighthouse_tpu.serve.stack import BLS_BACKENDS

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--port", type=int, default=5053,
                    help="HTTP port (0 = ephemeral)")
    ap.add_argument("--bls-backend", choices=BLS_BACKENDS, default="python",
                    help="BLS engine that verifies: jax runs the batch "
                         "kernel on JAX's default device (the TPU where "
                         "one is attached)")
    ap.add_argument("--flush-margin", type=float, default=0.02,
                    help="seconds of headroom before the oldest pending "
                         "deadline at which a partial batch flushes — "
                         "the latency/throughput knob")
    ap.add_argument("--default-deadline-ms", type=float, default=250.0,
                    help="deadline for submissions that carry none")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="default per-tenant sustained sets/s")
    ap.add_argument("--burst", type=float, default=400.0,
                    help="default per-tenant token-bucket burst (sets)")
    ap.add_argument("--max-queue", type=int, default=1024,
                    help="default per-tenant pooled-set bound")
    ap.add_argument("--tick-interval", type=float, default=0.002,
                    help="pump period of the dispatch loop (seconds)")
    ap.add_argument("--run-secs", type=float, default=None,
                    help="exit after N seconds (tests)")
    return ap


def start(argv=None):
    """Boot the service in this process exactly as ``main`` does and
    return ``(service, server, boot)``; the caller stops both.  ``boot``
    names the device that verifies and holds each compiled batch size's
    warm-up seconds (``warm_seconds``)."""
    args = build_parser().parse_args(argv)

    from lighthouse_tpu.serve import (
        ServeApiServer, TenantPolicy, VerifyService,
    )
    from lighthouse_tpu.serve.stack import select_bls_backend

    boot = select_bls_backend(args.bls_backend)
    if args.bls_backend == "jax":
        from lighthouse_tpu.crypto.bls.jax_backend.backend import (
            enable_compile_cache,
        )

        boot["compile_cache"] = enable_compile_cache()
    print(f"bls backend: {json.dumps(boot)}", flush=True)
    service = VerifyService.standalone(
        default_policy=TenantPolicy(
            rate=args.rate, burst=args.burst, max_queue=args.max_queue,
        ),
        flush_margin=args.flush_margin,
        default_deadline_s=args.default_deadline_ms / 1000.0,
    )
    boot["warm_seconds"] = service.stack.warm(service.compiled_sizes)
    if boot["warm_seconds"]:
        print(f"compiled batch sizes (s): "
              f"{json.dumps(boot['warm_seconds'])}", flush=True)
    service.start(interval=args.tick_interval)
    server = ServeApiServer(service, port=args.port).start()
    print(f"verification service up: "
          f"http://127.0.0.1:{server.port}/eth/v1/verify/batch "
          f"(flush_margin={args.flush_margin}s)", flush=True)
    return service, server, boot


def main(argv=None) -> int:
    run_secs = build_parser().parse_args(argv).run_secs
    service, server, _boot = start(argv)
    try:
        if run_secs is not None:
            time.sleep(run_secs)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        service.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
