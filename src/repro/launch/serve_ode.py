"""ODE serving launcher: drive a SolveService with a synthetic request stream.

    PYTHONPATH=src python -m repro.launch.serve_ode \
        --requests 256 --max-batch 16 --features 2 4 --eval-points 0 8 \
        --method dopri5 --prewarm --max-inflight 4

Simulates the serving workload the batcher exists for -- a stream of
single-instance solve requests with mixed feature sizes, eval grids, spans
and tolerances -- and reports the service's stats surface (throughput, pad
waste, queue/pack/device time split, in-flight window, bucket/cache
behaviour).  Batches launch asynchronously and round-robin across every
visible device; ``--sync`` (or ``--max-inflight 0``) restores the blocking
pre-async service for comparison.  This is the operational smoke tool; the
apples-to-apples comparison against per-request dispatch lives in
``benchmarks/serving_bench.py``.
"""

from __future__ import annotations

import argparse
import sys
import time

import jax.numpy as jnp
import numpy as np

from repro.core import SolveRequest, SolveService, Status
from repro.launch.compile_cache import enable_compile_cache


def _decay(t, y, args):
    return -y * args


def build_stream(opts, rng) -> list[SolveRequest]:
    reqs = []
    for _ in range(opts.requests):
        feat = int(rng.choice(opts.features))
        n_eval = int(rng.choice(opts.eval_points))
        reqs.append(SolveRequest(
            f=_decay,
            y0=jnp.asarray(rng.uniform(0.5, 1.5, (feat,)), jnp.float32),
            t0=0.0,
            t1=float(rng.uniform(0.5, 1.5)),
            t_eval=np.linspace(0.0, 0.5, n_eval) if n_eval else None,
            args=jnp.asarray(np.full((feat,), rng.uniform(0.5, 2.0), np.float32)),
            rtol=float(rng.choice([1e-3, 1e-4, 1e-5])),
            method=opts.method,
        ))
    return reqs


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--requests", type=int, default=256)
    parser.add_argument("--max-batch", type=int, default=16)
    parser.add_argument("--deadline-ms", type=float, default=2.0)
    parser.add_argument("--features", type=int, nargs="+", default=[2, 4],
                        help="feature sizes to mix in the stream")
    parser.add_argument("--eval-points", type=int, nargs="+", default=[0, 8],
                        help="eval-grid lengths to mix (0 = final state only)")
    parser.add_argument("--method", default="dopri5")
    parser.add_argument("--prewarm", action="store_true",
                        help="AOT-compile every batch class before the stream")
    parser.add_argument("--max-inflight", type=int, default=4,
                        help="launched-but-unharvested batch window "
                             "(0 = blocking service)")
    parser.add_argument("--sync", action="store_true",
                        help="shorthand for --max-inflight 0")
    parser.add_argument("--seed", type=int, default=0)
    opts = parser.parse_args()

    enable_compile_cache()
    svc = SolveService(max_batch=opts.max_batch,
                       max_delay=opts.deadline_ms / 1e3,
                       max_inflight=0 if opts.sync else opts.max_inflight)
    print(f"serving on {len(svc.devices)} device(s), "
          f"max_inflight={svc.max_inflight}")
    rng = np.random.default_rng(opts.seed)
    stream = build_stream(opts, rng)

    if opts.prewarm:
        t0 = time.perf_counter()
        n = sum(svc.prewarm(r) for r in stream[: 4 * len(opts.features)])
        print(f"prewarm: {n} programs in {time.perf_counter() - t0:.2f}s")

    t0 = time.perf_counter()
    futures = [svc.submit(r) for r in stream]
    svc.flush()
    svc.drain()
    n_ok = n_errors = 0
    for fut in futures:
        try:
            n_ok += bool((fut.result().status == Status.SUCCESS).all())
        except Exception as e:  # noqa: BLE001 -- counted and reported below
            n_errors += 1
            print(f"request failed: {type(e).__name__}: {e}", file=sys.stderr)
    wall = time.perf_counter() - t0

    print(f"served {len(futures)} requests in {wall:.3f}s "
          f"({len(futures) / wall:.1f} req/s end-to-end), {n_ok} fully "
          f"successful, {n_errors} raised")
    for name, value in svc.stats().items():
        print(f"  {name:>24}: {value:.4g}" if isinstance(value, float)
              else f"  {name:>24}: {value}")
    if n_ok != len(futures):
        sys.exit(1)


if __name__ == "__main__":
    main()
