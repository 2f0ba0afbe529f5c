"""Serving CLI: the continuous-batching LLM engine, or degraded block reads.

PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --requests 8
PYTHONPATH=src python -m repro.launch.serve --blocks --requests 400

``--blocks`` serves a Zipfian multi-client read load from a demo stripe
store with one failed node: live blocks stream straight from disk, lost
blocks reconstruct inline through the planner (local group first), with
request coalescing and the hot-block cache on — then prints the
degraded-read report (p50/p99, coalescing ratio, cache hit rate).
"""
from __future__ import annotations

import argparse
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.launch.cache import use_compile_cache


def serve_blocks(args) -> None:
    from repro.ftx import StoreConfig, StripeStore, read_report
    from repro.serve.blocks import BlockServer, zipf_requests

    cfg = StoreConfig(scheme="cp-azure", k=6, r=2, p=2,
                      block_size=args.block_size, pipeline_window=0)
    with tempfile.TemporaryDirectory() as tmp:
        store = StripeStore(Path(tmp) / "store", cfg)
        payload = np.random.default_rng(0).integers(
            0, 256, args.stripes * cfg.k * cfg.block_size, dtype=np.uint8)
        store.put("blob", payload.tobytes())
        store.seal()
        requests = zipf_requests(store, args.requests, seed=1)
        store.fail_node(store.stripes[0].node_of_block[0])
        server = BlockServer(store, clients=args.clients)
        t0 = time.time()
        server.run(requests)
        dt = time.time() - t0
        rep = read_report(store)
        print(f"{len(requests)} reads ({args.clients} clients) in {dt:.2f}s: "
              f"{rep.direct_reads} direct, {rep.degraded_reads} degraded")
        print(f"decode launches {rep.decode_launches} "
              f"(coalescing ratio {rep.coalescing_ratio:.1f}x, "
              f"coalesced {rep.coalesced_reads}, "
              f"cache hit rate {rep.cache_hit_rate:.2f}, "
              f"local fraction {rep.local_decode_fraction:.2f})")
        print(f"latency p50 {rep.p50_ms:.2f}ms p99 {rep.p99_ms:.2f}ms "
              f"({rep.served_bytes} bytes served)")


def serve_model(args) -> None:
    import jax

    from repro.configs import get_model
    from repro.serve.engine import ServeEngine

    api = get_model(args.arch, smoke=True)
    engine = ServeEngine(api, max_batch=args.max_batch, max_len=args.max_len)
    engine.load(api.init_params(jax.random.key(0)))
    rng = np.random.default_rng(0)
    reqs = [engine.submit(rng.integers(0, api.cfg.vocab_size,
                                       int(rng.integers(4, 32))),
                          max_new=args.max_new)
            for _ in range(args.requests)]
    t0 = time.time()
    engine.run()
    toks = sum(len(r.out_tokens) for r in reqs)
    stats = engine.latency_stats()
    print(f"{len(reqs)} requests -> {toks} tokens in {time.time() - t0:.1f}s "
          f"(p50 {stats['p50_ms']:.0f}ms p99 {stats['p99_ms']:.0f}ms)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--blocks", action="store_true",
                    help="serve degraded block reads from a demo stripe "
                         "store instead of the LLM engine")
    ap.add_argument("--stripes", type=int, default=32,
                    help="demo store size for --blocks")
    ap.add_argument("--block-size", type=int, default=4096)
    ap.add_argument("--clients", type=int, default=8,
                    help="front-end reader threads for --blocks")
    args = ap.parse_args()
    use_compile_cache()
    if args.blocks:
        serve_blocks(args)
    else:
        serve_model(args)


if __name__ == "__main__":
    main()
