#!/usr/bin/env python3
"""Smoke test of the stripe store's main path on a TPU.

    python3 chip_smoke.py [--seed N]               # one chip
    python3 chip_smoke.py --four-chips [--seed N]  # one 2x2 host

One chip runs five phases through the store's own entry points, at the
paper's P5 geometry, cp-azure (24,2,2), with the default 1 MiB blocks:

  encode      ``put`` + ``seal`` of 32 stripes (768 MiB of data, 896 MiB
              with parity); several whole stripes against the numpy
              encoder ``LRCScheme.encode``
  repair1     one node lost (its block files deleted), ``repair_all``;
              every rebuilt block against its bytes before the loss
  repair2     the nodes of one local group's data block and local parity
              lost, which repairs through the cascaded group
  degraded    a few hundred Zipfian ``BlockServer`` reads with a node
              down, every lost data block among them, against healthy
              reads
  checkpoint  ``CheckpointManager.save_async`` of a 256 MiB pytree, a lost
              host, ``restore`` bit for bit

``--four-chips`` runs only the stripe-sharded repair: a (4, 1) mesh under
``with_rules``, a store on a 4-domain ``Topology``, ``repair_all`` of one
lost node, against the same repair on one device.

Each phase prints one JSON line naming the kernel formulation that ran
(``effective_backend``: a Pallas kernel, never the jnp oracle ``ref``),
its compile seconds and its wall seconds. The last line is
``{"ok": true, "device": {...}}``. A failed check, or a first JAX device
that is not a TPU, exits non-zero without that line. Data is made from
``--seed``; the stores live in a temporary directory inside the checkout,
removed at exit. Compiled programs persist in ``.jax_cache/`` unless
``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
PALLAS = ("gf", "crs", "mxu")
GEOMETRY = {"scheme": "cp-azure", "k": 24, "r": 2, "p": 2}  # the paper's P5
STRIPES = 32
READS = 300          # Zipfian requests of the degraded phase
STATE_MIB = 256      # checkpointed pytree


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and its
    persistent-cache hits, read from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kwargs):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def run_phase(name: str, clock: CompileClock, fn, *args) -> dict:
    """Run one phase, check that a Pallas kernel ran, print its line."""
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    out = fn(*args)
    line = {"phase": name, **out,
            "compile_seconds": clock.seconds - c0,
            "cache_hits": clock.cache_hits - h0,
            "wall_seconds": time.perf_counter() - t0}
    print(json.dumps(line), flush=True)
    for key in ("effective_backend", "encode_backend"):
        if key in out:
            check(out[key] in PALLAS, f"{name} ran {key}={out[key]!r}")
    return line


def fill(store, stripes: int):
    """Put and seal ``stripes`` full stripes of seeded random objects of two
    thirds of a stripe each, so puts straddle blocks and stripes; returns
    the payload and each object's ``(lo, hi)`` in it."""
    cfg = store.cfg
    extent = cfg.k * cfg.block_size
    rng = np.random.default_rng(cfg.seed)
    payload = np.frombuffer(rng.bytes(stripes * extent), np.uint8)
    size = 2 * extent // 3
    objects = {}
    for i, lo in enumerate(range(0, len(payload), size)):
        objects[f"obj{i}"] = (lo, min(lo + size, len(payload)))
        store.put(f"obj{i}", payload[lo:lo + size])
    store.seal()
    check(len(store.stripes) == stripes,
          f"{len(store.stripes)} stripes sealed, want {stripes}")
    return payload, objects


def lose_nodes(store, nodes) -> dict:
    """Fail ``nodes`` and delete their block files, as lost disks; returns
    the healthy bytes of every block they held."""
    nodes = set(nodes)
    held = {(sid, b): store.read(sid, b)
            for sid, st in store.stripes.items()
            for b, node in enumerate(st.node_of_block) if node in nodes}
    for node in nodes:
        store.fail_node(node)
        shutil.rmtree(store.root / f"node{node}")
        (store.root / f"node{node}").mkdir()
    return held


def read_back(store, nodes, held: dict) -> dict:
    """Revive ``nodes`` and read every block they held from disk."""
    for node in nodes:
        store.revive_node(node)
    return {key: store.read(*key) for key in held}


# ------------------------------------------------------------------ phases
def phase_encode(store, stripes: int) -> dict:
    from repro.kernels.ops import effective_backend

    cfg = store.cfg
    payload, objects = fill(store, stripes)
    extent = cfg.k * cfg.block_size
    sample = sorted({0, 1, stripes // 2, stripes - 1})
    for sid in sample:
        blocks = np.stack([store.read(sid, b) for b in range(store.n)])
        check(blocks[:cfg.k].tobytes()
              == payload[sid * extent:(sid + 1) * extent].tobytes(),
              f"stripe {sid} data blocks differ from the payload")
        check(np.array_equal(blocks, store.scheme.encode(blocks[:cfg.k])),
              f"stripe {sid} parity differs from LRCScheme.encode")
    for key in ("obj0", f"obj{len(objects) // 2}", f"obj{len(objects) - 1}"):
        lo, hi = objects[key]
        check(store.get(key).tobytes() == payload[lo:hi].tobytes(),
              f"get({key}) differs from what was put")
    return {"stripes": len(store.stripes),
            "bytes_with_parity": len(store.stripes) * store.n * cfg.block_size,
            "stripes_checked": len(sample),
            "effective_backend": effective_backend(cfg.backend)}


def phase_repair(store, nodes) -> dict:
    held = lose_nodes(store, nodes)
    cascade = [down for down in {store._down_blocks(sid)
                                 for sid, _ in held}
               if any(m == "cascade" for _, m in
                      store.engine.planner.multi_plan(down).meta.steps)]
    rep = store.repair_all()
    got = read_back(store, nodes, held)
    for key, want in held.items():
        check(np.array_equal(got[key], want),
              f"rebuilt block {key} differs from its bytes before the loss")
    check(rep["stripes_repaired"] == len({sid for sid, _ in held}),
          f"{rep['stripes_repaired']} stripes repaired")
    return {"nodes": list(nodes), "blocks": len(held),
            "stripes": rep["stripes_repaired"], "patterns": rep["patterns"],
            "cascade_patterns": len(cascade), "launches": rep["launches"],
            "effective_backend": rep["effective_backend"]}


def phase_degraded(store, requests: int, seed: int) -> dict:
    from repro.ftx import read_report
    from repro.serve.blocks import BlockServer, zipf_requests

    node = store.stripes[0].node_of_block[5]
    reqs = zipf_requests(store, requests, seed=seed)
    reqs += [(sid, b) for sid, st in store.stripes.items()
             for b, n in enumerate(st.node_of_block)
             if n == node and b < store.cfg.k]
    reqs = [reqs[i] for i in np.random.default_rng(seed).permutation(len(reqs))]
    truth = {key: store.read(*key) for key in set(reqs)}
    lose_nodes(store, [node])
    store.telemetry.reset()
    got = BlockServer(store, clients=8).run(reqs)
    for key, data in zip(reqs, got):
        check(np.array_equal(data, truth[key]),
              f"degraded read {key} differs from the healthy read")
    rep = read_report(store)
    check(rep.degraded_reads > 0, "no read was degraded")
    return {"requests": len(reqs), "degraded_reads": rep.degraded_reads,
            "decode_launches": rep.decode_launches,
            "coalesced_reads": rep.coalesced_reads,
            "effective_backend": store.engine.effective_backend}


def make_state(seed: int, mib: int):
    """A training-state-like pytree of ``mib`` MiB, made on the device."""
    import jax
    import jax.numpy as jnp

    rows = mib * (1 << 20) // (12 * 4096)   # 12 bytes per (row, column)
    key = jax.random.key(seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def bits(k, shape, dtype):
        width = jnp.dtype(dtype).itemsize * 8
        raw = jax.random.bits(k, shape, jnp.dtype(f"uint{width}"))
        return jax.lax.bitcast_convert_type(raw, dtype)

    return {"params": {"w_in": bits(k1, (rows, 4096), jnp.float32),
                       "w_out": bits(k2, (4096, rows), jnp.float32)},
            "opt": {"mu": bits(k3, (rows, 4096), jnp.bfloat16),
                    "nu": bits(k4, (rows, 4096), jnp.bfloat16),
                    "step": jnp.asarray(7, jnp.int32)}}


def phase_checkpoint(root: Path, cfg, mib: int) -> dict:
    import jax

    from repro.ftx import CheckpointConfig, CheckpointManager

    state = make_state(cfg.seed, mib)
    cm = CheckpointManager(root, CheckpointConfig(store=cfg))
    info = cm.save_async(1, state).result()
    store = cm.store_for(1)
    encode_backend = store.engine.effective_backend
    host = store.stripes[0].node_of_block[0]
    cm.fail_hosts(1, [host])
    shutil.rmtree(store.root / f"node{host}")
    (store.root / f"node{host}").mkdir()
    restored, tele = cm.restore(1, state)
    want, got = jax.tree.leaves(state), jax.tree.leaves(restored)
    check(len(want) == len(got), "restored pytree has other leaves")
    for a, b in zip(want, got):
        check(np.asarray(a).tobytes() == np.asarray(b).tobytes(),
              "a restored leaf differs from the saved one")
    check(tele["degraded_blocks"] > 0, "restore decoded no block")
    return {"bytes": info["bytes"], "stripes": info["stripes"],
            "host_lost": host, "degraded_blocks": tele["degraded_blocks"],
            "decode_launches": tele["restore_decode_launches"],
            "encode_backend": encode_backend,
            "effective_backend": store.engine.effective_backend}


def phase_four_chips(root: Path, cfg, stripes: int) -> dict:
    import jax

    from repro.dist.sharding import with_rules
    from repro.dist.topology import Topology
    from repro.ftx import StripeStore
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, want 4")
    store = StripeStore(root, cfg, topology=Topology(num_nodes=28,
                                                     num_domains=4))
    fill(store, stripes)
    node = store.stripes[0].node_of_block[0]
    held = lose_nodes(store, [node])
    with with_rules(make_mesh((4, 1), ("data", "model"))):
        sharded = store.repair_all()
    check(sharded["devices"] == 4,
          f"sharded repair ran on {sharded['devices']} devices")
    got4 = read_back(store, [node], held)
    lose_nodes(store, [node])
    single = store.repair_all()
    check(single["devices"] == 1, "the one-device repair was sharded")
    got1 = read_back(store, [node], held)
    for key, want in held.items():
        check(np.array_equal(got4[key], got1[key]),
              f"block {key}: the 4-device repair differs from one device")
        check(np.array_equal(got4[key], want),
              f"block {key}: rebuilt bytes differ from before the loss")
    return {"blocks": len(held), "stripes": sharded["stripes_repaired"],
            "devices": sharded["devices"],
            "device_launches": sharded["device_launches"],
            "single_device_wall_seconds": single["wall_seconds"],
            "sharded_wall_seconds": sharded["wall_seconds"],
            "effective_backend": sharded["effective_backend"]}


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="only the stripe-sharded repair on a (4, 1) mesh")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: the first JAX device is a "
              f"{devices[0].platform}, not a TPU; nothing was run",
              file=sys.stderr)
        return 1
    from repro.ftx import StoreConfig, StripeStore
    from repro.launch.cache import use_compile_cache

    print(json.dumps({"compile_cache": use_compile_cache()}), flush=True)
    clock = CompileClock(jax)
    cfg = StoreConfig(**GEOMETRY, seed=args.seed)
    with tempfile.TemporaryDirectory(prefix=".smoke-", dir=ROOT) as tmp:
        tmp = Path(tmp)
        if args.four_chips:
            run_phase("sharded_repair", clock, phase_four_chips,
                      tmp / "store", cfg, STRIPES)
        else:
            store = StripeStore(tmp / "store", cfg)
            run_phase("encode", clock, phase_encode, store, STRIPES)
            st0 = store.stripes[0].node_of_block
            run_phase("repair1", clock, phase_repair, store, [st0[0]])
            # data block 0 and its local parity 24: the cascade path
            line = run_phase("repair2", clock, phase_repair, store,
                             [st0[0], st0[24]])
            check(line["cascade_patterns"] > 0, "no cascade repair ran")
            run_phase("degraded", clock, phase_degraded, store, READS,
                      args.seed)
            shutil.rmtree(store.root)
            run_phase("checkpoint", clock, phase_checkpoint, tmp / "ckpt",
                      cfg, STATE_MIB)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
