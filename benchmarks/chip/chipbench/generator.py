"""The one traffic generator: node failures, repairs, open-loop reads and
ingest, composed from the parts a traffic mix names.

A traffic mix (``traffic/<name>.json``) is data this module reads. It names
one or more parts, and every part it names runs in the measured window, each
on a thread of its own when there are several:

``failures``
    ``offsets`` (default ``[0]``) are the nodes that fail together, counted
    from a start node: the set ``{(start + o) % nodes}``; ``[0, 26]`` at
    P5 loses, in stripe 0, a data block and its local parity.
    ``mode: "rotate"`` (the ``rebuild`` part): each cycle fails the set,
    moves its nodes' block files aside (the lost disks), calls
    ``repair_all()`` with the store's defaults and revives the nodes; the
    first start comes from the seed, and the start steps through every node
    in order. ``mode: "hold"``: one set, its start chosen from the seed
    among those whose nodes hold the most data blocks, is down for the whole
    run.
``reads``
    An open loop: Poisson arrivals at ``rate_per_s``, served by ``clients``
    threads through ``BlockServer.read``. Each request reads one byte range
    of one data block; popularity is Zipfian with constant ``zipf_theta``
    over all data blocks, and range lengths are log-uniform over
    ``range_bytes``. Every seed gets the same multiset of requests and
    inter-arrival gaps, drawn from ``template_seed``; under ``"hold"`` also
    the same ranks on the lost blocks: the seed picks the down nodes, which
    concrete blocks take which ranks, and the order of requests and gaps.
    A request is degraded when a node of its block is down as it starts.
``writes``
    Ingest, closed loop: objects of whole stripes, their stripe counts
    log-uniform over ``object_stripes`` (a fixed template, in the seed's
    order), each streamed through ``stream_writer`` and encoded on the
    device, one object after another until the window ends.

A cell's store lives in a temporary directory of the checkout, one file per
block; where those files are served from (page cache, disk, the machine's
file transport) is the machine's, and ``PERF.md`` says which the cells
measure. Lost block files are moved aside, not deleted, so the check can
read every block that a repair in the window wrote, also where a later
cycle lost that node again.
"""
from __future__ import annotations

import collections
import concurrent.futures as cf
import contextlib
import dataclasses
import math
import os
import threading
import time
from pathlib import Path

import numpy as np

from . import reference

FILL_CHUNK_BYTES = 256 << 20      # data per encode launch while filling
DRAIN_SECONDS = 60.0              # how long a late read is waited for
WRITE_CHECK_STRIPES = 16          # ingested stripes compared per run
_U64 = 1 << 64
PARTS = ("rebuild", "reads", "writes")


def _span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Run:
    """What a run recorded; the metric readers read this."""
    parts: frozenset                  # of PARTS
    chips: int
    block_size: int
    setup_s: float = 0.0
    window_s: dict = dataclasses.field(default_factory=dict)   # per part
    rebuilt_bytes: int = 0
    ingested_bytes: int = 0
    reports: list = dataclasses.field(default_factory=list)
    requests: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    trace: object = None              # trace_reduce.ReducedTrace
    peaks: object = None              # peaks.Peaks


def _store_counters(store) -> dict:
    return {f.name: getattr(store.telemetry, f.name)
            for f in dataclasses.fields(store.telemetry)
            if isinstance(getattr(store.telemetry, f.name), (int, float))}


def zipf_ranks(rng, n_items: int, theta: float, count: int) -> np.ndarray:
    """``count`` 0-based ranks, P(rank r) proportional to ``(r + 1)^-theta``."""
    cdf = np.cumsum(1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** theta)
    return np.minimum(np.searchsorted(cdf / cdf[-1], rng.random(count)),
                      n_items - 1)


def log_uniform(rng, lo: int, hi: int, count: int) -> np.ndarray:
    return np.exp(rng.uniform(math.log(lo), math.log(hi), count)).astype(
        np.int64).clip(lo, hi)


def parts_of(traffic: dict) -> frozenset:
    """The parts a mix runs in its window; refuses a mix that runs none or
    names a failure mode this generator does not know."""
    failures = traffic.get("failures")
    parts = set()
    if failures:
        if failures["mode"] not in ("rotate", "hold"):
            raise ValueError(f"unknown failures.mode {failures['mode']!r}")
        if failures["mode"] == "rotate":
            parts.add("rebuild")
        elif not traffic.get("reads"):
            raise ValueError("nodes held down with no reads measure nothing")
    parts.update(p for p in ("reads", "writes") if traffic.get(p))
    if not parts:
        raise ValueError("the mix names no part to run in the window")
    return frozenset(parts)


class Traffic:
    """One run of one cell: set-up, the measured window and the check."""

    def __init__(self, config: dict, traffic: dict, *, seed: int,
                 seconds: float, workdir: Path, chips: int):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed % _U64
        self.seconds = float(seconds)
        self.workdir = Path(workdir)
        self.chips = chips
        self.rng = np.random.default_rng(self.seed)
        self.run = Run(parts=parts_of(traffic), chips=chips,
                       block_size=config["block_size"])
        failures = traffic.get("failures") or {}
        self.offsets = sorted({o % config["nodes"]
                               for o in failures.get("offsets", [0])})
        self.store = None
        self.down: set = set()
        self._lost = 0
        self._moved: list = []        # (dir, node, holds window rebuilds)
        self._rebuilt_nodes: set = set()
        self._written: list = []      # stripe ids the window ingested
        self.errors: list = []
        self.timings: dict = {}

    # --------------------------------------------------------------- store
    def _mesh(self):
        mesh = self.cfg.get("mesh")
        if not mesh:
            return contextlib.nullcontext()
        import jax

        from repro.dist.sharding import with_rules
        from repro.launch.mesh import make_mesh

        return with_rules(make_mesh(mesh["shape"], mesh["axes"],
                                    devices=jax.devices()[:self.chips]))

    def _build(self):
        from repro.dist.topology import Topology
        from repro.ftx import StoreConfig, StripeStore

        c = self.cfg
        sc = StoreConfig(scheme=c["scheme"], k=c["k"], r=c["r"], p=c["p"],
                         block_size=c["block_size"],
                         read_cache_blocks=c["read_cache_blocks"],
                         seed=self.seed)
        topo = (Topology(num_nodes=c["nodes"], num_domains=c["failure_domains"])
                if c["failure_domains"] > 1 else None)
        store = StripeStore(self.workdir / "store", sc, num_nodes=c["nodes"],
                            topology=topo)
        if store.n != c["k"] + c["r"] + c["p"]:
            raise ValueError("the store's stripe width differs from k + r + p")
        return store

    def _stream(self, key: str, stripes: int, step: int) -> list:
        """One object of ``stripes`` seeded stripes through the store's
        streaming write path, encoded on the device ``step`` at a time."""
        store, k, bs = self.store, self.cfg["k"], self.cfg["block_size"]
        writer = store.stream_writer(key, stripes * k * bs)
        for first in range(0, stripes, step):
            sids = writer.sids[first:first + step]
            data = reference.stripe_data(self.seed, sids, k, bs)
            writer.write_window(first, np.asarray(store.engine.encode(data)))
        writer.close()
        return list(writer.sids)

    def _fill(self):
        """The store's contents, a few stripes per encode launch."""
        k, bs, stripes = (self.cfg[x] for x in ("k", "block_size", "stripes"))
        step = max([1] + [s for s in range(1, stripes + 1)
                          if stripes % s == 0
                          and s * k * bs <= FILL_CHUNK_BYTES])
        with _span("bench.fill"):
            self._stream("fill", stripes, step)
        self.node_blocks = collections.defaultdict(list)
        for sid, st in self.store.stripes.items():
            for b, node in enumerate(st.node_of_block):
                self.node_blocks[node].append((sid, b))

    def _failed_set(self, start: int) -> tuple:
        return tuple(sorted((start + o) % self.cfg["nodes"]
                            for o in self.offsets))

    def _lose(self, nodes, in_window: bool) -> None:
        """Fail ``nodes`` and move their block files aside, as lost disks."""
        for node in nodes:
            self.store.fail_node(node)
            self.down.add(node)
            home = self.store.root / f"node{node}"
            moved = self.workdir / "lost" / f"{self._lost:05d}_node{node}"
            self._lost += 1
            moved.parent.mkdir(exist_ok=True)
            os.rename(home, moved)
            home.mkdir()
            self._moved.append((moved, node,
                                in_window and node in self._rebuilt_nodes))

    def setup(self) -> None:
        t0 = time.perf_counter()
        self.store = self._build()
        self._fill()
        t1 = time.perf_counter()
        parts = self.run.parts
        if "reads" in parts:
            from repro.serve.blocks import BlockServer

            self.server = BlockServer(self.store,
                                      clients=self.traffic["reads"]["clients"])
        if "rebuild" in parts:
            for start in self._cover(self._warm_shapes):
                self._cycle(self._failed_set(start), in_window=False)
        if "reads" in parts:
            self._setup_reads()
        if "writes" in parts:
            self._setup_writes()
        self.timings.update(fill_s=t1 - t0, warm_s=time.perf_counter() - t1)

    def _cover(self, shapes_of) -> list:
        """Few starts whose failed sets together give every shape that any
        start gives: the warm-up."""
        shapes = {s: shapes_of(self._failed_set(s))
                  for s in sorted(self.node_blocks)}
        need = set().union(*shapes.values())
        chosen = []
        while need:
            best = max(shapes, key=lambda s: len(shapes[s] & need))
            chosen.append(best)
            need -= shapes[best]
        return chosen

    def _patterns(self, nodes) -> collections.Counter:
        """Stripes per failure pattern (the lost block indices) of ``nodes``."""
        lost = {sid for node in nodes for sid, _ in self.node_blocks[node]}
        return collections.Counter(
            frozenset(b for b, n in enumerate(self.store.stripes[sid]
                                              .node_of_block) if n in nodes)
            for sid in lost)

    # ------------------------------------------------------------- rebuild
    def _launch_shapes(self, nodes) -> set:
        """What sets the shapes of a repair of ``nodes``: per failure
        pattern, its stripe count and its plan's read and target counts."""
        out = set()
        for down, count in self._patterns(nodes).items():
            plan = self.store.engine.planner.multi_plan(down)
            out.add((count, len(plan.reads), len(plan.targets)))
        return out

    def _warm_shapes(self, nodes) -> set:
        """The launch shapes of a repair of ``nodes``, and with reads in the
        mix the serving-plan shapes of reads while they are down."""
        shapes = self._launch_shapes(nodes)
        if "reads" in self.run.parts:
            shapes |= set(self._serving_shapes(nodes))
        return shapes

    def _cycle(self, nodes, in_window: bool) -> None:
        store = self.store
        with _span("bench.cycle"):
            with _span("bench.fail_node"):
                self._lose(nodes, in_window)
            if not in_window and "reads" in self.run.parts:
                for sid, b in self._serving_shapes(nodes).values():
                    with _span("bench.read"):
                        self.server.read(sid, b, 0, self.cfg["block_size"])
            try:
                with self._mesh(), _span("bench.repair_all"):
                    report = store.repair_all()
            except Exception as e:          # a failed repair fails the run
                self.errors.append(repr(e))
                report = None
            with _span("bench.revive_node"):
                for node in nodes:
                    store.revive_node(node)
                    self.down.discard(node)
        if in_window:
            self._rebuilt_nodes.update(nodes)
            self.run.rebuilt_bytes += (
                len({x for node in nodes for x in self.node_blocks[node]})
                * self.cfg["block_size"])
            if report is not None:
                self.run.reports.append(report)

    def _rebuild_window(self) -> None:
        nodes = sorted(self.node_blocks)
        start = int(self.rng.integers(len(nodes)))
        t0 = time.perf_counter()
        i = 0
        slowest = 0.0
        with _span("bench.window"):
            while True:
                t = time.perf_counter()
                self._cycle(self._failed_set(nodes[(start + i) % len(nodes)]),
                            in_window=True)
                slowest = max(slowest, time.perf_counter() - t)
                i += 1
                if time.perf_counter() - t0 >= self.seconds:
                    break
        self.run.window_s["rebuild"] = time.perf_counter() - t0
        self.timings.update(cycles=i, slowest_cycle_s=slowest)

    # --------------------------------------------------------------- reads
    def _serving_shapes(self, nodes) -> dict:
        """One lost data block per serving-plan shape of ``nodes`` down."""
        k, out = self.cfg["k"], {}
        for node in nodes:
            for sid, b in self.node_blocks[node]:
                if b >= k:
                    continue
                down = frozenset(bb for bb, n in enumerate(
                    self.store.stripes[sid].node_of_block) if n in nodes)
                plan = self.store.engine.planner.serving_plan(b, down)
                out.setdefault(("serve", len(plan.reads), len(plan.targets)),
                               (sid, b))
        return out

    def _setup_reads(self) -> None:
        k, q = self.cfg["k"], self.traffic["reads"]
        held = self.traffic["failures"]["mode"] == "hold"
        if held:
            data_held = {s: sum(b < k for node in self._failed_set(s)
                                for _, b in self.node_blocks[node])
                         for s in sorted(self.node_blocks)}
            most = max(data_held.values())
            candidates = [s for s, v in data_held.items() if v == most]
            start = candidates[int(self.rng.integers(len(candidates)))]
            self._lose(self._failed_set(start), in_window=False)
        pool = sorted((sid, b) for sid in self.store.stripes for b in range(k))
        lost = sorted({x for node in self.down for x in self.node_blocks[node]
                       if x[1] < k})
        live = sorted(set(pool) - set(lost))
        # the seed decides which concrete blocks take the lost and live slots
        self.slot_block = ([lost[i] for i in self.rng.permutation(len(lost))]
                           + [live[i] for i in self.rng.permutation(len(live))])
        self.n_lost = len(lost)
        self.schedule(q["rate_per_s"], self.seconds)
        bs = self.cfg["block_size"]
        if held:
            # warm-up: one decode per serving-plan shape, on the least
            # popular lost block of that shape (rotating failures warm
            # theirs in the rebuild warm-up)
            warm = {}
            by_rank = sorted(range(self.n_lost),
                             key=lambda s: -self.slot_rank[s])
            for sid, b in (self.slot_block[s] for s in by_rank):
                down = frozenset(bb for bb, n in enumerate(
                    self.store.stripes[sid].node_of_block) if n in self.down)
                plan = self.store.engine.planner.serving_plan(b, down)
                warm.setdefault((len(plan.reads), len(plan.targets)), (sid, b))
            for sid, b in warm.values():
                with _span("bench.read"):
                    self.server.read(sid, b, 0, bs)
        with _span("bench.read"):
            self.server.read(*self.slot_block[-1], 0, bs)
        # then the mix itself, unmeasured, as a long-running server has seen
        # it: the host's allocator, threads and the hot-block cache are warm
        self.schedule(q["rate_per_s"], q["warmup_s"])
        self._reads_window()
        self.schedule(q["rate_per_s"], self.seconds)

    def schedule(self, rate: float, seconds: float) -> None:
        """``seconds`` of requests at ``rate`` per second: the template's
        multiset of requests and gaps, in the seed's order."""
        q, bs = self.traffic["reads"], self.cfg["block_size"]
        trng = np.random.default_rng(q["template_seed"])
        n_items = len(self.slot_block)
        rank_slot = trng.permutation(n_items)
        self.slot_rank = np.argsort(rank_slot)
        n = max(1, round(rate * seconds))
        ranks = zipf_ranks(trng, n_items, q["zipf_theta"], n)
        lengths = np.minimum(log_uniform(trng, *q["range_bytes"], n), bs)
        offsets = trng.random(n)
        gaps = trng.exponential(1.0 / rate, n)
        order = self.rng.permutation(n)
        gaps = gaps[self.rng.permutation(n)]
        slots = rank_slot[ranks[order]]
        lengths = lengths[order]
        lo = (offsets[order] * (bs - lengths + 1)).astype(np.int64)
        self.requests = [(*self.slot_block[s], int(a), int(a + ln))
                         for s, a, ln in zip(slots, lo, lengths)]
        self.due = np.concatenate([[0.0], np.cumsum(gaps[1:])])
        self.on_lost = slots < self.n_lost       # lost since set-up
        self.keep = self.on_lost | (self.rng.random(n) < q["check_live_share"])

    def _client_pool(self):
        """The client threads, made once: the warm-up mix starts them, and
        the window reuses them."""
        if getattr(self, "pool", None) is None:
            self.pool = cf.ThreadPoolExecutor(self.traffic["reads"]["clients"])
        return self.pool

    def _is_down(self, sid: int, block: int) -> bool:
        return self.store.stripes[sid].node_of_block[block] in self.down

    def _reads_window(self) -> None:
        n = len(self.requests)
        submit, start, end = (np.full(n, np.nan) for _ in range(3))
        ok = np.zeros(n, bool)
        degraded = np.zeros(n, bool)
        kept = {}
        errors = self.errors

        def job(i):
            start[i] = time.perf_counter()
            degraded[i] = self._is_down(*self.requests[i][:2])
            try:
                with _span("bench.read"):
                    data = self.server.read(*self.requests[i])
            except Exception as e:          # a failed read fails the run
                errors.append(repr(e))
                return
            finally:
                end[i] = time.perf_counter()
            ok[i] = True
            if self.keep[i] or degraded[i]:
                kept[i] = data

        before = _store_counters(self.store)
        pool = self._client_pool()
        futures = []
        t0 = time.perf_counter()
        with _span("bench.window"):
            for i in range(n):
                wait = t0 + self.due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                submit[i] = time.perf_counter()
                futures.append(pool.submit(job, i))
            _, late = cf.wait(futures, timeout=DRAIN_SECONDS)
        if late:
            pool.shutdown(wait=False, cancel_futures=True)
            self.pool = None
        self.unfinished = len(late)
        after = _store_counters(self.store)
        self.run.counters = {key: after[key] - before[key] for key in after}
        due = t0 + self.due
        latency = np.where(ok, end - due, np.inf)
        self.run.window_s["reads"] = float(np.nanmax(end) - t0)
        self.run.requests = {"latency_s": latency, "lag_s": submit - due,
                             "degraded": degraded, "ok": ok}
        self.kept = kept

    # -------------------------------------------------------------- writes
    def _object_sizes(self) -> np.ndarray:
        """The template's object sizes in stripes, in the seed's order."""
        w = self.traffic["writes"]
        trng = np.random.default_rng(w["template_seed"])
        sizes = log_uniform(trng, *w["object_stripes"], 64)
        return sizes[self.rng.permutation(sizes.size)]

    def _write_step(self) -> int:
        return max(1, FILL_CHUNK_BYTES // (self.cfg["k"]
                                           * self.cfg["block_size"]))

    def _setup_writes(self) -> None:
        self.sizes = self._object_sizes()
        step = self._write_step()
        shapes = {min(step, int(s)) for s in self.sizes}
        shapes |= {int(s) % step for s in self.sizes} - {0}
        for i, s in enumerate(sorted(shapes)):     # every launch shape once
            with _span("bench.write"):
                self._stream(f"warm{i}", s, step)

    def _writes_window(self) -> None:
        step, i = self._write_step(), 0
        per_stripe = self.cfg["k"] * self.cfg["block_size"]
        t0 = time.perf_counter()
        with _span("bench.window"):
            while time.perf_counter() - t0 < self.seconds:
                stripes = int(self.sizes[i % self.sizes.size])
                try:
                    with _span("bench.write"):
                        self._written += self._stream(f"obj{i}", stripes,
                                                      step)
                except Exception as e:      # a failed write fails the run
                    self.errors.append(repr(e))
                    break
                self.run.ingested_bytes += stripes * per_stripe
                i += 1
        self.run.window_s["writes"] = time.perf_counter() - t0
        self.timings.update(objects=i)

    # --------------------------------------------------------- the window
    def window(self) -> None:
        runs = {"rebuild": self._rebuild_window, "reads": self._reads_window,
                "writes": self._writes_window}
        jobs = [runs[p] for p in PARTS if p in self.run.parts]
        if len(jobs) == 1:
            jobs[0]()
            return
        threads = [threading.Thread(target=self._guarded, args=(job,))
                   for job in jobs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    def _guarded(self, job) -> None:
        try:
            job()
        except Exception as e:              # an error fails the run
            self.errors.append(repr(e))

    def memory_peak_bytes(self) -> int:
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.devices()[:self.chips]]
        return int(max(peaks))

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        import gc

        if getattr(self, "pool", None) is not None:
            self.pool.shutdown(wait=True)
            self.pool = None
        self.node_of = {sid: tuple(st.node_of_block)
                        for sid, st in self.store.stripes.items()}
        self.store_root = self.store.root
        self.store = self.server = None
        gc.unfreeze()
        gc.collect()

    # ------------------------------------------------------------ the check
    def check(self) -> dict:
        """Each number compared, with its limit: ``{name: (value, limit)}``."""
        out = {"errors": (len(self.errors), 0)}
        if "rebuild" in self.run.parts:
            out.update(self._check_rebuilt())
        if "reads" in self.run.parts:
            out.update(self._check_reads())
        if "writes" in self.run.parts:
            out.update(self._check_written())
        return out

    def _compare_blocks(self, wanted: dict) -> tuple[int, int]:
        """Bad and missing block files among ``{sid: [(block, path)]}``."""
        bs = self.cfg["block_size"]
        bad = missing = 0
        for sid in sorted(wanted):
            ref = reference.reference_stripe(self.seed, sid, self.cfg)
            for b, path in wanted[sid]:
                if not path.exists():
                    missing += 1
                    continue
                got = np.fromfile(path, np.uint8)
                bad += got.size != bs or not np.array_equal(got, ref[b])
        return bad, missing

    def _check_rebuilt(self) -> dict:
        dirs = [(d, node) for d, node, rebuilt in self._moved if rebuilt]
        dirs += [(self.store_root / f"node{node}", node)
                 for node in sorted(self._rebuilt_nodes)]
        wanted = collections.defaultdict(list)
        for d, node in dirs:
            for sid, b in self.node_blocks[node]:
                wanted[sid].append((b, d / f"s{sid}_b{b}.blk"))
        bad, missing = self._compare_blocks(wanted)
        out = {"bad_blocks": (bad, 0), "missing_blocks": (missing, 0),
               "empty_window": (int(not wanted), 0)}
        mesh = self.cfg.get("mesh")
        if mesh:
            span = math.prod(mesh["shape"])
            launches = sum(r["launches"] for r in self.run.reports)
            spread = sum(r["device_launches"] for r in self.run.reports)
            out["unsharded_launches"] = ((span * launches - spread)
                                         // (span - 1), 0)
        return out

    def _check_reads(self) -> dict:
        k, bs = self.cfg["k"], self.cfg["block_size"]
        by_sid = collections.defaultdict(list)
        for i in self.kept:
            by_sid[self.requests[i][0]].append(i)
        bad = 0
        for sid in sorted(by_sid):
            data = np.asarray(reference.stripe_data(self.seed, [sid], k, bs)[0])
            for i in by_sid[sid]:
                _, b, lo, hi = self.requests[i]
                bad += not np.array_equal(self.kept[i], data[b, lo:hi])
        return {"bad_reads": (bad, 0), "unfinished_reads": (self.unfinished, 0),
                "empty_window": (int(not self.kept), 0)}

    def _check_written(self) -> dict:
        """Every block of a seeded sample of the window's ingested stripes,
        on disk, against the reference stripe."""
        written = self._written
        pick = sorted(self.rng.permutation(len(written))[:WRITE_CHECK_STRIPES])
        wanted = {written[i]: [(b, self.store_root / f"node{node}"
                                / f"s{written[i]}_b{b}.blk")
                               for b, node in enumerate(self.node_of[written[i]])]
                  for i in pick}
        bad, missing = self._compare_blocks(wanted)
        return {"bad_written": (bad, 0), "missing_written": (missing, 0),
                "empty_writes": (int(not wanted), 0)}

    def attempted_failed(self, checks: dict) -> tuple[int, int]:
        """Blocks rebuilt, requests sent and stripes ingested in the window,
        and how many of them failed or came out wrong."""
        attempted = failed = 0
        if "rebuild" in self.run.parts:
            attempted += self.run.rebuilt_bytes // self.cfg["block_size"]
            failed += checks["bad_blocks"][0] + checks["missing_blocks"][0]
        if "reads" in self.run.parts:
            attempted += len(self.requests)
            failed += (int((~self.run.requests["ok"]).sum())
                       + checks["bad_reads"][0])
        if "writes" in self.run.parts:
            attempted += len(self._written)
            failed += (checks["bad_written"][0]
                       + checks["missing_written"][0])
        return attempted, failed
