"""Runs one cell of ``BENCHMARK.json`` once (see ``bench/run.py``).

A run: draw the cell's keys from the seed, build the index
(``Index.build`` and the first ``sync_device``: ``build_s``), put
``MicroBatchQueue`` -> ``Probe`` -> ``EpochPipeline`` -> ``Index`` in
front of it, warm the traffic's shapes, then drive the traffic mix for
``--seconds`` and wait for every answer due in the window.  Once the
window has closed and the program's state is freed, every answer is
compared with the plain reference, and the metrics the cell lists are
read by their readers (``bench/metrics/<name>.py``).

A configuration whose ``build`` names ``shards`` builds a
range-partitioned ``ShardedIndex``; its device image is the shard
fan-out (``sync_fanout``), built in set-up, and every warm-up lookup
must reach it.  A run uses the first ``chips`` devices of its cell and
reports only those.

Standard output holds one JSON record per line; the last is the result.
The numbers compared, each with its limit, are also the last lines of
standard error.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import time

import numpy as np

from bench import generator as gen
from bench import trace as trace_mod
from bench.probe import (FAULTS, CompileClock, Probe, annotate_flush,
                         peak_bytes, trace_span)
from bench.reference import NEVER, Reference

ROOT = pathlib.Path(__file__).resolve().parents[1]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
ANSWER_WAIT_S = 60.0
CLOSED_RANKS = 4096   # ranks drawn per closed-loop client, used cyclically


class BenchError(Exception):
    """The cell cannot be run as specified."""


# ---------------------------------------------------------------------------
# discovery: everything is found by name under the root


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: pathlib.Path):
    name = "bench_plugin_" + "".join(c if c.isalnum() else "_"
                                     for c in str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(spec: dict, workload: str, root: pathlib.Path = ROOT):
    """(cell entry, configuration dict, traffic dict) of ``workload``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    entries = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((root / entries[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def cell_metrics(spec: dict, workload: str, kind: str) -> list:
    """Entries of ``spec[kind]`` this cell reports.  A metric without a
    ``workloads`` key is reported wherever the end-to-end metric it
    moves is (end-to-end metrics without one: everywhere)."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def merge(base: dict, over: dict | None) -> dict:
    """``base`` with ``over``'s values; a nested section of ``over`` that
    ``base`` lacks is left out."""
    out = dict(base)
    for k, v in (over or {}).items():
        if isinstance(v, dict):
            if isinstance(out.get(k), dict):
                out[k] = merge(out[k], v)
        else:
            out[k] = v
    return out


# ---------------------------------------------------------------------------
# the run


class Run:
    """What a metric reader sees of one run (host clock in seconds)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def calls_in_window(self, calls):
        t0, t1 = self.window
        return [c for c in calls if t0 <= c[0] < t1]

    def mean_ms(self, calls):
        calls = self.calls_in_window(calls)
        if not calls:
            return None
        return 1e3 * float(np.mean([c[1] - c[0] for c in calls]))

    def open_latency_ms(self, q):
        """``q``-th percentile, over every open-loop request due in the
        window, of (end of the pipeline call that answered it - the time
        it was due)."""
        req = self.requests
        if not req or not req["served"].any():
            return None
        lat = (req["end"] - req["due"])[req["served"]]
        return 1e3 * float(np.percentile(lat, q))


def emit(rec: dict, out=None) -> None:
    print(json.dumps(rec, default=_jsonable), file=out or sys.stdout,
          flush=True)


def _jsonable(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        return float(o)
    if isinstance(o, (set, tuple)):
        return sorted(o) if isinstance(o, set) else list(o)
    return str(o)


class GcPauses:
    """Pauses of the interpreter's cyclic garbage collector, by
    generation, while registered."""

    def __init__(self):
        self.pauses = {0: [], 1: [], 2: []}
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses[info["generation"]].append(time.perf_counter()
                                                   - self._t)

    def close(self):
        gc.callbacks.remove(self._on)

    def summary(self) -> dict:
        return {f"gen{g}": [len(p), float(sum(p)), float(max(p, default=0))]
                for g, p in self.pauses.items()}


def _pct(a, q):
    return float(np.percentile(a, q)) if len(a) else None


class Cell:
    """One cell, built and warmed, ready to measure windows."""

    def __init__(self, root, spec, workload, seed, *, fault=None,
                 overrides=None, log=emit):
        if fault is not None and fault not in FAULTS:
            raise BenchError(f"unknown fault {fault!r}")
        self.log = log
        self.cell, cfg, traffic = find_cell(spec, workload, root)
        self.chips = int(self.cell["chips"])
        self.cfg = merge(cfg, (overrides or {}).get("config"))
        self.sharded = "shards" in self.cfg["build"]
        self.traffic = merge(traffic, (overrides or {}).get("traffic"))
        self.root = root
        self.fault = fault
        self.clock = CompileClock()
        ss = np.random.SeedSequence(int(seed))
        self.rng_data, self.rng_build, self.rng_traffic = (
            np.random.default_rng(s) for s in ss.spawn(3))
        self._data()

    # -- data and build ---------------------------------------------------
    def _data(self):
        cfg, tr = self.cfg, self.traffic
        t0 = time.perf_counter()
        kg = cfg["keys"]
        sampler = load_module(self.root / "bench" / "keys"
                              / f"{kg['generator']}.py").sample
        params = kg.get("params", {})
        n = int(cfg["records"])
        bulk, load = tr.get("bulk"), tr.get("load")
        self.n_present = (int(round(bulk["batch_keys"] * bulk["present_share"]))
                          if bulk else 0)
        n_absent = (bulk["pool_batches"] * (bulk["batch_keys"] - self.n_present)
                    if bulk else 0)
        n_pool = int(cfg.get("insert_pool", 0)) if load else 0
        self.keys_sorted, extra = gen.draw(
            lambda r, s: sampler(r, s, **params), n, n_absent + n_pool,
            self.rng_data)
        lo = min(self.keys_sorted[0], extra.min() if extra.size else np.inf)
        hi = max(self.keys_sorted[-1], extra.max() if extra.size else -1)
        if lo < 0 or hi >= float(cfg["key_limit"]):
            raise BenchError(f"a key lies outside [0, {cfg['key_limit']})")
        self.ordinals = gen.Ordinals(n, self.rng_data)
        self.pays_sorted = self.ordinals.ordinal(np.arange(n))
        self.absent = extra[:n_absent]
        self.pool = extra[n_absent:]
        self.pool_pays = n + np.arange(self.pool.size, dtype=np.int64)
        self.ref_keys = np.concatenate([self.keys_sorted, self.pool])
        self.ref_pays = np.concatenate([self.pays_sorted, self.pool_pays])
        self.ref_since = np.concatenate([
            np.zeros(n, np.int64), np.full(self.pool.size, NEVER, np.int64)])
        self.log({"phase": "data", "records": n, "absent": int(n_absent),
                  "insert_pool": int(self.pool.size),
                  "key_min": float(lo), "key_max": float(hi),
                  "gen_s": time.perf_counter() - t0})

    def build(self):
        from repro.core import Index
        from repro.serving import EpochPipeline, MicroBatchQueue

        t0 = time.perf_counter()
        self.index = Index.build(self.keys_sorted, payloads=self.pays_sorted,
                                 rng=self.rng_build, **self.cfg["build"])
        t1 = time.perf_counter()
        engine = (sync_fanout(self.index, self.chips) if self.sharded
                  else self.index.sync_device())
        t2 = time.perf_counter()
        self.build_s, self.freeze_s = t2 - t0, t2 - t1
        facts = (self._sharded_facts(engine) if self.sharded
                 else self._single_facts(engine))
        self.pipe = EpochPipeline(self.index, **self.cfg.get("pipeline", {}))
        control = None
        if self.fault == "control_f32":
            control = Reference(self.ref_keys, self.ref_pays, self.ref_since,
                                dtype=np.float32)
        self.probe = Probe(self.pipe, fault=self.fault, control=control)
        self.queue = MicroBatchQueue(self.probe, **self.cfg.get("queue", {}))
        self.probe.queue = self.queue
        self.log({"phase": "build", "build_s": self.build_s,
                  "host_build_s": t1 - t0, "freeze_s": self.freeze_s,
                  "learn_s": self.learn_s, **facts})

    def _single_facts(self, engine) -> dict:
        self.learn_s = float(self.index.learn_seconds)
        self.err_bound = err_bound(self.index.mech)
        return {"n_slots": int(self.index.gapped.n_slots),
                "segments": int(self.index.mech.plm.n_segments),
                "max_abs_error": self.err_bound,
                "key_wide": bool(engine.arrays.key_wide),
                "fused_impl": engine.fused_impl}

    def _sharded_facts(self, fan) -> dict:
        shards = self.index.shards
        self.learn_s = float(sum(sh.learn_seconds for sh in shards))
        # the roofline's bound holds for every shard: the largest
        self.err_bound = max(err_bound(sh.mech) for sh in shards)
        slots = [int(sh.gapped.n_slots) for sh in shards]
        return {"n_slots": sum(slots),
                "segments": sum(int(sh.mech.plm.n_segments)
                                for sh in shards),
                "max_abs_error": self.err_bound,
                "key_wide": bool(fan.statics["key_wide"]),
                "fused_impl": "fanout", "n_slots_per_shard": slots,
                "fanout_shards": fan.S, "fanout_devices": fan.D}

    # -- traffic ----------------------------------------------------------
    def prepare(self):
        tr, rng = self.traffic, self.rng_traffic
        n = self.keys_sorted.size
        self.loader = None
        if tr.get("load"):
            # every key in insertion order: the loaded records by ordinal,
            # then the insert pool
            order = np.concatenate([
                self.keys_sorted[self.ordinals.position(np.arange(n))],
                self.pool])
            self.loader = gen.Loader(self.pool, self.pool_pays,
                                     tr["load"]["batch_keys"], order, n)
        self.bulk_pool = None
        if tr.get("bulk"):
            b = tr["bulk"]
            k = b["batch_keys"] - self.n_present
            absent = self.absent.reshape(b["pool_batches"], k)
            self.bulk_pool = [rng.permutation(np.concatenate([
                self.keys_sorted[rng.integers(0, n, self.n_present)],
                absent[j]]))
                for j in range(b["pool_batches"])]
        self.zipf = None
        reads = tr.get("open") or tr.get("closed")
        if reads:
            self.zipf = gen.Zipfian(n, reads["theta"])

    def _key_of(self, kind, ranks):
        """Key, at send time, of the read with Zipfian rank ``ranks[i]``."""
        if kind == "scrambled_zipfian":
            # rank r is the record of ordinal r, scattered over the key
            # space by the insertion order
            return self.keys_sorted[self.ordinals.position(ranks)].__getitem__
        if kind == "latest":
            loader = self.loader
            return lambda i: loader.order[loader.n_acked - 1 - ranks[i]]
        raise BenchError(f"unknown key distribution {kind!r}")

    def reader(self, rate, seconds):
        """An open-loop reader at ``rate`` over ``seconds``."""
        o, rng = self.traffic["open"], self.rng_traffic
        due = gen.poisson_due(rate, seconds, rng)
        return gen.OpenReader(due, self._key_of(o["keys"],
                                                self.zipf.ranks(rng, due.size)))

    def closed_readers(self):
        """The closed-loop readers; client ``c``'s ``j``-th read takes rank
        ``j`` (cyclically) of its own run of ranks drawn from the seed."""
        c = self.traffic["closed"]
        k, per = int(c["clients"]), CLOSED_RANKS
        key_of = self._key_of(c["keys"], self.zipf.ranks(self.rng_traffic,
                                                         k * per))
        return gen.ClosedReaders(k, lambda i, j: key_of(i * per + j % per))

    def warm(self):
        tr, q = self.traffic, self.queue
        rng = np.random.default_rng(0)
        t0 = time.perf_counter()
        mark = self.clock.mark()
        before = self._fanout_lookups()
        for b in tr.get("warm_buckets", []):
            for _ in range(2):
                keys = self.keys_sorted[rng.integers(
                    0, self.keys_sorted.size, b)]
                q.result(q.submit_lookup(keys))
        if self.sharded:
            self._warm_fanout(before)
        else:
            # the escape patch's host copy, built lazily
            self.index.sync_device()._host_views()
        if self.loader is not None:
            for _ in range(int(tr["load"].get("warm_batches", 1))):
                self._send_batch()
        self.log({"phase": "warm", "warm_s": time.perf_counter() - t0,
                  "buckets": tr.get("warm_buckets", []),
                  **self.clock.since(mark)})

    def _fanout_lookups(self):
        return self.index.stats["fanout_lookups"] if self.sharded else None

    def _warm_fanout(self, before: int) -> None:
        """The fan-out built in set-up still serves (no shard changed),
        each shard's host copy for the escape patch is built now rather
        than by the window's first escape, and every warm-up lookup of a
        bucket the fan-out takes went through it: a cell whose warm-up
        took the host route would measure the host."""
        fan = sync_fanout(self.index, self.chips)
        for s in range(fan.S):
            fan._shard_host_views(s)
        if self.fault == "control_f32":
            return   # the control answers in the program's place
        due = 2 * sum(1 for b in self.traffic.get("warm_buckets", [])
                      if b >= self.index.min_device_batch)
        moved = self._fanout_lookups() - before
        if due == 0 or moved != due:
            raise BenchError(f"{moved} of {due} warm-up lookups reached the "
                             "shard fan-out")

    def _send_batch(self):
        if not self.loader.send(self.queue):
            raise BenchError("the insert pool is exhausted")

    # -- a measured window --------------------------------------------------
    def window(self, seconds, *, rate=None, trace_dir=None):
        """Drive the traffic for ``seconds`` and wait for every answer due
        in it.  Returns a dict of raw records."""
        tr, q, probe = self.traffic, self.queue, self.probe
        reader = None
        if tr.get("open"):
            reader = self.reader(rate or tr["open"]["rate_per_s"], seconds)
        bulk = gen.BulkClient(self.bulk_pool) if self.bulk_pool else None
        closed = self.closed_readers() if tr.get("closed") else None
        base = q.stats["coalesced_lookups"]
        n_look, n_ing = len(probe.lookups), len(probe.ingests)
        n_batches = len(self.loader.batches) if self.loader else 0
        fan0 = self._fanout_lookups()
        if trace_dir is not None:
            probe.span = trace_span
            annotate_flush(q)
        mark = self.clock.mark()
        gc_pauses = GcPauses()
        t0 = time.perf_counter() + 0.01
        t1 = t0 + seconds
        threads = []
        if reader is not None:
            threads.append(gen.start(reader.run, q, t0, base))
        if bulk is not None:
            threads.append(gen.start(bulk.run, q, t0, t1))
        if closed is not None:
            threads.append(gen.start(closed.run, q, t0, t1))
        if self.loader is not None:
            threads.append(gen.start(self.loader.run, q, t0, t1))
        traced = planes = None
        if trace_dir is not None:
            traced, planes = self._trace(trace_dir, t0, seconds)
        for th in threads:
            th.join()
        deadline = max(time.perf_counter(), t1) + ANSWER_WAIT_S
        if reader is not None:
            while (q.stats["coalesced_lookups"] - base < reader.n_submitted
                   and time.perf_counter() < deadline):
                time.sleep(0.002)
        t_done = time.perf_counter()
        gc_pauses.close()
        if reader is not None:
            reader.drain(q, base)
        out = {"t0": t0, "t1": t1, "t_done": t_done, "base": base,
               "reader": reader, "bulk": bulk,
               "closed": closed.records() if closed else None,
               "lookups": list(probe.lookups[n_look:]),
               "ingests": list(probe.ingests[n_ing:]),
               "batches": (self.loader.batches[n_batches:]
                           if self.loader else []),
               "compiles": self.clock.since(mark), "profile": traced,
               "planes": planes, "gc": gc_pauses.summary()}
        if fan0 is not None:
            out["fanout_lookups"] = self._fanout_lookups() - fan0
        return out

    def _trace(self, trace_dir, t0, seconds):
        """Profile a stretch of the window: the profile as the cell's chips
        read it, and every plane's lines with their event counts."""
        import jax

        lead = min(1.0, seconds / 4)
        span = max(0.5, min(float(self.traffic.get("trace_seconds", 3.0)),
                            seconds - lead - 0.25))
        time.sleep(max(0.0, t0 + lead - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with trace_span(trace_mod.WINDOW_SPAN):
                time.sleep(span)
        finally:
            jax.profiler.stop_trace()
        raw = trace_mod.load_profile(str(trace_dir))
        planes = [[p["name"], [[ln["name"], len(ln["events"])]
                               for ln in p["lines"]]] for p in raw["planes"]]
        return (trace_mod.keep_devices(
            raw, [d.id for d in cell_devices(self.chips)]), planes)

    def mark_acked(self, ref: Reference, batches) -> None:
        for a, n, ts, ta, rep in batches:
            ref.mark(self.pool[a:a + n], rep.epoch)

    def readback(self, batches) -> list:
        """Read every key acknowledged in ``batches`` back through the
        served path; returns ``(keys, answer)`` per lookup."""
        chunk = max(self.traffic.get("warm_buckets", [512]))
        keys = np.concatenate([self.pool[a:a + n]
                               for a, n, *_ in batches]) if batches else []
        out = []
        for i in range(0, len(keys), chunk):
            k = keys[i:i + chunk]
            out.append((k, gen._answer(
                self.queue.result(self.queue.submit_lookup(k)))))
        return out

    def close(self):
        """Free the program's state (before the reference runs)."""
        self.queue.close()
        self.pipe.close()
        self.probe.pipe = None
        del self.queue, self.pipe, self.index
        gc.collect()


def err_bound(mech) -> float:
    """An index's declared error bound: the PGM mechanism's eps."""
    return float(getattr(mech, "eps", None) or mech.plm.max_abs_error())


def cell_devices(chips: int) -> list:
    """The devices a run of a ``chips``-chip cell uses and reports."""
    import jax

    return jax.devices()[:chips]


def sync_fanout(index, chips: int):
    """The sharded index's device image, built now: its shard fan-out
    (the shard images stacked and placed over the mesh), the object its
    large lookups serve from, returned again while no shard changes.
    Raises where the fan-out cannot serve the shard set, or where its
    mesh reaches past the cell's devices: never the host route."""
    fan = index._fanout()
    if fan is None:
        raise BenchError("the shard fan-out cannot serve this shard set")
    outside = set(fan.mesh.devices.flat) - set(cell_devices(chips))
    if outside:
        raise BenchError(f"the shard fan-out's mesh uses devices "
                         f"{sorted(d.id for d in outside)}, beyond the "
                         f"cell's {chips} chips")
    return fan


# ---------------------------------------------------------------------------
# reductions


def attribute(w: dict) -> dict:
    """Per open-loop request: the lookup call that served it (see
    ``Probe``), its start and end, and the request's due time."""
    r = w["reader"]
    calls = w["lookups"]
    n = r.n_submitted
    if not calls or n == 0:
        return {}
    before = np.array([c[2] for c in calls], np.int64)
    idx = w["base"] + np.arange(n)
    d = np.searchsorted(before, idx, side="right") - 1
    served = d >= 0
    start = np.array([c[0] for c in calls])
    end = np.array([c[1] for c in calls])
    counts = np.diff(np.append(before, w["base"] + n))
    dd = np.clip(d, 0, len(calls) - 1)
    return {"due": r.due[:n] + w["t0"], "submit": r.submit[:n],
            "start": np.where(served, start[dd], np.nan),
            "end": np.where(served, end[dd], np.nan),
            "call_keys": counts, "served": served}


def _stale(ref: Reference, keys, epochs) -> int:
    """Reads served at an epoch older than their key's: every reader
    chooses keys already acknowledged (read-your-acknowledged-writes)."""
    pos = np.minimum(np.searchsorted(ref.keys, keys), ref.keys.size - 1)
    return int(np.count_nonzero(ref.since[pos] > epochs))


def check(cell: Cell, w: dict, ref: Reference, readback) -> dict:
    """The numbers compared, each with its limit (exact: 0)."""
    wrong = unanswered = stale = 0
    r = w["reader"]
    if r is not None:
        n_due = r.due.size
        unanswered += n_due - r.n_submitted
        ok = r.answered[:r.n_submitted]
        unanswered += int(np.count_nonzero(~ok))
        if ok.any():
            keys = r.keys[:r.n_submitted][ok]
            ep = r.epoch[:r.n_submitted][ok]
            wrong += ref.count_wrong(keys, r.payloads[:r.n_submitted][ok],
                                     r.found[:r.n_submitted][ok], ep)
            stale += _stale(ref, keys, ep)
    if w["closed"] is not None:
        ans = [x for x in w["closed"] if x[3] is not None]
        unanswered += len(w["closed"]) - len(ans)
        if ans:
            keys = np.array([x[0] for x in ans])
            ep = np.array([x[3][2] for x in ans], np.int64)
            wrong += ref.count_wrong(
                keys, np.array([x[3][0][0] for x in ans]),
                np.array([x[3][1][0] for x in ans]), ep)
            stale += _stale(ref, keys, ep)
    b = w["bulk"]
    if b is not None:
        cache = {}
        for (j, _, _), a in zip(b.sent, b.answers):
            if a is None:
                unanswered += 1
                continue
            q = cell.bulk_pool[j]
            key = (j, a[2])
            if key not in cache:
                cache[key] = ref.lookup(q, a[2])
            pay, fnd = cache[key]
            wrong += int(np.count_nonzero((a[0] != pay) | (a[1] != fnd)))
    out = {"wrong_answers": [wrong, 0], "unanswered": [unanswered, 0]}
    if cell.loader is not None:
        rb_wrong = 0
        for k, a in readback:
            if a is None:
                rb_wrong += k.size
                continue
            pay, fnd = ref.lookup(k, a[2])
            rb_wrong += int(np.count_nonzero((a[0] != pay) | (a[1] != fnd)
                                             | ~a[1]))
        out["stale_reads"] = [stale, 0]
        out["readback_wrong"] = [rb_wrong, 0]
    return out


def make_run(cell: Cell, w: dict, setup_s: float, seconds: float,
             device_kind: str) -> Run:
    att = attribute(w) if w["reader"] is not None else {}
    window = (w["t0"], w["t1"])
    profile = w["profile"]
    tw = None
    if profile is not None:
        tw = trace_mod.traced_window(profile)
    return Run(seconds=seconds, window=window, setup_s=setup_s,
               build_s=cell.build_s, freeze_s=cell.freeze_s,
               learn_s=cell.learn_s, err_bound=cell.err_bound,
               traffic=cell.traffic, config=cell.cfg,
               lookups=w["lookups"], ingests=w["ingests"],
               batches=w["batches"], bulk=w["bulk"], requests=att,
               reads=w["closed"],
               profile=profile, trace_window=tw, device_kind=device_kind)


def metric_file(root, name: str) -> pathlib.Path:
    """The reader of metric ``name``: ``bench/metrics/<name>.py``, or for a
    split name ``<base>.<part>`` with no file of its own, the base's."""
    d = pathlib.Path(root) / "bench" / "metrics"
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        path = d / (".".join(parts[:k]) + ".py")
        if path.is_file():
            return path
    raise BenchError(f"no reader for metric {name!r} in {d}")


def read_metrics(root, entries, run: Run) -> dict:
    out = {}
    for m in entries:
        v = load_module(metric_file(root, m["name"])).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def window_summary(w: dict, run: Run) -> dict:
    rec = {"phase": "window", "seconds": run.seconds,
           "compiles_in_window": w["compiles"]["compiles"],
           "compile_s_in_window": w["compiles"]["compile_s"],
           "lookup_calls": len(w["lookups"]),
           "ingest_calls": len(w["ingests"]),
           "answer_wait_s": w["t_done"] - w["t1"],
           "gc_count_total_max_s": w["gc"]}
    req = run.requests
    if req:
        lat = (req["end"] - req["due"])[req["served"]]
        late = req["submit"] - req["due"]
        rec.update({
            "requests": int(req["due"].size),
            "offered_per_s": req["due"].size / run.seconds,
            "latency_p50_ms": 1e3 * _pct(lat, 50),
            "latency_p99_ms": 1e3 * _pct(lat, 99),
            "latency_max_ms": 1e3 * float(np.max(lat)) if lat.size else None,
            "generator_late_p50_ms": 1e3 * _pct(late, 50),
            "generator_late_p99_ms": 1e3 * _pct(late, 99),
            "generator_late_max_ms": 1e3 * float(np.max(late)),
            "keys_per_call_mean": float(np.mean(req["call_keys"]))})
        # where the tail comes from: 100 ms bins whose worst latency
        # passes 50 ms, and the lookup calls that took over 20 ms
        t0 = run.window[0]
        b = ((req["due"] - t0) // 0.1).astype(np.int64)[req["served"]]
        worst = np.zeros(int(b.max()) + 1 if b.size else 0)
        np.maximum.at(worst, b, lat)
        rec["slow_bins_s_ms"] = [[round(0.1 * i, 1), round(1e3 * v, 1)]
                                 for i, v in enumerate(worst) if v > 0.05]
    calls = run.calls_in_window(w["lookups"])
    rec["long_calls_s_ms_rows"] = [
        [round(c[0] - run.window[0], 3), round(1e3 * (c[1] - c[0]), 1), c[3]]
        for c in calls if c[1] - c[0] > 0.02][:50]
    if "fanout_lookups" in w:
        rec["fanout_lookups"] = w["fanout_lookups"]
    if w["bulk"] is not None:
        rec["bulk_batches"] = len(w["bulk"].sent)
    if w["closed"] is not None:
        lat = np.array([x[2] - x[1] for x in w["closed"]])
        rec.update({
            "closed_reads": len(w["closed"]),
            "read_p50_ms": 1e3 * _pct(lat, 50),
            "read_p99_ms": 1e3 * _pct(lat, 99),
            "read_max_ms": 1e3 * float(np.max(lat)) if lat.size else None,
            # reads served while the loader ran: answered before its last
            # acknowledgement, and the epochs the reads saw
            "reads_during_load": sum(
                1 for x in w["closed"]
                if x[2] < max((b[3] for b in w["batches"]), default=0.0)),
            "read_epochs": len({x[3][2] for x in w["closed"]
                                if x[3] is not None})})
    if w["batches"]:
        rec["ingest_batches"] = len(w["batches"])
        rec["ingest_s"] = [b[3] - b[2] for b in w["batches"]]
        reps = [b[4] for b in w["batches"]]
        rec["placements"] = sorted({r.placement for r in reps})
        rec["device_syncs"] = sorted({r.device for r in reps})
        rec["device_elems"] = int(sum(r.device_elems for r in reps))
    return rec


# ---------------------------------------------------------------------------


def run_cell(root, spec, workload, seed, seconds, trace, *, t_start=None,
             fault=None, overrides=None, log=emit) -> dict:
    """Build, warm, measure one window, check; returns the result."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(root, spec, workload, seed, fault=fault, overrides=overrides,
                log=log)
    cell.build()
    cell.prepare()
    cell.warm()
    setup_s = time.perf_counter() - t_start
    trace_dir = None
    if trace:
        trace_dir = pathlib.Path(_out_dir(root)) / f"trace-{workload}-{seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    w = cell.window(seconds, trace_dir=trace_dir)
    dev = cell_devices(cell.chips)
    peaks = peak_bytes(dev)
    peak = max(peaks) if peaks else None
    readback = cell.readback(w["batches"]) if cell.loader else []
    counters = {"queue": dict(cell.queue.stats),
                "pipeline": dict(cell.pipe.stats),
                "index": {k: v for k, v in cell.index.stats.items()
                          if isinstance(v, (int, float, str))}}
    cell.close()
    ref = Reference(cell.ref_keys, cell.ref_pays, cell.ref_since)
    cell.mark_acked(ref, cell.loader.batches if cell.loader else [])
    t_ref = time.perf_counter()
    checks = check(cell, w, ref, readback)
    ref_s = time.perf_counter() - t_ref
    run = make_run(cell, w, setup_s, seconds, dev[0].device_kind)
    log(window_summary(w, run))
    log({"phase": "counters", **counters, "memory_peak_bytes": peak,
         "memory_peak_bytes_per_device": peaks, "reference_s": ref_s})
    kind = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(root, cell_metrics(spec, workload, kind), run)
    attempted = (int(w["reader"].due.size) if w["reader"] else 0) + (
        len(w["bulk"].sent) if w["bulk"] else 0) + len(w["batches"]) + (
        len(w["closed"]) if w["closed"] is not None else 0)
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev), "memory_peak_bytes": peak}
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": attempted,
              "failed": int(checks["unanswered"][0]),
              "metrics": metrics, "device": device}
    if trace:
        prof, tw = w["profile"], run.trace_window
        log({"phase": "trace", "dir": str(trace_dir), "planes": w["planes"],
             "chip_planes": [p["name"]
                             for p in trace_mod.device_planes(prof)]})
        device["busy_s"] = trace_mod.busy_seconds(prof, tw)
        device["window_s"] = (tw[1] - tw[0]) / 1e9
        result["breakdown"] = {"device_ops": trace_mod.top_ops(prof, tw),
                               "idle_gaps": trace_mod.idle_gaps(prof, tw)}
        (trace_dir / "trace_trimmed.json").write_text(
            json.dumps(trace_mod.trim(prof, tw)))
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def sweep(root, spec, workload, seed, seconds, rates, *, log=emit) -> None:
    """Offered rates one after another on one set-up: per rate, what was
    completed and how the tail and the generator's lateness behaved."""
    cell = Cell(root, spec, workload, seed, log=log)
    cell.build()
    cell.prepare()
    cell.warm()
    for rate in rates:
        w = cell.window(seconds, rate=rate)
        run = make_run(cell, w, 0.0, seconds, "")
        rec = window_summary(w, run)
        req = run.requests
        if req:
            # a backlog that grows: the last tenth of requests later than
            # the first tenth
            tenth = max(1, req["due"].size // 10)
            late = req["submit"] - req["due"]
            rec["late_growth_ms"] = 1e3 * float(
                np.median(late[-tenth:]) - np.median(late[:tenth]))
            rec["completed_per_s"] = float(np.count_nonzero(
                req["end"] <= w["t1"]) / seconds)
        rec["phase"] = "sweep"
        rec["rate"] = rate
        log(rec)
    cell.close()


def _out_dir(root) -> str:
    d = pathlib.Path(root) / "bench" / "out"
    d.mkdir(parents=True, exist_ok=True)
    return str(d)


def enable_cache(root) -> str:
    import jax

    path = os.environ.get(CACHE_ENV) or str(
        pathlib.Path(root) / "bench" / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", choices=FAULTS, default=None,
                    help="plant a fault or the float32 control (checks)")
    ap.add_argument("--rates", default=None,
                    help="comma-separated offered rates: sweep the open "
                    "loop on one set-up instead of a measured run")
    return ap.parse_args(argv)


def main(argv=None, *, t_start=None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(sys.argv[1:] if argv is None else argv)
    try:
        spec = load_spec(ROOT)
        cell, _, _ = find_cell(spec, args.workload, ROOT)
    except (BenchError, OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < int(cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_cache(ROOT)
    emit({"phase": "device", "platform": devices[0].platform,
          "kind": devices[0].device_kind, "count": len(devices),
          "jax": jax.__version__, "compile_cache": cache})
    if args.rates:
        sweep(ROOT, spec, args.workload, args.seed, args.seconds,
              [float(r) for r in args.rates.split(",")])
        return 0
    result = run_cell(ROOT, spec, args.workload, args.seed, args.seconds,
                      args.trace, t_start=t_start, fault=args.fault)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"check correct {result['correct']}", file=sys.stderr, flush=True)
    emit(result)
    return 0
