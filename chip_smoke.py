#!/usr/bin/env python3
"""Smoke run of the served index path on a TPU.

    python chip_smoke.py                 # one chip: build -> serve -> ingest
    python chip_smoke.py --chips 4       # four chips: the sharded fan-out only

One chip: ``--n`` distinct integer keys drawn uniformly from [0, 2^40)
(the wide f32 hi/lo key path; payload = the key's rank) are built into a
gap-inserted PGM index (``Index.build``) and served through
``EpochPipeline`` behind a ``MicroBatchQueue``: lookup batches (half
stored keys, half absent), then one ingest of new keys, ``publish()``,
and lookup batches mixing old and new keys.  Every answer is compared
with ``np.searchsorted`` over the sorted key set, and every lookup must
have been served by the device ``fused`` stage (checked through the
engine's ``last_stage`` and the engine, pipeline and queue counters).

Four chips: ``ShardedIndex.build(keys, shards=4)`` served by the
``shard_map`` fan-out across the four chips, compared with the same
reference and with a single-device ``Index`` over the same keys.

Each phase prints JSON records, one per line; the last line is
``{"ok": true, "device": {...}}``.  Any failure exits non-zero before
that line.  Without a TPU the script exits non-zero at once.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
KEY_SPACE = 1 << 40
BUILD = dict(method="pgm", gap_rho=0.15, sample_rate=0.01)


class SmokeError(AssertionError):
    """A check of the smoke run failed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


# ---------------------------------------------------------------------------
# data and reference


def make_keys(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` distinct integers drawn uniformly from [0, 2^40), sorted, as
    f64 (exact: every key is below 2^53)."""
    keys = np.unique(rng.integers(0, KEY_SPACE, n + n // 64 + 64))
    while keys.size < n:
        keys = np.union1d(keys, rng.integers(0, KEY_SPACE, n - keys.size + 64))
    if keys.size > n:
        keys = np.delete(keys, rng.choice(keys.size, keys.size - n,
                                          replace=False))
    return keys.astype(np.float64)


def draw_absent(m: int, stored: np.ndarray,
                rng: np.random.Generator) -> np.ndarray:
    """``m`` distinct integers from [0, 2^40) that are not in the sorted
    array ``stored``."""
    out = np.zeros(0, np.float64)
    while out.size < m:
        c = np.unique(rng.integers(0, KEY_SPACE, 2 * (m - out.size) + 64)
                      ).astype(np.float64)
        pos = np.minimum(np.searchsorted(stored, c), stored.size - 1)
        c = np.setdiff1d(c[stored[pos] != c], out)
        out = np.concatenate([out, c])
    return rng.permutation(out)[:m]


class Reference:
    """The plain reference: ``np.searchsorted`` over the sorted key set
    and its payloads."""

    def __init__(self, keys: np.ndarray, payloads: np.ndarray):
        order = np.argsort(keys, kind="stable")
        self.keys = np.asarray(keys, np.float64)[order]
        self.payloads = np.asarray(payloads, np.int64)[order]

    def add(self, keys: np.ndarray, payloads: np.ndarray) -> "Reference":
        return Reference(np.concatenate([self.keys, keys]),
                         np.concatenate([self.payloads, payloads]))

    def lookup(self, q: np.ndarray):
        pos = np.minimum(np.searchsorted(self.keys, q), self.keys.size - 1)
        found = self.keys[pos] == q
        return np.where(found, self.payloads[pos], -1), found

    def agree(self, res, q: np.ndarray, what: str) -> None:
        pay, found = self.lookup(q)
        n_found = int(np.count_nonzero(np.asarray(res.found) != found))
        n_pay = int(np.count_nonzero(np.asarray(res.payloads) != pay))
        check(n_found == 0 and n_pay == 0,
              f"{what}: {n_found} found / {n_pay} payload mismatches vs "
              "the searchsorted reference")


def device_bytes(arrays) -> int:
    """Bytes of the device buffers of a frozen ``IndexArrays``."""
    import jax
    return int(sum(v.nbytes for v in vars(arrays).values()
                   if isinstance(v, jax.Array)))


def peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


class CompileClock:
    """Backend compile seconds and count in this process, summed from
    JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def since(self, mark) -> dict:
        return {"compile_s": self.seconds - mark[0],
                "compiles": self.count - mark[1]}

    def mark(self):
        return self.seconds, self.count


_CLOCK = None


def compile_clock() -> CompileClock:
    global _CLOCK
    if _CLOCK is None:
        _CLOCK = CompileClock()
    return _CLOCK


# ---------------------------------------------------------------------------
# phases


def phase_build(keys: np.ndarray, seed: int):
    """Host build + device freeze.  Returns ``(index, record)``."""
    from repro.core import Index

    clock = compile_clock()
    t0 = time.perf_counter()
    index = Index.build(keys, rng=np.random.default_rng(seed), **BUILD)
    t_build = time.perf_counter() - t0
    mark = clock.mark()
    t0 = time.perf_counter()
    engine = index.sync_device()
    t_freeze = time.perf_counter() - t0
    rec = {"phase": "build", "n": int(keys.size),
           "n_slots": int(index.gapped.n_slots),
           "segments": int(index.mech.plm.n_segments),
           "key_wide": bool(engine.arrays.key_wide),
           "build_s": t_build, "freeze_s": t_freeze,
           "freeze_compile_s": clock.since(mark)["compile_s"],
           "index_arrays_device_bytes": device_bytes(engine.arrays)}
    return index, rec


def _served_lookup(queue, pipe, index, q, platform: str) -> dict:
    """Submit one lookup batch through the queue and prove where it was
    served: a single queue dispatch, a live (not snapshot) pipeline
    read, and one fused call of the engine whose buffers live on
    ``platform``.  Returns the result and the checks' record."""
    engine = index.sync_device()
    calls0 = engine.stats["calls"]
    live0 = pipe.stats["live_lookups"]
    snap0 = pipe.stats["snapshot_lookups"]
    disp0 = queue.stats["lookup_dispatches"]
    t0 = time.perf_counter()
    res = queue.result(queue.submit_lookup(q))
    dt = time.perf_counter() - t0
    check(res.backend == "fused", f"lookup served by {res.backend!r}")
    check(engine.last_stage == "fused",
          f"engine last stage {engine.last_stage!r}")
    check(engine.stats["calls"] == calls0 + 1, "engine call count")
    check(pipe.stats["live_lookups"] == live0 + 1, "pipeline live read")
    check(pipe.stats["snapshot_lookups"] == snap0,
          "read served by the host snapshot")
    check(queue.stats["lookup_dispatches"] == disp0 + 1, "queue dispatch")
    devs = {d.platform for d in engine.arrays.slot_key.devices()}
    check(devs == {platform}, f"index buffers on {devs}, not {platform}")
    return {"res": res, "seconds": dt, "fused_impl": engine.fused_impl,
            "escapes": int(res.fallbacks),
            "oracle_escapes": int(res.oracle_escapes)}


def _lookup_batches(queue, pipe, index, ref, batches, platform, label):
    clock = compile_clock()
    times, escapes, oracle, impls = [], 0, 0, set()
    mark = clock.mark()
    first_compile = None
    for i, q in enumerate(batches):
        out = _served_lookup(queue, pipe, index, q, platform)
        if first_compile is None:
            first_compile = clock.since(mark)["compile_s"]
        ref.agree(out["res"], q, f"{label} batch {i}")
        times.append(out["seconds"])
        escapes += out["escapes"]
        oracle += out["oracle_escapes"]
        impls.add(out["fused_impl"])
    return {"phase": label, "batches": len(batches),
            "queries": int(sum(q.size for q in batches)),
            "stage": "fused", "fused_impl": sorted(impls),
            "device_checked": len(batches), "reference_checked":
            len(batches), "first_batch_s": times[0],
            "first_batch_compile_s": first_compile,
            "later_batch_s_median": float(np.median(times[1:]))
            if len(times) > 1 else None,
            **clock.since(mark),
            "escapes_host_patched": escapes, "oracle_escapes": oracle}


def phase_serve(index, keys: np.ndarray, rng: np.random.Generator, *,
                n_batches: int = 8, batch: int = 8192,
                n_new: int = 1 << 16, platform: str = "tpu") -> list:
    """Serve through ``MicroBatchQueue`` -> ``EpochPipeline`` -> ``Index``:
    lookups, one ingest + publish, lookups over old and new keys.
    Returns the phase records."""
    from repro.serving import EpochPipeline, MicroBatchQueue

    ref = Reference(keys, np.arange(keys.size, dtype=np.int64))
    pipe = EpochPipeline(index)
    queue = MicroBatchQueue(pipe)
    recs = []
    try:
        half = batch // 2
        before = [rng.permutation(np.concatenate([
            keys[rng.integers(0, keys.size, half)],
            draw_absent(batch - half, keys, rng)]))
            for _ in range(n_batches)]
        recs.append(_lookup_batches(queue, pipe, index, ref, before,
                                    platform, "lookup_before_ingest"))

        new = draw_absent(n_new, keys, rng)
        new_pay = keys.size + np.arange(n_new, dtype=np.int64)
        mark = compile_clock().mark()
        t0 = time.perf_counter()
        rep = queue.result(queue.submit_ingest(new, new_pay))
        t_ingest = time.perf_counter() - t0
        check(rep.n == n_new and rep.slot + rep.chain == n_new,
              f"ingest report {rep}")
        check(queue.stats["ingest_retries"] == 0
              and queue.stats["host_fallbacks"] == 0,
              "ingest was retried or forced onto the host path")
        t0 = time.perf_counter()
        epoch = pipe.publish()
        t_publish = time.perf_counter() - t0
        check(epoch == index.epoch, "publish did not serve the live epoch")
        recs.append({"phase": "ingest", "n": int(rep.n),
                     "slot": int(rep.slot), "chain": int(rep.chain),
                     "contested": int(rep.contested),
                     "placement": rep.placement, "device_sync": rep.device,
                     "device_elems": int(rep.device_elems),
                     "ingest_s": t_ingest, "publish_s": t_publish,
                     **compile_clock().since(mark), "epoch": int(epoch)})

        ref = ref.add(new, new_pay)
        third = batch // 3
        after = [rng.permutation(np.concatenate([
            keys[rng.integers(0, keys.size, third)],
            new[rng.integers(0, n_new, third)],
            draw_absent(batch - 2 * third, ref.keys, rng)]))
            for _ in range(n_batches)]
        recs.append(_lookup_batches(queue, pipe, index, ref, after,
                                    platform, "lookup_after_publish"))
        recs.append({"phase": "serve_stats",
                     "queue": {k: v for k, v in queue.stats.items()},
                     "pipeline": dict(pipe.stats),
                     "index": {k: v for k, v in index.stats.items()
                               if isinstance(v, (int, float))}})
    finally:
        queue.close()
        pipe.close()
    return recs


def phase_sharded(keys: np.ndarray, rng: np.random.Generator, *, seed: int,
                  shards: int = 4, n_batches: int = 4, batch: int = 8192,
                  expect_devices: int = 4) -> list:
    """``ShardedIndex`` fan-out over ``expect_devices`` devices, checked
    against the reference and a single-device ``Index`` over the same
    keys.  Returns the phase records."""
    from repro.core import Index
    from repro.dist import ShardedIndex

    ref = Reference(keys, np.arange(keys.size, dtype=np.int64))
    t0 = time.perf_counter()
    sharded = ShardedIndex.build(keys, shards=shards,
                                 rng=np.random.default_rng(seed), **BUILD)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = Index.build(keys, rng=np.random.default_rng(seed), **BUILD)
    t_single = time.perf_counter() - t0
    half = batch // 2
    times = []
    escapes = 0
    mark = compile_clock().mark()
    for i in range(n_batches):
        q = rng.permutation(np.concatenate([
            keys[rng.integers(0, keys.size, half)],
            draw_absent(batch - half, keys, rng)]))
        fan0 = sharded.stats["fanout_lookups"]
        t0 = time.perf_counter()
        res = sharded.lookup(q, backend="fanout")
        times.append(time.perf_counter() - t0)
        check(res.backend == "sharded-fanout",
              f"sharded lookup served by {res.backend!r}")
        check(sharded.stats["fanout_lookups"] == fan0 + 1,
              "fan-out counter did not rise")
        ref.agree(res, q, f"fanout batch {i}")
        one = single.lookup(q)
        check(one.backend == "fused", f"single index served {one.backend!r}")
        check(np.array_equal(one.payloads, res.payloads)
              and np.array_equal(one.found, res.found),
              f"fanout batch {i} differs from the single-device index")
        escapes += int(res.fallbacks)
    fan = sharded._fan
    check(fan is not None and fan.D == expect_devices,
          f"fan-out spans {getattr(fan, 'D', None)} devices, "
          f"expected {expect_devices}")
    spans = {k: len(v.devices()) for k, v in fan.stacked.items()}
    check(all(s == expect_devices for s in spans.values()),
          f"stacked arrays span {spans}")
    return [{"phase": "sharded_build", "n": int(keys.size),
             "shards": shards, "fan_D": int(fan.D),
             "stacked_devices": int(len(fan.stacked["slot_key"].devices())),
             "sharded_build_s": t_build, "single_build_s": t_single},
            {"phase": "fanout_lookup", "batches": n_batches,
             "queries": n_batches * batch, "stage": "sharded-fanout",
             "reference_checked": n_batches, "single_checked": n_batches,
             "first_batch_s": times[0],
             "later_batch_s_median": float(np.median(times[1:]))
             if n_batches > 1 else None,
             **compile_clock().since(mark),
             "escapes_host_patched": escapes,
             "fanout_lookups": int(sharded.stats["fanout_lookups"]),
             "mispredicts": int(fan.stats["mispredicts"])}]


# ---------------------------------------------------------------------------


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=1 << 25,
                    help="keys to build (default 2^25)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded fan-out phase")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache

    emit({"phase": "device", "platform": devices[0].platform,
          "device_kind": devices[0].device_kind, "count": len(devices),
          "jax": jax.__version__,
          "compile_cache": str(compile_cache.enable())})
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    keys = make_keys(args.n, rng)
    emit({"phase": "data", "n": int(keys.size), "key_max": float(keys[-1]),
          "gen_s": time.perf_counter() - t0})
    if args.chips == 4:
        for rec in phase_sharded(keys, rng, seed=args.seed,
                                 expect_devices=4):
            emit(rec)
    else:
        index, rec = phase_build(keys, args.seed)
        emit(rec)
        for rec in phase_serve(index, keys, rng):
            emit(rec)
    cache = compile_cache.cache_dir()
    emit({"phase": "memory", "peak_bytes_in_use": peak_bytes(),
          "compile_cache_entries": len(os.listdir(cache))
          if cache.is_dir() else 0})
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
