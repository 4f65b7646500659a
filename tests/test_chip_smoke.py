"""``chip_smoke.py`` rehearsed on the CPU: its phase functions at 2^14
keys with 1024-query batches (agreement with the searchsorted
reference, every stage check evaluated), and ``main()`` refusing a
platform that is not a TPU."""

import importlib.util
import json
import pathlib
import types

import jax
import numpy as np
import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _keys(smoke, n=1 << 14, seed=0):
    rng = np.random.default_rng(seed)
    return smoke.make_keys(n, rng), rng


def test_make_keys_distinct_wide_uniform(smoke):
    keys, rng = _keys(smoke)
    assert keys.size == 1 << 14
    assert np.all(np.diff(keys) > 0)
    assert keys[0] >= 0 and keys[-1] < smoke.KEY_SPACE
    assert keys[-1] > 2 ** 24  # the wide hi/lo key path
    absent = smoke.draw_absent(3000, keys, rng)
    assert np.unique(absent).size == 3000
    assert not np.isin(absent, keys).any()


def test_reference_agree_catches_mismatch(smoke):
    keys, _ = _keys(smoke, n=64)
    ref = smoke.Reference(keys, np.arange(64, dtype=np.int64))
    q = np.concatenate([keys[:4], keys[:2] + 0.5])
    pay, found = ref.lookup(q)
    assert pay.tolist() == [0, 1, 2, 3, -1, -1]
    assert found.tolist() == [True] * 4 + [False] * 2

    res = types.SimpleNamespace(payloads=pay.copy(), found=found.copy())
    ref.agree(res, q, "exact")
    res.payloads[1] = 7
    with pytest.raises(smoke.SmokeError, match="1 payload mismatches"):
        ref.agree(res, q, "off by one")


def test_build_and_serve_phases_on_cpu(smoke):
    keys, rng = _keys(smoke)
    index, rec = smoke.phase_build(keys, seed=0)
    assert rec["n"] == keys.size and rec["n_slots"] >= keys.size
    assert rec["key_wide"] is True
    assert rec["index_arrays_device_bytes"] > 4 * rec["n_slots"]
    recs = smoke.phase_serve(index, keys, rng, n_batches=3, batch=1024,
                             n_new=1024, platform="cpu")
    by = {r["phase"]: r for r in recs}
    for phase in ("lookup_before_ingest", "lookup_after_publish"):
        r = by[phase]
        assert r["device_checked"] == r["reference_checked"] == 3
        assert r["stage"] == "fused" and r["fused_impl"] == ["xla"]
        assert r["queries"] == 3 * 1024
        assert r["compiles"] >= 0 and r["first_batch_compile_s"] >= 0
    ing = by["ingest"]
    assert ing["n"] == 1024 and ing["slot"] + ing["chain"] == 1024
    stats = by["serve_stats"]
    assert stats["pipeline"]["live_lookups"] == 6
    assert stats["pipeline"]["snapshot_lookups"] == 0
    assert stats["queue"]["lookup_dispatches"] == 6
    assert stats["queue"]["host_fallbacks"] == 0
    json.dumps(recs)  # every record prints as one JSON line


def test_serve_phase_refuses_a_host_served_read(smoke, monkeypatch):
    """The device check reads what served, not the batch size: a read
    the engine did not serve fails the phase."""
    keys, rng = _keys(smoke, n=1 << 12)
    index, _ = smoke.phase_build(keys, seed=0)
    index.min_device_batch = 1 << 20  # every read now goes to the host
    with pytest.raises(smoke.SmokeError, match="numpy-oracle"):
        smoke.phase_serve(index, keys, rng, n_batches=1, batch=1024,
                          n_new=256, platform="cpu")


def test_sharded_phase_on_cpu(smoke):
    keys, rng = _keys(smoke)
    d = len(jax.devices())
    want = 4 if d >= 4 else (2 if d >= 2 else 1)
    recs = smoke.phase_sharded(keys, rng, seed=0, n_batches=2, batch=1024,
                               expect_devices=want)
    build, look = recs
    assert build["fan_D"] == want and build["stacked_devices"] == want
    assert look["reference_checked"] == look["single_checked"] == 2
    assert look["fanout_lookups"] == 2


def test_main_refuses_a_platform_that_is_not_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    rc = smoke.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok": true' not in out.out
    assert "needs a TPU" in out.err


def test_compile_cache_dir_is_fixed_or_from_the_environment(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    otherwise the cache is the fixed, git-ignored ``<checkout>/.jax_cache``.
    The cache itself is never turned on here."""
    from repro import compile_cache

    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache.DEFAULT_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.cache_dir() == compile_cache.DEFAULT_DIR
    monkeypatch.setenv(compile_cache.ENV, "/srv/xla-cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == pathlib.Path("/srv/xla-cache")
    assert jax.config.jax_compilation_cache_dir == before
