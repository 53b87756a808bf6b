"""Steady-state dispatch fast path (ISSUE 1 tentpole).

After the first step, Executor.run pins a per-(program, feed-sig, fetch)
dispatch record and goes straight from the user's feed dict to the jitted
call: no feed re-normalization, no cache-key rebuild, no host-op scan.
Covered here: record reuse on cache hit, fall-back + recompile on feed-shape
change, return_numpy=False round-trips, donation safety of async fetches,
rng advancement on the fast path, and the FLAGS_compile_cache_dir
persistent-compile-cache round trip across processes.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.framework import executor as executor_mod


def _mlp(batch=8, din=16, classes=4, dropout=False):
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = 7
    startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [din], dtype="float32")
        y = fluid.layers.data("y", [1], dtype="int64")
        h = fluid.layers.fc(x, 32, act="relu")
        if dropout:
            h = fluid.layers.dropout(h, dropout_prob=0.5)
        logits = fluid.layers.fc(h, classes)
        loss = fluid.layers.reduce_mean(
            fluid.layers.softmax_with_cross_entropy(logits, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    rs = np.random.RandomState(0)
    feed = {
        "x": rs.rand(batch, din).astype("float32"),
        "y": rs.randint(0, classes, (batch, 1)).astype("int64"),
    }
    return main, startup, feed, loss


def test_cache_hit_reuses_record(monkeypatch):
    main, startup, feed, loss = _mlp()
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        assert exe._fast_hits == 0
        n_records = len(exe._dispatch_records)
        n_compiled = len(exe._cache)
        assert n_records >= 1

        calls = []
        orig = executor_mod._normalize_feed
        monkeypatch.setattr(executor_mod, "_normalize_feed",
                            lambda var, v: calls.append(1) or orig(var, v))
        out = exe.run(main, feed=feed, fetch_list=[loss])
        assert exe._fast_hits == 1
        assert calls == []          # feed re-normalization skipped
        assert len(exe._dispatch_records) == n_records
        assert len(exe._cache) == n_compiled   # no recompile
        assert np.isfinite(out[0]).all()


def test_feed_shape_change_falls_back_and_recompiles():
    main, startup, feed8, loss = _mlp(batch=8)
    _, _, feed4, _ = _mlp(batch=4)
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed8, fetch_list=[loss])
        exe.run(main, feed=feed8, fetch_list=[loss])
        assert exe._fast_hits == 1
        n_compiled = len(exe._cache)

        # shape change: slow path, a second compiled block appears
        out4 = exe.run(main, feed=feed4, fetch_list=[loss])
        assert exe._fast_hits == 1
        assert len(exe._cache) == n_compiled + 1
        assert np.isfinite(out4[0]).all()

        # the replaced record serves the new shape on the next step
        exe.run(main, feed=feed4, fetch_list=[loss])
        assert exe._fast_hits == 2

        # and the old shape falls back again (correct, not cached-fast)
        out8 = exe.run(main, feed=feed8, fetch_list=[loss])
        assert len(exe._cache) == n_compiled + 1  # compiled block reused
        assert np.isfinite(out8[0]).all()


def test_return_numpy_false_roundtrip_matches_numpy_path():
    main, startup, feed, loss = _mlp()

    def run_steps(return_numpy):
        exe = fluid.Executor(fluid.XLAPlace(0))
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            vals = []
            for _ in range(4):
                out = exe.run(main, feed=feed, fetch_list=[loss],
                              return_numpy=return_numpy)
                vals.append(np.asarray(out[0]))
            return vals

    sync = run_steps(True)
    async_ = run_steps(False)
    np.testing.assert_allclose(async_, sync, rtol=1e-6)
    # training actually progressed (the loop is not a no-op)
    assert sync[-1] != sync[0]


def test_donation_safety_after_async_fetch():
    """A fetched written persistable must survive the NEXT step's buffer
    donation (no use-after-donate for return_numpy=False callers)."""
    main, startup, feed, loss = _mlp()
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()) as scope:
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])  # build the record
        rec = next(r for r in exe._dispatch_records.values()
                   if r.nfeeds == 2)
        wname = rec.exe._mutable_names[0]  # an SGD-updated weight

        f1 = exe.run(main, feed=feed, fetch_list=[loss, wname],
                     return_numpy=False)
        exe.run(main, feed=feed, fetch_list=[loss, wname],
                return_numpy=False)
        # materialize AFTER the next step donated the scope buffer
        w_snapshot = np.asarray(f1[1])
        assert np.isfinite(w_snapshot).all()
        w_now = np.asarray(scope.find_var(wname))
        # it is a snapshot of step-1's output, not an alias of live state
        assert not np.array_equal(w_snapshot, w_now)


def test_rng_program_advances_randomness_on_fast_path():
    main, startup, feed, loss = _mlp(dropout=True)
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        losses = [exe.run(main, feed=feed, fetch_list=[loss])[0]
                  for _ in range(3)]
        assert exe._fast_hits == 2
        rec = next(r for r in exe._dispatch_records.values()
                   if r.nfeeds == 2)
        assert rec.rng_used
        # dropout masks (and SGD updates) differ step to step
        assert len({float(l) for l in losses}) > 1


def test_rng_free_program_skips_fold_in():
    main, startup, feed, loss = _mlp(dropout=False)
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        rec = next(r for r in exe._dispatch_records.values()
                   if r.nfeeds == 2)
        assert not rec.rng_used


def test_flag_disables_fast_path():
    from paddle_tpu.framework.core import set_flags

    main, startup, feed, loss = _mlp()
    exe = fluid.Executor(fluid.XLAPlace(0))
    set_flags({"FLAGS_dispatch_fast_path": False})
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])
            exe.run(main, feed=feed, fetch_list=[loss])
            assert exe._fast_hits == 0
            assert not exe._dispatch_records
    finally:
        set_flags({"FLAGS_dispatch_fast_path": True})


def test_program_mutation_invalidates_record():
    main, startup, feed, loss = _mlp()
    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed=feed, fetch_list=[loss])
        assert exe._fast_hits == 1
        # mutate the program: the record's version token must miss, and the
        # full path must recompile instead of serving the stale executable
        blk = main.global_block()
        blk.create_var(name="z2", shape=[8, 1], dtype="float32")
        blk.append_op(type="scale", inputs={"X": [loss.name]},
                      outputs={"Out": ["z2"]}, attrs={"scale": 2.0})
        n_compiled = len(exe._cache)
        out = exe.run(main, feed=feed, fetch_list=[loss, "z2"])
        assert exe._fast_hits == 1           # no false fast hit
        assert len(exe._cache) == n_compiled + 1
        np.testing.assert_allclose(np.asarray(out[1]).ravel()[0],
                                   2.0 * float(out[0]), rtol=1e-5)


def test_prefetch_to_device_roundtrip_and_fastpath_compat():
    """Device-prefetched batches must flow through the dispatch fast path
    (no re-normalization mismatch from x64 canonicalization)."""
    from paddle_tpu.reader import prefetch_to_device

    main, startup, feed, loss = _mlp()
    batches = [dict(feed) for _ in range(4)]
    staged = list(prefetch_to_device(iter(batches), size=2))
    assert len(staged) == 4
    # int64 feeds arrive canonicalized (x64 off -> int32 device arrays)
    assert all(hasattr(b["x"], "devices") for b in staged)

    exe = fluid.Executor(fluid.XLAPlace(0))
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for b in staged:
            out = exe.run(main, feed=b, fetch_list=[loss],
                          return_numpy=False)
        assert exe._fast_hits >= len(staged) - 1
        assert np.isfinite(np.asarray(out[0])).all()

    # producer exceptions surface in the consumer
    def boom():
        yield dict(feed)
        raise RuntimeError("reader died")

    it = prefetch_to_device(boom(), size=1)
    next(it)
    with pytest.raises(RuntimeError, match="reader died"):
        for _ in it:
            pass


_CACHE_SCRIPT = r"""
import logging
import sys

logging.basicConfig(level=logging.INFO, stream=sys.stderr)

import numpy as np
import paddle_tpu as fluid
from paddle_tpu.framework.core import compile_cache_counters, set_flags

set_flags({"FLAGS_compile_cache_dir": sys.argv[1]})
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.layers.data("x", [8], dtype="float32")
    h = fluid.layers.fc(x, 8, act="relu")
    loss = fluid.layers.reduce_mean(h)
exe = fluid.Executor(fluid.XLAPlace(0))
exe.run(startup)
out = exe.run(main, feed={"x": np.ones((2, 8), "float32")},
              fetch_list=[loss])
hits, misses = compile_cache_counters()
print(f"CACHE hits={hits} misses={misses} loss={float(out[0]):.4f}")
"""


def test_persistent_compile_cache_across_processes(tmp_path):
    """Second process compiling the same program must be served from the
    FLAGS_compile_cache_dir on-disk cache (and log the hit)."""
    cache_dir = str(tmp_path / "xla_cache")
    # conftest turns the cache off for the lane and its children; this
    # test is about the cache
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="true")

    def run_once():
        return subprocess.run(
            [sys.executable, "-c", _CACHE_SCRIPT, cache_dir],
            capture_output=True, text=True, env=env, timeout=300)

    r1 = run_once()
    assert r1.returncode == 0, r1.stderr
    assert "misses=" in r1.stdout
    m1 = int(r1.stdout.split("misses=")[1].split()[0])
    assert m1 >= 1        # cold compile populated the cache

    r2 = run_once()
    assert r2.returncode == 0, r2.stderr
    h2 = int(r2.stdout.split("hits=")[1].split()[0])
    m2 = int(r2.stdout.split("misses=")[1].split()[0])
    assert h2 >= 1, (r2.stdout, r2.stderr)   # served from disk
    assert m2 == 0, (r2.stdout, r2.stderr)   # no cold compile
    assert "persistent compile cache hit" in r2.stderr
    # both processes computed the same thing
    assert r1.stdout.split("loss=")[1] == r2.stdout.split("loss=")[1]


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and no directory is then set in
    code; FLAGS_compile_cache_dir yields to it; the default is one fixed
    path inside the checkout (the path is part of the cache key)."""
    import jax

    from paddle_tpu.framework import core

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    thresholds = (jax.config.jax_persistent_cache_min_compile_time_secs,
                  jax.config.jax_persistent_cache_min_entry_size_bytes)
    monkeypatch.setitem(core._compile_cache_state, "dir", None)
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setitem(core._GLOBAL_FLAGS, "FLAGS_compile_cache_dir", "")
        d1, d2 = core.ensure_compile_cache(), core.ensure_compile_cache()
        assert d1 == d2 == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == d1
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1

        monkeypatch.setitem(core._GLOBAL_FLAGS, "FLAGS_compile_cache_dir",
                            "/flag/dir")
        assert core.ensure_compile_cache() == "/flag/dir"
        assert jax.config.jax_compilation_cache_dir == "/flag/dir"

        # the environment places the cache: jax read the variable itself,
        # the code must not overwrite what jax holds
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/env/dir")
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert core.ensure_compile_cache() == "/env/dir"
        assert jax.config.jax_compilation_cache_dir == "sentinel"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          thresholds[0])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          thresholds[1])
