"""Autotuner: block-constraint invariants + on-disk cache round-trips."""

import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels import autotune, ops
from repro.kernels.packing import pack_kmajor


@pytest.fixture(autouse=True)
def isolated_cache(tmp_path, monkeypatch):
    """Point the cache at a per-test file and reset in-memory state."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.ENV_CACHE_PATH, str(path))
    autotune.reset()
    yield path
    autotune.reset()


# ------------------------------------------------------------- heuristics --
@pytest.mark.parametrize("M,K,N,G", [(1, 512, 512, 0), (256, 384, 128, 64),
                                     (7, 9, 24, 0), (128, 1024, 4096, 128)])
def test_default_blocks_respect_kernel_constraints(M, K, N, G):
    b = autotune.default_blocks(M, K, N, group_size=G)
    assert b["bk"] % 2 == 0                       # planar halves
    if G:
        assert b["bk"] % (2 * G) == 0             # whole groups per half
    assert b["bm"] >= 8 and b["bn"] >= 128


def test_candidate_blocks_include_default_and_are_unique():
    M, K, N, G = 64, 512, 256, 128
    cands = autotune.candidate_blocks(M, K, N, group_size=G)
    assert autotune.default_blocks(M, K, N, G) in cands
    assert len({tuple(sorted(c.items())) for c in cands}) == len(cands)
    for c in cands:
        assert c["bk"] % (2 * G) == 0


def test_get_blocks_without_cache_returns_defaults():
    assert autotune.get_blocks("int4_matmul", 32, 256, 128, "int8") \
        == autotune.default_blocks(32, 256, 128)


# ------------------------------------------------------------ cache round --
def test_tune_persists_and_get_blocks_round_trips(isolated_cache):
    """tune() -> JSON on disk -> fresh in-memory state reads it back."""
    target = {"bm": 64, "bn": 128, "bk": 256}

    def fake_timer(fn):
        blocks = fn()                             # make_call returns blocks
        return 1.0 if blocks == target else 100.0

    best, us = autotune.tune(
        "int4_matmul", lambda blocks: (lambda b=blocks: b),
        64, 512, 256, "int8", timer=fake_timer)
    assert best == target and us == 1.0
    assert isolated_cache.exists()

    autotune.reset()                              # force a re-read from disk
    got = autotune.get_blocks("int4_matmul", 64, 512, 256, "int8")
    assert got == target


def test_tagged_entry_wins_over_untagged(isolated_cache):
    key_args = ("w4a16_matmul", 8, 256, 512, "bfloat16")
    autotune._CACHE[autotune.cache_key(*key_args, group_size=0)] = \
        {"bm": 128, "bn": 128, "bk": 512, "us": 5.0}
    autotune._CACHE[autotune.cache_key(*key_args, group_size=0,
                                       tag="ffn.w_in")] = \
        {"bm": 32, "bn": 128, "bk": 256, "us": 2.0}
    autotune.save_cache()
    autotune.reset()
    tagged = autotune.get_blocks(*key_args, tag="ffn.w_in")
    untagged = autotune.get_blocks(*key_args)
    assert tagged == {"bm": 32, "bn": 128, "bk": 256}
    assert untagged == {"bm": 128, "bn": 128, "bk": 512}


def test_cache_key_distinguishes_dtype_shape_backend():
    keys = {
        autotune.cache_key("int4_matmul", 8, 256, 512, "int8"),
        autotune.cache_key("int4_matmul", 8, 256, 512, "bfloat16"),
        autotune.cache_key("int4_matmul", 16, 256, 512, "int8"),
        autotune.cache_key("int4_matmul", 8, 256, 512, "int8", backend="tpu"),
        autotune.cache_key("w4a16_matmul", 8, 256, 512, "int8"),
        autotune.cache_key("int4_matmul", 8, 256, 512, "int8", group_size=64),
    }
    assert len(keys) == 6


def test_corrupt_cache_file_is_ignored(isolated_cache):
    isolated_cache.write_text("{not json")
    assert autotune.load_cache() == 0
    assert autotune.get_blocks("int4_matmul", 8, 64, 64, "int8") \
        == autotune.default_blocks(8, 64, 64)


def test_load_skips_malformed_entries(isolated_cache):
    isolated_cache.write_text(json.dumps({
        "good|key": {"bm": 8, "bn": 128, "bk": 64, "us": 1.0},
        "bad|key": {"bm": 8},
        "worse|key": 17,
    }))
    assert autotune.load_cache() == 1


def test_tune_skips_failing_candidates(isolated_cache):
    # a rejected tile raises one of the lowering/compile classes the tuner
    # catches (here: no Mosaic lowering); each skip bumps the rejection
    # counter
    boom = {"bm": 32, "bn": 128, "bk": 128}

    def make_call(blocks):
        def run():
            if blocks == boom:
                raise NotImplementedError("unsupported tile")
            return blocks
        return run

    def fake_timer(fn):
        fn()
        return 10.0

    from repro.observability.metrics import global_registry
    rejected = global_registry().counter(
        "autotune_tiles_rejected_total",
        "autotune candidates skipped on lowering/compile failure",
        op="int4_matmul")
    before = rejected.value
    best, _ = autotune.tune("int4_matmul", make_call, 64, 512, 256, "int8",
                            candidates=[boom, {"bm": 64, "bn": 128, "bk": 256}],
                            timer=fake_timer)
    assert best == {"bm": 64, "bn": 128, "bk": 256}
    assert rejected.value == before + 1


def test_tune_raises_when_every_candidate_fails(isolated_cache):
    # a kernel that compiles at no candidate tile must fail the tuning run,
    # not hand back untested defaults (nor persist a dead entry)
    def make_call(blocks):
        def run():
            raise NotImplementedError("no lowering")
        return run

    with pytest.raises(RuntimeError, match="all 2 candidate tiles failed"):
        autotune.tune("int4_matmul", make_call, 64, 512, 256, "int8",
                      candidates=[{"bm": 32, "bn": 128, "bk": 128},
                                  {"bm": 64, "bn": 128, "bk": 256}],
                      timer=lambda fn: (fn(), 10.0)[1])
    assert not isolated_cache.exists()
    assert autotune.get_blocks("int4_matmul", 64, 512, 256, "int8") \
        == autotune.default_blocks(64, 512, 256)


def test_tune_propagates_programming_errors(isolated_cache):
    # a TypeError is a bug in make_call, not a rejected tile: the narrowed
    # except must let it escape instead of silently discarding the
    # candidate
    def make_call(blocks):
        def run():
            raise TypeError("bug, not a bad tile")
        return run

    with pytest.raises(TypeError):
        autotune.tune("int4_matmul", make_call, 64, 512, 256, "int8",
                      candidates=[{"bm": 64, "bn": 128, "bk": 256}],
                      timer=lambda fn: (fn(), 10.0)[1])


def test_tune_key_matches_ops_lookup_key(isolated_cache):
    """The benchmark tunes under the key the ops wrapper reads at serving
    time (op, shape, *activation* dtype, group size).  A drift here makes
    every tuned entry dead weight, so pin the agreement."""
    from repro.kernels.ops import _blocks

    target = {"bm": 8, "bn": 32, "bk": 64}
    autotune.tune("int4_matmul", lambda b: (lambda: b), 8, 64, 32, "int8",
                  timer=lambda fn: 1.0, candidates=[target])
    assert _blocks("int4_matmul", 8, 64, 32, jnp.int8, 0, "", {}) == target
    # a site-tagged lookup falls back to the untagged tuned entry
    assert _blocks("int4_matmul", 8, 64, 32, jnp.int8, 0, "ffn.w_in", {}) \
        == target


# ----------------------------------------------------------- integration ---
def test_tuned_blocks_flow_into_kernel_call(isolated_cache):
    """End-to-end: a cache entry changes the tiles the ops wrapper uses, and
    the result still matches the oracle."""
    rng = np.random.default_rng(5)
    M, K, N = 16, 128, 64
    aq = jnp.asarray(rng.integers(-8, 8, (M, K), np.int8))
    a_s = jnp.ones((M, 1), jnp.float32)
    wq = jnp.asarray(rng.integers(-8, 8, (K, N), np.int8))
    w_s = jnp.ones((1, N), jnp.float32)
    wp = pack_kmajor(wq)

    autotune._CACHE[autotune.cache_key("int4_matmul", M, K, N, "int8")] = \
        {"bm": 8, "bn": 32, "bk": 64, "us": 1.0}
    got = ops.int4_matmul(aq, a_s, wp, w_s, interpret=True)
    exp = jnp.dot(aq.astype(jnp.int32), wq.astype(jnp.int32)).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp), rtol=1e-6)
