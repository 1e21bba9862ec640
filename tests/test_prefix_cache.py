"""Prompt-prefix KV cache tests (serving/prefix_cache.py + the
engine's warm admission path).

The load-bearing invariant: a WARM-admitted request (its prompt's text
KV scattered from the pool, slot starting at pos = text_seq_len) emits
EXACTLY the codes the cold path emits, which in turn equal
``generate_images`` solo — the text KV is a pure function of the
prompt, the RNG chain advance mirrors the cold loop's split-per-step,
and the input token at text_len is the teacher-forced last prompt
token. Pinned for both cache layouts, through slot recycling and under
co-tenancy, per the acceptance contract.

Plus: LRU byte-budget eviction (mid-flight eviction included),
budget-full fallback to the cold path, hash-collision safety (a
fingerprint match alone never serves another prompt's prefix), and the
kv_budget_mb reservation accounting.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_init import init_params
from dalle_tpu.config import ServingConfig, tiny_model_config
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.models.decode import (SamplingConfig, generate_images,
                                     resolve_buckets)
from dalle_tpu.serving import prefix_cache as pc
from dalle_tpu.serving.engine import DecodeEngine
from dalle_tpu.serving.prefix_cache import (PrefixCache,
                                            prefix_entry_bytes,
                                            prompt_fingerprint)
from dalle_tpu.serving.scheduler import SlotScheduler, kv_bytes_per_slot

SAM = SamplingConfig(temperature=1.0, top_k=8)

FLAT = dict(attn_types=("axial_row", "axial_col"), depth=2)
CYCLE = dict(attn_types=("axial_row", "axial_col", "axial_row",
                         "axial_row"), depth=6, shared_block_cycle=4,
             final_conv_block=True, conv_kernel=3)


@pytest.fixture(scope="module")
def flat_setup():
    cfg = tiny_model_config(**FLAT)
    params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def cycle_setup():
    cfg = tiny_model_config(**CYCLE)
    params = init_params(DALLE(cfg), jax.random.PRNGKey(0))
    return cfg, params


def _text(cfg, seed=100):
    return np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (cfg.text_seq_len,), 2,
        cfg.vocab_text))


def _solo(params, cfg, text, key, buckets):
    return np.asarray(generate_images(
        params, cfg, jnp.asarray(text[None]), key, SAM,
        buckets=buckets))[0]


def _engine(cfg, params, n_slots=2, prefix_mb=8.0, **kw):
    return DecodeEngine(
        params, cfg,
        ServingConfig(n_slots=n_slots, steps_per_call=4,
                      prefix_cache_mb=prefix_mb, **kw),
        sampling=SAM).start()


class TestWarmParity:
    """warm == cold == generate_images solo, byte for byte."""

    def test_warm_equals_cold_equals_solo_flat(self, flat_setup):
        cfg, params = flat_setup
        text = _text(cfg)
        buckets = resolve_buckets(None, 2)
        engine = _engine(cfg, params)
        try:
            keys = [jax.random.PRNGKey(7 + i) for i in range(3)]
            rows = [engine.submit(text, np.asarray(k)).result(timeout=120)
                    for k in keys]
        finally:
            engine.stop()
        # first request is the cold landing that pools the prefix;
        # every later one must be warm — and ALL must equal solo
        assert rows[0]["prefix_hit"] is False
        assert rows[1]["prefix_hit"] is True
        assert rows[2]["prefix_hit"] is True
        for row, key in zip(rows, keys):
            assert np.array_equal(row["codes"],
                                  _solo(params, cfg, text, key, buckets))

    def test_warm_parity_on_cycle_layout(self, cycle_setup):
        """The cycle-structured cache (k_body/k_conv, batch on a
        different axis per leaf) runs the same scatter/extract path."""
        cfg, params = cycle_setup
        text = _text(cfg)
        buckets = resolve_buckets(None, 2)
        engine = _engine(cfg, params)
        try:
            k1, k2 = jax.random.PRNGKey(3), jax.random.PRNGKey(4)
            r1 = engine.submit(text, np.asarray(k1)).result(timeout=180)
            r2 = engine.submit(text, np.asarray(k2)).result(timeout=180)
        finally:
            engine.stop()
        assert r2["prefix_hit"] is True
        assert np.array_equal(r1["codes"],
                              _solo(params, cfg, text, k1, buckets))
        assert np.array_equal(r2["codes"],
                              _solo(params, cfg, text, k2, buckets))

    def test_warm_parity_through_recycled_slots_and_cotenants(
            self, flat_setup):
        """The acceptance case: repeated + distinct prompts ragged
        through 2 slots — warm admissions land in RECYCLED slots next
        to cold co-tenants, and every request still reproduces its solo
        reference exactly."""
        cfg, params = flat_setup
        buckets = resolve_buckets(None, 2)
        text_a, text_b, text_c = (_text(cfg, 100), _text(cfg, 101),
                                  _text(cfg, 102))
        trace = [text_a, text_b, text_a, text_c, text_a, text_b]
        engine = _engine(cfg, params)
        try:
            keys = [jax.random.PRNGKey(40 + i)
                    for i in range(len(trace))]
            handles = [engine.submit(t, np.asarray(k))
                       for t, k in zip(trace, keys)]
            rows = [h.result(timeout=240) for h in handles]
        finally:
            engine.stop()
        for row, t, k in zip(rows, trace, keys):
            assert np.array_equal(row["codes"],
                                  _solo(params, cfg, t, k, buckets))
        # the repeats of text_a/text_b behind slot recycling were warm
        hits = [r["prefix_hit"] for r in rows]
        assert sum(hits) >= 2, hits

    def test_eviction_mid_flight_keeps_parity(self, flat_setup):
        """Evicting an entry while a warm-admitted request is still
        decoding only drops the pool's reference — the dispatched
        scatter keeps the device buffers alive and the codes stay
        exact; the NEXT same-prompt request is simply cold again."""
        cfg, params = flat_setup
        text = _text(cfg)
        buckets = resolve_buckets(None, 2)
        engine = _engine(cfg, params)
        try:
            k1, k2, k3 = (jax.random.PRNGKey(11), jax.random.PRNGKey(12),
                          jax.random.PRNGKey(13))
            engine.submit(text, np.asarray(k1)).result(timeout=120)
            h2 = engine.submit(text, np.asarray(k2))   # warm admission
            # evict while (or right after) it decodes
            assert engine.prefix_cache.evict(prompt_fingerprint(text))
            r2 = h2.result(timeout=120)
            r3 = engine.submit(text, np.asarray(k3)).result(timeout=120)
        finally:
            engine.stop()
        assert np.array_equal(r2["codes"],
                              _solo(params, cfg, text, k2, buckets))
        assert np.array_equal(r3["codes"],
                              _solo(params, cfg, text, k3, buckets))


class TestBudgetAndCollisions:
    def test_budget_full_falls_back_to_cold_path(self, flat_setup):
        """A pool whose budget cannot hold ONE entry refuses inserts;
        every admission stays cold (and correct)."""
        cfg, params = flat_setup
        text = _text(cfg)
        buckets = resolve_buckets(None, 2)
        # budget below one entry: entry bytes for this tiny config is
        # ~16 KB, 1e-5 MB ≈ 10 bytes
        engine = _engine(cfg, params, prefix_mb=1e-5)
        try:
            k1, k2 = jax.random.PRNGKey(21), jax.random.PRNGKey(22)
            r1 = engine.submit(text, np.asarray(k1)).result(timeout=120)
            r2 = engine.submit(text, np.asarray(k2)).result(timeout=120)
            stats = engine.prefix_cache.stats()
        finally:
            engine.stop()
        assert r1["prefix_hit"] is False
        assert r2["prefix_hit"] is False
        assert stats["entries"] == 0
        # the refusals are VISIBLE: a pool too small to hold anything
        # must not report healthy telemetry while dropping every insert
        assert stats["refused"] >= 2
        assert np.array_equal(r2["codes"],
                              _solo(params, cfg, text, k2, buckets))

    def test_lru_eviction_under_byte_budget(self, flat_setup):
        """The pool holds floor(budget/entry) entries and evicts least
        recently used first."""
        cfg, params = flat_setup
        entry = prefix_entry_bytes(cfg)
        pool = PrefixCache(entry, budget_bytes=2 * entry)
        kv = {"k": np.zeros(1), "v": np.zeros(1)}
        ta, tb, tc = (np.arange(4, dtype=np.int32),
                      np.arange(4, 8, dtype=np.int32),
                      np.arange(8, 12, dtype=np.int32))
        assert pool.insert("a", ta, kv)
        assert pool.insert("b", tb, kv)
        assert pool.lookup("a", ta) is not None   # refresh a's LRU slot
        assert pool.insert("c", tc, kv)           # evicts b, not a
        assert "a" in pool and "c" in pool and "b" not in pool
        assert pool.stats()["evictions"] == 1
        assert pool.stats()["bytes"] == 2 * entry

    def test_hash_collision_serves_a_miss_never_wrong_prefix(
            self, flat_setup, monkeypatch):
        """Force every prompt onto ONE fingerprint: the second prompt
        must NOT be served the first prompt's prefix — the stored-token
        comparison degrades the collision to a miss, and the codes stay
        exact."""
        cfg, params = flat_setup
        buckets = resolve_buckets(None, 2)
        monkeypatch.setattr(pc, "prompt_fingerprint",
                            lambda tokens: "collide")
        # the engine module imported the name directly — patch it there
        # too (the collision must cover submit-time keying)
        from dalle_tpu.serving import engine as engine_mod
        monkeypatch.setattr(engine_mod, "prompt_fingerprint",
                            lambda tokens: "collide")
        text_a, text_b = _text(cfg, 100), _text(cfg, 101)
        engine = _engine(cfg, params)
        try:
            ka, kb = jax.random.PRNGKey(31), jax.random.PRNGKey(32)
            ra = engine.submit(text_a, np.asarray(ka)).result(timeout=120)
            rb = engine.submit(text_b, np.asarray(kb)).result(timeout=120)
            stats = engine.prefix_cache.stats()
        finally:
            engine.stop()
        assert ra["prefix_hit"] is False
        assert rb["prefix_hit"] is False          # collision -> miss
        assert stats["collisions"] >= 1
        assert np.array_equal(rb["codes"],
                              _solo(params, cfg, text_b, kb, buckets))

    def test_pool_lookup_checks_tokens(self):
        pool = PrefixCache(64, budget_bytes=640)
        toks = np.arange(4, dtype=np.int32)
        pool.insert("k", toks, {"k": 1})
        assert pool.lookup("k", toks) is not None
        assert pool.lookup("k", toks + 1) is None   # collision safety
        assert pool.stats()["collisions"] == 1


class TestAccounting:
    def test_entry_bytes_is_text_fraction_of_slot(self, cycle_setup):
        cfg, _ = cycle_setup
        per_slot = kv_bytes_per_slot(cfg)
        assert prefix_entry_bytes(cfg) == \
            per_slot * cfg.text_seq_len // cfg.total_seq_len

    def test_pool_budget_reserved_out_of_kv_budget(self, flat_setup):
        """With kv_budget_mb set, the pool's budget reduces max_live —
        slots + pool stay under the ONE existing budget."""
        cfg, _ = flat_setup
        per_slot = kv_bytes_per_slot(cfg)
        # a budget worth exactly 4 slots (fractional MB so the clamp
        # binds below n_slots)
        budget_mb = 4 * per_slot / 2 ** 20
        base = SlotScheduler(8, per_slot, kv_budget_mb=budget_mb)
        assert base.max_live == 4
        reserved = SlotScheduler(8, per_slot, kv_budget_mb=budget_mb,
                                 reserved_bytes=2 * per_slot)
        assert reserved.max_live == 2
        # a reserve past the whole budget still leaves one slot
        floor = SlotScheduler(8, per_slot, kv_budget_mb=budget_mb,
                              reserved_bytes=10 ** 12)
        assert floor.max_live == 1

    def test_prefix_counters_ride_readiness_and_stats(self, flat_setup):
        cfg, params = flat_setup
        text = _text(cfg)
        engine = _engine(cfg, params)
        try:
            engine.submit(text, 0).result(timeout=120)
            engine.submit(text, 1).result(timeout=120)
            ready = engine.readiness()
            snap = engine.stats()
        finally:
            engine.stop()
        assert ready["prefix_hits"] == 1
        assert ready["prefix_misses"] == 1
        assert snap["prefix_hits"] == 1
        assert snap["prefix_cache"]["entries"] == 1

    def test_no_pool_means_no_verdict(self, flat_setup):
        """prefix_cache_mb=None (the default): no pool, no per-row
        verdict, admission byte-identical to the r12 path."""
        cfg, params = flat_setup
        text = _text(cfg)
        engine = DecodeEngine(
            params, cfg, ServingConfig(n_slots=1, steps_per_call=4),
            sampling=SAM).start()
        try:
            row = engine.submit(text, 0).result(timeout=120)
        finally:
            engine.stop()
        assert engine.prefix_cache is None
        assert "prefix_hit" not in row
