"""Tile autotuner: legal-candidate enumeration under the §4 VMEM budget,
persistent on-disk cache round-trips, cache reuse instead of re-timing, and
ops.py dispatch actually honoring the tuned cache."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pack_weight, ternary_quantize
from repro.kernels import autotune, ref_mpgemm, select_tiles, vlut_mpgemm


@pytest.fixture
def tmp_cache(tmp_path):
    """Point the process-default cache at a throwaway file; restore after."""
    cache = autotune.reset_default_cache(str(tmp_path / "tiles.json"))
    yield cache
    autotune.reset_default_cache()


class TestCandidates:
    @pytest.mark.parametrize("g", [4, 5])
    @pytest.mark.parametrize("impl", ["lookup", "decode"])
    @pytest.mark.parametrize("fused", [True, False])
    def test_all_candidates_respect_vmem_budget(self, g, impl, fused):
        cands = autotune.candidate_tiles(
            g, impl, 4096, 1024, 512, fused=fused
        )
        assert cands
        for t in cands:
            b = autotune.tile_vmem_bytes(
                g, impl, t["bm"], t["bn"], t["bkg"], fused=fused
            )
            assert b <= autotune.VMEM_BUDGET_BYTES, (t, b)
            assert t["bn"] % 128 == 0          # N_tile: multiple of lane width
            assert t["bm"] % 8 == 0            # sublane alignment
            # bkg is the lane dim of the (bm, bkg) packed-weight block
            assert t["bkg"] % 128 == 0 or t["bkg"] == 1024

    def test_lookup_g5_is_table_constrained(self):
        """The 3^5-entry table dominates: no g=5 lookup candidate may pair
        large bkg with large bn (the §4 K_tile rule with VMEM as the cache);
        at 4096 x 1024 groups x 512 only the smallest legal bkg and bn fit."""
        cands = autotune.candidate_tiles(5, "lookup", 4096, 1024, 512)
        assert cands
        for t in cands:
            assert (t["bn"], t["bkg"]) == (128, 128)

    def test_clamped_to_problem(self):
        cands = autotune.candidate_tiles(4, "decode", 16, 4, 8)
        for t in cands:
            assert t["bkg"] <= 4

    def test_heuristic_matches_select_tiles(self):
        for g in (4, 5):
            for impl in ("lookup", "decode"):
                assert select_tiles(g, impl) == autotune.heuristic_tiles(g, impl)


class TestCacheRoundTrip:
    def test_disk_round_trip(self, tmp_path):
        path = str(tmp_path / "tiles.json")
        c1 = autotune.TileCache(path)
        key = autotune.cache_key(5, "lookup", 320, 64, 32, backend="cpu", fused=True)
        c1.put(key, dict(bm=64, bn=128, bkg=16), seconds=1.25e-3)
        # a fresh instance (fresh process analogue) reads the same winner
        c2 = autotune.TileCache(path)
        assert c2.get(key) == dict(bm=64, bn=128, bkg=16)
        raw = json.load(open(path))
        assert raw[key]["seconds"] == pytest.approx(1.25e-3)

    def test_missing_and_corrupt_cache_are_empty(self, tmp_path):
        assert autotune.TileCache(str(tmp_path / "nope.json")).get("k") is None
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert autotune.TileCache(str(bad)).get("k") is None


class TestTuneAndReuse:
    def test_cached_entries_reused_instead_of_retimed(self, tmp_path):
        cache = autotune.TileCache(str(tmp_path / "tiles.json"))
        calls = []

        def fake_bench(tiles):
            calls.append(dict(tiles))
            return float(tiles["bkg"])  # smallest bkg "wins"

        args = dict(fused=True, backend="test", cache=cache, benchmark=fake_bench,
                    tune_if_missing=True)
        t1 = autotune.get_tiles(4, "decode", 64, 16, 32, **args)
        assert calls, "cold cache must time candidates"
        n_timed = len(calls)
        assert t1["bkg"] == min(c["bkg"] for c in calls)
        # warm cache: no further timing, identical answer
        t2 = autotune.get_tiles(4, "decode", 64, 16, 32, **args)
        assert t2 == t1
        assert len(calls) == n_timed

    def test_env_tuning_skips_interpret_backend(self, tmp_path, monkeypatch):
        """REPRO_VLUT_AUTOTUNE=1 must not time candidates through the
        interpreter (minutes per candidate, meaningless numbers): interpret
        dispatch gets the heuristic unless tuning is requested explicitly."""
        monkeypatch.setenv(autotune.TUNE_ENV, "1")
        cache = autotune.TileCache(str(tmp_path / "tiles.json"))
        calls = []
        t = autotune.get_tiles(
            4, "decode", 64, 16, 32,
            fused=True, backend="interpret", cache=cache,
            benchmark=lambda tiles: calls.append(tiles) or 1.0,
        )
        assert not calls
        assert t == autotune.heuristic_tiles(4, "decode", fused=True)

    def test_cold_cache_falls_back_to_heuristic(self, tmp_path):
        cache = autotune.TileCache(str(tmp_path / "tiles.json"))
        t = autotune.get_tiles(
            5, "lookup", 64, 16, 32,
            fused=True, backend="test", cache=cache, tune_if_missing=False,
        )
        assert t == autotune.heuristic_tiles(5, "lookup", fused=True)

    def test_fused_heuristic_respects_budget(self):
        """The cold-cache fallback must fit the *fused* working set (f32 A
        tile + int32 scratch), not just the unfused int8 one."""
        for g in (4, 5):
            for impl in ("lookup", "decode"):
                t = autotune.heuristic_tiles(g, impl, fused=True)
                assert (
                    autotune.tile_vmem_bytes(g, impl, **t, fused=True)
                    <= autotune.VMEM_BUDGET_BYTES
                ), (g, impl, t)

    def test_tune_times_real_kernel_and_persists(self, tmp_path):
        """End-to-end: tune() on a tiny problem with the real (interpreted)
        kernel benchmark writes a winner that get_tiles then serves."""
        cache = autotune.TileCache(str(tmp_path / "tiles.json"))
        cands = [dict(bm=8, bn=128, bkg=4), dict(bm=8, bn=128, bkg=8)]
        res = autotune.tune(
            4, "decode", 8, 8, 4,
            fused=True, interpret=True, cache=cache, candidates=cands,
        )
        assert res.tiles in cands
        assert len(res.trials) == len(cands)
        assert all(s > 0 for _, s in res.trials)
        hit = autotune.get_tiles(
            4, "decode", 8, 8, 4,
            fused=True, backend="interpret", cache=cache, tune_if_missing=False,
        )
        assert hit == res.tiles


class TestRefusedCandidates:
    """On a hardware backend the compiler may refuse a tile: it loses. If it
    refuses every tile, tune raises instead of guessing."""

    CANDS = [dict(bm=8, bn=128, bkg=128), dict(bm=8, bn=128, bkg=256)]

    def test_refused_candidate_loses(self, tmp_path):
        def bench(tiles):
            if tiles["bkg"] == 128:
                raise RuntimeError("Mosaic refused the block")
            return 1.0

        res = autotune.tune(
            4, "decode", 8, 256, 8, backend="tpu", candidates=self.CANDS,
            cache=autotune.TileCache(str(tmp_path / "tiles.json")),
            benchmark=bench,
        )
        assert res.tiles == self.CANDS[1]
        assert [t for t, _ in res.trials] == [self.CANDS[1]]

    def test_every_candidate_refused_raises(self, tmp_path):
        cache = autotune.TileCache(str(tmp_path / "tiles.json"))

        def bench(tiles):
            raise RuntimeError("Mosaic refused the block")

        with pytest.raises(RuntimeError, match="every tile candidate"):
            autotune.tune(
                4, "decode", 8, 256, 8, backend="tpu", candidates=self.CANDS,
                cache=cache, benchmark=bench,
            )
        assert cache.get(autotune.cache_key(
            4, "decode", 8, 256, 8, backend="tpu", fused=True)) is None

    def test_interpreter_failure_is_a_bug(self, tmp_path):
        def bench(tiles):
            raise ValueError("kernel bug")

        with pytest.raises(ValueError, match="kernel bug"):
            autotune.tune(
                4, "decode", 8, 256, 8, interpret=True, candidates=self.CANDS,
                cache=autotune.TileCache(str(tmp_path / "tiles.json")),
                benchmark=bench,
            )


class TestDispatchIntegration:
    def test_ops_dispatch_uses_cached_tiles(self, tmp_cache):
        """Seed the process cache with odd-but-legal tiles for the exact
        segment the fused dispatch will ask about; the kernel must run with
        them (observable: result still exact vs the oracle, and the cache is
        the only place those tiles exist)."""
        m, k, n = 16, 40, 8   # single g=5 segment of 8 groups
        key = autotune.cache_key(
            5, "decode", m, 8, n, backend="interpret", fused=True
        )
        tmp_cache.put(key, dict(bm=8, bn=128, bkg=2))
        assert autotune.get_tiles(
            5, "decode", m, 8, n, fused=True, backend="interpret",
            tune_if_missing=False,
        ) == dict(bm=8, bn=128, bkg=2)

        rng = np.random.default_rng(0)
        w = rng.standard_normal((m, k)).astype(np.float32)
        a = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32))
        tw = ternary_quantize(jnp.asarray(w))
        pw = pack_weight(tw.values, tw.scale, "i1")
        out = np.asarray(vlut_mpgemm(pw, a, impl="decode", interpret=True))
        np.testing.assert_allclose(
            out, np.asarray(ref_mpgemm(pw, a)), rtol=1e-6, atol=1e-6
        )
