"""repro.lint: one positive (flagged) + one negative (clean) fixture per
rule, the suppression-verification contract, report plumbing, and the
sanitizer/compile-guard runtime pieces that don't need a model."""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.lint import (
    CompileGuard,
    lint_source,
    registered_rules,
    report_json,
)


def findings(src, path="x.py", select=None):
    return lint_source(textwrap.dedent(src), path, select=select)


def rules_of(fs):
    return [f.rule for f in fs]


class TestRegistry:
    def test_all_rules_registered(self):
        assert set(registered_rules()) == {"R1", "R2", "R3", "R4", "R5"}


# --------------------------------------------------------------------------
# R1 — cache scatter modes
# --------------------------------------------------------------------------
class TestR1Scatter:
    def test_flags_pr5_past_the_end_scatter(self):
        # the PR 5 bug, verbatim shape: a verify step's multi-token write
        # whose mask-padded tail positions run past max_len; without mode=
        # the scatter CLAMPS them onto the last valid entry, corrupting the
        # newest real K/V — rollback is idx-only and cannot undo it
        fs = findings(
            """
            def write(cache, bidx, positions, k):
                ck = cache["k"].at[bidx, positions].set(k)
                return ck
            """
        )
        assert rules_of(fs) == ["R1"]
        assert "clamp" in fs[0].message

    def test_explicit_mode_is_clean(self):
        fs = findings(
            """
            def write(cache, bidx, positions, k):
                return cache["k"].at[bidx, positions].set(k, mode="drop")
            """
        )
        assert fs == []

    def test_non_cache_target_is_clean(self):
        fs = findings(
            """
            def mask(logits, j):
                keep = logits.at[j].set(0.0)
                return keep
            """
        )
        assert fs == []

    def test_dynamic_update_slice_on_cache_needs_justification(self):
        fs = findings(
            """
            import jax
            def scat(full_cache, one, slot):
                return jax.lax.dynamic_update_slice_in_dim(
                    full_cache, one, slot, axis=1
                )
            """
        )
        assert rules_of(fs) == ["R1"]

    def test_add_scatter_also_flagged(self):
        fs = findings("y = kv_buf.at[i].add(x)\n")
        assert rules_of(fs) == ["R1"]


# --------------------------------------------------------------------------
# R2 — recompile hazards
# --------------------------------------------------------------------------
class TestR2Recompile:
    def test_flags_jit_in_loop(self):
        fs = findings(
            """
            import jax
            for s in (8, 16):
                fn = jax.jit(lambda x: x * s)
                fn(1.0)
            """
        )
        assert "R2" in rules_of(fs)

    def test_flags_throwaway_jit_wrapper(self):
        fs = findings("import jax\nout = jax.jit(lambda x: x + 1)(3.0)\n")
        assert rules_of(fs) == ["R2"]

    def test_hoisted_wrapper_is_clean(self):
        fs = findings(
            """
            import jax
            fn = jax.jit(lambda x: x + 1)
            for _ in range(3):
                out = fn(3.0)
            """
        )
        assert fs == []

    def test_aot_lower_chain_exempt(self):
        fs = findings(
            "import jax\nlowered = jax.jit(lambda x: x).lower(1.0)\n"
        )
        assert fs == []

    def test_flags_traced_value_branch_in_jit(self):
        fs = findings(
            """
            import jax
            @jax.jit
            def step(x, n):
                if n > 0:
                    return x + n
                return x
            """
        )
        assert rules_of(fs) == ["R2"]
        assert "step" in fs[0].message

    def test_static_shape_branch_is_clean(self):
        fs = findings(
            """
            import jax
            @jax.jit
            def step(x, cache):
                if x.shape[0] > 1 and cache is not None and len(x.shape) == 2:
                    return x * 2
                return x
            """
        )
        assert fs == []

    def test_metadata_attribute_branch_is_clean(self):
        # pytree params carry static fields as attributes (pw.M, spec.k)
        fs = findings(
            """
            import jax
            @jax.jit
            def gemm(pw, a):
                scale = pw.scale if pw.scale.shape[-1] == pw.M else pw.scale.T
                return a * scale
            """
        )
        assert fs == []

    def test_static_declared_arg_branch_is_clean(self):
        fs = findings(
            """
            import functools, jax
            @functools.partial(jax.jit, static_argnames=("k",))
            def step(x, k):
                if k > 2:
                    return x[:k]
                return x
            """
        )
        assert fs == []

    def test_flags_unhashable_static_literal(self):
        fs = findings(
            """
            import functools, jax
            @functools.partial(jax.jit, static_argnames=("dims",))
            def f(x, dims):
                return x
            y = f(1.0, dims=[1, 2])
            """
        )
        assert rules_of(fs) == ["R2"]
        assert "tuple" in fs[0].message


# --------------------------------------------------------------------------
# R3 — host syncs on the serving hot path
# --------------------------------------------------------------------------
class TestR3HostSync:
    PATH = "src/repro/serve/engine.py"   # rule is path-scoped

    def test_flags_item_in_tick_loop(self):
        fs = findings(
            """
            def tick(self, logits):
                for slot in range(8):
                    t = logits[slot].item()
            """,
            path=self.PATH,
        )
        assert rules_of(fs) == ["R3"]

    def test_flags_per_element_int_of_device_value(self):
        fs = findings(
            """
            def tick(self, device_out):
                for slot in range(8):
                    tok = int(device_out[slot])
            """,
            path=self.PATH,
        )
        assert rules_of(fs) == ["R3"]

    def test_batched_asarray_then_index_is_clean(self):
        # the idiom the rule pushes toward: one host transfer, host indexing
        fs = findings(
            """
            import numpy as np
            def tick(self, device_out):
                nxt = np.asarray(device_out)
                for slot in range(8):
                    tok = int(nxt[slot])
                    more = [int(t) for t in nxt]
            """,
            path=self.PATH,
        )
        assert fs == []

    def test_other_modules_not_in_scope(self):
        fs = findings(
            """
            def tick(self, logits):
                for slot in range(8):
                    t = logits[slot].item()
            """,
            path="src/repro/models/decoder.py",
        )
        assert fs == []

    def test_block_until_ready_outside_loop_is_clean(self):
        fs = findings(
            """
            import jax
            def run(self):
                jax.block_until_ready(self.cache)
            """,
            path=self.PATH,
        )
        assert fs == []


# --------------------------------------------------------------------------
# R4 — time.time
# --------------------------------------------------------------------------
class TestR4Timing:
    def test_flags_time_time(self):
        fs = findings("import time\nt0 = time.time()\n")
        assert rules_of(fs) == ["R4"]

    def test_flags_from_time_import_time(self):
        fs = findings("from time import time\n")
        assert rules_of(fs) == ["R4"]

    def test_perf_counter_is_clean(self):
        fs = findings("import time\nt0 = time.perf_counter()\n")
        assert fs == []


# --------------------------------------------------------------------------
# R5 — pallas_call geometry
# --------------------------------------------------------------------------
class TestR5Pallas:
    def test_flags_index_map_arity_mismatch(self):
        fs = findings(
            """
            import jax.experimental.pallas as pl
            def launch(kernel, w, bm, bn):
                return pl.pallas_call(
                    kernel,
                    grid=(4, 4, 2),
                    in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
                    out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
                    out_shape=None,
                )(w)
            """
        )
        assert rules_of(fs) == ["R5"]
        assert "2 grid indices" in fs[0].message

    def test_flags_index_map_rank_mismatch(self):
        fs = findings(
            """
            import jax.experimental.pallas as pl
            def launch(kernel, w, bm, bn):
                return pl.pallas_call(
                    kernel,
                    grid=(4, 4),
                    in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j, 0))],
                    out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
                    out_shape=None,
                )(w)
            """
        )
        assert rules_of(fs) == ["R5"]
        assert "rank 2" in fs[0].message

    def test_flags_non_affine_index_expr(self):
        fs = findings(
            """
            import jax.experimental.pallas as pl
            def launch(kernel, w, table, bm):
                return pl.pallas_call(
                    kernel,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((bm,), lambda i: (table[i],))],
                    out_specs=pl.BlockSpec((bm,), lambda i: (i,)),
                    out_shape=None,
                )(w)
            """
        )
        assert rules_of(fs) == ["R5"]
        assert "affine" in fs[0].message

    def test_flags_operand_count_mismatch(self):
        fs = findings(
            """
            import jax.experimental.pallas as pl
            def launch(kernel, w, a, bm):
                return pl.pallas_call(
                    kernel,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((bm,), lambda i: (i,))],
                    out_specs=pl.BlockSpec((bm,), lambda i: (i,)),
                    out_shape=None,
                )(w, a)
            """
        )
        assert rules_of(fs) == ["R5"]
        assert "operand" in fs[0].message

    def test_flags_undercovering_literal_grid(self):
        fs = findings(
            """
            import jax
            import jax.numpy as jnp
            import jax.experimental.pallas as pl
            def launch(kernel, w):
                return pl.pallas_call(
                    kernel,
                    grid=(3,),
                    in_specs=[pl.BlockSpec((128,), lambda i: (i,))],
                    out_specs=pl.BlockSpec((128,), lambda i: (i,)),
                    out_shape=jax.ShapeDtypeStruct((512,), jnp.float32),
                )(w)
            """
        )
        assert rules_of(fs) == ["R5"]
        assert "never" in fs[0].message

    def test_default_capture_and_floordiv_are_clean(self):
        # flash_attention idiom: GQA head-group map with default-arg capture
        fs = findings(
            """
            import jax.experimental.pallas as pl
            def launch(kernel, q, k, g, bq, bk, d):
                return pl.pallas_call(
                    kernel,
                    grid=(2, 8, 4, 4),
                    in_specs=[
                        pl.BlockSpec(
                            (1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)
                        ),
                        pl.BlockSpec(
                            (1, 1, bk, d),
                            lambda b, h, i, j, g=g: (b, h // g, j, 0),
                        ),
                    ],
                    out_specs=pl.BlockSpec(
                        (1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)
                    ),
                    out_shape=None,
                )(q, k)
            """
        )
        assert fs == []

    def test_vmem_budget_uses_autotune_math(self):
        # a "lookup" entry point whose default tile blows the streamed-table
        # budget: at bkg=256, bn=512 the g=4 table alone is over 64 MiB
        fs = findings(
            """
            import jax.experimental.pallas as pl
            def vlut_lookup_entry(kernel, w, *, bm=128, bn=512, bkg=256):
                return pl.pallas_call(
                    kernel,
                    grid=(4, 4, 4),
                    in_specs=[
                        pl.BlockSpec((bm, bkg), lambda i, j, k: (i, k))
                    ],
                    out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
                    out_shape=None,
                )(w)
            """
        )
        assert rules_of(fs) == ["R5"]
        assert "VMEM" in fs[0].message and "autotune" in fs[0].message

    def test_repo_default_tiles_fit_budget(self):
        from repro.kernels.autotune import VMEM_BUDGET_BYTES, tile_vmem_bytes

        # the real entry-point defaults R5 validates in-tree
        assert tile_vmem_bytes(5, "lookup", 128, 128, 128, fused=True) \
            <= VMEM_BUDGET_BYTES
        assert tile_vmem_bytes(4, "decode", 128, 256, 128, fused=True) \
            <= VMEM_BUDGET_BYTES


# --------------------------------------------------------------------------
# suppressions (R0)
# --------------------------------------------------------------------------
class TestSuppressions:
    SRC_FLAGGED = "y = kv_cache.at[i].set(x)\n"

    def test_justified_suppression_silences(self):
        fs = findings(
            "y = kv_cache.at[i].set(x)  "
            "# lint: disable=R1 -- i is bounded by construction\n"
        )
        assert fs == []

    def test_standalone_comment_covers_next_line(self):
        fs = findings(
            "# lint: disable=R1 -- i is bounded by construction\n"
            "y = kv_cache.at[i].set(x)\n"
        )
        assert fs == []

    def test_missing_justification_is_R0_and_does_not_suppress(self):
        fs = findings(
            "y = kv_cache.at[i].set(x)  # lint: disable=R1 -- ok\n"
        )
        assert sorted(rules_of(fs)) == ["R0", "R1"]

    def test_malformed_suppression_is_R0(self):
        fs = findings(
            "y = kv_cache.at[i].set(x)  # lint: disable=R1\n"
        )
        assert sorted(rules_of(fs)) == ["R0", "R1"]

    def test_wrong_rule_does_not_suppress(self):
        fs = findings(
            "y = kv_cache.at[i].set(x)  "
            "# lint: disable=R4 -- wrong rule named here\n"
        )
        assert rules_of(fs) == ["R1"]

    def test_multi_rule_suppression(self):
        fs = findings(
            "import time\n"
            "t = time.time()  # lint: disable=R4, R1 -- display timestamp only\n"
        )
        assert fs == []


# --------------------------------------------------------------------------
# report + CLI
# --------------------------------------------------------------------------
class TestReport:
    def test_json_report_shape(self):
        fs = findings("import time\nt0 = time.time()\n")
        rep = report_json(fs, files_scanned=1)
        assert rep["version"] == 1
        assert rep["counts"] == {"R4": 1}
        assert rep["findings"][0]["rule"] == "R4"
        assert set(rep["rules"]) == {"R1", "R2", "R3", "R4", "R5"}
        json.dumps(rep)   # must be serializable as-is

    def test_syntax_error_is_reported_not_raised(self):
        fs = findings("def broken(:\n")
        assert rules_of(fs) == ["E0"]

    def test_cli_clean_and_dirty_exit_codes(self, tmp_path):
        clean = tmp_path / "clean.py"
        clean.write_text("import time\nt0 = time.perf_counter()\n")
        dirty = tmp_path / "dirty.py"
        dirty.write_text("import time\nt0 = time.time()\n")
        report = tmp_path / "report.json"

        ok = subprocess.run(
            [sys.executable, "-m", "repro.lint", str(clean)],
            capture_output=True, text=True,
        )
        assert ok.returncode == 0, ok.stdout + ok.stderr

        bad = subprocess.run(
            [sys.executable, "-m", "repro.lint", str(dirty),
             "--json", str(report)],
            capture_output=True, text=True,
        )
        assert bad.returncode == 1
        assert "R4" in bad.stdout
        payload = json.loads(report.read_text())
        assert payload["counts"] == {"R4": 1}

    def test_repo_is_lint_clean(self):
        """The acceptance gate, as a test: the tree must stay lint-clean."""
        from repro.lint import lint_paths

        import pathlib

        root = pathlib.Path(__file__).resolve().parent.parent
        fs, n = lint_paths(
            [str(root / "src"), str(root / "tests"), str(root / "benchmarks")]
        )
        assert n > 0
        assert fs == [], "\n".join(f.format() for f in fs)


# --------------------------------------------------------------------------
# runtime sanitizers + compile guard (model-free)
# --------------------------------------------------------------------------
class TestSanitizeRuntime:
    def test_enable_restore_roundtrip(self):
        import jax

        from repro.lint import enable_sanitizers, restore_sanitizers

        prev = enable_sanitizers(debug_nans=False)
        try:
            assert jax.config.jax_numpy_rank_promotion == "raise"
            assert jax.config.jax_numpy_dtype_promotion == "strict"
            import jax.numpy as jnp

            with pytest.raises(ValueError):
                # (3,) + (2, 3) silent rank promotion must now raise
                jnp.ones((3,)) + jnp.ones((2, 3))
        finally:
            restore_sanitizers(prev)
        assert jax.config.jax_numpy_rank_promotion == prev[
            "jax_numpy_rank_promotion"
        ]

    def test_compile_guard_detects_recompiles(self):
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda x: x * 2)
        fn(jnp.ones((2,)))
        guard = CompileGuard({"fn": fn})
        guard.arm()
        fn(jnp.ones((2,)))          # cache hit: steady
        guard.assert_steady()
        fn(jnp.ones((3,)))          # new shape: one miss
        with pytest.raises(AssertionError, match="fn"):
            guard.assert_steady()
        assert guard.new_compiles() == {"fn": 1}

    def test_compile_guard_opaque_callable_is_tracked_as_zero(self):
        guard = CompileGuard({"plain": lambda x: x})
        guard.arm()
        guard.assert_steady()
