"""repro.lint.ir: one positive (firing) + one negative (clean) fixture per
IR pass (I1–I5), the registry-level suppression contract (I0), registry
coverage/determinism, and the repo-is-IR-clean acceptance gate."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.lint.ir import (
    IREntry,
    all_eqns,
    default_entries,
    mpgemm_entries,
    pinned_trace_env,
    registered_passes,
    run_passes,
    signature,
    snapshot_dir,
    write_snapshot,
)

REPO = pathlib.Path(__file__).resolve().parent.parent


def entry(fn, *args, name="fixture/f", meta=None, suppress=None):
    return IREntry(
        name=name, jaxpr=jax.make_jaxpr(fn)(*args),
        meta=meta or {}, suppress=suppress or {},
    )


def rules_of(fs):
    return sorted(f.rule for f in fs)


def prims_of(e):
    return {eqn.primitive.name for eqn, _ in all_eqns(e.jaxpr.jaxpr)}


# fixture shapes: codes are the packed-trit stand-in (uint8 taints I1)
N, K, M = 8, 16, 4
CODES = jnp.zeros((N, K), jnp.uint8)
ACT = jnp.zeros((K, M), jnp.float32)
ACT_I8 = jnp.zeros((K, M), jnp.int8)
WSCALE = jnp.ones((N, 1), jnp.float32)

_DOT = (((1,), (0,)), ((), ()))


class TestRegistry:
    def test_all_passes_registered(self):
        assert set(registered_passes()) == {"I1", "I2", "I3", "I4", "I5"}


# --------------------------------------------------------------------------
# I1 — quantized-dtype flow
# --------------------------------------------------------------------------
class TestI1DtypeFlow:
    def test_flags_promoted_f32_lut_kernel(self):
        # the forbidden rework: decode trit codes straight to float and run
        # the heavy dot in f32 with NO scale applied — numerically fine,
        # performance class forfeited
        def promoted(codes, a):
            w = codes.astype(jnp.float32) - 1.0
            return jax.lax.dot_general(w, a, _DOT)

        fs = run_passes([entry(promoted, CODES, ACT)], select={"I1"})
        assert rules_of(fs) == ["I1"]
        assert "float" in fs[0].message

    def test_int8_datapath_is_clean(self):
        # the intended datapath: integer dot over decoded trits, dequant
        # (scale mul) only in the epilogue
        def int8_path(codes, a_q, w_scale):
            w = codes.astype(jnp.int8) - jnp.int8(1)
            acc = jax.lax.dot_general(
                w, a_q, _DOT, preferred_element_type=jnp.int32
            )
            return acc.astype(jnp.float32) * w_scale

        fs = run_passes(
            [entry(int8_path, CODES, ACT_I8, WSCALE)], select={"I1"}
        )
        assert fs == []

    def test_dequant_before_dot_is_clean(self):
        # mad_dense idiom: applying the scale BEFORE the dot is the dequant
        # event — the float dot downstream is legitimate
        def dequant_first(codes, a, w_scale):
            w = (codes.astype(jnp.float32) - 1.0) * w_scale
            return jax.lax.dot_general(w, a, _DOT)

        fs = run_passes(
            [entry(dequant_first, CODES, ACT, WSCALE)], select={"I1"}
        )
        assert fs == []

    def test_lut_index_use_is_clean(self):
        # using codes as gather indices IS the LUT technique — index
        # operands must not propagate taint
        def lut_gather(codes, table, a):
            w = table[codes.astype(jnp.int32)]     # (N, K) f32 via lookup
            return jax.lax.dot_general(w, a, _DOT)

        table = jnp.zeros((3,), jnp.float32)
        fs = run_passes([entry(lut_gather, CODES, table, ACT)], select={"I1"})
        assert fs == []

    def test_taint_follows_through_pjit(self):
        inner = jax.jit(
            lambda w, a: jax.lax.dot_general(w, a, _DOT)
        )

        def promoted_nested(codes, a):
            return inner(codes.astype(jnp.float32) - 1.0, a)

        fs = run_passes([entry(promoted_nested, CODES, ACT)], select={"I1"})
        assert rules_of(fs) == ["I1"]


# --------------------------------------------------------------------------
# I2 — effect/host audit
# --------------------------------------------------------------------------
class TestI2Effects:
    def test_flags_debug_callback(self):
        def chatty(x):
            jax.debug.print("x = {}", x)
            return x + 1.0

        fs = run_passes([entry(chatty, ACT)], select={"I2"})
        assert "I2" in rules_of(fs)
        assert "debug" in fs[0].message

    def test_flags_argument_derived_device_put(self):
        def shipping(x):
            return jax.device_put(x * 2.0) + 1.0

        fs = run_passes([entry(shipping, ACT)], select={"I2"})
        assert rules_of(fs) == ["I2"]
        assert "argument-derived" in fs[0].message

    def test_pure_device_program_is_clean(self):
        def pure(w, a):
            return jax.lax.dot_general(w.T, a, _DOT)

        fs = run_passes([entry(pure, jnp.zeros((K, N)), ACT)], select={"I2"})
        assert fs == []

    def test_constant_device_put_is_not_flagged(self):
        # device_put of data derived from a closed-over CONSTANT is hoisted
        # once, not a per-step transfer, and must stay silent. (A device_put
        # of the bare concrete constant runs eagerly at trace time and
        # leaves no eqn at all, so the fixture derives a traced value first.)
        table = np.arange(M, dtype=np.float32)

        def hoisted(x):
            return x + jax.device_put(jnp.asarray(table) * 2.0)

        e = entry(hoisted, ACT, name="fixture/const_device_put")
        assert "device_put" in prims_of(e)   # the discrimination is real
        assert run_passes([e], select={"I2"}) == []


# --------------------------------------------------------------------------
# I3 — dead code
# --------------------------------------------------------------------------
class TestI3DeadCode:
    def test_flags_dead_dot(self):
        def leftover(w, a):
            _dead = jax.lax.dot_general(w.T, a, _DOT)
            return a + 1.0

        fs = run_passes([entry(leftover, jnp.zeros((K, N)), ACT)],
                        select={"I3"})
        assert rules_of(fs) == ["I3"]
        assert "dot_general" in fs[0].message

    def test_flags_large_dead_intermediate(self):
        def bloated(x):
            _dead = jnp.broadcast_to(x[0, 0], (512, 512))  # 1 MiB, dropped
            return x * 2.0

        fs = run_passes([entry(bloated, ACT)], select={"I3"})
        assert rules_of(fs) == ["I3"]

    def test_small_dead_plumbing_is_quiet(self):
        # serve-mode graphs drop small scalars all the time — not findings
        def tiny(x):
            _dead = x[0, 0] + 1.0
            return x * 2.0

        fs = run_passes([entry(tiny, ACT)], select={"I3"})
        assert fs == []

    def test_all_live_is_clean(self):
        def live(w, a):
            return jax.lax.dot_general(w.T, a, _DOT) + 1.0

        fs = run_passes([entry(live, jnp.zeros((K, N)), ACT)], select={"I3"})
        assert fs == []

    def test_dead_inside_pjit_dropped_by_caller(self):
        # pjit bodies are entered with the CALLER's output liveness: compute
        # returned by the jit but dropped by every caller is dead
        inner = jax.jit(lambda w, a: (
            jax.lax.dot_general(w.T, a, _DOT), a + 1.0
        ))

        def outer(w, a):
            _dropped, keep = inner(w, a)
            return keep

        fs = run_passes([entry(outer, jnp.zeros((K, N)), ACT)],
                        select={"I3"})
        assert rules_of(fs) == ["I3"]
        assert "jit/" in fs[0].message


# --------------------------------------------------------------------------
# I4 — traffic vs roofline
# --------------------------------------------------------------------------
class TestI4Traffic:
    GEMM_META = dict(m_out=N, k=K, m_tokens=M, fused=True)

    @staticmethod
    def gemm(w, a):
        return jax.lax.dot_general(w.T, a, _DOT)

    def test_forced_tiny_factor_fires(self):
        e = entry(self.gemm, jnp.zeros((K, N)), ACT,
                  meta=dict(self.GEMM_META, traffic_factor=1e-6))
        fs = run_passes([e], select={"I4"})
        assert rules_of(fs) == ["I4"]
        assert "roofline" in fs[0].message

    def test_generous_factor_is_clean(self):
        e = entry(self.gemm, jnp.zeros((K, N)), ACT,
                  meta=dict(self.GEMM_META, traffic_factor=1e6))
        assert run_passes([e], select={"I4"}) == []

    def test_entry_without_cost_meta_is_skipped(self):
        e = entry(self.gemm, jnp.zeros((K, N)), ACT)   # no m_out/k/m_tokens
        assert run_passes([e], select={"I4"}) == []

    def test_estimate_ignores_fused_away_views(self):
        from repro.lint.ir.traffic import estimate_bytes

        viewy = entry(lambda x: x.reshape(M, K).T, ACT)
        assert estimate_bytes(viewy.jaxpr.jaxpr) == 0.0

    def test_estimate_counts_dot_io(self):
        from repro.lint.ir.traffic import estimate_bytes

        e = entry(self.gemm, jnp.zeros((K, N)), ACT)
        # transpose is a view; the dot moves its two operands + one output
        want = 4 * (K * N + K * M + N * M)
        assert estimate_bytes(e.jaxpr.jaxpr) == float(want)


# --------------------------------------------------------------------------
# I5 — golden jaxpr snapshots
# --------------------------------------------------------------------------
def _snap_fn_a(w, a):
    return jax.lax.dot_general(w.T, a, _DOT)


def _snap_fn_b(w, a):
    # structurally different graph under the SAME entry name -> stale
    return jax.lax.dot_general(w.T, a, _DOT) * 2.0 + 1.0


class TestI5Snapshots:
    W = jnp.zeros((K, N), jnp.float32)

    def test_missing_snapshot_is_a_finding(self, tmp_path):
        e = entry(_snap_fn_a, self.W, ACT, name="fixture/snap")
        fs = run_passes([e], select={"I5"}, snapshot_root=str(tmp_path))
        assert rules_of(fs) == ["I5"]
        assert "no golden snapshot" in fs[0].message

    def test_update_then_check_roundtrip(self, tmp_path):
        e = entry(_snap_fn_a, self.W, ACT, name="fixture/snap")
        fs = run_passes([e], select={"I5"}, snapshot_root=str(tmp_path),
                        update_snapshots=True)
        assert fs == []
        path = tmp_path / jax.default_backend() / "fixture__snap.json"
        payload = json.loads(path.read_text())
        assert payload["entry"] == "fixture/snap"
        assert payload["primitives"].get("dot_general") == 1
        # retracing the same fn must verify clean
        e2 = entry(_snap_fn_a, self.W, ACT, name="fixture/snap")
        assert run_passes([e2], select={"I5"},
                          snapshot_root=str(tmp_path)) == []

    def test_stale_snapshot_is_a_finding(self, tmp_path):
        e = entry(_snap_fn_a, self.W, ACT, name="fixture/snap")
        write_snapshot(e, str(tmp_path))
        changed = entry(_snap_fn_b, self.W, ACT, name="fixture/snap")
        fs = run_passes([changed], select={"I5"},
                        snapshot_root=str(tmp_path))
        assert rules_of(fs) == ["I5"]
        assert "diverged" in fs[0].message
        assert "mul" in fs[0].message          # the primitive-count delta

    def test_signature_is_structural_not_identity(self):
        h1, c1 = signature(jax.make_jaxpr(_snap_fn_a)(self.W, ACT))
        h2, c2 = signature(jax.make_jaxpr(_snap_fn_a)(self.W, ACT))
        assert (h1, c1) == (h2, c2)            # fresh trace, same hash
        h3, _ = signature(
            jax.make_jaxpr(_snap_fn_a)(self.W, jnp.zeros((K, 2 * M)))
        )
        assert h3 != h1                        # shapes enter the hash


# --------------------------------------------------------------------------
# I0 — registry-level suppression contract
# --------------------------------------------------------------------------
class TestI0Suppressions:
    def firing_entry(self, suppress):
        return entry(
            TestI4Traffic.gemm, jnp.zeros((K, N)), ACT,
            meta=dict(TestI4Traffic.GEMM_META, traffic_factor=1e-6),
            suppress=suppress,
        )

    def test_justified_suppression_silences(self):
        e = self.firing_entry(
            {"I4": "table residency is measured by the crossover bench"}
        )
        assert run_passes([e], select={"I4"}) == []

    def test_under_justified_is_I0_and_does_not_suppress(self):
        e = self.firing_entry({"I4": "ok"})
        fs = run_passes([e], select={"I4"})
        assert rules_of(fs) == ["I0", "I4"]

    def test_wrong_pass_does_not_suppress(self):
        e = self.firing_entry(
            {"I1": "this justification names the wrong pass"}
        )
        fs = run_passes([e], select={"I4"})
        assert rules_of(fs) == ["I4"]


# --------------------------------------------------------------------------
# registry coverage, determinism, CLI contract, and the acceptance gate
# --------------------------------------------------------------------------
ENGINE_NAMES = {
    "engine/prefill1", "engine/decode", "engine/chunk_verify",
    "engine/verify", "engine/drafter.prefill", "engine/drafter.verify",
    "engine/drafter.decode", "engine/tree_verify", "engine/compact",
    "engine/paged_decode", "engine/paged_chunk_verify",
    "engine/set_tab", "engine/scrub", "engine/paged_compact",
}
FULL_ONLY_NAMES = {
    "engine/mla_decode", "engine/mla_chunk_verify",
    "engine/paged_mla_decode", "engine/paged_mla_chunk_verify",
}
IMPLS = (
    "vlut", "vlut_packed_fused", "vlut_packed_unfused",
    "scalar_lut", "mad_dense", "mad_int8",
)


class TestRegistryAndGate:
    @pytest.fixture(scope="class")
    def entries(self):
        return default_entries()

    def test_registry_covers_every_impl_and_entry_point(self, entries):
        from repro.lint.ir.registry import QUICK_MS

        names = {e.name for e in entries}
        for impl in IMPLS:
            for m in QUICK_MS:
                assert f"mpgemm/{impl}/M{m}" in names
        assert ENGINE_NAMES <= names

    def test_mpgemm_meta_feeds_the_traffic_pass(self, entries):
        for e in entries:
            if e.kind == "mpgemm":
                assert {"impl", "m_out", "k", "m_tokens", "fused"} <= set(
                    e.meta
                )

    def test_snapshots_exist_for_full_registry(self):
        """Acceptance: a committed golden snapshot for every engine entry
        and every mpGeMM impl x fusion combo at every nightly M — by
        filename, so this stays cheap (no full-lane tracing here)."""
        from repro.lint.ir.registry import FULL_MS

        snap = pathlib.Path(snapshot_dir(str(REPO / "tests"
                                              / "ir_snapshots")))
        want = {
            f"mpgemm/{impl}/M{m}" for impl in IMPLS for m in FULL_MS
        } | ENGINE_NAMES | FULL_ONLY_NAMES
        have = {p.stem.replace("__", "/") for p in snap.glob("*.json")}
        missing = want - have
        assert not missing, f"missing golden snapshots: {sorted(missing)}"

    def test_pinned_trace_env_restores_environment(self):
        from repro.kernels import autotune

        os.environ[autotune.VMEM_BUDGET_ENV] = "123456"
        os.environ[autotune.TUNE_ENV] = "1"
        try:
            with pinned_trace_env():
                assert os.environ[autotune.TUNE_ENV] == "0"
                assert autotune.VMEM_BUDGET_ENV not in os.environ
            assert os.environ[autotune.VMEM_BUDGET_ENV] == "123456"
            assert os.environ[autotune.TUNE_ENV] == "1"
        finally:
            os.environ.pop(autotune.VMEM_BUDGET_ENV, None)
            os.environ.pop(autotune.TUNE_ENV, None)

    def test_cli_ir_flags_require_ir(self):
        out = subprocess.run(
            [sys.executable, "-m", "repro.lint", "--update-snapshots"],
            capture_output=True, text=True,
        )
        assert out.returncode == 2
        assert "--ir" in out.stderr

    def test_repo_is_ir_clean(self, entries):
        """The acceptance gate, as a test: the traced hot path must stay
        clean under every IR pass, golden snapshots included."""
        fs = run_passes(
            entries,
            snapshot_root=str(REPO / "tests" / "ir_snapshots"),
        )
        assert fs == [], "\n".join(f.format() for f in fs)

    def test_retrace_hashes_are_deterministic(self, entries):
        """I5 stability: re-tracing two representative mpGeMM entries in
        the same process reproduces their hashes exactly."""
        fresh = {e.name: e for e in mpgemm_entries()}
        for e in entries:
            if e.name in ("mpgemm/vlut_packed_fused/M16",
                          "mpgemm/mad_int8/M1"):
                assert signature(fresh[e.name].jaxpr) == signature(e.jaxpr)
