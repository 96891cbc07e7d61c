"""Serving engine: slot-based continuous batching (paper §5.3.2).

The engine owns a batched KV cache with `max_slots` request slots. All
shapes are static (JAX-compile-once): requests of different lengths coexist
through per-slot `idx` positions and position-masked attention.

Two prefill policies:

  * Whole-prompt (`prefill_chunk=0`, the legacy path): admission runs the
    request's entire prompt as one blocking B=1 bucketed prefill scattered
    into its slot, then every tick runs one batched decode step — llama.cpp's
    mixed prefill/decode policy, the workload on which the paper reports
    273.5 tok/s. Under load the Vec-LUT kernels see their big-M win only at
    admission; every active decode slot stalls behind each whole prompt.

  * Chunked (`prefill_chunk=N`): admission only *claims* a slot
    (PREFILLING); the prompt is consumed N tokens per tick by a single
    batched (max_slots, N) multi-token step (`models.verify_step` — the same
    machinery as speculative verification, so GQA and MLA are exact) that
    carries every scheduled prefill chunk AND, when speculation is off, the
    last-token decode rows of all DECODING slots. The mpGeMM kernels see
    M ≈ chunk x (prefilling slots) + (decode rows) parallel tokens *every*
    tick, not just at admission — serving itself becomes the parallel-token
    workload of the paper's thesis. A left-over chunk is mask-padded: the
    pad tail's positions exceed every real query position (causal position
    mask) and its cache writes are rolled back before the next step.
    `token_budget` caps the real tokens scheduled per tick (decode rows
    first, then FCFS prefill chunks; at least one chunk always advances).
    TTFT is measured when the *last* chunk completes and the first token is
    sampled. Greedy chunked output is token-identical to the whole-prompt
    path. Chunked mode needs rollbackable caches (full-buffer attention/MLA;
    ssm and windowed ring caches are refused, exactly like speculation).

With speculation enabled, PREFILLING slots are excluded from draft/verify
rows until their last chunk lands (the drafter's `on_admit` fires at the
PREFILLING→DECODING transition, so a ModelDrafter's mirrored cache syncs to
the full prompt exactly once); each tick then runs the chunk step over
prefilling slots followed by the usual spec step over decoding slots —
chain, adaptive-K, and tree modes all compose with chunked prefill.

With `spec=SpecConfig(...)` the decode step becomes speculative: a drafter
proposes K tokens per slot, one batched `models.verify_step` runs the target
over (B, K+1) candidates — the Vec-LUT mpGeMM kernels see M=K+1 parallel
tokens instead of M=1 — and `sampling.accept_speculative` keeps the longest
valid prefix, rolling the KV cache back past the first rejection. Greedy
outputs are token-for-token identical to plain decoding.

With `SpecConfig(adaptive_k=True)` the engine additionally tracks a per-slot
acceptance-rate EWMA and drafts only `k_eff = spec.k_policy(ewma)` real
tokens per slot each step (0 for cold slots — their verify row degenerates to
a plain last-token decode), padding the rest so the one compiled (B, K+1)
verify step serves every mixture of slot speeds; `accept_speculative` is
handed the matching `draft_mask` and never accepts past a slot's k_eff.
`SpecConfig(stochastic=True)` makes a ModelDrafter sample its proposals at
the serving temperature and threads the per-position draft distributions
into acceptance (`draft_probs`), so temperature>0 serving emits exact
target-model samples with real draft probability mass credited.

`SpecConfig(tree=(b1, b2, ...))` switches the step to tree-structured
multi-candidate verification: the drafter proposes a token *tree* of depth k
(top-b_d candidates at each of the first depths, one chain continuation per
leaf after), flattened in DraftTree node order into a single (B, n_nodes)
verify pass — each slot's verify row carries n_nodes > k+1 candidates
through the Vec-LUT kernels. Inside the step, node i occupies cache slot
idx+i with position idx+depth(i) and attends the cached prefix plus its tree
ancestors only, so its logits are exactly sequential decode's after the
root-to-i path; `accept_tree` keeps the longest accepted root-to-leaf path,
`compact_tree_cache` gathers the winners onto contiguous slots (and stamps
slot_pos = -1 on the losers, preserving the rollback stale-entry safety
argument: every surviving entry's recorded position is either live-correct
or unreachable), and the idx rolls back to the accepted depth. Greedy tree
output stays token-for-token identical to plain decode; chain mode
(tree=None) is bit-identical to pre-tree behavior.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs as obs_mod
from repro.configs.base import ModelConfig
from repro.kernels import ops as kernel_ops
from repro.models import compact_tree_cache, decode_step as model_decode
from repro.models import gather_page, init_cache, prefill as model_prefill
from repro.models import prefill_bucket, prefill_into_slot, reset_slot_idx
from repro.models import restore_page, rollback_cache, scrub_pages
from repro.models import set_block_tables
from repro.models import verify_step as model_verify
from repro.spec import SpecConfig
from .paging import OutOfPages, PagedKVConfig, Pager
from .sampling import accept_speculative, accept_tree, sample


# single definitions of the speculative metrics, shared by Engine (live
# counters) and ServeStats (per-run snapshot) so the two can never diverge.
# The third consumer — the repro.obs metrics registry — is synced *from* the
# engine's live counters at tick boundaries (obs.Obs.on_tick), so enabling
# observability adds an export surface without a parallel set of counters.
def spec_acceptance_rate(accepted_tokens: int, drafted_tokens: int) -> float:
    """Fraction of drafted tokens the target model accepted."""
    return accepted_tokens / drafted_tokens if drafted_tokens else 0.0


def spec_tokens_per_step(decode_tokens: int, spec_slot_steps: int) -> float:
    """Mean tokens a slot emits per verify step (1..k+1; 1.0 unspeculated)."""
    return decode_tokens / spec_slot_steps if spec_slot_steps else 1.0


def spec_skip_rate(spec_skipped_steps: int, spec_slot_steps: int) -> float:
    """Fraction of slot verify steps that skipped drafting (k_eff=0)."""
    return spec_skipped_steps / spec_slot_steps if spec_slot_steps else 0.0


def spec_mean_k(
    drafted_tokens: int, spec_slot_steps: int, spec_skipped_steps: int
) -> float:
    """Mean effective draft length over the slot steps that did draft."""
    drafting = spec_slot_steps - spec_skipped_steps
    return drafted_tokens / drafting if drafting else 0.0


def spec_nodes_per_step(verified_nodes: int, spec_slot_steps: int) -> float:
    """Mean candidate tokens one slot's verify row carries per step — k+1 in
    chain mode, the tree's node count under tree verification (1.0 when
    unspeculated). This is the M the Vec-LUT mpGeMM kernels see per slot."""
    return verified_nodes / spec_slot_steps if spec_slot_steps else 1.0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    # filled by the engine
    slot: int = -1
    prefill_pos: int = 0          # prompt tokens already in cache (chunked)
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    error: str = ""               # admission rejection reason (done, no output)
    t_submit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0


class Engine:
    """Slot-based continuous-batching engine over a static (max_slots,
    max_len) KV cache.

    `spec=SpecConfig(...)` turns decode into draft→verify→accept;
    `SpecConfig(adaptive_k=True)` additionally adapts each slot's draft
    length to its acceptance EWMA (see `_choose_k_eff` / `SpecConfig.
    k_policy`; live per-slot state in `slot_accept` / `slot_k_eff`), and
    `SpecConfig(stochastic=True)` samples ModelDrafter proposals at the
    serving `temperature`, threading their distributions into rejection
    sampling. Admission budgets `len(prompt) + max_new_tokens - 1` cache
    positions (+ the k-token draft window under speculation): the final
    generated token is sampled but never written back."""

    def __init__(
        self,
        params,
        cfg: ModelConfig,
        *,
        max_slots: int = 8,
        max_len: int = 512,
        mode: str = "serve",
        enc_len: int = 0,
        temperature: float = 0.0,
        seed: int = 0,
        mpgemm_impl: str | None = None,
        mpgemm_fusion: str | None = None,
        mpgemm_interpret: bool | None = None,
        spec: SpecConfig | None = None,
        prefill_chunk: int = 0,
        token_budget: int = 0,
        paged_kv: PagedKVConfig | None = None,
        obs: "obs_mod.ObsConfig | obs_mod.Obs | None" = None,
    ):
        self.params = params
        self.cfg = cfg
        self.mode = mode
        # observability: the null instance is free (every method early-
        # returns); an enabled Obs also installs itself for the kernel-side
        # dispatch hooks (ops.ternary_matmul / autotune.tune)
        if obs is None:
            self.obs = obs_mod.NULL_OBS
        elif isinstance(obs, obs_mod.Obs):
            self.obs = obs
        else:
            self.obs = obs_mod.Obs(obs)
        if self.obs.enabled:
            obs_mod.install(self.obs)
        # mpGeMM routing for every BitLinear this engine traces: by default
        # the fused single-pass kernel on TPU / streamed XLA elsewhere; the
        # knobs force e.g. the interpreted fused path for CPU validation.
        self._mpgemm = dict(
            impl=mpgemm_impl, fusion=mpgemm_fusion, interpret=mpgemm_interpret
        )
        self.max_slots = max_slots
        self.max_len = max_len
        self.temperature = temperature
        self.rng = jax.random.PRNGKey(seed)
        # paged KV: a physical page pool + per-slot block tables replace the
        # dense (max_slots, max_len) slabs. The host-side Pager owns
        # allocation, radix prefix sharing, and host-RAM offload
        # (serve.paging); the device side is pure data movement
        # (models.paged). Admission reserves the full worst-case page budget
        # up front, so pool exhaustion surfaces exactly once — at add(),
        # where the scheduler queues the request for pages.
        self.pager: Pager | None = None
        self._set_tab = self._scrub = None
        if paged_kv is not None:
            if any(s.mixer == "ssm" for s in cfg.layer_specs()):
                raise ValueError(
                    "paged KV needs per-position cache entries a block table "
                    f"can own; {cfg.name} has ssm layer(s), whose recurrent "
                    "state is neither rollbackable nor pageable"
                )
            if any(s.window for s in cfg.layer_specs()):
                raise ValueError(
                    "paged KV is exact only for full-buffer caches; "
                    f"{cfg.name} has windowed (ring-cache) layers — a ring "
                    "buffer overwrites itself in place, so its pages can "
                    "never be remapped or shared"
                )
            if enc_len:
                raise ValueError(
                    "paged KV does not cover cross-attention caches "
                    "(enc_len > 0): encoder K/V is per-request dense state, "
                    "not positionally growing history"
                )
            ps = paged_kv.page_size
            n_pages = paged_kv.n_pages or max_slots * (max_len // ps) + 1
            self.cache = init_cache(
                cfg, max_slots, max_len, page_size=ps, n_pages=n_pages
            )
            self.pager = Pager(
                paged_kv, max_slots=max_slots, max_len=max_len,
                n_pages=n_pages, page_out=self._page_out,
                page_in=self._page_in,
            )
            self._set_tab = jax.jit(set_block_tables, donate_argnums=(0,))
            self._scrub = jax.jit(scrub_pages, donate_argnums=(0,))
        else:
            self.cache = init_cache(cfg, max_slots, max_len, enc_len=enc_len)
        self.slot_free = [True] * max_slots
        self.slot_req: dict[int, Request] = {}
        self.last_token = jnp.zeros((max_slots, 1), jnp.int32)
        self.active = np.zeros(max_slots, bool)
        # obs on only: host clock when the last step's sampled tokens reached
        # the host; the next step's launch_gap_us counts from it. The
        # scheduler clears it when a tick leaves no work (the device then
        # waits for requests, not for the host)
        self.last_sync_t: float | None = None

        self._prefill1 = jax.jit(
            lambda p, c, t: model_prefill(p, t, c, cfg, mode=mode)
        )
        self._decode = jax.jit(
            lambda p, c, t: model_decode(p, t, c, cfg, mode=mode),
            donate_argnums=(1,),
        )
        # chunked prefill: admission claims a slot (PREFILLING); the prompt
        # is consumed prefill_chunk tokens per step() by one batched
        # multi-token pass shared with the decode rows (see _chunk_step)
        if prefill_chunk < 0:
            raise ValueError(f"prefill_chunk must be >= 0, got {prefill_chunk}")
        if token_budget < 0:
            raise ValueError(f"token_budget must be >= 0, got {token_budget}")
        if prefill_chunk:
            if prefill_chunk > max_len:
                raise ValueError(
                    f"prefill_chunk ({prefill_chunk}) exceeds max_len "
                    f"({max_len}); the chunk step cannot outgrow the cache"
                )
            if any(s.mixer == "ssm" for s in cfg.layer_specs()):
                raise ValueError(
                    "chunked prefill needs rollbackable KV caches (the "
                    "mask-padded chunk tail is rolled back); "
                    f"{cfg.name} has ssm layer(s), whose recurrent state is "
                    "neither rollbackable nor pageable"
                )
            if any(s.window for s in cfg.layer_specs()):
                raise ValueError(
                    "chunked prefill is exact only for full-buffer or paged "
                    f"KV caches; {cfg.name} has windowed (ring-cache) "
                    "layers, whose in-window history the padded-tail "
                    "rollback would clobber (the ring overwrites in place, "
                    "so it is genuinely non-pageable too)"
                )
        self.prefill_chunk = prefill_chunk
        self.token_budget = token_budget
        self.prefilling: dict[int, Request] = {}    # slot → mid-prefill req
        # decode rows ride the chunk step only when their logits come off
        # the very path plain decode uses: MLA decode is absorbed while the
        # chunk step reads via prefill_resume (naive expansion, quantized
        # like whole-prompt prefill) — those slots decode in their own
        # absorbed step each tick instead, exactly like spec engines
        self._decode_rides = spec is None and not any(
            s.mixer == "mla" for s in cfg.layer_specs()
        )
        # logit_cols: each slot only ever needs the distribution after ONE
        # chunk position (its last real token), so the head matmul runs on
        # (B, 1, d) gathered hidden states, never (B, chunk, V) — non-final
        # chunks skip the full-vocab projection entirely. Paged engines need
        # this entry even in whole-prompt mode: their admission prefill is a
        # wide in-place verify pass (the B=1 scatter-a-fresh-cache path has
        # no block tables to write through)
        self._chunk_verify = (
            jax.jit(
                lambda p, c, t, col: model_verify(
                    p, t, c, cfg, mode=mode, prefill_resume=True,
                    logit_cols=col,
                ),
                donate_argnums=(1,),
            )
            if (prefill_chunk or paged_kv is not None) else None
        )
        # speculative decoding (draft → verify → accept)
        self.spec = spec
        self.drafter = None
        self._tree = None
        if spec is not None:
            bad = [s.mixer for s in cfg.layer_specs() if s.mixer == "ssm"]
            if bad:
                raise ValueError(
                    "speculative decoding needs rollbackable KV caches; "
                    f"{cfg.name} has {len(bad)} ssm layer(s), whose "
                    "recurrent state is neither rollbackable nor pageable"
                )
            if any(s.window for s in cfg.layer_specs()):
                raise ValueError(
                    "speculative decoding is exact only for full-buffer or "
                    f"paged KV caches; {cfg.name} has windowed (ring-cache) "
                    "layers, whose in-window history a rollback would "
                    "clobber (the ring overwrites in place, so it is "
                    "genuinely non-pageable too)"
                )
            self.drafter = spec.build(max_slots=max_slots, max_len=max_len, mode=mode)
            # tree mode: the static DraftTree layout is baked into the
            # verify trace (per-node depths/positions + ancestor mask) and
            # into the post-acceptance window compaction
            self._tree = spec.tree_struct()
            self._verify = jax.jit(
                lambda p, c, t: model_verify(
                    p, t, c, cfg, mode=mode, tree=self._tree
                ),
                donate_argnums=(1,),
            )
            if self._tree is not None:
                self._compact = jax.jit(compact_tree_cache, donate_argnums=(0,))
                if temperature > 0.0:
                    import warnings

                    warnings.warn(
                        "tree verification at temperature>0 greedy-matches "
                        "the draft nodes and only *samples* the correction "
                        "token — output is greedy-filtered, not an exact "
                        "target-temperature sample (chain mode is exact; "
                        "see sampling.accept_tree's TODO)",
                        stacklevel=3,
                    )
        # per-slot adaptive-K state: acceptance EWMA (slots start optimistic
        # at 1.0 on admission), the consecutive-skip streak that triggers a
        # cold slot's k_min probe, and the last k_eff the policy chose
        self.slot_accept = np.ones(max_slots, np.float64)
        self.slot_skip_streak = np.zeros(max_slots, np.int64)
        self.slot_k_eff = np.full(max_slots, self._draft_k, np.int64)
        # stats
        self.prefill_tokens = 0     # real prompt tokens prefilled
        self.prefill_pad_tokens = 0  # bucket/chunk padding (not real work)
        self.decode_tokens = 0
        self.decode_steps = 0       # batched decode/verify step invocations
        self.chunk_steps = 0        # batched mixed chunk-step invocations
        self.spec_steps = 0         # batched verify steps (engine ticks)
        self.spec_slot_steps = 0    # per-slot verify steps (Σ active slots)
        self.spec_skipped_steps = 0  # slot steps that skipped drafting (k_eff=0)
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.verified_nodes = 0     # candidate tokens verified (Σ per slot)

    # ------------------------------------------------------------------
    @property
    def _draft_k(self) -> int:
        return self.spec.k if self.spec is not None else 0

    @property
    def _draft_window(self) -> int:
        """Cache slots one verify step writes past the root's position: k in
        chain mode, the tree's draft-node count under tree verification
        (every flattened node gets its own slot)."""
        if self._tree is not None:
            return self._tree.n_draft
        return self._draft_k

    def _validate(self, req: Request) -> None:
        """Reject requests that would overflow the slot KV cache: the prompt
        plus every decode position (and, speculatively, the draft window
        past the last kept token) must fit in max_len. The final generated
        token is sampled but never written back, so it needs no cache
        position: prompt + max_new_tokens - 1 (+ draft window) is the exact
        budget."""
        need = len(req.prompt) + req.max_new_tokens - 1 + self._draft_window
        if need > self.max_len:
            extra = (
                f" + draft window ({self._draft_window})"
                if self._draft_window else ""
            )
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens - 1 ({req.max_new_tokens - 1}){extra} = {need} "
                f"exceeds the model context (max_len={self.max_len}); "
                f"truncate the prompt, lower max_new_tokens, or grow the "
                f"engine's max_len — this can never succeed, unlike a "
                f"transient out-of-pages deferral"
            )
        if self.pager is not None:
            # a reservation larger than the ENTIRE pool is equally permanent:
            # no amount of waiting (or prefix sharing — shared pages are pool
            # pages too) can ever map that many pages to one slot
            ps = self.pager.cfg.page_size
            need_pages = -(-need // ps)
            if need_pages > self.pager.total_pages:
                raise ValueError(
                    f"request {req.rid}: needs {need_pages} KV pages "
                    f"({need} positions at page_size={ps}) but the pool "
                    f"only has {self.pager.total_pages} allocatable pages; "
                    f"grow n_pages or shrink the request — this can never "
                    f"succeed, unlike a transient out-of-pages deferral"
                )

    def add(self, req: Request) -> bool:
        """Admit a request into a free slot. False if no slot free; raises
        ValueError if the request cannot fit in max_len at all.

        Whole-prompt mode (prefill_chunk=0) runs the full B=1 bucketed
        prefill here and samples the first token. Chunked mode only claims
        the slot (PREFILLING): the prompt is consumed chunk by chunk by
        subsequent `step()` calls and the first token is sampled when the
        last chunk lands."""
        self._validate(req)
        try:
            slot = self.slot_free.index(True)
        except ValueError:
            return False
        req.slot = slot
        req.t_submit = req.t_submit or time.perf_counter()
        matched = 0
        if self.pager is not None:
            # reserve the request's full worst-case page budget (the same
            # bound _validate just checked against max_len), reusing shared
            # prefix pages where the radix index matches. OutOfPages is a
            # TRANSIENT condition — decoding slots will finish and free
            # pages — so the request stays queued (return False), in
            # contrast to the permanent exceeds-model-context ValueError.
            need = len(req.prompt) + req.max_new_tokens - 1 + self._draft_window
            try:
                with self.obs.span("engine.pager.admit"):
                    matched = self.pager.admit(slot, np.asarray(req.prompt), need)
            except OutOfPages as e:
                req.error = f"queued: waiting for free KV pages ({e})"
                return False
            req.error = ""
            self._flush_pager()
            # matched prefix pages already hold their KV: the slot's write
            # position starts at the matched frontier and only the prompt
            # suffix runs through the model
            self.cache = reset_slot_idx(self.cache, slot, value=matched)
        if self.prefill_chunk:
            self.slot_free[slot] = False
            req.prefill_pos = matched
            self.prefilling[slot] = req
            if self.pager is None:
                # the slot's write position restarts at 0; stale K/V needs
                # no clearing (see models.reset_slot_idx) — contiguous
                # chunk writes re-cover every position before a query sees it
                self.cache = reset_slot_idx(self.cache, slot)
            return True
        if self.pager is not None:
            self._paged_prefill(slot, req, matched)
            return True
        # SSM/hybrid archs can't mask pads inside the scan → exact lengths.
        has_ssm = any(s.mixer == "ssm" for s in self.cfg.layer_specs())
        with kernel_ops.dispatch_override(**self._mpgemm):
            logits, self.cache, padded = prefill_into_slot(
                self.params, self.cache, slot, req.prompt, self.cfg,
                max_len=self.max_len, prefill_fn=self._prefill1,
                exact_len=has_ssm,
            )
        # only real prompt tokens are prefill work; bucket padding is
        # accounted separately so tok/s can't be inflated by left-pads
        self.prefill_tokens += len(req.prompt)
        self.prefill_pad_tokens += padded - len(req.prompt)
        nxt = int(self._sample(logits)[0])
        self._start_decoding(slot, req, nxt, time.perf_counter())
        return True

    def _paged_prefill(self, slot: int, req: Request, matched: int) -> None:
        """Whole-prompt admission for a paged engine: one wide in-place
        verify pass over the unmatched prompt suffix, writing K/V through
        the slot's freshly flushed block table. The dense path's B=1
        scatter-a-fresh-cache trick has no analogue here (a fresh cache has
        no pages), so paged admission reuses the chunked-prefill machinery
        with chunk = the whole suffix: other slots' rows are mask-padding
        whose frontier scribbles are rolled back exactly like a chunk
        step's. A prefix hit shrinks the pass to the suffix alone — the
        shared pages' KV is already resident."""
        rem = req.prompt[matched:]
        bucket = prefill_bucket(len(rem), self.max_len)
        tokens = np.zeros((self.max_slots, bucket), np.int32)
        tokens[slot, :len(rem)] = rem
        col = np.zeros(self.max_slots, np.int64)
        col[slot] = len(rem) - 1
        new_idx = self._idx_vector()
        new_idx[slot] = len(req.prompt)
        with kernel_ops.dispatch_override(**self._mpgemm):
            rows, cache = self._chunk_verify(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(col, np.int32),
            )                                                    # rows: (B, V)
        self.cache = rollback_cache(cache, jnp.asarray(new_idx))
        self.prefill_tokens += len(rem)
        self.prefill_pad_tokens += bucket - len(rem)
        nxt = int(self._sample(rows[slot][None])[0])
        self._start_decoding(slot, req, nxt, time.perf_counter())

    def _start_decoding(self, slot: int, req: Request, first_tok: int,
                        now: float) -> None:
        """Prefill complete (whole-prompt or final chunk): record the first
        generated token and transition the slot to DECODING — or finish it
        outright when max_new_tokens=1 asked for nothing more."""
        req.generated.append(first_tok)
        req.t_first_token = now
        if req.t_submit:
            self.obs.observe_ttft(now - req.t_submit)
        self.last_token = self.last_token.at[slot, 0].set(first_tok, mode="drop")
        if len(req.generated) >= req.max_new_tokens:
            # prefill already produced everything asked for (max_new_tokens=1)
            req.done = True
            req.t_done = req.t_first_token
            self.slot_free[slot] = True
            if self.pager is not None:
                with self.obs.span("engine.pager.release"):
                    self.pager.release(slot, np.asarray(req.prompt))
            return
        self.slot_free[slot] = False
        self.slot_req[slot] = req
        self.active[slot] = True
        if self.drafter is not None:
            # chunked mode defers this to the PREFILLING→DECODING
            # transition: the drafter syncs the full prompt exactly once
            self.drafter.on_admit(slot, req.prompt)
        # fresh request → optimistic acceptance state (starts at full k)
        self.slot_accept[slot] = 1.0
        self.slot_skip_streak[slot] = 0
        self.slot_k_eff[slot] = self._draft_k

    def _sample(self, logits):
        self.rng, k = jax.random.split(self.rng)
        return sample(logits, k, temperature=self.temperature)

    # -- paged-KV device sync ------------------------------------------
    def _page_out(self, page: int):
        """Pager offload callback: copy one physical page to host numpy."""
        return gather_page(self.cache, page)

    def _page_in(self, page: int, data) -> None:
        """Pager page-in callback: restore a host copy into `page`. The
        restored slot_pos rides along with the K/V, so paged-in pages are
        deliberately NOT scrubbed (a scrub would erase the positions that
        make the restored prefix attendable)."""
        self.cache = restore_page(self.cache, page, data)

    def _flush_pager(self) -> None:
        """Push the pager's host state to the device before the next jitted
        step: scrub slot_pos = -1 on freshly allocated pages (fixed-width
        batches padded with the out-of-range n_pages sentinel, so the jitted
        scrub never recompiles and pads are mode="drop"ped) and broadcast
        the new block tables into every layer's tab. Called at admission
        (before the prefill pass) and at tick start (after releases)."""
        if self.pager is None or not self.pager.dirty:
            return
        with self.obs.span("engine.pager.flush"):
            tab, fresh = self.pager.take_flush()
            if fresh:
                w = self.pager.cfg.scrub_batch
                fresh = fresh + [self.pager.n_pages] * ((-len(fresh)) % w)
                for i in range(0, len(fresh), w):
                    self.cache = self._scrub(
                        self.cache, jnp.asarray(fresh[i:i + w], jnp.int32)
                    )
            self.cache = self._set_tab(self.cache, jnp.asarray(tab, jnp.int32))

    def _slot_exhausted(self, req: Request) -> bool:
        """True when the slot has no room for another decode (or verify)
        step: the next write position (+ draft window) would pass max_len.
        Admission bounds this (so this never fires for admitted requests —
        it is a safety re-check against buffer scribbles), but it must use
        the same exact bound: the last generated token is never written, so
        the next step writes slots next_pos .. next_pos + draft_window where
        next_pos is the cache slot last_token will occupy."""
        next_pos = len(req.prompt) + len(req.generated) - 1  # last_token's slot
        return next_pos + self._draft_window >= self.max_len

    def _finish_slot(self, slot: int, req: Request, now: float):
        req.done = True
        req.t_done = now
        # TPOT = mean inter-token gap after the first token (undefined for
        # single-token requests, which finish in _start_decoding anyway)
        if len(req.generated) > 1 and req.t_first_token:
            self.obs.observe_tpot(
                (now - req.t_first_token) / (len(req.generated) - 1)
            )
        self.active[slot] = False
        self.slot_free[slot] = True
        del self.slot_req[slot]
        if self.pager is not None:
            # prefix pages return to the radix index (the next request with
            # this prompt prefix admits at near-zero prefill cost), the rest
            # to the free pool; the block-table flush is deferred to the
            # next admission or tick (no jitted step runs before either)
            with self.obs.span("engine.pager.release"):
                self.pager.release(slot, np.asarray(req.prompt))
        if self.drafter is not None:
            self.drafter.on_release(slot)

    @property
    def has_work(self) -> bool:
        """True when a step() would do anything: slots mid-prefill or
        actively decoding. The scheduler skips the tick's batched step
        entirely when this is False (e.g. every admission was satisfied by
        prefill alone) instead of burning a dispatch on an empty batch."""
        return bool(self.prefilling) or bool(self.active.any())

    def _idx_vector(self) -> np.ndarray:
        """Host mirror of every slot's true cache write position: a DECODING
        slot's idx is its last sampled token's cache position (that token is
        never written until the next step), a PREFILLING slot's is its
        consumed-prompt prefix, and free slots sit at 0 (chunked admission
        resets them; whole-prompt admission rescatters a fresh cache).
        Every batched rollback starts from this vector so a step over one
        subset of slots can never scribble the idx of another."""
        idx = np.zeros(self.max_slots, np.int64)
        for slot, req in self.prefilling.items():
            idx[slot] = req.prefill_pos
        for slot, req in self.slot_req.items():
            if self.active[slot]:
                idx[slot] = len(req.prompt) + len(req.generated) - 1
        return idx

    def step(self):
        """One engine tick: the chunked-prefill mixed step (when any slot is
        PREFILLING), then/or the batched decode step. The scheduler's tick
        entry point; whole-prompt engines fall straight through to
        decode_once()."""
        self._flush_pager()    # released slots' tab rows → null before any step
        if self.prefilling:
            self._chunk_step()
            if not self._decode_rides:
                # spec engines (draft→verify→accept) and MLA archs (absorbed
                # decode vs the chunk step's prefill_resume read) exclude
                # decode rows from the chunk step — their own decode step
                # runs in the same tick
                self.decode_once()
        else:
            self.decode_once()

    def _chunk_step(self):
        """One batched mixed prefill/decode step over the (max_slots,
        prefill_chunk) token grid — the tentpole of chunked prefill.

        Row contents: a scheduled PREFILLING slot carries its next c =
        min(chunk, remaining) prompt tokens (left-over chunk mask-padded —
        pad positions exceed every real query position and are rolled back
        below); when speculation is off, every DECODING slot rides along as
        a last-token row (column 0 is exactly a plain decode — verify
        semantics — so mixed ticks keep emitting); all other rows are
        padding. One `models.verify_step` pass appends everything at
        per-slot positions, so the Vec-LUT mpGeMM kernels see
        M ≈ chunk x (scheduled prefills) + (decode rows) real parallel
        tokens in a single launch.

        `token_budget` caps the real tokens scheduled per step: decode rows
        are mandatory and count first, then prefill chunks are granted FCFS
        (admission order); at least one chunk always advances so prefill
        can never starve."""
        obs = self.obs
        with obs.step_span("chunk") as step:
            chunk = self.prefill_chunk
            include_decode = self._decode_rides and bool(self.active.any())
            if include_decode:
                with obs.span("engine.sync.last_token") as sync_last:
                    last = np.asarray(self.last_token)[:, 0]
            with obs.span("engine.chunk.prepare") as prep:
                used = int(self.active.sum()) if include_decode else 0
                budget = self.token_budget
                chosen: list[tuple[int, int]] = []
                for slot, req in self.prefilling.items():
                    c = min(chunk, len(req.prompt) - req.prefill_pos)
                    if chosen and budget and used + c > budget:
                        break
                    chosen.append((slot, c))
                    used += c
                tokens = np.zeros((self.max_slots, chunk), np.int32)
                col = np.zeros(self.max_slots, np.int64)  # logits column per slot
                new_idx = self._idx_vector()
                for slot, c in chosen:
                    req = self.prefilling[slot]
                    tokens[slot, :c] = req.prompt[req.prefill_pos:req.prefill_pos + c]
                    col[slot] = c - 1
                    new_idx[slot] = req.prefill_pos + c
                decode_slots: list[int] = []
                if include_decode:
                    for slot, req in self.slot_req.items():
                        if not self.active[slot]:
                            continue
                        tokens[slot, 0] = last[slot]
                        new_idx[slot] += 1      # idx_vector holds last_token's pos
                        decode_slots.append(slot)
                if obs.enabled:
                    work = self._chunk_work(chosen, decode_slots, new_idx)
            with obs.span("engine.chunk.launch") as launch:
                with kernel_ops.dispatch_override(**self._mpgemm):
                    rows, cache = self._chunk_verify(
                        self.params, self.cache, jnp.asarray(tokens),
                        jnp.asarray(col, np.int32),
                    )                                            # rows: (B, V)
            with obs.span("engine.sync.tokens") as sync:
                nxt = np.asarray(self._sample(rows))
            with obs.span("engine.chunk.commit") as commit:
                now = time.perf_counter()
                self.chunk_steps += 1
                for slot, c in chosen:
                    req = self.prefilling[slot]
                    req.prefill_pos += c
                    self.prefill_tokens += c
                    self.prefill_pad_tokens += chunk - c
                    if req.prefill_pos < len(req.prompt):
                        continue
                    # final chunk landed: first token, PREFILLING → DECODING
                    del self.prefilling[slot]
                    self._start_decoding(slot, req, int(nxt[slot]), now)
                for slot in decode_slots:
                    req = self.slot_req[slot]
                    self.decode_tokens += 1
                    req.generated.append(int(nxt[slot]))
                    self.last_token = self.last_token.at[slot, 0].set(
                        nxt[slot], mode="drop"
                    )
                    if (len(req.generated) >= req.max_new_tokens
                            or self._slot_exhausted(req)):
                        self._finish_slot(slot, req, now)
            with obs.span("engine.rollback") as rollback:
                self.cache = rollback_cache(cache, jnp.asarray(new_idx))
            if obs.enabled:
                # used = real tokens this step carried (chunk tokens + decode
                # rows) — the effective M the batched mpGeMM dispatch saw
                step.args.update(
                    m_real=used, m_padded=self.max_slots * chunk,
                    prefills=len(chosen), decodes=len(decode_slots), **work,
                    **self._phase_args(
                        prep, launch, sync, commit, rollback,
                        sync_last.us if include_decode else 0.0,
                    ),
                )

    def _chunk_work(self, chosen: list[tuple[int, int]],
                    decode_slots: list[int], new_idx: np.ndarray) -> dict:
        """What a chunk step computes, before its commit (obs on only):
        free slots, keys attended summed over the real tokens (a token at
        position p attends p + 1 keys) and rows whose logits serve a token
        (decode rows and final chunks)."""
        keys = sum(int(new_idx[s]) for s in decode_slots)   # p + 1 each
        served = len(decode_slots)
        for slot, c in chosen:
            req = self.prefilling[slot]
            p0 = req.prefill_pos
            keys += c * p0 + c * (c + 1) // 2
            served += p0 + c == len(req.prompt)
        return dict(free_slots=sum(self.slot_free), attn_keys=keys,
                    logit_rows=served)

    def _phase_args(self, prep, launch, sync, commit, rollback,
                    sync_extra_us: float = 0.0) -> dict:
        """A step's phase times in microseconds (obs on only), and the host
        time since the previous step's tokens reached the host
        (``launch_gap_us``, when the previous tick ran a step)."""
        args = dict(
            prepare_us=prep.us, sync_us=sync.us + sync_extra_us,
            commit_us=commit.us, rollback_us=rollback.us if rollback else 0.0,
        )
        if self.last_sync_t is not None:
            args["launch_gap_us"] = (launch.t0 - self.last_sync_t) * 1e6
        self.last_sync_t = sync.t1
        return args

    def decode_once(self):
        """One batched decode step over every active slot. With spec enabled
        this is draft → verify → accept (1..k+1 tokens per slot)."""
        self._flush_pager()    # bench loops call decode_once without step()
        if not self.active.any():
            return
        if self._tree is not None:
            return self._decode_spec_tree()
        if self.spec is not None:
            return self._decode_spec()
        obs = self.obs
        rollback = None
        with obs.step_span("decode") as step:
            with obs.span("engine.decode.prepare") as prep:
                self.decode_steps += 1
                # the jit'd decode step advances EVERY slot's idx by 1 and
                # scatters a (garbage) token at every slot's frontier; with
                # slots mid-chunked-prefill that drift must be undone — the
                # restored frontier index is rewritten by the slot's next
                # chunk before it can be attended
                restore = bool(self.prefilling)
                if restore:
                    new_idx = self._idx_vector()
                    new_idx[np.asarray(self.active)] += 1  # decode wrote last_token
                if obs.enabled:
                    # every active row counts, finishing in this step or not;
                    # the last token sits at len(prompt) + len(generated) - 1
                    rows = int(self.active.sum())
                    work = dict(
                        m_real=rows, free_slots=sum(self.slot_free),
                        attn_keys=sum(len(r.prompt) + len(r.generated)
                                      for s, r in self.slot_req.items()
                                      if self.active[s]),
                        logit_rows=rows,
                    )
            with obs.span("engine.decode.launch") as launch:
                with kernel_ops.dispatch_override(**self._mpgemm):
                    logits, self.cache = self._decode(
                        self.params, self.cache, self.last_token
                    )
            with obs.span("engine.sync.tokens") as sync:
                nxt = np.asarray(self._sample(logits))               # (B,)
            with obs.span("engine.decode.commit") as commit:
                self.last_token = jnp.asarray(nxt)[:, None]
                now = time.perf_counter()
                for slot, req in list(self.slot_req.items()):
                    if not self.active[slot]:
                        continue
                    self.decode_tokens += 1
                    req.generated.append(int(nxt[slot]))
                    if (len(req.generated) >= req.max_new_tokens
                            or self._slot_exhausted(req)):
                        self._finish_slot(slot, req, now)
            if restore:
                with obs.span("engine.rollback") as rollback:
                    self.cache = rollback_cache(self.cache, jnp.asarray(new_idx))
            if obs.enabled:
                step.args.update(
                    m_padded=self.max_slots, **work,
                    **self._phase_args(prep, launch, sync, commit, rollback),
                )

    def _choose_k_eff(self) -> np.ndarray:
        """Per-slot effective draft length for this step: spec.k everywhere
        unless adaptive_k, in which case each active slot gets
        spec.k_policy(acceptance EWMA, skip streak) ∈ [0, k]."""
        k_eff = np.full(self.max_slots, self.spec.k, np.int64)
        if not self.spec.adaptive_k:
            return k_eff
        for slot in range(self.max_slots):
            if self.active[slot]:
                k_eff[slot] = self.spec.k_policy(
                    float(self.slot_accept[slot]),  # lint: disable=R3 -- slot_accept is a host np.ndarray EWMA
                    int(self.slot_skip_streak[slot]),  # lint: disable=R3 -- slot_skip_streak is host np.ndarray state
                )
        return k_eff

    def _update_slot_accept(self, slot: int, k_eff: int, n_acc: int) -> None:
        """Fold one verify step's verdict into the slot's acceptance EWMA;
        skipped (k_eff=0) steps only advance the probe streak."""
        if k_eff == 0:
            self.slot_skip_streak[slot] += 1
            self.spec_skipped_steps += 1
            return
        self.slot_skip_streak[slot] = 0
        a = self.spec.accept_ewma
        self.slot_accept[slot] = a * self.slot_accept[slot] + (1 - a) * (
            n_acc / k_eff
        )

    def _gather_contexts(self):
        """Per-slot drafting inputs: the full token context (prompt +
        generated; None for free slots) and the cache idx of the last
        sampled token. → (contexts, pos)."""
        contexts: list = [None] * self.max_slots
        pos = np.zeros(self.max_slots, np.int64)     # per-slot cache idx
        for slot, req in self.slot_req.items():
            if self.active[slot]:
                contexts[slot] = np.concatenate(
                    # lint: disable=R3 -- prompt/generated are host python lists
                    [np.asarray(req.prompt, np.int64), np.asarray(req.generated, np.int64)]
                )
                pos[slot] = len(req.prompt) + len(req.generated) - 1
        return contexts, pos

    def _decode_spec(self):
        """One speculative decode step: drafter proposal, a single batched
        (B, K+1) verify pass through the Vec-LUT kernels, longest-accepted-
        prefix emission, and KV rollback to the last kept position.

        Shapes are static for every mixture of per-slot draft lengths: a slot
        drafting k_eff < k real tokens pads the rest of its row, and the
        draft_mask handed to accept_speculative stops acceptance at k_eff
        (a k_eff=0 row is a plain last-token decode)."""
        _t0 = time.perf_counter() if self.obs.enabled else 0.0
        active0 = self.active.copy()         # slots finishing mid-loop flip it
        k = self.spec.k
        contexts, pos = self._gather_contexts()
        k_eff = self._choose_k_eff()
        self.slot_k_eff = k_eff.copy()
        stochastic = self.spec.stochastic and self.temperature > 0.0
        draft_probs = None
        if stochastic:
            self.rng, draft_key = jax.random.split(self.rng)
            draft, probs = self.drafter.propose(
                contexts, k, slot_k=k_eff, rng=draft_key,
                temperature=self.temperature, return_probs=True,
            )
            if probs is not None:
                draft_probs = jnp.asarray(probs)
        else:
            draft = self.drafter.propose(contexts, k, slot_k=k_eff)
        draft = np.asarray(draft, np.int32)
        mask = np.arange(k)[None, :] < k_eff[:, None]            # (B, K)
        tokens = jnp.concatenate([self.last_token, jnp.asarray(draft)], axis=1)
        with kernel_ops.dispatch_override(**self._mpgemm):
            logits, cache = self._verify(self.params, self.cache, tokens)
        self.rng, key = jax.random.split(self.rng)
        n_acc, out = accept_speculative(
            jnp.asarray(draft), logits, key, temperature=self.temperature,
            draft_probs=draft_probs, draft_mask=jnp.asarray(mask),
        )
        n_acc, out = np.asarray(n_acc), np.asarray(out)
        # inactive slots keep their true idx (free: 0, PREFILLING: the
        # consumed-prompt prefix) — the batched rollback must never scribble
        # a mid-prefill slot's write position
        new_idx = self._idx_vector()
        new_last = np.asarray(self.last_token).copy()
        now = time.perf_counter()
        for slot, req in list(self.slot_req.items()):
            if not self.active[slot]:
                continue
            remaining = req.max_new_tokens - len(req.generated)
            take = min(int(n_acc[slot]) + 1, remaining)
            req.generated.extend(int(t) for t in out[slot, :take])
            new_last[slot, 0] = out[slot, take - 1]
            new_idx[slot] = pos[slot] + take
            self.decode_tokens += take
            self.spec_slot_steps += 1
            self.drafted_tokens += int(k_eff[slot])  # lint: disable=R3 -- _choose_k_eff returns host np.ndarray
            self.verified_nodes += k + 1
            # acceptance counts the verifier's verdict, not the emission cap:
            # a request finishing mid-step still accepted n_acc draft tokens.
            self.accepted_tokens += int(n_acc[slot])
            self._update_slot_accept(slot, int(k_eff[slot]), int(n_acc[slot]))  # lint: disable=R3 -- k_eff is host np from _choose_k_eff
            if len(req.generated) >= req.max_new_tokens or self._slot_exhausted(req):
                self._finish_slot(slot, req, now)
        self.spec_steps += 1
        self.decode_steps += 1
        self.last_token = jnp.asarray(new_last)
        self.cache = rollback_cache(cache, jnp.asarray(new_idx))
        if self.obs.enabled:
            # every verify row carries k_eff + 1 real candidate tokens
            self.obs.step_event(
                "verify", _t0, m_real=int(np.sum(k_eff[active0] + 1)),
                m_padded=self.max_slots * (k + 1), k=k,
            )

    def _decode_spec_tree(self):
        """One tree-speculative decode step: the drafter proposes a token
        *tree* per slot (spec.tree.DraftTree, n_nodes flattened nodes), one
        batched (B, n_nodes) verify pass runs the target over every node —
        the Vec-LUT kernels see M = n_nodes parallel tokens per slot —
        `accept_tree` keeps the longest accepted root-to-leaf path, the
        winning path's cache entries are compacted back onto contiguous
        slots (compact_tree_cache), and the idx rolls back to the accepted
        depth. Greedy output is token-for-token plain decode."""
        _t0 = time.perf_counter() if self.obs.enabled else 0.0
        _m_active = int(self.active.sum())
        tree = self._tree
        n_nodes = tree.n_nodes
        contexts, pos = self._gather_contexts()
        draft = np.asarray(
            self.drafter.propose(contexts, self.spec.k, tree=tree), np.int32
        )                                            # (B, n_nodes-1)
        tokens = jnp.concatenate([self.last_token, jnp.asarray(draft)], axis=1)
        with kernel_ops.dispatch_override(**self._mpgemm):
            logits, cache = self._verify(self.params, self.cache, tokens)
        self.rng, key = jax.random.split(self.rng)
        n_acc, out, path = accept_tree(
            tokens, logits, tree, key, temperature=self.temperature
        )
        n_acc, out, path = np.asarray(n_acc), np.asarray(out), np.asarray(path)
        new_idx = self._idx_vector()    # inactive slots keep their true idx
        # slots outside this verify step (free or PREFILLING) pass take =
        # n_nodes with an identity sel: compact_tree_cache leaves their
        # window byte-for-byte unchanged instead of stamping slot_pos = -1
        # over a mid-prefill slot's live prefix
        take_arr = np.full(self.max_slots, n_nodes, np.int64)
        new_last = np.asarray(self.last_token).copy()
        now = time.perf_counter()
        for slot, req in list(self.slot_req.items()):
            if not self.active[slot]:
                continue
            remaining = req.max_new_tokens - len(req.generated)
            take = min(int(n_acc[slot]) + 1, remaining)
            req.generated.extend(int(t) for t in out[slot, :take])
            new_last[slot, 0] = out[slot, take - 1]
            new_idx[slot] = pos[slot] + take
            take_arr[slot] = take
            self.decode_tokens += take
            self.spec_slot_steps += 1
            # drafted counts the per-PATH budget (depth k, the most any
            # step can accept), keeping acceptance_rate/mean_draft_k
            # comparable with chain mode; the tree's node-level width is
            # reported separately via verified_nodes / nodes_per_step
            self.drafted_tokens += tree.k
            # as in chain mode: acceptance counts the verifier's verdict,
            # not the emission cap of a request finishing mid-step
            self.accepted_tokens += int(n_acc[slot])
            self.verified_nodes += n_nodes
            if len(req.generated) >= req.max_new_tokens or self._slot_exhausted(req):
                self._finish_slot(slot, req, now)
        self.spec_steps += 1
        self.decode_steps += 1
        self.last_token = jnp.asarray(new_last)
        # window compaction: gather the winning path's nodes onto contiguous
        # slots (depth d → slot pos+d) and invalidate the losers, so the
        # rolled-back cache is indistinguishable from one that decoded the
        # accepted tokens sequentially
        sel = np.tile(np.arange(n_nodes, dtype=np.int64), (self.max_slots, 1))
        sel[:, 1 : tree.k + 1] = np.where(
            (np.arange(1, tree.k + 1)[None, :] <= n_acc[:, None]),
            path[:, 1:],
            sel[:, 1 : tree.k + 1],
        )
        self.cache = self._compact(
            cache, jnp.asarray(pos), jnp.asarray(sel), jnp.asarray(take_arr)
        )
        self.cache = rollback_cache(self.cache, jnp.asarray(new_idx))
        if self.obs.enabled:
            self.obs.step_event(
                "tree_verify", _t0, m_real=_m_active * n_nodes,
                m_padded=self.max_slots * n_nodes, n_nodes=n_nodes,
            )

    def jit_entries(self) -> dict:
        """Every jitted entry point this engine dispatches through, by name —
        the surface `repro.lint.CompileGuard` watches to assert steady-state
        ticks stop compiling after warmup (the dynamic R2 check). The
        drafter's own entries ride along prefixed `drafter.`."""
        entries = {"prefill1": self._prefill1, "decode": self._decode}
        if self._chunk_verify is not None:
            entries["chunk_verify"] = self._chunk_verify
        if self.pager is not None:
            entries["set_tab"] = self._set_tab
            entries["scrub"] = self._scrub
        if self.spec is not None:
            entries["verify"] = self._verify
        if self._tree is not None:
            entries["compact"] = self._compact
        if self.drafter is not None:
            probe = getattr(self.drafter, "jit_entries", None)
            if callable(probe):
                entries.update(
                    {f"drafter.{k}": v for k, v in probe().items()}
                )
        return entries

    def reset_stats(self):
        """Zero the token/acceptance counters (e.g. after a warmup run, so a
        timed run's stats exclude it). Slot/cache state is untouched."""
        self.prefill_tokens = self.prefill_pad_tokens = self.decode_tokens = 0
        self.decode_steps = self.chunk_steps = 0
        self.spec_steps = self.spec_slot_steps = self.spec_skipped_steps = 0
        self.drafted_tokens = self.accepted_tokens = self.verified_nodes = 0
        if self.pager is not None:
            self.pager.prefix_hit_tokens = self.pager.prefix_hit_requests = 0

    @property
    def prefix_hit_tokens(self) -> int:
        """Prompt tokens admitted straight off shared radix-prefix pages
        (their prefill was skipped entirely). 0 on unpaged engines."""
        return self.pager.prefix_hit_tokens if self.pager is not None else 0

    @property
    def prefix_hit_requests(self) -> int:
        """Admissions that matched at least one shared prefix page."""
        return self.pager.prefix_hit_requests if self.pager is not None else 0

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def acceptance_rate(self) -> float:
        return spec_acceptance_rate(self.accepted_tokens, self.drafted_tokens)

    @property
    def decode_tokens_per_step(self) -> float:
        return spec_tokens_per_step(self.decode_tokens, self.spec_slot_steps)

    @property
    def skip_rate(self) -> float:
        return spec_skip_rate(self.spec_skipped_steps, self.spec_slot_steps)

    @property
    def mean_draft_k(self) -> float:
        return spec_mean_k(
            self.drafted_tokens, self.spec_slot_steps, self.spec_skipped_steps
        )

    @property
    def nodes_per_step(self) -> float:
        return spec_nodes_per_step(self.verified_nodes, self.spec_slot_steps)
