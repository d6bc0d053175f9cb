"""Grammar-constrained serving engine (paper Algorithm 3 as a runtime).

The engine is built around **continuous batching** over a fixed pool of
`B = slots` decode slots:

  * one jitted `[B, V]` decode step advances every active request at once
    (decode caches are allocated `[.., B, ..]` up front; per-request
    prefill results are inserted into their slot on admission),
  * the host side of Algorithm 2 runs in two context-split stages
    (`GrammarConstraint.ci_rows_batch` + `cd_overlay_batch`): a `[B, A]`
    matrix of PRECOMPUTED store row ids and a `[B, W]` residue-word
    overlay covering the few context-dependent tokens per step,
  * a single fused mask+filter+sample device call unions the packed
    store rows with the residue overlay and draws every slot's next
    token with per-request greedy/temperature/top-k/top-p
    (`repro.kernels.fused_select`; an all-greedy batch rides a
    host-static argmax-only variant, sampling batches precomputed
    Gumbel noise) — only the `[B]` sampled ids come back to the host,
    never `[B, V]`,
  * the paper's *opportunistic masking* fast path (§5 Baselines) validates
    the whole batch's unconstrained proposals first and computes mask rows
    only for the slots whose proposal was rejected,
  * the exactness wrapper survives batching: because the α≤1 mask store
    over-approximates (sound, not complete — paper §4.4), sampled ids are
    verified against the precise parser oracle; invalid picks are demoted
    and the affected rows resampled on device, so emitted text provably
    stays in L_p(G) and terminates only when in L(G),
  * finished requests free their slot and the next queued request is
    admitted immediately (no round-robin sweep), so the pool stays full
    under load.

The per-step bodies of every mode (dense / paged / speculative) live on
ONE shared step-loop core, `serving/loop.py` — the `generate*` entry
points here drive that loop to completion over a fixed request list,
and `serving/async_engine.py` drives the same loop persistently with
live admission, streaming, cancellation and deadlines (docs/serving.md).
This module keeps the engine's device plumbing (jitted decode / fused
mask+sample / paged feed builders), the admission and selection
machinery the modes call into, and the request/stats dataclasses.

`generate_sequential` keeps the original one-request-at-a-time stepping
path for comparison benchmarks (benchmarks/bench_tables.py::
batched_engine_throughput) and as an oracle for the batched scheduler.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.constrain import GrammarConstraint, MAX_ACCEPT
from repro.core.decoding import (DecodeConfig, NEG_INF, select_batch,
                                 select_span)
from repro.core.tokenizer import BOS_ID, ByteTokenizer, EOS_ID
from repro.distributed.api import use_sharding
from repro.distributed.sharding import (serving_cache_shardings,
                                        serving_param_shardings,
                                        serving_rules,
                                        serving_store_sharding)
from repro.core.constrain import accept_width
from repro.kernels.fused_select.ops import (fused_mask_select,
                                            fused_mask_select_span,
                                            gumbel_noise)
from repro.obs import Telemetry
from repro.serving.kvpool import PagedAllocator, PoolExhausted
from repro.spec.scheduler import SPAN_BUCKETS, SlotPhase, SpecConfig

# shared disabled telemetry: the `obs=None` default of the selection
# helpers — span() returns the no-op NULL_SPAN, so un-instrumented
# callers (tests poking _select_tokens directly) pay nothing
_OBS_OFF = Telemetry(enabled=False)


def insert(full, one, b):
    """Write a prefilled request's caches (batch 1) into slot b of the
    dense caches (donated by the engine: updated in place)."""
    return jax.tree.map(
        lambda f, o: jax.lax.dynamic_update_slice_in_dim(
            f, o.astype(f.dtype), b, axis=1), full, one)


# span widths the paged feed path jits against (chunked prefill drains
# prompt backlog through these; decode-only steps ride the width-1 bucket
# at exactly the dense engine's per-step cost)
FEED_BUCKETS = (1, 2, 4, 8, 16, 32)


@dataclass
class Request:
    rid: int
    prompt: bytes = b""
    grammar: Optional[str] = None           # None = unconstrained
    grammar_mode: Optional[str] = None      # "grammar_mask" (overapprox.) |
                                            # "grammar_strict" (underapprox.,
                                            # terminal-boundary-aligned);
                                            # None = engine default
    max_new_tokens: int = 128
    decode: DecodeConfig = field(default_factory=DecodeConfig)
    seed: int = 0
    deadline: Optional[float] = None        # seconds from admission; on
                                            # expiry the request finishes
                                            # with reason "deadline"


@dataclass
class RequestState:
    req: Request
    caches: object = None                   # sequential path only
    pos: int = 0
    generated: bytes = b""
    token_ids: list = field(default_factory=list)
    constraint: Optional[GrammarConstraint] = None
    done: bool = False
    finish_reason: str = ""
    pending_logits: object = None
    mask_time: float = 0.0
    mask_computations: int = 0
    opportunistic_hits: int = 0
    steps: int = 0
    slot: int = -1
    # --- speculation (generate_speculative) ---
    phase: str = SlotPhase.DECODING.value   # jumping/drafting/verifying/…
    jump_tokens: int = 0                    # grammar-forced, zero model calls
    draft_proposed: int = 0
    draft_accepted: int = 0
    # --- paged KV (engine paged mode) ---
    prompt_len: int = 0
    write_from: int = 0         # first position this slot may write into
                                # its pages (below = shared prefix pages)
    kv_pages: int = 0           # pages held when the request finished
    # --- async lifecycle (serving/loop.py) ---
    cancelled: bool = False     # set from any thread; the loop frees the
                                # slot (and its KV pages) next step
    deadline_at: Optional[float] = None     # perf_counter() expiry
    admit_t: Optional[float] = None         # perf_counter() at admission
                                            # (telemetry: slot trace span)
    first_commit_t: Optional[float] = None  # perf_counter() at the commit
                                            # of the first token


@dataclass
class EngineStats:
    requests: int = 0
    tokens: int = 0
    wall: float = 0.0
    mask_time: float = 0.0
    mask_computations: int = 0
    opportunistic_hits: int = 0
    decode_steps: int = 0                   # CONSUMED batched [B,V] device
                                            # steps (one per engine step; a
                                            # discarded speculative forward
                                            # is extra device work counted
                                            # in overlap_discarded, not here)
    batch_slots: int = 0
    mesh_devices: int = 1                   # tensor-parallel mesh size
    # --- host/device overlap (serving/loop.py::DenseMode) ---
    overlap_dispatched: int = 0             # speculative forwards launched
    overlap_hits: int = 0                   # ...that the next step consumed
    overlap_discarded: int = 0              # ...dropped unused (an
                                            # admission, or an unclean step)
    # --- the exact oracle (_select_resolve) ---
    oracle_accepted: int = 0                # device picks the oracle passed
    oracle_rejected: int = 0                # ...and rejected
    resample_calls: int = 0                 # device resample dispatches
    exact_fallbacks: int = 0                # slots drawn by the exact filter
    # --- the step loop (serving/loop.py::StepLoop) ---
    steps_timed: int = 0                    # steps: admission to commit
    step_s: float = 0.0                     # their wall seconds
    admit_steps: int = 0                    # steps that admitted a request
    admit_step_s: float = 0.0               # their wall seconds
    # --- speculation (generate_speculative) ---
    jump_tokens: int = 0                    # emitted with zero model calls
    draft_proposed: int = 0
    draft_accepted: int = 0
    plan_time: float = 0.0                  # host planning (jump + draft)
    # --- paged KV cache (engine paged mode) ---
    kv_pages_in_use: int = 0                # pages still referenced at end
    kv_peak_utilization: float = 0.0        # peak pages-in-use / pool size
    prefix_hit_rate: float = 0.0            # shared / total prompt tokens
    kv_page_allocs: int = 0                 # page allocations over the run
    kv_evictions: int = 0                   # cold pages evicted
    kv_cow_copies: int = 0                  # copy-on-write device copies
    # --- device-time attribution (obs/devtime; nonzero only when the
    # run had device timing on, i.e. bench mode) ---
    device_forward_s: float = 0.0           # synced forward intervals
    device_mask_sample_s: float = 0.0       # synced mask+sample intervals
    overlap_hidden_s: float = 0.0           # device time hidden under
                                            # host work by the overlap gate
    attribution: Optional[dict] = None      # Telemetry.attribution() split

    @property
    def tokens_per_sec(self):
        return self.tokens / max(self.wall, 1e-9)

    @property
    def jump_fraction(self):
        return self.jump_tokens / max(self.tokens, 1)

    @property
    def acceptance_rate(self):
        return self.draft_accepted / max(self.draft_proposed, 1)

    @property
    def overlap_hit_rate(self):
        return self.overlap_hits / max(self.overlap_dispatched, 1)


@dataclass
class _SelectCtx:
    """In-flight state between `_select_dispatch` and `_select_resolve`.

    `ids` is the FIRST-round sampled ids still on device — the overlap
    path (serving/loop.py::DenseMode) feeds it straight into the next
    forward. `clean` ends True iff the host changed nothing: every
    pending slot committed exactly its first-round device id."""
    committed: dict
    pending: set
    ctr: dict
    salts: np.ndarray
    masked: object = None
    ids: object = None
    ok: object = None
    need_mask: object = None
    clean: bool = True
    mask_elapsed: float = 0.0   # ci_lookup + cd_check + mask_dispatch
                                # span seconds (resolve adds its sync
                                # span, then distributes the total per
                                # slot)


class Engine:
    def __init__(self, model, params, tokenizer: ByteTokenizer,
                 grammar_bundles: dict, max_len: int = 512,
                 opportunistic: bool = False,
                 slots: int = 4, paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None, prefill_chunk: int = 32,
                 mesh=None,
                 trunk_shard: bool = False, overlap: bool = True,
                 grammar_mode: str = "grammar_mask",
                 telemetry: bool = True, devtime: bool = False):
        """grammar_bundles: name -> (grammar, table, store).
        slots: decode-pool width B of the batched scheduler.
        paged: serve KV through the paged pool (docs/kv_paging.md) —
        page-table attention, refcounted prefix sharing and chunked
        prefill; token-for-token identical to the dense engine.
        num_pages defaults to slots * ceil(max_len / page_size), i.e.
        the dense engine's exact KV memory budget.
        mesh: a jax Mesh with a "model" axis (launch/mesh.py::
        make_serving_mesh) — serve tensor-parallel across its devices:
        embed/lm_head, the [.., V] logits, the packed mask store and
        the whole mask hot path run vocab-sharded, with one gather in
        the selector; output stays token-for-token identical to the
        single-device engine (docs/sharding.md).
        trunk_shard: additionally shard the trunk megatron-style
        (param_spec/cache_shardings) — TPU-scale memory relief that
        gives up bit-exactness vs the single-device engine.
        overlap: host/device overlap in the dense step loop — dispatch
        step k+1's forward with the on-device sampled ids while the
        host validates step k and builds step k+1's mask rows
        (serving/loop.py). Token-for-token identical; auto-disabled
        for recurrent archs and under opportunistic masking.
        grammar_mode: default approximation family for requests that
        don't set one — "grammar_mask" (the paper's overapproximating
        dmatch rows) or "grammar_strict" (underapproximating,
        terminal-boundary-aligned rows).
        telemetry: default for the step loop's observability layer
        (docs/observability.md) — phase spans, latency histograms,
        request lifecycle, trace capture. False keeps only the exact
        count stats (tokens/mask computations/...); timing fields of
        EngineStats then read 0. Token streams are identical either
        way — instrumentation wraps host-side work only and never
        adds a device synchronization.
        devtime: bench mode — device-span brackets around the
        jitted calls sync on exit (obs/devtime.py), so EngineStats and
        /stats carry true device intervals instead of dispatch lower
        bounds. OFF for serving: the default preserves the no-sync
        contract above."""
        if grammar_mode not in GrammarConstraint.MODES:
            raise ValueError(f"unknown grammar_mode {grammar_mode!r}; "
                             f"expected one of {GrammarConstraint.MODES}")
        self.model = model
        self.params = params
        self.tok = tokenizer
        self.bundles = dict(grammar_bundles)
        self.grammar_mode = grammar_mode
        self.max_len = max_len
        self.opportunistic = opportunistic
        self.slots = max(1, int(slots))
        self.paged = bool(paged)
        self.page_size = max(1, int(page_size))
        self.max_pages = -(-max_len // self.page_size)
        self.num_pages = int(num_pages or self.slots * self.max_pages)
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.mesh = mesh
        self.trunk_shard = bool(trunk_shard)
        self.overlap = bool(overlap)
        self.telemetry_enabled = bool(telemetry)
        # bench mode: step loops sync devtime brackets on exit
        # (the documented exception to the serving no-sync contract)
        self.devtime_enabled = bool(devtime)
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    "serving mesh needs a 'model' axis "
                    "(launch/mesh.py::make_serving_mesh)")
            self._rules = serving_rules(mesh, model.cfg,
                                        trunk_shard=self.trunk_shard)
            self.params = jax.device_put(
                params, serving_param_shardings(
                    params, mesh, model.cfg,
                    trunk_shard=self.trunk_shard))
        else:
            self._rules = None
        if self.paged and not model.supports_span_decode:
            raise ValueError(
                "paged KV serving needs position-addressed decode caches "
                "(attn/moe layer kinds); this arch has recurrent or "
                "side-input state")
        if self.paged and model.cfg.sliding_window:
            raise ValueError(
                "paged KV serving does not support sliding-window "
                "attention")
        self._prefill = self._shard_jit(
            lambda p, b, tl: model.prefill(p, b, cache_len=max_len,
                                           true_len=tl))
        # the dense decode writes one row per slot and layer into the
        # caches it is given (models/model.py::_decode_trunk); every
        # program that updates the dense caches takes them donated, so
        # the update aliases its input
        self._decode = self._shard_jit(model.decode_step, donate_argnums=1)
        # one concatenated device store for all grammars: a request's rows
        # index its grammar's block via the per-grammar row offset (shared
        # by the batched and sequential paths — the store lives on device
        # exactly once)
        self._row_offset: dict[str, int] = {}
        self._rebuild_store_cat()
        self._build_batched_fns()

    def _rebuild_store_cat(self):
        """(Re)build the concatenated device store from self.bundles.
        Insertion order fixes each grammar's block, so registering a new
        grammar appends a block without moving existing offsets."""
        self._row_offset = {}
        parts, off = [], 0
        for name, b in self.bundles.items():
            self._row_offset[name] = off
            parts.append(b[2].packed)
            off += b[2].packed.shape[0]
        words = (self.tok.vocab_size + 31) // 32
        cat = (np.concatenate(parts, axis=0) if parts
               else np.zeros((1, words), np.uint32))
        if self.mesh is None:
            # zero rows/words up to the fused kernel's (8, 128) HBM tile,
            # so its row DMAs are aligned and no step pads the store
            # again. A mesh runs the reference, and there the word
            # shards must stay aligned with the logits' vocab shards.
            cat = np.pad(cat, ((0, -cat.shape[0] % 8),
                               (0, -cat.shape[1] % 128)))
            self._store_cat = jnp.asarray(cat)
        else:
            # the packed mask store lives vocab-sharded on the mesh:
            # word w of every row sits on the shard owning vocab ids
            # [w*32, (w+1)*32) — the row gather + bitwise union +
            # logits mask of the reference stay shard-local
            self._store_cat = jax.device_put(
                cat, serving_store_sharding(self.mesh, cat.shape[1]))

    def register_grammar(self, name: str, bundle) -> None:
        """Hot-register a freshly compiled (grammar, table, store) bundle.

        Appends the store's rows to the concatenated device store and
        makes `name` servable by subsequent requests — no engine restart.
        NOT safe concurrent with a running step: callers must invoke it
        between steps (AsyncEngine.load_grammar posts it onto the step
        loop's control queue, which drains at the top of each loop
        iteration). Jitted mask fns take the store as a call argument,
        so the grown array just triggers one benign retrace.
        """
        if name in self.bundles:
            raise ValueError(f"grammar {name!r} already registered")
        store = bundle[2]
        if store.packed.shape[1] * 32 < self.tok.vocab_size:
            raise ValueError(
                f"store for {name!r} built for a smaller vocab "
                f"({store.packed.shape[1] * 32} < {self.tok.vocab_size})")
        self.bundles[name] = bundle
        self._rebuild_store_cat()

    def _shard_jit(self, fn, **jit_kw):
        """jit, plus (when a mesh is configured) the serving
        `use_sharding` context around every call — shard_hint rules
        bind at trace time, and per-bucket retraces re-enter them."""
        jf = jax.jit(fn, **jit_kw)
        if self.mesh is None:
            return jf

        def call(*args, **kwargs):
            with use_sharding(self.mesh, self._rules):
                return jf(*args, **kwargs)
        return call

    def _note_jit_cost(self, tele, name: str, fn, *args) -> None:
        """Lazily attach static roofline terms (distributed/hlo_cost
        over the compiled HLO) for a jitted fn to the devtime registry —
        once per fn name, only in bench mode, at the exact
        shapes the caller just dispatched (the lowering hits the
        compilation cache, so this is a walk, not a recompile). Sharded
        closures and anything else without .lower() are skipped."""
        devtime = tele.devtime
        if not devtime.enabled or name in devtime.costs:
            return
        devtime.costs[name] = {"flops": 0.0, "hbm_bytes": 0.0,
                               "wire_bytes": 0.0}     # one attempt only
        try:
            from repro.distributed.hlo_cost import estimate_jit_cost
            c = estimate_jit_cost(fn, *args)
        except Exception:
            return
        devtime.set_cost(name, c["flops"], c["hbm_bytes"],
                         c.get("wire_bytes", 0.0))

    def _place_caches(self, caches):
        """Commit freshly-initialized decode caches / paged pools to the
        mesh (replicated in the bit-exact default; kv-head-sharded under
        trunk_shard). No-op without a mesh."""
        if self.mesh is None:
            return caches
        return jax.device_put(
            caches, serving_cache_shardings(caches, self.mesh,
                                            self.model.cfg,
                                            trunk_shard=self.trunk_shard))

    def _build_batched_fns(self):
        vocab = self.model.cfg.vocab_size

        def fused_greedy(logits, store, rows, cd, eos, constrained):
            """Host-static all-greedy variant: one fused mask+argmax
            device call — no filter math, no PRNG (the selected ids are
            the masked argmax regardless of the per-slot configs)."""
            ids, masked = fused_mask_select(logits, store, rows, cd, eos,
                                            constrained)
            ok = jnp.any(masked > NEG_INF / 2, axis=-1)
            return masked, ids, ok

        def fused_sample(logits, store, rows, cd, eos, constrained,
                         greedy, temp, top_k, top_p, noise):
            """Sampling variant: precomputed Gumbel noise replaces the
            per-call categorical streams — `argmax(filtered + noise)`
            selects the bit-identical token (kernels/fused_select) while
            the PRNG work rides the previous step's resolve."""
            ids, masked = fused_mask_select(
                logits, store, rows, cd, eos, constrained,
                greedy, temp, top_k, top_p, noise=noise)
            ok = jnp.any(masked > NEG_INF / 2, axis=-1)
            return masked, ids, ok

        def resample(masked, ban, redo, greedy, temp, top_k, top_p, keys):
            V = masked.shape[-1]
            hit = (jnp.arange(V)[None, :] == ban[:, None]) & redo[:, None]
            masked = jnp.where(hit, jnp.asarray(NEG_INF, masked.dtype),
                               masked)
            ids = select_batch(masked, keys, greedy, temp, top_k, top_p)
            ok = jnp.any(masked > NEG_INF / 2, axis=-1)
            return masked, ids, ok

        def span_mask_select(logits, store, rows, cd, eos, constrained,
                             greedy, temp, top_k, top_p, keys):
            """Fused speculation pass: grammar-mask a [B, S, V] span and
            select a token at every position (constrained positions via
            the precomputed store rows + per-position residue overlay,
            padding/unconstrained pass through). The accept test is a
            host-side == against the [B, S] ids."""
            _, masked = fused_mask_select_span(logits, store, rows, cd, eos,
                                               constrained)
            ids = select_span(masked, keys, greedy, temp, top_k, top_p)
            ok = jnp.any(masked > NEG_INF / 2, axis=-1)
            return masked, ids, ok

        def span_feed_paged(p, c, toks, pos, fm, pt, sel):
            """Paged feed for the plain engine: decode a [B, S] span
            through the page tables and return each slot's logits at its
            selection index (clamped; non-selecting rows are ignored by
            the caller), so the downstream mask/sample machinery sees
            the same [B, V] it would from a dense decode_step."""
            logits, c = self.model.decode_span(
                p, c, toks, pos, feed_mask=fm,
                batch_ctx={"page_table": pt})
            B, S = toks.shape
            sel_logits = logits[jnp.arange(B), jnp.clip(sel, 0, S - 1)]
            return sel_logits, c

        def copy_page(c, s, d):
            """Apply one allocator-directed COW copy to the page pools
            (leaves are [count, P, ps, K, Dh])."""
            return jax.tree.map(lambda a: a.at[:, d].set(a[:, s]), c)

        self._fused_greedy = self._shard_jit(fused_greedy)
        self._fused_sample = self._shard_jit(fused_sample)
        self._gumbel = self._shard_jit(
            lambda keys: gumbel_noise(keys, vocab))
        self._noise_cache = None    # (keys bytes, [B, V] device noise)
                                    # speculatively dispatched by the
                                    # previous step's resolve
        self._resample = self._shard_jit(resample)
        self._sample_plain = self._shard_jit(select_batch)
        self._insert_caches = self._shard_jit(insert, donate_argnums=0)
        self._span_mask_select = self._shard_jit(span_mask_select)
        self._span_decode = self._shard_jit(
            lambda p, c, toks, pos, fm: self.model.decode_span(
                p, c, toks, pos, feed_mask=fm), donate_argnums=1)
        self._span_decode_paged = self._shard_jit(
            lambda p, c, toks, pos, fm, pt: self.model.decode_span(
                p, c, toks, pos, feed_mask=fm,
                batch_ctx={"page_table": pt}))
        self._span_feed_paged = self._shard_jit(span_feed_paged)
        self._copy_page = self._shard_jit(copy_page)

    # ------------------------------ lifecycle -----------------------------

    def _make_constraint(self, req: Request) -> Optional[GrammarConstraint]:
        if req.grammar is None:
            return None
        g, tab, store = self.bundles[req.grammar]
        return GrammarConstraint(g, tab, store, self.tok,
                                 mode=req.grammar_mode or self.grammar_mode)

    def _request_ids(self, req: Request) -> list[int]:
        ids = self._prompt_ids(req)
        if len(ids) == 1:
            # prefill needs >= 1 token before the decode loop takes
            # over; re-feeding the last prompt token would double-step
            # recurrent caches, so prepend BOS instead
            ids = [BOS_ID] + ids
        return ids

    def _bucketed_prompt(self, ids: list[int]):
        """Zero-pad a prompt to its power-of-two jit bucket (capped at
        max_len) -> ([1, bucket] int32, n). The prefill specializes once
        per bucket instead of once per length; `true_len = n` masks the
        padded tail's cache entries. Recurrent/SSM layer kinds fold a
        padded tail into their carried state (true_len can't mask it),
        so those archs keep exact-length prefill."""
        n = len(ids)
        bucket = n
        if self.model.prefill_padding_safe:
            bucket = max(n, min(1 << max(0, n - 1).bit_length(),
                                self.max_len))
        prompt = np.zeros((1, bucket), np.int32)
        prompt[0, :n] = ids
        return jnp.asarray(prompt), n

    def _admit_common(self, req: Request, b: int, caches):
        """Shared slot admission: build request state, prefill the
        prompt, insert its caches into slot b. Returns (state, caches);
        per-loop array updates stay with the caller.

        The prompt is zero-padded to a power-of-two bucket before the
        prefill call, so the jitted prefill specializes once per bucket
        instead of once per distinct prompt length (true_len masks the
        padded tail's cache entries); admission cost amortizes across
        requests."""
        st = RequestState(req=req, slot=b)
        st.constraint = self._make_constraint(req)
        ids = self._request_ids(req)
        prompt, n = self._bucketed_prompt(ids[:-1])
        _, pc = self._prefill(self.params, {"tokens": prompt}, jnp.int32(n))
        caches = self._insert_caches(caches, pc, jnp.int32(b))
        st.token_ids = list(ids)
        st.pos = len(ids)
        st.prompt_len = len(ids)
        return st, caches

    def _prompt_ids(self, req: Request) -> list[int]:
        ids = self.tok.encode(req.prompt) if req.prompt else []
        if not ids:
            ids = [BOS_ID]
        return ids

    def _commit(self, st: RequestState, token: int):
        st.token_ids.append(token)
        st.pos += 1
        if token == EOS_ID:
            st.done = True
            st.finish_reason = "eos"
            return
        st.generated += self.tok.id_to_bytes[token]
        if st.steps >= st.req.max_new_tokens:
            st.done = True
            st.finish_reason = "length"
        if st.pos >= self.max_len - 1:
            st.done = True
            st.finish_reason = "max_len"

    # ============================ batched path ============================

    def _step_keys(self, seeds: np.ndarray, salts: np.ndarray,
                   attempt: int) -> np.ndarray:
        """[B, 2] uint32 threefry key data: one counter-mode stream per
        slot, advanced by (salts[b], attempt). salts are PER-SLOT step
        counters (st.steps), not the global engine step, so a slot's
        sample stream depends only on its own progress — which is what
        keeps the paged engine (whose chunked prefill consumes engine
        steps) token-for-token identical to the dense one. Greedy rows
        ignore keys."""
        k = np.empty((seeds.shape[0], 2), np.uint32)
        k[:, 0] = seeds
        k[:, 1] = (salts.astype(np.uint32) << np.uint32(4)) | \
            np.uint32(attempt & 0xF)
        return k

    def _fallback_exact(self, st: RequestState, row: np.ndarray,
                        attempt_salt: int) -> Optional[int]:
        """Rare slow path: the sampled ids kept failing the oracle (or the
        mask emptied after demotions). Exact-filter the remaining allowed
        set (|allowed| oracle calls) and draw host-side, so the step never
        dead-ends while a valid continuation exists. top-k/top-p are not
        re-applied here — this path fires when the mask kept only a
        handful of candidates anyway."""
        gc = st.constraint
        allowed = np.where(row > NEG_INF / 2)[0]
        valid = [int(t) for t in allowed
                 if t == EOS_ID or gc.is_valid_extension(st.generated,
                                                         int(t))]
        if not valid:
            return None
        sub = row[valid].astype(np.float64)
        if st.req.decode.method == "greedy":
            return valid[int(np.argmax(sub))]
        temp = max(st.req.decode.temperature, 1e-6)
        p = np.exp((sub - sub.max()) / temp)
        p /= p.sum()
        rng = np.random.default_rng(
            (st.req.seed * 1000003 + st.steps * 31 + attempt_salt)
            & 0xFFFFFFFF)
        return int(rng.choice(valid, p=p))

    def _select_dispatch(self, logits, slot_state, pending: set,
                         seeds, greedy, temp, top_k, top_p, obs=None):
        """Phase A of per-step token selection: the opportunistic fast
        path (host sync) and the fused mask+sample DISPATCH — no sync of
        the sampled ids. Returns a `_SelectCtx` whose `.ids` device array
        is what the overlap path feeds into the next forward before the
        host ever sees it. `_select_resolve` is phase B. `obs` is the
        step loop's Telemetry; its spans only bracket host work that was
        already timed — no device sync is added."""
        if obs is None:
            obs = _OBS_OFF
        # reprolint: mutated-inflight=greedy,temp,top_k,top_p admit() rewrites the decode configs while dispatches are in flight
        B = self.slots
        committed: dict[int, int] = {}
        pending = set(pending)
        ctr = {"mask_computations": 0, "opportunistic_hits": 0,
               "oracle_accepted": 0, "oracle_rejected": 0,
               "resample_calls": 0, "exact_fallbacks": 0}
        salts = np.array([slot_state[b].steps if slot_state[b] else 0
                          for b in range(B)], np.uint32)
        ctx = _SelectCtx(committed=committed, pending=pending, ctr=ctr,
                         salts=salts)

        # ---- opportunistic fast path (whole batch at once) ----------
        if self.opportunistic and any(
                slot_state[b].constraint is not None for b in pending):
            with obs.span("opportunistic"):
                keys = self._step_keys(seeds, salts, 0)
                prop = np.asarray(self._sample_plain(
                    logits, jnp.asarray(keys),
                    jnp.asarray(greedy.copy()),
                    jnp.asarray(temp.copy()),
                    jnp.asarray(top_k.copy()),
                    jnp.asarray(top_p.copy())))
                ctx.clean = False   # committed ids came from the
                                    # unmasked proposal stream
                for b in list(pending):
                    st = slot_state[b]
                    t = int(prop[b])
                    if st.constraint is None:
                        committed[b] = t
                        pending.discard(b)
                    elif st.constraint.is_valid_extension(st.generated, t):
                        st.opportunistic_hits += 1
                        ctr["opportunistic_hits"] += 1
                        committed[b] = t
                        pending.discard(b)

        if not pending:
            return ctx

        # ---- context-split host stages + fused mask/select dispatch -
        # Three spans partition the old rows_build+mask_dispatch
        # bracket: ci_lookup (parse, group, emit precomputed row ids),
        # cd_check (the context-dependent residue overlay — a handful
        # of packed words per slot), mask_dispatch (the device call).
        # Their sum (ctx.mask_elapsed) keeps the historical mask_time
        # accounting byte-identical.
        with obs.span("ci_lookup") as sp_ci:
            cons = [slot_state[b].constraint
                    if (b in pending and slot_state[b] is not None)
                    else None for b in range(B)]
            texts = [slot_state[b].generated if slot_state[b] else b""
                     for b in range(B)]
            offs = np.array(
                [self._row_offset.get(slot_state[b].req.grammar, 0)
                 if slot_state[b] is not None else 0
                 for b in range(B)], np.int64)
            rows, eos, _, groups = GrammarConstraint.ci_rows_batch(
                cons, texts, max_accept=MAX_ACCEPT, row_offsets=offs)
        with obs.span("cd_check") as sp_cd:
            cd = GrammarConstraint.cd_overlay_batch(
                cons, groups, int(self._store_cat.shape[1]))
        with obs.device_span("mask_sample") as dv:
            with obs.span("mask_dispatch") as sp_disp:
                need_mask = np.array([c is not None for c in cons], bool)
                # numpy args go into the jitted calls DIRECTLY — an
                # explicit jnp.asarray round-trip costs ~25x the
                # dispatch itself on CPU. The per-step arrays (rows,
                # cd, eos, need_mask, keys) are freshly allocated each
                # step; the long-lived decode-config arrays are mutated
                # by admit(), so they ship private copies (the same
                # zero-copy aliasing hazard class as the paged feed).
                if bool(np.all(greedy)):
                    ctx.masked, ctx.ids, ctx.ok = self._fused_greedy(  # reprolint: dispatch
                        logits, self._store_cat, rows, cd, eos,
                        need_mask)
                    cost_args = (logits, self._store_cat, rows, cd,
                                 eos, need_mask)
                    cost_fn = self._fused_greedy
                else:
                    keys = self._step_keys(seeds, salts, 1)
                    noise = self._noise_take(keys)
                    ctx.masked, ctx.ids, ctx.ok = self._fused_sample(  # reprolint: dispatch
                        logits, self._store_cat, rows, cd, eos,
                        need_mask, greedy.copy(), temp.copy(),
                        top_k.copy(), top_p.copy(), noise)
                    cost_args = (logits, self._store_cat, rows, cd,
                                 eos, need_mask, greedy.copy(),
                                 temp.copy(), top_k.copy(),
                                 top_p.copy(), noise)
                    cost_fn = self._fused_sample
            # host span stays dispatch-only; in bench mode the
            # device bracket blocks on the sampled ids here
            dv.done((ctx.ids, ctx.ok))
        self._note_jit_cost(obs, "mask_sample", cost_fn, *cost_args)
        ctx.need_mask = need_mask
        ctr["mask_computations"] += int(need_mask.sum())
        ctx.mask_elapsed = sp_ci.dur + sp_cd.dur + sp_disp.dur
        return ctx

    # --------------------- Gumbel-noise speculation ---------------------

    def _noise_take(self, keys: np.ndarray):
        """[B, V] device Gumbel noise for exactly these threefry keys.
        The previous step's resolve usually dispatched it speculatively
        (`_noise_prefetch`); a miss — admission changed a seed, a slot
        finished — computes it inline. Either way the noise is the
        bitwise `jax.random.gumbel` stream of `keys`, so sampling
        equivalence never depends on the cache."""
        kb = keys.tobytes()
        cached, self._noise_cache = self._noise_cache, None
        if cached is not None and cached[0] == kb:
            return cached[1]
        return self._gumbel(keys)

    def _noise_prefetch(self, slot_state, seeds: np.ndarray) -> None:
        """Dispatch next step's first-round noise with PREDICTED salts
        (every live slot advances one step). The dispatch is async —
        the host returns immediately; the device fills the noise while
        the host runs the oracle loop and the next forward."""
        B = self.slots
        salts = np.array(
            [slot_state[b].steps + 1
             if slot_state[b] is not None and not slot_state[b].done
             else 0 for b in range(B)], np.uint32)
        keys = self._step_keys(seeds, salts, 1)
        self._noise_cache = (keys.tobytes(), self._gumbel(keys))

    def _select_resolve(self, ctx, slot_state,
                        seeds, greedy, temp, top_k, top_p, obs=None):
        """Phase B: sync the sampled ids, verify against the exact
        oracle, demote+resample on device, exact-filter fallback.
        Returns (committed, counters); `ctx.clean` stays True only when
        every pending slot committed its FIRST-round device id — the
        overlap path's speculative forward is valid exactly then."""
        if obs is None:
            obs = _OBS_OFF
        B = self.slots
        committed, pending, ctr = ctx.committed, ctx.pending, ctx.ctr
        salts = ctx.salts
        if ctx.ids is None:
            return committed, ctr
        masked = ctx.masked
        with obs.span("select_resolve") as sp_sync:
            ids_h, ok_h = np.asarray(ctx.ids), np.asarray(ctx.ok)
        # speculative Gumbel dispatch for the NEXT step: the device
        # draws the noise while this step's oracle loop runs
        if not bool(np.all(greedy)):
            self._noise_prefetch(slot_state, seeds)
        n_masked = int(ctx.need_mask.sum())
        # ci lookup + cd check + dispatch + sync — the historical
        # mask_time definition (the oracle loop was never part of it)
        elapsed = sp_sync.dur + ctx.mask_elapsed
        for b in np.where(ctx.need_mask)[0]:
            slot_state[b].mask_computations += 1
            slot_state[b].mask_time += elapsed / max(n_masked, 1)

        # rejection wrapper: the α<=1 mask is sound but over-
        # approximate; verify with the exact oracle, demote invalid
        # picks on device, resample only the affected rows. Only
        # [B] ids/flags ever cross back to the host here.
        with obs.span("host_oracle"):
            for attempt in range(2, 6):
                redo = np.zeros(B, bool)
                ban = np.zeros(B, np.int32)
                for b in sorted(pending):
                    st = slot_state[b]
                    if st.constraint is None:
                        committed[b] = int(ids_h[b])
                        pending.discard(b)
                        continue
                    if not ok_h[b]:
                        ctx.clean = False
                        continue    # mask exhausted -> fallback
                    t = int(ids_h[b])
                    if t == EOS_ID or st.constraint.is_valid_extension(
                            st.generated, t):
                        ctr["oracle_accepted"] += 1
                        committed[b] = t
                        pending.discard(b)
                    else:
                        ctr["oracle_rejected"] += 1
                        redo[b] = True
                        ban[b] = t
                if not redo.any():
                    break
                ctx.clean = False
                ctr["resample_calls"] += 1
                keys = self._step_keys(seeds, salts, attempt)
                masked, ids, ok = self._resample(
                    masked, jnp.asarray(ban), jnp.asarray(redo),
                    jnp.asarray(greedy), jnp.asarray(temp),
                    jnp.asarray(top_k), jnp.asarray(top_p),
                    jnp.asarray(keys))
                ids_h, ok_h = np.asarray(ids), np.asarray(ok)

            # exact-filter fallback for slots that never validated
            for b in sorted(pending):
                ctx.clean = False
                ctr["exact_fallbacks"] += 1
                st = slot_state[b]
                nxt = self._fallback_exact(st, np.asarray(masked[b]),
                                           st.steps)
                if nxt is None:
                    # nothing valid (should not happen for C_k in
                    # L_p(G)) — stop this request
                    st.done = True
                    st.finish_reason = "mask_exhausted"
                else:
                    committed[b] = nxt
                pending.discard(b)
        return committed, ctr

    def _select_tokens(self, logits, slot_state, pending: set,
                       seeds, greedy, temp, top_k, top_p, obs=None):
        """Shared per-step token selection for the batched engines (the
        dense loop and the paged feed loop run this IDENTICAL code on a
        [B, V] logits matrix — equivalence by construction): the
        opportunistic fast path, one fused mask+sample device call, the
        on-device demote/resample rejection wrapper, and the exact-filter
        fallback. `pending` names the slots that need a token this step;
        rows outside it are ignored. Returns (committed: {slot: token},
        counters). Slots whose mask dead-ends are marked done
        ("mask_exhausted") and excluded from `committed`."""
        ctx = self._select_dispatch(logits, slot_state, pending, seeds,
                                    greedy, temp, top_k, top_p, obs=obs)
        return self._select_resolve(ctx, slot_state, seeds, greedy, temp,
                                    top_k, top_p, obs=obs)

    def generate(self, requests: list[Request], verbose: bool = False):
        """Continuous batching over a fixed pool of `self.slots` slots.

        Per engine step: ONE [B, V] decode for every active slot, ONE
        fused mask+sample call (constrained and unconstrained slots mixed
        via the `constrained` flag), and only [B]-sized transfers back to
        the host. Finished slots are refilled from the queue immediately.
        With `overlap` (the default) the next step's forward is
        dispatched with the on-device sampled ids before the host syncs,
        hiding the host-side grammar work behind device compute.

        In paged mode the same selection machinery runs behind the paged
        feed loop: chunked prefill, prefix sharing and page-table
        attention replace the dense per-slot caches.

        The step body lives in serving/loop.py (one shared loop for the
        sync and async engines, all modes)."""
        from repro.serving.loop import ListSource, StepLoop, make_mode
        loop = StepLoop(self, make_mode(self), ListSource(requests),
                        verbose=verbose)
        return loop.run()

    # ============================= paged path =============================
    # Paged KV serving (docs/kv_paging.md): the dense per-slot decode
    # caches are replaced by ONE global page pool per attention layer;
    # slots read/write through refcounted page tables. Admission
    # chain-hashes the prompt at page granularity and ATTACHES matching
    # shared pages instead of re-prefilling them; the unmatched tail
    # drains as chunked prefill through the same per-step span call that
    # decoding slots ride at width 1 — so one long admission never
    # stalls the pool, and N requests sharing a schema/system prompt pay
    # its prefill once and hold one physical copy.

    def _paged_setup(self, B):
        """Fresh allocator + zeroed device page pools for one run."""
        alloc = PagedAllocator(self.num_pages, self.page_size, B,
                               self.max_pages)
        caches = self._place_caches(
            self.model.init_paged_caches(self.num_pages, self.page_size))
        return alloc, caches

    def _admit_paged(self, req: Request, b: int, alloc, ids=None):
        """Paged admission: no prefill device call here — the prompt is
        attached from shared pages where its page-aligned prefix
        chain-hash hits, and the rest becomes feed backlog drained by
        the chunked-prefill span steps."""
        st = RequestState(req=req, slot=b)
        st.constraint = self._make_constraint(req)
        if ids is None:
            ids = self._request_ids(req)
        st.token_ids = list(ids)
        st.pos = len(ids)
        st.prompt_len = len(ids)
        plan = alloc.admit(b, ids)
        st.write_from = plan.write_from
        return st, plan

    def _paged_can_admit(self, alloc, req, ids_cache) -> bool:
        """Admission gate: only admit a request when its whole prompt's
        pages can be reserved (prefix hits just reduce the need). Its
        token ids are computed once and cached by rid, so a request
        blocked for many steps isn't re-tokenized each step."""
        ids = ids_cache.get(req.rid)
        if ids is None:
            ids = ids_cache[req.rid] = self._request_ids(req)
        return alloc.can_admit(len(ids))

    def _paged_wake(self, alloc, b, st, feed_pos, waiting) -> bool:
        """Re-check a waiting slot (shared prefix pages still being
        filled by another slot); on wake, adopt the — possibly
        orphan-claim-lowered — feed/write cursors. True = slot live."""
        if not waiting[b]:
            return True
        r = alloc.ready(b)
        if r is None:
            return False
        waiting[b] = False
        feed_pos[b], st.write_from = r
        return True

    def _feed_width(self, pend: list) -> int:
        """Smallest feed bucket covering the widest per-slot backlog,
        capped at prefill_chunk (steady-state decode rides width 1)."""
        cands = [s for s in FEED_BUCKETS if s <= self.prefill_chunk] or [1]
        top = max(pend)
        for S in cands:
            if S >= top:
                return S
        return cands[-1]

    def _prepare_feed(self, alloc, caches, b, st, fs, k):
        """Reserve/COW pages for slot b's feed of positions [fs, fs+k)
        (only [max(fs, write_from), fs+k) is actually written) and apply
        any copy-on-write device copies. Returns the updated caches, or
        None if the pool is truly exhausted — the caller finishes the
        request with 'kv_oom' instead of crashing the pool."""
        ws = max(fs, st.write_from)
        if fs + k > ws:
            try:
                for s_, d_ in alloc.prepare_write(b, ws, fs + k):
                    caches = self._copy_page(caches, jnp.int32(s_),
                                             jnp.int32(d_))
            except PoolExhausted:
                st.done = True
                st.finish_reason = "kv_oom"
                return None
        return caches

    def _kv_stats(self, stats: EngineStats, alloc) -> EngineStats:
        stats.kv_pages_in_use = alloc.pages_in_use
        stats.kv_peak_utilization = alloc.peak_in_use / max(alloc.P, 1)
        stats.prefix_hit_rate = alloc.prefix_hit_rate
        stats.kv_page_allocs = alloc.total_allocs
        stats.kv_evictions = alloc.evictions
        stats.kv_cow_copies = alloc.cow_copies
        return stats

    # ========================== speculative path ==========================
    # Grammar-aware speculative decoding on top of the batched pool:
    # jump-forward (grammar-forced tokens committed with zero model
    # calls) + draft-verify (host proposer drafts, one fused [B, S, V]
    # span decode + mask + select verifies the whole window). Greedy
    # speculative decoding is token-for-token identical to generate():
    # forced tokens are the masked argmax's only support point, accepted
    # drafts equal the span selection the plain engine would have made,
    # and the bonus/demote path replays the same deterministic order.

    def _resolve_span_selection(self, st: RequestState, masked_dev, b: int,
                                idx: int, proposed: int, row_ok: bool,
                                salt: int) -> Optional[int]:
        """Validate one span selection against the exact oracle, demoting
        invalid picks in the same order as generate()'s device-side
        rejection wrapper (4 demote rounds, then the exact-filter
        fallback). Pulls the [V] masked row to the host only when the
        first pick fails (rare)."""
        gc = st.constraint
        if gc is None:
            return proposed
        row = None
        t = proposed
        if row_ok:
            for attempt in range(4):
                if t == EOS_ID or gc.is_valid_extension(st.generated, t):
                    return t
                if row is None:
                    row = np.asarray(masked_dev[b, idx], np.float32)
                row[t] = NEG_INF
                if not (row > NEG_INF / 2).any():
                    break
                if st.req.decode.method == "greedy":
                    t = int(np.argmax(row))
                else:
                    # host-side redraw (temperature softmax over the
                    # demoted row; sampling carries no equivalence
                    # obligation — see docs/speculation.md)
                    temp = max(st.req.decode.temperature, 1e-6)
                    r = row.astype(np.float64)
                    finite = r > NEG_INF / 2
                    p = np.where(finite, np.exp((r - r[finite].max())
                                                / temp), 0.0)
                    p /= p.sum()
                    rng = np.random.default_rng(
                        (st.req.seed * 1000003 + st.steps * 31
                         + salt * 7 + attempt) & 0xFFFFFFFF)
                    t = int(rng.choice(len(r), p=p))
        if row is None:
            row = np.asarray(masked_dev[b, idx], np.float32)
        return self._fallback_exact(st, row, salt)

    @staticmethod
    def _choose_span(desired: list) -> int:
        """Pick the span bucket maximizing committed-tokens-per-compute:
        a span of width S costs ~B*S model work, and serves min(d, S)
        useful positions per slot. The +0.3 denominator models the fixed
        per-step overhead, breaking ties toward wider spans."""
        top = max(desired)
        best, best_score = 1, -1.0
        for S in SPAN_BUCKETS:
            score = sum(min(d, S) for d in desired) / (S + 0.3)
            if score > best_score:
                best, best_score = S, score
            if S >= top:
                break
        return best

    def _span_keys(self, seeds: np.ndarray,
                   salts: np.ndarray, S: int) -> np.ndarray:
        """[B, S, 2] uint32 threefry key data: one counter-mode stream
        per (slot, span position). `salts` are PER-SLOT step counters
        (st.steps), like `_step_keys` — a slot's sample stream depends
        only on its own progress, never on the loop-global step count,
        so async admission timing cannot change sampled speculative
        streams (a slot commits >= 1 token per selecting span, so
        consecutive spans' salt<<6 windows never collide for S <= 64).
        Greedy rows ignore keys."""
        B = seeds.shape[0]
        k = np.empty((B, S, 2), np.uint32)
        k[:, :, 0] = seeds[:, None]
        k[:, :, 1] = ((salts.astype(np.uint32)[:, None] << np.uint32(6))
                      + np.arange(S, dtype=np.uint32)[None, :])
        return k

    def generate_speculative(self, requests: list[Request],
                             spec: Optional[SpecConfig] = None,
                             verbose: bool = False):
        """Continuous batching with grammar-aware speculation.

        Per engine step and per active slot: the scheduler first chases
        grammar-FORCED tokens (jump-forward, committed host-side with no
        model call), then drafts up to K oracle-vetted tokens from the
        slot's own history. One fused span decode replays forced tokens
        and scores drafts for every slot at once ([B, S, V], S bucketed),
        one fused span mask+select turns that into per-position picks,
        and the host accepts each slot's longest matching draft prefix
        plus a bonus token. Slots with nothing to speculate ride the same
        span at width 1 — identical cost to generate()'s step.

        The step body lives in serving/loop.py::SpecMode (dense or
        paged, on the same shared loop as every other mode).
        """
        from repro.serving.loop import ListSource, SpecMode, StepLoop
        loop = StepLoop(self, SpecMode(self, spec), ListSource(requests),
                        verbose=verbose)
        return loop.run()


    # =========================== sequential path ==========================
    # The original one-request-at-a-time engine (paper Algorithm 3,
    # round-robin). Kept as the baseline the batched scheduler is
    # benchmarked against, and as a behavioral oracle in tests.

    def _start(self, req: Request) -> RequestState:
        st = RequestState(req=req)
        st.constraint = self._make_constraint(req)
        ids = self._prompt_ids(req)
        prompt, n = self._bucketed_prompt(ids)
        logits, caches = self._prefill(self.params, {"tokens": prompt},
                                       jnp.int32(n))
        st.caches = caches
        st.pos = n
        st.token_ids = list(ids)
        st.pending_logits = logits[:, n - 1]    # prediction for next token
        return st

    def _logits(self, st: RequestState):
        if getattr(st, "pending_logits", None) is not None:
            lg = st.pending_logits
            st.pending_logits = None
            return lg
        tok = jnp.asarray([st.token_ids[-1]], jnp.int32)
        pos = jnp.asarray([st.pos - 1], jnp.int32)
        lg, st.caches = self._decode(self.params, st.caches, tok, pos)
        return lg  # [1, V] device array

    def _select(self, st: RequestState, logits, key) -> int:
        return int(st.req.decode.select(logits, key)[0])

    def _step(self, st: RequestState, key, obs=None) -> None:
        if obs is None:
            obs = _OBS_OFF
        logits = self._logits(st)
        st.steps += 1
        req = st.req

        if st.constraint is None:
            nxt = self._select(st, logits, key)
            self._commit(st, nxt)
            return

        gc = st.constraint
        text = st.generated

        if self.opportunistic:
            with obs.span("opportunistic"):
                proposal = self._select(st, logits, key)
                hit = gc.is_valid_extension(text, proposal)
            if hit:
                st.opportunistic_hits += 1
                self._commit(st, proposal)
                return

        with obs.span("ci_lookup") as sp_rows:
            sg = gc.step_groups(text)
            rlist = gc.group_rows(sg.groups)
            off = self._row_offset[req.grammar]
            rows = np.full((1, accept_width(len(rlist), gc.max_accept)),
                           -1, np.int32)
            rows[0, :len(rlist)] = [r + off for r in rlist]
            eos = np.array([sg.eos_allowed])
        with obs.span("cd_check") as sp_cd:
            cdw = gc.cd_overlay(sg.groups)
            cd = None if cdw is None else cdw[None, :]
        with obs.span("mask_dispatch") as sp_disp:
            _, masked = fused_mask_select(logits, self._store_cat, rows,
                                          cd, eos, np.ones(1, bool))
        st.mask_time += sp_rows.dur + sp_cd.dur + sp_disp.dur
        st.mask_computations += 1

        # rejection wrapper (see generate() for the batched variant)
        masked = np.asarray(masked, np.float32)
        for attempt in range(4):
            key, sub = jax.random.split(key)
            # masked is a long-lived host buffer mutated in place below
            # (the demote line) while jnp.asarray may zero-copy alias it
            # — safe today only because _select syncs before returning.
            # Ship a private copy, same invariant as every other
            # dispatch site (RL001).
            nxt = self._select(st, jnp.asarray(masked.copy()), sub)
            if masked[0, nxt] <= NEG_INF / 2:
                break
            if nxt == EOS_ID or gc.is_valid_extension(text, nxt):
                self._commit(st, nxt)
                return
            masked[0, nxt] = NEG_INF

        allowed = np.where(masked[0] > NEG_INF / 2)[0]
        for t in allowed:
            if not (t == EOS_ID or gc.is_valid_extension(text, int(t))):
                masked[0, t] = NEG_INF
        if (masked[0] > NEG_INF / 2).any():
            key, sub = jax.random.split(key)
            nxt = self._select(st, jnp.asarray(masked), sub)
            self._commit(st, nxt)
            return
        # nothing valid (should not happen for C_k in L_p(G)) — stop
        st.done = True
        st.finish_reason = "mask_exhausted"

    def generate_sequential(self, requests: list[Request],
                            verbose: bool = False):
        """Round-robin continuous stepping, one request per device call."""
        obs = Telemetry(enabled=self.telemetry_enabled)
        t0 = time.perf_counter()
        states = [self._start(r) for r in requests]
        keys = {r.rid: jax.random.PRNGKey(r.seed) for r in requests}
        active = list(states)
        while active:
            for st in list(active):
                keys[st.req.rid], sub = jax.random.split(keys[st.req.rid])
                self._step(st, sub, obs)
                if st.done:
                    active.remove(st)
                    if verbose:
                        print(f"[req {st.req.rid}] {st.finish_reason}: "
                              f"{st.generated[:70]!r}")
        stats = EngineStats(
            requests=len(states),
            tokens=sum(s.steps for s in states),
            wall=time.perf_counter() - t0,
            mask_time=sum(s.mask_time for s in states),
            mask_computations=sum(s.mask_computations for s in states),
            opportunistic_hits=sum(s.opportunistic_hits for s in states),
            decode_steps=sum(s.steps for s in states),
            batch_slots=1,
            mesh_devices=self.mesh.size if self.mesh else 1,
        )
        return states, stats
