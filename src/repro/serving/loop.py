"""One persistent step-loop core for every serving-engine mode.

Before this module, the engine carried three nearly-identical ~300-line
slot loops (`generate`, `_generate_paged`, `generate_speculative`), each
re-implementing admission, slot bookkeeping, finish handling and stats.
`StepLoop` owns all of that exactly once; the mode objects below plug in
the per-step body (plan → device step → select → commit):

  * `DenseMode`  — one [B, V] decode + fused mask/sample per step, with
    optional host/device OVERLAP: after the fused mask+sample of step k
    is dispatched, step k+1's unmasked forward is dispatched immediately
    with the on-device sampled ids (the token never leaves the device);
    the host then validates step k against the exact oracle and builds
    step k+1's mask rows while the device is already busy. When the host
    changes the outcome (oracle demotion, exact fallback, a finished
    slot, an admission), the speculative forward is discarded and the
    corrected step re-dispatched — position-addressed KV caches make the
    rewrite idempotent (`kv_pos <= q_pos` masking hides the stale
    write), so the result is token-for-token identical to the
    non-overlapped engine.
  * `PagedMode`  — the paged feed loop (chunked prefill through bucketed
    [B, S] spans, prefix-share waking, COW prepare) feeding the same
    selection machinery.
  * `SpecMode`   — grammar-aware speculation (jump-forward + draft
    spans), dense or paged.

The loop is also where every request-lifecycle feature lives once for
all modes: per-token emit callbacks (streaming), per-request
cancellation (frees the slot and its KV pages immediately), deadlines
(a distinct `deadline` finish reason) and graceful drain. `AsyncEngine`
(serving/async_engine.py) runs one persistent StepLoop on a background
thread against a live `QueueSource`; the synchronous `Engine.generate*`
entry points run the same loop to completion over a `ListSource`, which
is what keeps the two token-for-token identical by construction.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

from repro.core.constrain import MAX_ACCEPT
from repro.core.decoding import DecodeConfig
from repro.obs import Telemetry
from repro.serving.devbridge import attach as _attach_devbridge
from repro.serving.kvpool import PoolExhausted
from repro.spec.scheduler import SlotPhase, SpecConfig, SpecScheduler


# --------------------------- request sources ---------------------------

class ListSource:
    """Fixed batch of requests (the synchronous generate() path)."""

    def __init__(self, requests):
        self._q = deque(requests)

    def __len__(self):
        return len(self._q)

    def try_pop(self):
        return self._q.popleft() if self._q else None

    def push_front(self, req) -> None:
        self._q.appendleft(req)

    @property
    def closed(self) -> bool:
        return True                     # nothing more is ever coming

    def wait_for_work(self, timeout: float) -> bool:
        return False


class QueueSource:
    """Thread-safe live admission queue for the persistent async loop.

    submit() may be called from any thread; the step-loop thread pops.
    close() stops admission (drain): the loop exits once the queue and
    the slot pool empty out.
    """

    def __init__(self):
        self._q: deque = deque()
        self._cv = threading.Condition()
        self._closed = False

    def __len__(self):
        with self._cv:
            return len(self._q)

    def submit(self, req) -> None:
        with self._cv:
            if self._closed:
                raise RuntimeError("source closed (engine draining)")
            self._q.append(req)
            self._cv.notify_all()

    def try_pop(self):
        """Pop the head or None — the loop thread's only read primitive.
        (A compound len()/peek()/pop() would race with `remove()` from
        the asyncio thread: cancel-withdraw can empty the queue between
        the check and the pop.)"""
        with self._cv:
            return self._q.popleft() if self._q else None

    def push_front(self, req) -> None:
        """Return a popped-but-not-admitted request to the head (the
        paged admission gate rejected it; it stays next in line)."""
        with self._cv:
            self._q.appendleft(req)

    def remove(self, req) -> bool:
        """Withdraw a queued request (cancel before admission)."""
        with self._cv:
            try:
                self._q.remove(req)
                return True
            except ValueError:
                return False

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    @property
    def closed(self) -> bool:
        return self._closed

    def wait_for_work(self, timeout: float) -> bool:
        """Block until work arrives or the source closes. True = work."""
        with self._cv:
            if self._q:
                return True
            if self._closed:
                return False
            self._cv.wait(timeout)
            return bool(self._q)


# ------------------------------ the loop -------------------------------

class StepLoop:
    """Shared slot-pool loop: admission, cancellation/deadline sweep,
    per-mode step body, finish bookkeeping, stats. One instance per
    synchronous generate() call; ONE persistent instance per AsyncEngine.
    """

    def __init__(self, engine, mode, source, verbose: bool = False,
                 on_token: Optional[Callable] = None,
                 on_admit: Optional[Callable] = None,
                 on_finish: Optional[Callable] = None,
                 keep_states: bool = True,
                 telemetry: Optional[Telemetry] = None):
        self.eng = engine
        self.mode = mode
        self.source = source
        self.verbose = verbose
        self.on_token = on_token
        self.on_admit = on_admit
        self.on_finish = on_finish
        self.keep_states = keep_states
        # one Telemetry per loop: the sync generate() paths get a fresh
        # per-run instance (EngineStats derives from it); AsyncEngine
        # passes its persistent one so /metrics is cumulative
        self.tele = telemetry if telemetry is not None else \
            Telemetry(enabled=getattr(engine, "telemetry_enabled", True))
        # bind the jax sync/profiler capabilities (devbridge is the one
        # sanctioned jax touchpoint for obs); device timing itself stays
        # OFF unless the engine was built for bench mode
        _attach_devbridge(self.tele)
        if getattr(engine, "devtime_enabled", False):
            self.tele.devtime.enabled = True

        B = engine.slots
        self.B = B
        self.slot_state = [None] * B
        self.feed_pos = np.zeros(B, np.int32)
        self.waiting = np.zeros(B, bool)
        self.seeds = np.zeros(B, np.uint32)
        self.greedy = np.ones(B, bool)
        self.temp = np.ones(B, np.float32)
        self.top_k = np.zeros(B, np.int32)
        self.top_p = np.ones(B, np.float32)
        self.ids_cache: dict[int, list] = {}
        self.stall = 0

        # control queue: closures posted from other threads, executed on
        # the loop thread between steps (hot grammar registration — the
        # engine's concatenated device store must never change while a
        # step that read it is in flight)
        self._controls: deque = deque()
        self._ctl_lock = threading.Lock()

        # cumulative counters now live in the telemetry registry —
        # stats() derives EngineStats from them (one accounting, two
        # views). Count-style instruments are live even with telemetry
        # disabled (plain float adds; the exact token/count invariants
        # must hold either way); only spans/histograms/lifecycle/trace
        # ride the disabled no-op path.
        self.t0 = time.perf_counter()
        self.all_states: list = []
        reg = self.tele.registry
        self.c_requests = reg.counter(
            "repro_requests_total", "requests admitted (incl. failed)")
        self.c_tokens = reg.counter(
            "repro_tokens_total", "tokens committed")
        self.c_steps = reg.counter(
            "repro_slot_steps_total",
            "per-slot step increments (sum of st.steps)")
        self.c_decode_steps = reg.counter(
            "repro_decode_steps_total", "device decode/span calls")
        self.c_mask_comp = reg.counter(
            "repro_mask_computations_total", "grammar mask rows computed")
        self.c_opp_hits = reg.counter(
            "repro_opportunistic_hits_total",
            "unconstrained proposals accepted by the oracle")
        self.c_jump = reg.counter(
            "repro_jump_tokens_total",
            "grammar-forced tokens committed with no model call")
        self.c_draft_prop = reg.counter(
            "repro_draft_tokens_total", "speculative draft tokens",
            {"kind": "proposed"})
        self.c_draft_acc = reg.counter(
            "repro_draft_tokens_total", "speculative draft tokens",
            {"kind": "accepted"})
        # overlap gate outcomes: dispatched = speculative forwards
        # issued, hit = consumed next step (miss = dispatched - hit),
        # probe = dispatches issued only to re-measure a gated-off
        # regime. Registered eagerly so the series exist at zero.
        self.c_overlap_disp = reg.counter(
            "repro_overlap_forwards_total", "overlap gate outcomes",
            {"outcome": "dispatched"})
        self.c_overlap_hit = reg.counter(
            "repro_overlap_forwards_total", "overlap gate outcomes",
            {"outcome": "hit"})
        self.c_overlap_probe = reg.counter(
            "repro_overlap_forwards_total", "overlap gate outcomes",
            {"outcome": "probe"})
        # discarded = dispatched but never consumed, counted where it is
        # dropped: an admission invalidated the pending forward, or the
        # host changed the step's outcome. dispatched - hit - discarded
        # is 1 while a forward is in flight, else 0.
        self.c_overlap_discard = {
            reason: reg.counter(
                "repro_overlap_forwards_total", "overlap gate outcomes",
                {"outcome": "discarded", "reason": reason})
            for reason in ("admit", "unclean")}
        # the exact oracle (_select_resolve): device picks checked on the
        # host, resample dispatches, exact-filter fallbacks
        self.c_oracle = {
            outcome: reg.counter(
                "repro_oracle_checks_total",
                "device picks validated by the exact oracle",
                {"outcome": outcome})
            for outcome in ("accepted", "rejected")}
        self.c_resample = reg.counter(
            "repro_resample_calls_total",
            "device resample dispatches after an oracle rejection")
        self.c_fallback = reg.counter(
            "repro_exact_fallbacks_total",
            "slots drawn by the host exact filter")
        # steps of the loop (admission through commit), by whether they
        # admitted a request: counts always live, seconds from the step
        # span (0 with telemetry disabled)
        self.c_loop_steps = {
            a: reg.counter("repro_loop_steps_total",
                           "step-loop steps, by whether they admitted",
                           {"admitting": a})
            for a in ("true", "false")}
        self.c_loop_step_s = {
            a: reg.counter("repro_loop_step_seconds_total",
                           "wall seconds of step-loop steps, by whether "
                           "they admitted", {"admitting": a})
            for a in ("true", "false")}
        self.step_no = 0
        if self.tele.enabled:
            reg.gauge("repro_queue_depth", "requests waiting for a slot",
                      fn=lambda: float(len(self.source)))
            reg.gauge("repro_slots_active", "slots currently serving",
                      fn=lambda: float(len(self.active())))
            reg.gauge("repro_slots_total", "decode pool width",
                      fn=lambda: float(self.B))

        mode.setup(self)

    # ------------------------- slot lifecycle -------------------------

    def active(self) -> list[int]:
        return [b for b in range(self.B) if self.slot_state[b] is not None]

    def admit(self, b: int, req) -> None:
        with self.tele.span("admit", args={"rid": req.rid}) as sp:
            st = self.mode.admit(self, b, req)
            self.slot_state[b] = st
            self.seeds[b] = np.uint32(req.seed & 0xFFFFFFFF)
            g, t, k, p = DecodeConfig.batch_arrays([req.decode])
            self.greedy[b], self.temp[b] = g[0], t[0]
            self.top_k[b], self.top_p[b] = k[0], p[0]
            if req.deadline is not None:
                st.deadline_at = time.perf_counter() + req.deadline
        self.c_requests.inc()
        st.admit_t = sp.t0 if self.tele.enabled else time.perf_counter()
        self.tele.lifecycle.on_admit(req.rid)
        if self.keep_states:
            self.all_states.append(st)
        if self.on_admit:
            self.on_admit(st)

    def finish(self, b: int) -> None:
        st = self.slot_state[b]
        self.mode.release(self, b, st)
        self.slot_state[b] = None
        self.waiting[b] = False
        self.feed_pos[b] = 0
        if self.verbose:
            print(f"[req {st.req.rid}] {st.finish_reason}: "
                  f"{st.generated[:70]!r}")
        self.tele.lifecycle.on_finish(st.req.rid, st.finish_reason)
        tr = self.tele.tracer
        if tr.active:
            now = time.perf_counter()
            t0 = getattr(st, "admit_t", None) or now
            tr.add(f"slot {b}", f"req {st.req.rid}", t0, now - t0,
                   {"reason": st.finish_reason, "tokens": st.steps})
        if self.on_finish:
            self.on_finish(st)

    def commit(self, st, token: int) -> None:
        """THE commit point for every mode (incl. jump-forward commits):
        engine bookkeeping + the streaming emit callback."""
        self.eng._commit(st, token)
        self.c_tokens.inc()
        self.tele.lifecycle.on_token(st.req.rid)
        tr = self.tele.tracer
        if st.first_commit_t is None:
            st.first_commit_t = time.perf_counter()
            if tr.active and st.admit_t is not None:
                tr.add(f"slot {st.slot}", "admit_to_first_token",
                       st.admit_t, st.first_commit_t - st.admit_t,
                       {"rid": st.req.rid})
        if tr.active:
            tr.instant(f"slot {st.slot}", "token", time.perf_counter(),
                       {"id": int(token)})
        if self.on_token:
            self.on_token(st, token)

    def note_steps(self, n: int) -> None:
        """Mirror per-slot st.steps increments into a loop-level total,
        so async stats (keep_states=False) report the same steps-based
        token count as the sync path's sum(st.steps)."""
        self.c_steps.inc(n)

    def fail_request(self, req, reason: str) -> None:
        """Finish a request that never got a slot (e.g. a prompt the KV
        pool can never fit, on the persistent path)."""
        from repro.serving.engine import RequestState
        self.ids_cache.pop(req.rid, None)
        st = RequestState(req=req)
        st.done = True
        st.finish_reason = reason
        self.c_requests.inc()
        self.tele.lifecycle.on_finish(req.rid, reason)
        if self.keep_states:
            self.all_states.append(st)
        if self.on_admit:
            self.on_admit(st)
        if self.on_finish:
            self.on_finish(st)

    # --------------------------- control queue ------------------------

    def post_control(self, fn: Callable[[], None]) -> None:
        """Run fn() on the loop thread before the next step (thread-safe,
        FIFO). fn must do its own error handling — an exception escaping
        a control kills the loop like any other step error."""
        with self._ctl_lock:
            self._controls.append(fn)

    def _drain_controls(self) -> None:
        while True:
            with self._ctl_lock:
                fn = self._controls.popleft() if self._controls else None
            if fn is None:
                return
            fn()

    # --------------------- cancellation / deadlines -------------------

    def _sweep(self) -> None:
        now = None
        for b in self.active():
            st = self.slot_state[b]
            if st.cancelled:
                st.done = True
                st.finish_reason = "cancelled"
                self.finish(b)
                continue
            if st.deadline_at is not None:
                now = time.perf_counter() if now is None else now
                if now >= st.deadline_at:
                    st.done = True
                    st.finish_reason = "deadline"
                    self.finish(b)

    # ------------------------------ run -------------------------------

    def run(self, idle_wait: float = 0.1):
        """Drive the loop until the source is closed AND drained AND the
        pool is idle. For a ListSource this is the synchronous generate
        path; for a QueueSource it is the persistent serving loop (idles
        between requests, exits on close())."""
        while True:
            self._drain_controls()
            self._sweep()
            if self._step():
                continue
            # no slot is active: nothing is running or was admitted
            req = self.source.try_pop()
            if req is not None:
                if self.mode.can_admit_req(self, req):
                    # admittable after all (e.g. submitted after the
                    # admission sweep): next iteration takes it
                    self.source.push_front(req)
                    continue
                # no slot can ever take this request (paged pool too
                # small): strict sources raise, live sources fail
                # the request gracefully and keep serving
                if self.source.closed:
                    raise PoolExhausted(
                        "KV pool too small for the next request's "
                        "prompt")
                self.fail_request(req, "kv_oom")
                continue
            if self.source.closed:
                break
            # idle: the queue is empty, so any memoized prompt ids
            # belong to withdrawn/failed requests — drop them (rids
            # are never reused, so they could only accumulate)
            self.ids_cache.clear()
            self.mode.on_idle(self)
            self.source.wait_for_work(idle_wait)
        return (self.all_states, self.stats()) if self.keep_states \
            else (None, self.stats())

    def _admit_free(self) -> int:
        """Fill free slots from the source; returns how many it admitted."""
        n = 0
        for b in range(self.B):
            if self.slot_state[b] is not None:
                continue
            # pop-then-gate (never len/peek-then-pop): cancel withdrawal
            # runs on another thread, so the queue can empty between a
            # check and a pop
            req = self.source.try_pop()
            if req is None:
                break
            if not self.mode.can_admit_req(self, req):
                self.source.push_front(req)
                break
            self.admit(b, req)
            n += 1
        return n

    def _step(self) -> bool:
        """One step under one `step` span: admission, then the mode's
        step body. False, with nothing recorded, where no slot is active
        after admission (the caller's idle path)."""
        if not self.active() and not len(self.source):
            return False
        with self.tele.step_span(self.step_no) as sp:
            n = self._admit_free()
            active = self.active()
            if not active:
                sp.drop()
                return False
            self.mode.step(self, active)
            sp.note(n)
        key = "true" if n else "false"
        self.c_loop_steps[key].inc()
        self.c_loop_step_s[key].inc(sp.dur)
        self.step_no += 1
        return True

    # ------------------------------ stats ------------------------------

    def stats(self):
        """EngineStats as a view over the telemetry registry: counts
        come from the always-live counters; mask_time/plan_time are the
        phase-span totals (the historical mask_time bracket = rows
        build + mask dispatch + ids sync — the oracle loop was never
        included and reports separately as the host_oracle phase).
        With telemetry disabled the timing fields read 0."""
        from repro.serving.engine import EngineStats
        tele = self.tele
        s = EngineStats(
            requests=int(self.c_requests.value),
            tokens=sum(st.steps for st in self.all_states)
            if self.keep_states else int(self.c_steps.value),
            wall=time.perf_counter() - self.t0,
            mask_time=(tele.phase_seconds("ci_lookup")
                       + tele.phase_seconds("cd_check")
                       + tele.phase_seconds("mask_dispatch")
                       + tele.phase_seconds("select_resolve")),
            mask_computations=int(self.c_mask_comp.value),
            opportunistic_hits=int(self.c_opp_hits.value),
            decode_steps=int(self.c_decode_steps.value),
            batch_slots=self.B,
            mesh_devices=self.eng.mesh.size if self.eng.mesh else 1,
            jump_tokens=int(self.c_jump.value),
            draft_proposed=int(self.c_draft_prop.value),
            draft_accepted=int(self.c_draft_acc.value),
            plan_time=tele.phase_seconds("plan"),
            overlap_dispatched=int(self.c_overlap_disp.value),
            overlap_hits=int(self.c_overlap_hit.value),
            overlap_discarded=int(sum(
                c.value for c in self.c_overlap_discard.values())),
            oracle_accepted=int(self.c_oracle["accepted"].value),
            oracle_rejected=int(self.c_oracle["rejected"].value),
            resample_calls=int(self.c_resample.value),
            exact_fallbacks=int(self.c_fallback.value),
            steps_timed=int(sum(c.value
                                for c in self.c_loop_steps.values())),
            step_s=sum(c.value for c in self.c_loop_step_s.values()),
            admit_steps=int(self.c_loop_steps["true"].value),
            admit_step_s=self.c_loop_step_s["true"].value,
            device_forward_s=(tele.devtime.seconds("forward")
                              + tele.devtime.seconds("overlap_forward")),
            device_mask_sample_s=tele.devtime.seconds("mask_sample"),
            overlap_hidden_s=tele.c_overlap_hidden.value,
            attribution=tele.attribution() if tele.enabled else None,
        )
        return self.mode.stats_extra(self, s)

    def add_select_ctr(self, ctr: dict) -> None:
        self.c_mask_comp.inc(ctr["mask_computations"])
        self.c_opp_hits.inc(ctr["opportunistic_hits"])
        self.c_oracle["accepted"].inc(ctr["oracle_accepted"])
        self.c_oracle["rejected"].inc(ctr["oracle_rejected"])
        self.c_resample.inc(ctr["resample_calls"])
        self.c_fallback.inc(ctr["exact_fallbacks"])


# ------------------------------- modes ---------------------------------

class _ModeBase:
    def can_admit_req(self, loop, req) -> bool:
        return True

    def on_idle(self, loop) -> None:
        pass

    def release(self, loop, b, st) -> None:
        pass

    def stats_extra(self, loop, stats):
        return stats


class DenseMode(_ModeBase):
    """Plain continuous batching over dense per-slot decode caches, with
    optional host/device overlap (see module docstring).

    Overlap is ADAPTIVE: a speculative forward only pays off when the
    host usually validates the whole batch unchanged (greedy and
    low-temperature serving — the masked argmax almost always passes the
    exact oracle). High-temperature sampling over an over-approximate
    mask rejects some slot most steps, so every speculative forward
    would be discarded; the mode tracks a windowed hit rate and stops
    speculating below `OVERLAP_MIN_RATE`, re-probing every
    `OVERLAP_PROBE` steps in case the workload shifts. Token streams are
    identical either way — gating only decides where device time goes."""

    # Break-even: speculation pays when rate*min(host, fwd) exceeds
    # (1-rate)*fwd — at fwd <= host that is rate > 0.5, and for
    # fwd > host the threshold only rises, so 0.5 is the permissive
    # edge of profitability.
    OVERLAP_MIN_RATE = 0.5      # windowed hits/dispatches to keep going
    OVERLAP_WINDOW = 64         # halve counters at this many dispatches
    OVERLAP_PROBE = 16          # gated-off steps between re-probes
    OVERLAP_WARMUP = 8          # unconditional dispatches before gating

    def __init__(self, engine, overlap: Optional[bool] = None):
        self.eng = engine
        self.overlap = engine.overlap if overlap is None else overlap
        if not engine.model.supports_span_decode:
            # recurrent/side-input state cannot absorb a discarded
            # speculative forward (no position-addressed rewrite)
            self.overlap = False
        self.caches = None
        self.cur_tok = None
        self.pending_logits = None      # speculative forward for the
                                        # NEXT step, still on device
        self._spec_disp_t = None        # host clock when that dispatch
                                        # returned (overlap-hidden attr)
        self._disp_w = 0                # windowed dispatch count
        self._hit_w = 0                 # windowed hit count
        self._gated_steps = 0           # steps since last probe

    def setup(self, loop):
        eng = self.eng
        self.caches = eng._place_caches(
            eng.model.init_decode_caches(eng.slots, eng.max_len))
        self.cur_tok = np.zeros(eng.slots, np.int32)

    def admit(self, loop, b, req):
        st, self.caches = self.eng._admit_common(req, b, self.caches)
        st.slot = b
        self.cur_tok[b] = st.token_ids[-1]
        loop.feed_pos[b] = st.pos - 1
        # the inserted prefill caches invalidate any in-flight
        # speculative forward for this slot
        if self.pending_logits is not None:
            loop.c_overlap_discard["admit"].inc()
        self.pending_logits = None
        self._spec_disp_t = None
        return st

    def step(self, loop, active):
        eng = self.eng
        tele = loop.tele
        if self.pending_logits is not None:
            logits = self.pending_logits       # dispatched last step
            self.pending_logits = None
            loop.c_overlap_hit.inc()
            self._hit_w += 1    # counted at CONSUMPTION, so a forward
                                # invalidated by admit() is a miss in
                                # the gate's window too
            # overlap-hidden attribution: the host-work window between
            # the speculative dispatch finishing and this consumption is
            # device time the overlap hid. Clamp to the latest measured
            # forward interval when devtime has one (bench mode);
            # otherwise the window itself is the documented upper bound.
            # Never sync the speculative forward — that would serialize
            # the very overlap being measured.
            if self._spec_disp_t is not None:
                window = time.perf_counter() - self._spec_disp_t
                dev = tele.devtime.last_dur.get("forward", 0.0)
                tele.add_overlap_hidden(min(window, dev) if dev > 0.0
                                        else window)
                self._spec_disp_t = None
        else:
            # cur_tok/feed_pos are mutated in place after the resolve
            # sync; the sync does guarantee this dispatch completed
            # first, but copy anyway — same aliasing hazard class as
            # the paged feed (see PagedMode.step)
            with tele.device_span("forward") as dv:
                with tele.span("forward"):
                    tok_dev = jnp.asarray(self.cur_tok.copy())
                    pos_dev = jnp.asarray(loop.feed_pos.copy())
                    logits, self.caches = eng._decode(
                        eng.params, self.caches, tok_dev, pos_dev)
                dv.done(logits)     # host span stays dispatch-only; the
                # device bracket blocks here in bench mode
            eng._note_jit_cost(tele, "forward", eng._decode, eng.params,
                               self.caches, tok_dev, pos_dev)
        loop.c_decode_steps.inc()
        for b in active:
            loop.slot_state[b].steps += 1
        loop.note_steps(len(active))

        ctx = eng._select_dispatch(
            logits, loop.slot_state, set(active), loop.seeds,
            loop.greedy, loop.temp, loop.top_k, loop.top_p, obs=tele)

        # ---- overlap: dispatch step k+1's forward with the on-device
        # sampled ids BEFORE syncing step k back to the host ----------
        spec_logits = None
        if self.overlap and not eng.opportunistic and \
                ctx.ids is not None and self._speculate_now(loop):
            with tele.span("overlap_forward"):
                spec_logits, self.caches = eng._decode(
                    eng.params, self.caches, ctx.ids,
                    jnp.asarray(loop.feed_pos + 1))
            self._spec_disp_t = time.perf_counter()
            loop.c_overlap_disp.inc()
            self._disp_w += 1
            if self._disp_w >= self.OVERLAP_WINDOW:
                self._disp_w //= 2      # exponential decay: old hit
                self._hit_w //= 2       # rates age out

        committed, ctr = eng._select_resolve(
            ctx, loop.slot_state, loop.seeds, loop.greedy, loop.temp,
            loop.top_k, loop.top_p, obs=tele)
        loop.add_select_ctr(ctr)

        for b, t in committed.items():
            st = loop.slot_state[b]
            loop.commit(st, t)
            self.cur_tok[b] = t
            loop.feed_pos[b] = st.pos - 1
        for b in active:
            st = loop.slot_state[b]
            if st is not None and st.done:
                loop.finish(b)

        # speculation valid iff the host changed NOTHING the device
        # didn't already know: every active slot committed its first-
        # round device id. A slot that finished (eos/length) committed
        # that same id — its speculative row is simply ignored from now
        # on, and `admit()` drops the pending forward if the freed slot
        # is refilled. Discarded forwards are harmless: position-
        # addressed caches rewrite idempotently.
        if spec_logits is None:
            return
        if ctx.clean and set(committed) == set(active):
            self.pending_logits = spec_logits
        else:
            loop.c_overlap_discard["unclean"].inc()
            self._spec_disp_t = None    # discarded forward hides nothing

    def _speculate_now(self, loop) -> bool:
        if self._disp_w < self.OVERLAP_WARMUP:      # warm-up: always try
            return True
        if self._hit_w / self._disp_w >= self.OVERLAP_MIN_RATE:
            return True
        self._gated_steps += 1          # hostile regime: probe rarely
        if self._gated_steps >= self.OVERLAP_PROBE:
            self._gated_steps = 0
            loop.c_overlap_probe.inc()  # dispatch issued only to
            return True                 # re-measure a gated-off regime
        return False


class PagedMode(_ModeBase):
    """Paged-KV continuous batching: chunked prefill drained through
    bucketed [B, S] span feeds, prefix-share waking, COW page prepare —
    then the IDENTICAL selection machinery as DenseMode."""

    def __init__(self, engine):
        self.eng = engine
        self.alloc = None
        self.caches = None

    def setup(self, loop):
        self.alloc, self.caches = self.eng._paged_setup(self.eng.slots)
        if loop.tele.enabled:
            loop.tele.register_kv(self.alloc)

    def can_admit_req(self, loop, req) -> bool:
        return self.eng._paged_can_admit(self.alloc, req, loop.ids_cache)

    def admit(self, loop, b, req):
        st, plan = self.eng._admit_paged(
            req, b, self.alloc, loop.ids_cache.pop(req.rid, None))
        st.slot = b
        loop.feed_pos[b] = plan.feed_from
        loop.waiting[b] = True      # shared pages may still be filling
        if not self.eng._paged_wake(self.alloc, b, st, loop.feed_pos,
                                    loop.waiting):
            st.phase = SlotPhase.PREFILLING.value
        return st

    def release(self, loop, b, st) -> None:
        st.kv_pages = len(self.alloc.tables[b])
        self.alloc.release(b)

    def stats_extra(self, loop, stats):
        return self.eng._kv_stats(stats, self.alloc)

    def step(self, loop, active):
        eng = self.eng
        alloc, B = self.alloc, loop.B

        # ---- wake waiters whose shared prefix finished filling ------
        live = [b for b in active
                if eng._paged_wake(alloc, b, loop.slot_state[b],
                                   loop.feed_pos, loop.waiting)]
        if not live:
            loop.stall += 1
            if loop.stall > 4 * B + 16:
                raise RuntimeError("paged scheduler stalled")
            return
        loop.stall = 0

        # ---- ONE [B, S] paged span feed for the whole pool ----------
        with loop.tele.span("feed_build"):
            pend = {b: loop.slot_state[b].pos - int(loop.feed_pos[b])
                    for b in live}
            S = eng._feed_width(list(pend.values()))
            tokens = np.zeros((B, S), np.int32)
            fmask = np.zeros((B, S), bool)
            sel = np.full(B, -1, np.int32)
            feed_n: dict[int, int] = {}
            for b in live:
                st = loop.slot_state[b]
                fs = int(loop.feed_pos[b])
                k = min(pend[b], S)
                new_caches = eng._prepare_feed(alloc, self.caches, b, st,
                                               fs, k)
                if new_caches is None:
                    continue                 # kv_oom: no feed
                self.caches = new_caches
                if pend[b] <= S:
                    sel[b] = k - 1           # selection this step
                tokens[b, :k] = st.token_ids[fs:fs + k]
                for i in range(k):
                    fmask[b, i] = (fs + i) >= st.write_from
                feed_n[b] = k
        live = [b for b in live if b in feed_n]
        if live:
            page_tab = alloc.table_rows(np)
            # feed_pos is a long-lived array mutated IN PLACE right
            # after this dispatch (prefill-drain steps never sync), and
            # jnp.asarray may zero-copy alias host memory on CPU — the
            # async computation would read the NEXT step's cursors.
            # Ship a private copy (jax keeps it alive; nobody mutates
            # it). Root-caused from a 5.47-magnitude logits drift in
            # chunked-prefill runs; see CHANGES.md PR 5 addendum.
            with loop.tele.device_span("forward") as dv:
                with loop.tele.span("forward"):
                    pos_dev = jnp.asarray(loop.feed_pos.copy())
                    logits, self.caches = eng._span_feed_paged(
                        eng.params, self.caches, jnp.asarray(tokens),
                        pos_dev, jnp.asarray(fmask),
                        jnp.asarray(page_tab), jnp.asarray(sel))
                dv.done(logits)
            eng._note_jit_cost(
                loop.tele, "forward", eng._span_feed_paged, eng.params,
                self.caches, jnp.asarray(tokens), pos_dev,
                jnp.asarray(fmask), jnp.asarray(page_tab),
                jnp.asarray(sel))
            loop.c_decode_steps.inc()
            for b in live:
                st = loop.slot_state[b]
                alloc.note_fill(b, min(int(loop.feed_pos[b]) + feed_n[b],
                                       st.prompt_len))
                if sel[b] < 0:               # chunked prefill drain
                    loop.feed_pos[b] += feed_n[b]
                    st.phase = SlotPhase.PREFILLING.value
            selecting = [b for b in live if sel[b] >= 0]
            for b in selecting:
                loop.slot_state[b].steps += 1
                loop.slot_state[b].phase = SlotPhase.DECODING.value
            loop.note_steps(len(selecting))
            if selecting:
                committed, ctr = eng._select_tokens(
                    logits, loop.slot_state, set(selecting), loop.seeds,
                    loop.greedy, loop.temp, loop.top_k, loop.top_p,
                    obs=loop.tele)
                loop.add_select_ctr(ctr)
                for b, t in committed.items():
                    st = loop.slot_state[b]
                    loop.commit(st, t)
                    loop.feed_pos[b] = st.pos - 1
        for b in active:
            st = loop.slot_state[b]
            if st is not None and st.done:
                loop.finish(b)


class SpecMode(_ModeBase):
    """Grammar-aware speculation (jump-forward + draft-verify spans)
    over dense or paged caches — generate_speculative's step body on the
    shared loop."""

    def __init__(self, engine, spec: Optional[SpecConfig] = None):
        self.eng = engine
        self.spec = spec or SpecConfig()
        self.paged = engine.paged
        self.sched = None
        self.alloc = None
        self.caches = None

    def setup(self, loop):
        eng = self.eng
        if not eng.model.supports_span_decode:
            raise ValueError(
                "speculative decoding needs position-addressed decode "
                "caches (attn/moe layer kinds); this arch has recurrent "
                "or side-input state")
        self.sched = SpecScheduler(
            self.spec, eng.tok,
            telemetry=loop.tele if loop.tele.enabled else None)
        if self.paged:
            self.alloc, self.caches = eng._paged_setup(eng.slots)
            if loop.tele.enabled:
                loop.tele.register_kv(self.alloc)
        else:
            self.caches = eng._place_caches(
                eng.model.init_decode_caches(eng.slots, eng.max_len))

    def can_admit_req(self, loop, req) -> bool:
        if not self.paged:
            return True
        return self.eng._paged_can_admit(self.alloc, req, loop.ids_cache)

    def admit(self, loop, b, req):
        eng = self.eng
        if self.paged:
            st, plan = eng._admit_paged(
                req, b, self.alloc, loop.ids_cache.pop(req.rid, None))
            st.slot = b
            loop.feed_pos[b] = plan.feed_from
            loop.waiting[b] = True
            if not eng._paged_wake(self.alloc, b, st, loop.feed_pos,
                                   loop.waiting):
                st.phase = SlotPhase.PREFILLING.value
        else:
            st, self.caches = eng._admit_common(req, b, self.caches)
            st.slot = b
            loop.feed_pos[b] = st.pos - 1
        self.sched.on_admit(st)
        return st

    def release(self, loop, b, st) -> None:
        if self.paged:
            st.kv_pages = len(self.alloc.tables[b])
            self.alloc.release(b)
        self.sched.on_finish(st)

    def stats_extra(self, loop, stats):
        if self.paged:
            return self.eng._kv_stats(stats, self.alloc)
        return stats

    def step(self, loop, active):
        eng = self.eng
        B = loop.B
        slot_state = loop.slot_state
        feed_pos = loop.feed_pos
        # reprolint: mutated-inflight=loop.greedy,loop.temp,loop.top_k,loop.top_p admit() rewrites the decode configs while the span dispatch is in flight

        def commit_one(st, token):
            st.steps += 1
            loop.note_steps(1)
            loop.commit(st, token)

        # ---- wake waiters whose shared prefix finished filling ------
        if self.paged:
            for b in active:
                eng._paged_wake(self.alloc, b, slot_state[b], feed_pos,
                                loop.waiting)

        # ---- host planning: jump-forward commits + drafting ---------
        plans = {}
        with loop.tele.span("plan"):
            for b in active:
                st = slot_state[b]
                if loop.waiting[b]:
                    from repro.spec.scheduler import SlotPlan
                    plans[b] = SlotPlan()
                    continue
                backlog = (st.pos - 1) - int(feed_pos[b])
                pre = st.jump_tokens
                plans[b] = self.sched.plan_slot(st, commit_one,
                                                eng.max_len,
                                                backlog=backlog)
                loop.c_jump.inc(st.jump_tokens - pre)
                st.phase = plans[b].phase.value
        for b in active:
            st = slot_state[b]
            if st.done:      # finished mid-jump: nothing left to feed
                self.sched.on_commit(st, plans[b].jumped)
                loop.finish(b)
        live = [b for b in active
                if slot_state[b] is not None and not loop.waiting[b]]
        if not live:
            loop.stall += 1
            if loop.stall > 4 * B + 16:
                raise RuntimeError("paged scheduler stalled")
            return
        loop.stall = 0

        # ---- span width: maximize commits per unit of compute -------
        pend_n = {b: slot_state[b].pos - int(feed_pos[b]) for b in live}
        S = eng._choose_span(
            [pend_n[b] + len(plans[b].drafts) for b in live])
        tokens = np.zeros((B, S), np.int32)
        fmask = np.zeros((B, S), bool)
        sel0 = {}        # b -> span index of first selection (-1 none)
        fed = {}         # b -> tokens fed this span
        for b in list(live):
            st = slot_state[b]
            fs = int(feed_pos[b])
            pend = st.token_ids[fs: st.pos]
            if len(pend) > S:          # backlog drain: feed only
                feed = pend[:S]
                sel0[b] = -1
                plans[b].drafts = []
            else:
                plans[b].drafts = plans[b].drafts[: S - len(pend)]
                feed = pend + plans[b].drafts
                sel0[b] = len(pend) - 1
            if self.paged:
                new_caches = eng._prepare_feed(self.alloc, self.caches,
                                               b, st, fs, len(feed))
                if new_caches is None:
                    loop.finish(b)     # kv_oom under true pressure
                    live.remove(b)
                    continue
                self.caches = new_caches
                for i in range(len(feed)):
                    fmask[b, i] = (fs + i) >= st.write_from
            else:
                fmask[b, : len(feed)] = True
            tokens[b, : len(feed)] = feed
            fed[b] = len(feed)
            if plans[b].drafts:
                st.phase = SlotPhase.VERIFYING.value
        if not live:
            return
        # feed_pos is mutated in place after dispatch — ship a private
        # copy (zero-copy aliasing hazard; see PagedMode.step)
        with loop.tele.device_span("forward") as dv:
            with loop.tele.span("forward"):
                if self.paged:
                    page_tab = self.alloc.table_rows(np)
                    logits, self.caches = eng._span_decode_paged(
                        eng.params, self.caches, jnp.asarray(tokens),
                        jnp.asarray(feed_pos.copy()), jnp.asarray(fmask),
                        jnp.asarray(page_tab))
                else:
                    logits, self.caches = eng._span_decode(
                        eng.params, self.caches, jnp.asarray(tokens),
                        jnp.asarray(feed_pos.copy()), jnp.asarray(fmask))
            dv.done(logits)
        loop.c_decode_steps.inc()
        if self.paged:
            for b in live:
                st = slot_state[b]
                self.alloc.note_fill(b, min(int(feed_pos[b]) + fed[b],
                                            st.prompt_len))

        # ---- mask rows for every selection position -----------------
        # four spans partitioning the historical mask_time bracket:
        # host row building (ci_lookup), residue overlay (cd_check),
        # fused mask+select dispatch, ids sync
        with loop.tele.span("ci_lookup"):
            span_sms: dict[tuple, tuple] = {}  # (b, f) -> (StepMask, off)
            eosm = np.zeros((B, S), bool)
            consm = np.zeros((B, S), bool)
            for b in live:
                st = slot_state[b]
                pl = plans[b]
                if st.constraint is None or sel0[b] < 0:
                    continue
                off = eng._row_offset[st.req.grammar]
                text = st.generated
                for i in range(len(pl.drafts) + 1):
                    if i > 0:
                        text = text + eng.tok.id_to_bytes[pl.drafts[i - 1]]
                    if i == 0 and pl.stop_mask is not None:
                        sm = pl.stop_mask  # reuse jump analyzer's mask
                    else:
                        sm = st.constraint.step_rows(text)
                    f = sel0[b] + i
                    span_sms[(b, f)] = (sm, off)
                    eosm[b, f] = sm.eos_allowed
                    consm[b, f] = True
                    st.mask_computations += 1
                    loop.c_mask_comp.inc()
            # row width grows in accept_width buckets on overflow
            # (soundness)
            A = max([MAX_ACCEPT] + [sm.rows.shape[0]
                                    for sm, _ in span_sms.values()])
            rows = np.full((B, S, A), -1, np.int32)
            for (b, f), (sm, off) in span_sms.items():
                r = np.where(sm.rows >= 0, sm.rows + off, sm.rows)
                rows[b, f, :r.shape[0]] = r
        with loop.tele.span("cd_check"):
            W = int(eng._store_cat.shape[1])
            cdm = np.zeros((B, S, W), np.uint32)
            for (b, f), (sm, _) in span_sms.items():
                if sm.cd_words is not None:
                    cdm[b, f, :sm.cd_words.shape[0]] = sm.cd_words
        with loop.tele.device_span("mask_sample") as dv:
            with loop.tele.span("mask_dispatch"):
                salts = np.array([slot_state[b].steps if slot_state[b]
                                  else 0 for b in range(B)], np.uint32)
                keys = eng._span_keys(loop.seeds, salts, S)
                # per-step arrays go in as numpy (fresh allocations);
                # the admit()-mutated decode configs ship copies
                masked, ids, ok = eng._span_mask_select(  # reprolint: dispatch
                    logits, eng._store_cat, rows, cdm, eosm, consm,
                    loop.greedy.copy(), loop.temp.copy(),
                    loop.top_k.copy(), loop.top_p.copy(), keys)
            dv.done((ids, ok))
        with loop.tele.span("select_resolve"):
            ids_h, ok_h = np.asarray(ids), np.asarray(ok)

        # ---- accept: longest valid draft prefix + bonus token -------
        with loop.tele.span("host_oracle"):
            for b in live:
                st = slot_state[b]
                pl = plans[b]
                if sel0[b] < 0:
                    # pure backlog drain (jump replay or chunked
                    # prefill): advance the feed cursor; the step's jump
                    # commits must still reach the proposer history
                    self.sched.on_commit(st, pl.jumped)
                    feed_pos[b] += fed[b]
                    if self.paged and feed_pos[b] < st.prompt_len:
                        st.phase = SlotPhase.PREFILLING.value
                    continue
                idx = sel0[b]
                committed = []
                for d in pl.drafts:
                    if st.done or int(ids_h[b, idx]) != d:
                        break
                    commit_one(st, d)
                    committed.append(d)
                    idx += 1
                st.draft_proposed += len(pl.drafts)
                st.draft_accepted += len(committed)
                loop.c_draft_prop.inc(len(pl.drafts))
                loop.c_draft_acc.inc(len(committed))
                self.sched.on_verify(st, len(pl.drafts), len(committed))
                if not st.done:
                    nxt = eng._resolve_span_selection(
                        st, masked, b, idx, int(ids_h[b, idx]),
                        bool(ok_h[b, idx]), st.steps)
                    if nxt is None:
                        st.done = True
                        st.finish_reason = "mask_exhausted"
                    else:
                        commit_one(st, nxt)
                        committed.append(nxt)
                self.sched.on_commit(st, pl.jumped + committed)
                if st.done:
                    loop.finish(b)
                else:
                    feed_pos[b] = st.pos - 1
                    st.phase = SlotPhase.DECODING.value


def make_mode(engine, spec: Optional[SpecConfig] = None,
              speculative: bool = False, overlap: Optional[bool] = None):
    """Mode factory mirroring the Engine entry points."""
    if speculative or spec is not None:
        return SpecMode(engine, spec)
    if engine.paged:
        return PagedMode(engine)
    return DenseMode(engine, overlap=overlap)
