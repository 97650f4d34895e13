"""Continuous-batching inference engine.

The engine serves a stream of variable-length requests through the model's
``prefill`` / ``decode_step`` with jit-stable shapes:

* the decode batch is always ``n_slots`` rows (free slots carry inert
  filler — row-independent block families make their garbage harmless);
* each step interleaves: admit waiting requests into free slots, (paged
  mode) run prefill chunks under the token budget, then one batched decode
  of every live slot with per-slot sampling params and per-request stop
  conditions (EOS id, max_new_tokens); finished slots are evicted and
  backfilled from the queue on the next step.

Two memory models select at construction:

* **slot-dense** (default): admission prefills one request at a time,
  bucket-padded (one compile per bucket) with the length-aware
  ``prefill(lengths=...)``, samples the first token in the same dispatch,
  then writes the batch-1 caches into the assigned slot
  (:class:`SlotCache`). One blocking dispatch per admission; every slot
  reserves ``max_len`` KV rows.
* **paged** (``paged=True``): attention K/V lives in a global page pool
  (:class:`PagedCache`). Admission only builds the request's block table
  (reusing trie-cached prefix pages — a shared system prompt is prefilled
  once); the prompt is then processed in fixed-shape page-multiple
  *chunks* interleaved with decode under a per-step token budget, so a
  long prompt never head-of-line-blocks running decodes and there is no
  largest-bucket rejection (ONE prefill compile total, vs one per
  bucket). Decode runs through the paged-attention op over an *active*
  block-table width that tracks the deepest live sequence (power-of-two
  ladder — a handful of compiles), so decode bandwidth follows actual
  depth, not ``max_len``. Admission blocks on page-pool pressure, not
  just free slots; eviction returns a request's non-shared pages.

Per-slot sampling state (current token, temperature, top-k, PRNG key,
generation counter) lives on device and round-trips through the single
jitted decode call — the steady-state step is one dispatch plus one small
token transfer for the host-side stop checks.

Exactness contract: for row-independent architectures (everything except
capacity-constrained MoE routing) greedy output is token-for-token
identical to a static batched decode of the same prompts — in BOTH memory
models — verified in ``tests/test_serve_engine.py`` /
``tests/test_serve_paged.py``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import time
from typing import Any, Callable, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.export import pack_biases
from repro.dist import sharding as dist_sh
from repro.kernels import ops

from . import sampling as sampling_lib
from .cache import NULL_PAGE, PagedCache, SlotCache, publish_prefix_shared, \
    share_trie
from .metrics import ServeMetrics
from .resilience import STAGE_NAMES, InjectedFault, Resilience
from .scheduler import Request, RequestState, Scheduler

log = logging.getLogger("repro.serve.engine")

# Host spans the engine writes into a profiler trace, on the device ops'
# clock: one per step or per request event, never per slot or per token.
# With no profiler running each costs one activity check.
SPANS = ("engine.step", "engine.admit", "engine.prefill.chunk",
         "engine.prefill.sync", "engine.decode.prep", "engine.decode.dispatch",
         "engine.decode.sync", "engine.emit")
_span = jax.profiler.TraceAnnotation


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


@dataclasses.dataclass
class Handoff:
    """Prefill→decode migration payload (disaggregated serving).

    Carries everything the decode engine needs to adopt a prefilled
    request without recomputing the prompt: the per-attention-layer page
    *contents* for the prompt's pages (gathered before the prefill engine
    freed them, power-of-two padded with null-page columns for a bounded
    compile ladder), the first sampled token, and the prompt depth. Block
    tables stay host-authoritative per engine — the payload is content,
    the receiving engine builds its own table through the normal
    reservation-accounted admission path.
    """
    prompt_len: int
    n_pages: int                 # real pages; <= width (pow-2 padded)
    width: int
    first_token: int
    pages: List[Optional[Dict[str, Any]]]   # per block: {"kp","vp"} or None


class Engine:
    """Slot-based continuous-batching engine around one model + params."""

    def __init__(self, model, params, *, n_slots: int = 8, max_len: int = 128,
                 min_bucket: int = 16, buckets: Optional[Sequence[int]] = None,
                 dtype=None, metrics: Optional[ServeMetrics] = None,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 prefill_token_budget: Optional[int] = None,
                 spec_draft=None, spec_k: int = 4, preemption: bool = True,
                 resilience: Optional[Resilience] = None, device=None):
        cfg = model.cfg
        if not cfg.causal:
            raise ValueError(f"{cfg.name}: encoder-only arch has no decode step")
        if cfg.frontend != "token":
            raise ValueError(
                f"{cfg.name}: the engine serves token frontends only "
                "(embed-frontend archs have no incremental token stream)")
        self.model = model
        self.params = params        # biases also in packed order (the setter)
        self.n_slots = n_slots
        self.max_len = max_len
        self.paged = paged
        self.metrics = metrics if metrics is not None else ServeMetrics()
        self.step_count = 0
        # the (mesh, rules) pair active at construction: every trace the
        # engine ever runs — warmup AND the serve loop — re-enters this
        # context, so the TP chunk/decode ladder compiles under the same
        # shard_map closure it serves under (no first-request compile stall
        # per replica, no warm/serve program mismatch)
        self._mesh_ctx = dist_sh.current()
        # one-chip replica placement: params, caches and sampling state are
        # committed to ``device`` once built (below), and every step and
        # warmup runs with it as the default device (see _mesh_scope), so
        # per-step uploads land beside them
        self.device = device
        if device is not None and self._mesh_ctx[0] is not None:
            raise ValueError("an engine is placed either on one device or "
                             "on the active mesh, not both")

        # ---- resilience: the watchdog (per-step non-finite logit detection
        # + quarantine) is always on; the chaos injector and degradation
        # ladder activate when the caller passes a configured bundle
        # (launch.serve wires one; bare engines get an inert default).
        self.resilience = resilience if resilience is not None else Resilience()
        if self.resilience.injector is not None:
            self.resilience.injector.on_inject = self.metrics.on_fault_injected
        if self.resilience.ladder is not None:
            self.resilience.ladder.on_transition = self._on_ladder_transition
        self.n_quarantines = 0
        self.n_fault_failures = 0
        self.n_deadline_aborts = 0

        # ---- speculative decoding (paged only): a compressed draft model
        # proposes spec_k tokens per step; the target verifies the window in
        # one dispatch and the step advances by 1..spec_k+1 tokens. Archs
        # with recurrent state (mamba/rwkv) cannot roll state back cheaply:
        # they fall back to the one-token decode loop (spec_active False).
        self.spec_k = int(spec_k)
        self.spec_active = False
        self.draft_model = self.draft_params = None
        self.draft_cache: Optional[PagedCache] = None
        if spec_draft is not None:
            if not paged:
                raise ValueError("spec_draft requires paged=True (rollback "
                                 "is block-table truncation)")
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            draft_model, draft_params = spec_draft
            if draft_model.cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_model.cfg.vocab} != target vocab "
                    f"{cfg.vocab}")
            if model.spec_decode_supported and draft_model.spec_decode_supported:
                self.spec_active = True
                self.draft_model = draft_model
                self.draft_params, _ = pack_biases(draft_model, draft_params)
            else:
                log.info("recurrent blocks cannot re-score a token window — "
                         "speculative decoding disabled, using the plain "
                         "decode loop")

        if paged:
            slack = self.spec_k if self.spec_active else 0
            self.cache = PagedCache(model, n_slots, max_len,
                                    page_size=page_size, n_pages=n_pages,
                                    dtype=dtype, slack_tokens=slack)
            self.cache.injector = self.resilience.injector
            if self.spec_active:
                self.draft_cache = PagedCache(
                    self.draft_model, n_slots, max_len, page_size=page_size,
                    n_pages=n_pages, dtype=dtype, slack_tokens=slack)
                self.draft_cache.injector = self.resilience.injector
                # ONE token-keyed trie across both pools: draft and target
                # hit shared prefixes as a unit (trie hit counted once)
                share_trie([self.cache, self.draft_cache])
                self._propose = jax.jit(self._propose_impl)
                self._verify = jax.jit(self._verify_impl)
                self._chunk_draft = jax.jit(self._prefill_chunk_draft_impl)
                self._dbt_dev: Dict[int, jax.Array] = {}
            # chunks replace buckets: no largest-bucket rejection, one
            # prefill compile instead of one per bucket
            self.scheduler = Scheduler(n_slots, max_len, strict_buckets=False)
            ps = self.cache.page_size
            if prefill_chunk_tokens is None:
                prefill_chunk_tokens = min(4 * ps, self.cache.max_pages * ps)
            if prefill_chunk_tokens % ps:
                raise ValueError(
                    f"prefill_chunk_tokens({prefill_chunk_tokens}) must be a "
                    f"multiple of page_size({ps})")
            self.chunk_tokens = prefill_chunk_tokens
            self.prefill_token_budget = (prefill_token_budget
                                         or prefill_chunk_tokens)
            self._prefill_queue: Deque[Request] = collections.deque()
            self._chunk = jax.jit(self._prefill_chunk_impl,
                                  static_argnames=("final",))
            self._decode_paged = jax.jit(self._decode_paged_impl)
            self._bt_dev: Dict[int, jax.Array] = {}
            # disaggregated-serving handoff ops (compiled per pow-2 width
            # on first use): page-content gather on the prefill side,
            # scatter-adopt + slot arming on the decode side
            self._gather_pages = jax.jit(self._gather_pages_impl)
            self._adopt = jax.jit(self._adopt_impl)
            self._arm_slot = jax.jit(self._set_slot_impl)
            # observability for the prefix-reuse contract (tests assert a
            # shared-prefix batch skips chunks)
            self.n_prefill_chunks = 0
            self.n_prefill_tokens = 0          # computed
            self.n_prefill_tokens_skipped = 0  # reused from the trie
        else:
            self.scheduler = Scheduler(n_slots, max_len, min_bucket=min_bucket,
                                       buckets=buckets)
            self.cache = SlotCache(model, n_slots, max_len, dtype)
            self._admit = jax.jit(self._admit_impl)  # one compile per bucket

        # device-side per-slot sampling state (round-trips through _decode)
        self._dev = {
            "tokens": jnp.zeros((n_slots,), jnp.int32),
            "temps": jnp.zeros((n_slots,), jnp.float32),
            "top_ks": jnp.zeros((n_slots,), jnp.int32),
            "keys": jnp.zeros((n_slots, 2), jnp.uint32),
            "counters": jnp.zeros((n_slots,), jnp.int32),
        }
        self._live = np.zeros((n_slots,), bool)     # host-side liveness
        self._live_dev = None                       # device copy, lazy-synced
        # fault seam: additive per-slot logit poison. Always an operand of
        # the decode/verify programs (one compiled program with or without
        # chaos); zeros unless the injector schedules a NaN/Inf this step.
        self._zero_poison = jnp.zeros((n_slots,), jnp.float32)

        self._decode = jax.jit(self._decode_impl)
        self._clear_slot = jax.jit(self._clear_slot_impl)

        if device is not None:
            self.params = jax.device_put(self.params, device)
            self.cache.caches = jax.device_put(self.cache.caches, device)
            if self.spec_active:
                self.draft_params = jax.device_put(self.draft_params, device)
                self.draft_cache.caches = jax.device_put(
                    self.draft_cache.caches, device)
            self._dev = jax.device_put(self._dev, device)
            self._zero_poison = jax.device_put(self._zero_poison, device)

        # streaming hooks (the HTTP server wires these). token_cb fires for
        # every emitted token with its index in the request's output — a
        # preempted request regenerates deterministically and re-fires from
        # index 0, so consumers dedup by index; done_cb fires once at an
        # EOS/length stop (never for cancel or preemption).
        self.token_cb: Optional[Callable[[Request, int, int], None]] = None
        self.done_cb: Optional[Callable[[Request], None]] = None
        # disaggregation hook (the router wires this on prefill-role
        # replicas): fires instead of done_cb when a ``prefill_only``
        # request reaches its (clamped) budget without hitting EOS, with
        # ``req.handoff`` already extracted — the receiver resubmits the
        # request to a decode-role engine
        self.handoff_cb: Optional[Callable[[Request], None]] = None
        self.n_handoffs_out = 0
        self.n_handoffs_in = 0
        # interactive-over-batch preemption needs page eviction: paged only
        self.preemption = bool(preemption) and paged
        self.n_preemptions = 0

    @property
    def params(self):
        """The weights every program of this engine runs on: the tree the
        caller handed in, each packed linear's bias ``b`` with a copy
        ``bp`` in packed order beside it
        (:func:`repro.core.export.pack_biases`), so no program gathers a
        bias. Assigning a tree makes its ``bp`` from its ``b`` anew, so a
        tree read here, its ``b`` changed and assigned back serves the
        change."""
        return self._params

    @params.setter
    def params(self, params) -> None:
        self._params, self.n_packed_biases = pack_biases(self.model, params)

    # ------------------------------------------------------------ jitted ops
    def _admit_impl(self, params, caches, dev, padded, length, slot, temp,
                    top_k, key):
        """One-dispatch slot-dense admission: bucket-padded batch-1 prefill,
        first-token sampling, cache writeback into ``slot``, sampling-state
        update."""
        pcaches = self.model.init_caches(1, self.max_len, self.cache.dtype)
        logits, pcaches = self.model.prefill(params, padded, pcaches,
                                             lengths=length)
        caches = self.cache._write_impl(caches, pcaches, slot)
        keys = sampling_lib.fold_keys(key[None], jnp.zeros((1,), jnp.int32))
        tok = sampling_lib.sample(logits, temp[None], top_k[None], keys)[0]
        dev = self._set_slot_impl(dev, slot, tok, temp, top_k, key)
        return tok, caches, dev

    def _decode_impl(self, params, caches, dev, poison):
        logits, caches = self.model.decode_step(params, dev["tokens"], caches)
        # fault seam + watchdog: the injector's per-slot poison adds here
        # (zeros in normal operation), and the per-slot finite check rides
        # the same dispatch — non-finite rows are quarantined on the host,
        # their sampled garbage token never emitted
        logits = logits + poison[:, None]
        ok = jnp.isfinite(logits).all(axis=-1)
        keys = sampling_lib.fold_keys(dev["keys"], dev["counters"])
        tokens = sampling_lib.sample(logits, dev["temps"], dev["top_ks"], keys)
        dev = dict(dev, tokens=tokens, counters=dev["counters"] + 1)
        return dev, caches, ok

    def _decode_paged_impl(self, params, caches, dev, block_tables, live,
                           poison):
        logits, caches = self.model.decode_step(params, dev["tokens"], caches,
                                                block_tables=block_tables,
                                                live=live)
        logits = logits + poison[:, None]
        ok = jnp.isfinite(logits).all(axis=-1)
        keys = sampling_lib.fold_keys(dev["keys"], dev["counters"])
        tokens = sampling_lib.sample(logits, dev["temps"], dev["top_ks"], keys)
        dev = dict(dev, tokens=tokens, counters=dev["counters"] + 1)
        return dev, caches, ok

    def _prefill_chunk_impl(self, params, caches, dev, tokens, bt_row, slot,
                            start, chunk_len, temp, top_k, key, *,
                            final: bool = True):
        """One prefill chunk; on the final chunk first-token sampling + slot
        arming are fused into the same dispatch (admission stays one
        dispatch). ``final`` is static: non-final chunks skip final norm,
        unembed AND sampling entirely — only the caches matter, and the
        returned token is a zero sentinel nothing reads."""
        logits, caches = self.model.prefill_chunk(params, tokens, caches,
                                                  bt_row, slot, start,
                                                  chunk_len, final=final)
        if not final:
            return jnp.zeros((), jnp.int32), caches, dev
        keys = sampling_lib.fold_keys(key[None], jnp.zeros((1,), jnp.int32))
        tok = sampling_lib.sample(logits, temp[None], top_k[None], keys)[0]
        dev = self._set_slot_impl(dev, slot, tok, temp, top_k, key)
        return tok, caches, dev

    def _prefill_chunk_draft_impl(self, dparams, dcaches, tokens, bt_row,
                                  slot, start, chunk_len):
        """Draft-side prefill chunk: same tokens, the draft's own page pool.
        The draft's logits are never sampled during prefill — the pending
        token comes from the target — so only the caches survive (every
        draft chunk runs with ``final=False``: no unembed)."""
        _, dcaches = self.draft_model.prefill_chunk(
            dparams, tokens, dcaches, bt_row, slot, start, chunk_len,
            final=False)
        return dcaches

    def _propose_impl(self, dparams, dcaches, dev, block_tables, live, pos0,
                      poison):
        """Draft-propose: ``spec_k`` decode steps of the draft model in one
        jitted scan, starting from the host-authoritative accepted depth
        ``pos0``. Feeds the pending token first, so the draft cache ends
        holding K/V for window positions ``pos0 .. pos0+k-1``. Returns the
        proposed tokens (B, k), the proposal distributions q (B, k, V) the
        rejection sampler needs, and the draft caches."""
        dcaches = self.draft_model.set_paged_pos(dcaches, pos0)
        base = sampling_lib.fold_keys(dev["keys"], dev["counters"])

        def step_fn(carry, i):
            caches, toks = carry
            logits, caches = self.draft_model.decode_step(
                dparams, toks, caches, block_tables=block_tables, live=live)
            # draft_logits fault site: poisoned proposals yield non-finite
            # q, which the verify watchdog catches (the target never emits
            # a token derived from a poisoned draft)
            logits = logits + poison[:, None]
            # per-draft-position keys: salts 3.. (accept/resample use 1, 2)
            keys = jax.vmap(jax.random.fold_in, in_axes=(0, None))(base, 3 + i)
            nxt, q = sampling_lib.propose_token(logits, dev["temps"],
                                                dev["top_ks"], keys)
            return (caches, nxt), (nxt, q)

        (dcaches, _), (toks_seq, q_seq) = jax.lax.scan(
            step_fn, (dcaches, dev["tokens"]),
            jnp.arange(self.spec_k, dtype=jnp.int32))
        return (jnp.moveaxis(toks_seq, 0, 1), jnp.moveaxis(q_seq, 0, 1),
                dcaches)

    def _verify_impl(self, params, caches, dev, block_tables, live, pos0,
                     draft_toks, draft_q, poison):
        """Target-verify: score the (k+1)-token window [pending, d_1..d_k]
        in ONE dispatch, run acceptance in-graph, and advance the sampling
        state by the per-row acceptance count. Returns the updated device
        state, caches, the emitted-token window (B, k+1), n_accepted (B,)
        — the host emits ``out[:n+1]`` per live slot — and the per-slot
        watchdog verdict ``ok`` (finite target logits AND finite draft
        proposal distributions; a poisoned draft must not leak through
        acceptance resampling)."""
        caches = self.model.set_paged_pos(caches, pos0)
        window = jnp.concatenate([dev["tokens"][:, None], draft_toks], axis=1)
        logits, caches = self.model.verify_step(params, window, caches,
                                                block_tables, live=live)
        logits = logits + poison[:, None, None]
        ok = (jnp.isfinite(logits).all(axis=(-1, -2))
              & jnp.isfinite(draft_q).all(axis=(-1, -2)))
        base = sampling_lib.fold_keys(dev["keys"], dev["counters"])
        out, n_acc = sampling_lib.spec_accept(
            logits, draft_toks, draft_q, dev["temps"], dev["top_ks"], base)
        adv = jnp.where(live, n_acc + 1, 0).astype(jnp.int32)
        new_tok = jnp.take_along_axis(out, n_acc[:, None], axis=1)[:, 0]
        dev = dict(dev,
                   tokens=jnp.where(live, new_tok, dev["tokens"]),
                   counters=dev["counters"] + adv)
        return dev, caches, out, n_acc, ok

    def _set_slot_impl(self, dev, slot, tok, temp, top_k, key):
        return {
            "tokens": dev["tokens"].at[slot].set(tok),
            "temps": dev["temps"].at[slot].set(temp),
            "top_ks": dev["top_ks"].at[slot].set(top_k),
            "keys": dev["keys"].at[slot].set(key),
            # counter 0 produced the first token during prefill
            "counters": dev["counters"].at[slot].set(1),
        }

    def _clear_slot_impl(self, dev, slot):
        # evicted slots must read as greedy again, or one sampled request
        # would disable the all-greedy decode fast path for the engine's life
        return dict(dev, temps=dev["temps"].at[slot].set(0.0),
                    top_ks=dev["top_ks"].at[slot].set(0))

    def _gather_pages_impl(self, caches, ids):
        """Gather the page *contents* at pool indices ``ids`` from every
        attention layer (disagg handoff, prefill side). Recurrent blocks
        have no page-addressable state and yield None (the router gates
        disaggregation to all-attention archs)."""
        out = []
        for spec, c in zip(self.model.block_specs, caches):
            if spec["kind"] in ("attn", "attn_moe"):
                out.append({"kp": c["kp"][:, ids], "vp": c["vp"][:, ids]})
            else:
                out.append(None)
        return out

    def _adopt_impl(self, caches, pages, ids, slot, pos):
        """Scatter a handoff payload into this engine's pool at ``ids`` and
        set the slot's depth counter (disagg handoff, decode side). Padded
        columns carry the null page on both sides, so their scatter is the
        usual harmless null-page write; trie-matched destination pages
        receive bit-identical content (prefill is deterministic and the
        trie is token-keyed), so overwriting shared pages is a no-op by
        value."""
        new = []
        for c, p in zip(caches, pages):
            if p is not None:
                c = dict(c,
                         kp=c["kp"].at[:, ids].set(p["kp"].astype(c["kp"].dtype)),
                         vp=c["vp"].at[:, ids].set(p["vp"].astype(c["vp"].dtype)),
                         pos=c["pos"].at[:, slot].set(pos))
            new.append(c)
        return new

    # -------------------------------------------------------------- requests
    def submit(self, req: Request) -> None:
        # always stamped with the metrics clock: arrival_time is scheduling
        # metadata for the drive loop (serve_stream rebases the clock onto
        # the same timeline, so TTFT stays arrival-accurate there)
        self.scheduler.submit(req)
        self.metrics.on_submit(req.id, len(req.prompt),
                               priority=req.priority,
                               ttft_slo_s=req.ttft_slo_s,
                               e2e_slo_s=req.e2e_slo_s)

    def has_work(self) -> bool:
        return self.scheduler.has_work()

    def stats_gauges(self) -> Dict[str, float]:
        """Instantaneous engine gauges for the /metrics scrape — one method
        the HTTP server and the replica router both read, so a Router can
        stand in for an Engine without the server peeking at internals."""
        g = {
            "repro_serve_slots_live": float(self._live.sum()),
            "repro_serve_slots_total": float(self.n_slots),
            "repro_serve_engine_steps_total": float(self.step_count),
            # per-layer linear biases served in packed order: the load-time
            # pass engaged (Granite-8B: 36 layers x 7 projections)
            "repro_serve_packed_biases": float(self.n_packed_biases),
        }
        if self.paged:
            g["repro_serve_kv_pages_allocated"] = float(
                self.cache.pool.allocated_count)
            g["repro_serve_kv_pages_free"] = float(self.cache.pool.free_count)
        return g

    def cancel(self, req: Request) -> None:
        """Abort a request (client disconnect): pull it out of whichever
        stage it is in and return its pages to the pool immediately —
        waiting requests just leave the queue; admitted ones drop their
        prefill-queue entry, block-table refs, reservation, and liveness.
        Safe to call between engine steps; a no-op once the request is
        DONE."""
        if req.state == RequestState.DONE:
            return
        slot = req.slot
        if self.paged:
            try:
                self._prefill_queue.remove(req)
            except ValueError:
                pass
        self.scheduler.finish(req)
        self.metrics.on_cancel(req.id)
        if slot is not None:
            if self.paged:
                self.cache.free_slot(slot)
                if self.spec_active:
                    self.draft_cache.free_slot(slot)
            self._live[slot] = False
            if req.sampling.temperature > 0:
                self._dev = self._clear_slot(self._dev,
                                             jnp.asarray(slot, jnp.int32))
        log.info("request %d cancelled (%s, %d tokens streamed)",
                 req.id, req.priority, len(req.generated))

    # ----------------------------------------------------------- preemption
    def _preempt(self, victim: Request) -> None:
        """Evict ``victim`` from its slot: non-shared pages go back to the
        pool (trie-shared prefix pages survive — the trie holds its own
        ref), the slot frees, and the request requeues at its original
        arrival position. Re-admission re-prefills through the resubmit
        machinery; the prefix trie makes that cheap, and deterministic
        regeneration keeps the final output identical to an uncontended
        run."""
        slot = victim.slot
        try:
            self._prefill_queue.remove(victim)     # mid-prefill victims
        except ValueError:
            pass
        self.cache.preempt_slot(slot)
        if self.spec_active:
            self.draft_cache.preempt_slot(slot)
        self._live[slot] = False
        if victim.sampling.temperature > 0:
            self._dev = self._clear_slot(self._dev,
                                         jnp.asarray(slot, jnp.int32))
        self.scheduler.preempt(victim)
        self.metrics.on_preempt(victim.id)
        self.n_preemptions += 1
        log.info("preempted request %d (%s, slot %d, %d tokens in) for a "
                 "higher-priority admission", victim.id, victim.priority,
                 slot, len(victim.generated))

    def _preempt_for_head(self) -> bool:
        """The queue head cannot admit (no free slot, or page-pool
        pressure): evict the lowest-priority running request — youngest
        first within the class, so the FCFS order among victims is what a
        fresh arrival sequence would have produced — if and only if it
        ranks strictly below the head. Returns True if a slot was evicted
        (the caller retries admission, which re-checks capacity)."""
        if not self.preemption or not self.scheduler.waiting:
            return False
        # the head is the first *eligible* waiting request — a quarantined
        # request still in retry backoff is skipped by admission, so
        # evicting victims on its behalf makes no progress (the victim
        # just re-admits off its trie-published prefix, and the admission
        # loop wedges preempting it over and over within one step)
        head = next((r for r in self.scheduler.waiting
                     if self._retry_eligible(r)), None)
        if head is None:
            return False
        victims = [r for r in self.scheduler.running.values()
                   if r.priority_rank > head.priority_rank]
        if not victims:
            return False
        victim = max(victims, key=lambda r: (r.priority_rank, r.arrival_seq))
        self._preempt(victim)
        return True

    # ------------------------------------------------------------ step logic
    def _admit_one(self, req: Request, slot: int) -> None:
        padded, n = self.scheduler.pad_prompt(req)
        self.metrics.on_admit(req.id)
        sp = req.sampling
        tok_dev, self.cache.caches, self._dev = self._admit(
            self.params, self.cache.caches, self._dev, jnp.asarray(padded),
            jnp.asarray([n], jnp.int32), jnp.asarray(slot, jnp.int32),
            jnp.asarray(sp.temperature, jnp.float32),
            jnp.asarray(sp.top_k, jnp.int32), sampling_lib.base_key(sp.seed))
        self._live[slot] = True
        req.state = RequestState.DECODE
        self._emit(req, int(tok_dev))

    def _admit_one_paged(self, req: Request, slot: int) -> None:
        """Paged admission is bookkeeping only: build the block table
        (reusing trie-matched prefix pages) and queue the prefill chunks —
        no device work until the chunk loop runs. A request arriving with a
        :class:`Handoff` payload (disaggregated serving) adopts the
        prefilled pages instead of queueing chunks."""
        self.metrics.on_admit(req.id)
        if req.handoff is not None:
            self._admit_handoff(req, slot)
            return
        matched = self.cache.admit_request(slot, req.prompt,
                                           req.max_new_tokens)
        if self.spec_active:
            # the shared trie guarantees both caches match the same prefix,
            # so draft and target prefill skip identical token ranges
            dmatched = self.draft_cache.admit_request(slot, req.prompt,
                                                      req.max_new_tokens)
            assert dmatched == matched, (dmatched, matched)
        req.prefill_pos = matched
        req.n_matched = matched
        self.n_prefill_tokens_skipped += matched
        self._prefill_queue.append(req)

    # ------------------------------------------------- disaggregated serving
    def extract_handoff(self, req: Request) -> Handoff:
        """Gather the prompt's page contents for migration to a decode-role
        engine. Must run while the request still owns its block-table row
        (``_emit`` calls it just before the stop-path ``free_slot``)."""
        assert self.paged and req.slot is not None
        n_tok = len(req.prompt)
        n_pages = self.cache.pages_for(n_tok)
        width = min(_next_pow2(n_pages), self.cache.max_pages)
        ids = np.full((width,), NULL_PAGE, np.int32)
        ids[:n_pages] = self.cache.block_tables[req.slot][:n_pages]
        pages = self._gather_pages(self.cache.caches, jnp.asarray(ids))
        self.n_handoffs_out += 1
        return Handoff(prompt_len=n_tok, n_pages=n_pages, width=width,
                       first_token=int(req.generated[0]), pages=pages)

    def _admit_handoff(self, req: Request, slot: int) -> None:
        """Adopt a prefilled request: the normal reservation-accounted
        admission builds the block table (so handoff can never deadlock —
        ``can_admit`` already cleared the worst-case page count), the
        payload scatters into the allocated pages, and the slot arms with
        the first token the prefill engine sampled. No token is re-emitted:
        index 0 already streamed from the prefill replica."""
        h: Handoff = req.handoff
        assert h.prompt_len == len(req.prompt)
        pages = h.pages
        if self.device is not None:     # the payload left another replica
            pages = jax.device_put(pages, self.device)
        matched = self.cache.admit_request(slot, req.prompt,
                                           req.max_new_tokens)
        # scatter ALL prompt pages, trie-matched ones included: the trie is
        # token-keyed and prefill is deterministic, so matched destination
        # pages receive the bytes they already hold — one compile per
        # pow-2 width instead of one per (width, matched) pair
        ids = np.full((h.width,), NULL_PAGE, np.int32)
        ids[:h.n_pages] = self.cache.block_tables[slot][:h.n_pages]
        self.cache.caches = self._adopt(
            self.cache.caches, pages, jnp.asarray(ids),
            jnp.asarray(slot, jnp.int32),
            jnp.asarray(h.prompt_len, jnp.int32))
        sp = req.sampling
        self._dev = self._arm_slot(
            self._dev, jnp.asarray(slot, jnp.int32),
            jnp.asarray(h.first_token, jnp.int32),
            jnp.asarray(sp.temperature, jnp.float32),
            jnp.asarray(sp.top_k, jnp.int32),
            sampling_lib.base_key(sp.seed))
        req.handoff = None
        req.prefill_pos = h.prompt_len
        req.n_matched = matched
        req.generated = [h.first_token]
        req.state = RequestState.DECODE
        self._live[slot] = True
        # adopted pages hold real K/V: publish so later handoffs sharing
        # the prefix adopt into (bit-identical) cached pages
        self.cache.publish_prefix(req.prompt, slot, h.prompt_len)
        self.n_handoffs_in += 1

    def _prefill_chunks(self) -> bool:
        """Run prefill chunks FCFS under the per-step token budget; arm
        slots whose final chunk lands. Returns True if any chunk ran."""
        budget = self.prefill_token_budget
        ran = False
        while budget > 0 and self._prefill_queue:
            req = self._prefill_queue[0]
            slot = req.slot
            pos = req.prefill_pos
            plen = len(req.prompt)
            tc = self.chunk_tokens
            n_real = min(plen - pos, tc)
            with _span("engine.prefill.chunk", req=req.id, pos=pos,
                       n=n_real):
                toks = np.zeros((1, tc), np.int32)
                toks[0, :n_real] = req.prompt[pos:pos + n_real]
                # the chunk attends over [0, pos + tc): hand it only that
                # many block-table columns (power-of-two ladder, like
                # decode), so chunk attention reads context proportional to
                # actual depth
                ctx_pages = min(_next_pow2(self.cache.pages_for(pos + tc)),
                                self.cache.max_pages)
                sp = req.sampling
                final = pos + n_real >= plen
                tok_dev, self.cache.caches, self._dev = self._chunk(
                    self.params, self.cache.caches, self._dev,
                    jnp.asarray(toks),
                    jnp.asarray(self.cache.block_tables[req.slot][:ctx_pages]),
                    jnp.asarray(slot, jnp.int32), jnp.asarray(pos, jnp.int32),
                    jnp.asarray(n_real, jnp.int32),
                    jnp.asarray(sp.temperature, jnp.float32),
                    jnp.asarray(sp.top_k, jnp.int32),
                    sampling_lib.base_key(sp.seed), final=final)
                # KV bytes the chunk's attention read: the flash kernel
                # streams only pages at/below the causal horizon (∝ actual
                # depth); the jnp gather path reads the whole laddered table
                # width
                if ops.prefill_backend() == "jnp":
                    pages_read = ctx_pages
                else:
                    pages_read = min(self.cache.pages_for(pos + n_real),
                                     ctx_pages)
                self.metrics.on_prefill_kv_read(
                    int(pages_read * self.cache.page_size
                        * self.cache.token_bytes))
                if self.spec_active:
                    # mirror the chunk into the draft's page pool (one extra
                    # dispatch; its logits are discarded — the target
                    # samples)
                    dctx = min(
                        _next_pow2(self.draft_cache.pages_for(pos + tc)),
                        self.draft_cache.max_pages)
                    self.draft_cache.caches = self._chunk_draft(
                        self.draft_params, self.draft_cache.caches,
                        jnp.asarray(toks),
                        jnp.asarray(
                            self.draft_cache.block_tables[slot][:dctx]),
                        jnp.asarray(slot, jnp.int32),
                        jnp.asarray(pos, jnp.int32),
                        jnp.asarray(n_real, jnp.int32))
                req.prefill_pos = pos + n_real
                self.n_prefill_chunks += 1
                self.n_prefill_tokens += n_real
                self.metrics.on_prefill_tokens(n_real)
                budget -= tc
                ran = True
                # the chunk's full prompt pages now hold real K/V ->
                # shareable
                if self.spec_active:
                    publish_prefix_shared([self.cache, self.draft_cache],
                                          req.prompt, slot, req.prefill_pos,
                                          from_tokens=pos)
                else:
                    self.cache.publish_prefix(req.prompt, slot,
                                              req.prefill_pos,
                                              from_tokens=pos)
            if req.prefill_pos >= plen:
                self._prefill_queue.popleft()
                self._live[slot] = True
                req.state = RequestState.DECODE
                with _span("engine.prefill.sync", req=req.id):
                    tok = int(tok_dev)
                self._emit(req, tok)
        return ran

    def decode_widths(self) -> List[int]:
        """The active block-table widths paged decode can run at (the
        power-of-two ladder, capped at ``max_pages``) — one decode compile
        each."""
        if not self.paged:
            return []
        out, w = [], 1
        while w < self.cache.max_pages:
            out.append(w)
            w *= 2
        out.append(self.cache.max_pages)
        return out

    def prefill_widths(self) -> List[int]:
        """The active block-table widths prefill chunks can run at: the
        decode ladder truncated below the first chunk's width (a chunk
        always attends over at least ``chunk_tokens`` of context, so the
        narrower rungs never occur) — one chunk compile per rung per
        ``final`` variant."""
        if not self.paged:
            return []
        w_min = min(_next_pow2(self.cache.pages_for(self.chunk_tokens)),
                    self.cache.max_pages)
        return [w for w in self.decode_widths() if w >= w_min]

    def _mesh_scope(self):
        """Re-enter the (mesh, rules) context captured at construction, or
        the engine's device as JAX's default. Every jit trace the engine
        triggers — warmup and the serve loop alike — runs inside this
        scope, so the TP ``shard_map`` closure in the paged attention ops
        resolves identically everywhere: warmup compiles exactly the
        programs serving will run. Identity when the engine was built
        without a mesh or device."""
        if self.device is not None:
            return jax.default_device(self.device)
        mesh, rules = self._mesh_ctx
        if mesh is None or rules is None:
            return contextlib.nullcontext()
        return dist_sh.use_mesh_rules(mesh, rules)

    def warmup(self) -> None:
        """Pre-compile the paged decode program at every active-width rung
        so steady-state serving never pauses for a mid-stream compile (the
        width grows with the deepest live sequence). In spec mode the
        propose scan and the (k+1)-query verify program compile per rung
        instead. The chunked-prefill ladder compiles alongside — every
        prefill width × {non-final, final} chunk variant (plus the draft
        mirror in spec mode), against the null page so no real K/V moves.
        Results are discarded; engine state is untouched. No-op for the
        dense engine (one decode shape, compiled on first step). Mesh-aware:
        compiles under the construction-time mesh scope (see
        :meth:`_mesh_scope`), not whatever mesh happens to be active at
        call time."""
        with self._mesh_scope():
            self._warmup_inner()

    def lower_decode(self, width: int):
        """The jitted paged decode step lowered at block-table width
        ``width`` — the program serving runs, for inspecting what it
        compiles to (e.g. that the Pallas kernels are in it)."""
        with self._mesh_scope():
            return self._decode_paged.lower(
                self.params, self.cache.caches, self._dev,
                jnp.zeros((self.n_slots, width), jnp.int32),
                jnp.zeros((self.n_slots,), bool), self._zero_poison)

    def _warmup_inner(self) -> None:
        for w in self.decode_widths():
            zbt = jnp.zeros((self.n_slots, w), jnp.int32)
            zlive = jnp.zeros((self.n_slots,), bool)
            if self.spec_active:
                zpos = jnp.zeros((self.n_slots,), jnp.int32)
                dt, dq, _ = self._propose(self.draft_params,
                                          self.draft_cache.caches, self._dev,
                                          zbt, zlive, zpos, self._zero_poison)
                self._verify(self.params, self.cache.caches, self._dev, zbt,
                             zlive, zpos, dt, dq, self._zero_poison)
                # the degradation ladder can suspend spec mid-flight: the
                # plain-decode fallback must be warm too, or the first
                # degraded step pauses for a compile
                self._decode_paged(self.params, self.cache.caches, self._dev,
                                   zbt, zlive, self._zero_poison)
            else:
                self._decode_paged(self.params, self.cache.caches, self._dev,
                                   zbt, zlive, self._zero_poison)
        if self.paged:
            ztoks = jnp.zeros((1, self.chunk_tokens), jnp.int32)
            zslot = jnp.zeros((), jnp.int32)
            zstart = jnp.zeros((), jnp.int32)
            zlen = jnp.ones((), jnp.int32)
            for w in self.prefill_widths():
                zrow = jnp.zeros((w,), jnp.int32)   # null page: writes vanish
                for final in (False, True):
                    self._chunk(self.params, self.cache.caches, self._dev,
                                ztoks, zrow, zslot, zstart, zlen,
                                jnp.zeros((), jnp.float32),
                                jnp.zeros((), jnp.int32),
                                sampling_lib.base_key(0), final=final)
                if self.spec_active:
                    self._chunk_draft(self.draft_params,
                                      self.draft_cache.caches, ztoks, zrow,
                                      zslot, zstart, zlen)

    def _live_mask_dev(self) -> jax.Array:
        """Device copy of the liveness mask, re-uploaded only when slot
        liveness actually changed (admission/finish), not every step."""
        if self._live_dev is None or not np.array_equal(
                self._live_dev[1], self._live):
            self._live_dev = (jnp.asarray(self._live), self._live.copy())
        return self._live_dev[0]

    def _block_tables_dev(self, width: int) -> jax.Array:
        """Device copy of the first ``width`` block-table columns (cached
        per width; all widths invalidate together when the host table
        changes)."""
        if self.cache.dirty:
            self._bt_dev = {}
            self.cache.dirty = False
        if width not in self._bt_dev:
            self._bt_dev[width] = jnp.asarray(
                self.cache.block_tables[:, :width])
        return self._bt_dev[width]

    def _draft_block_tables_dev(self, width: int) -> jax.Array:
        """Draft-pool counterpart of :meth:`_block_tables_dev`."""
        if self.draft_cache.dirty:
            self._dbt_dev = {}
            self.draft_cache.dirty = False
        if width not in self._dbt_dev:
            self._dbt_dev[width] = jnp.asarray(
                self.draft_cache.block_tables[:, :width])
        return self._dbt_dev[width]

    def _emit(self, req: Request, tok: int) -> None:
        """Record one generated token; finish the request if it stops."""
        req.generated.append(tok)
        self.metrics.on_token(req.id)
        if self.token_cb is not None:
            self.token_cb(req, tok, len(req.generated) - 1)
        stop = (len(req.generated) >= req.max_new_tokens
                or (req.eos_id >= 0 and tok == req.eos_id))
        if stop:
            slot = req.slot
            # disaggregation: a prefill_only request that exhausted its
            # (clamped) budget without EOS migrates instead of finishing —
            # payload gathered while the slot still owns its pages, then
            # the normal free path runs and handoff_cb resubmits elsewhere.
            # An EOS stop is a real completion: no decode work remains.
            handing_off = (req.prefill_only and self.handoff_cb is not None
                           and self.paged
                           and not (req.eos_id >= 0 and tok == req.eos_id))
            if handing_off:
                req.handoff = self.extract_handoff(req)
            self.scheduler.finish(req)
            if not handing_off:
                self.metrics.on_done(req.id)
            if slot is not None:
                if self.paged:
                    self.cache.free_slot(slot)
                    if self.spec_active:
                        self.draft_cache.free_slot(slot)
                self._live[slot] = False
                if req.sampling.temperature > 0:
                    self._dev = self._clear_slot(
                        self._dev, jnp.asarray(slot, jnp.int32))
            if handing_off:
                self.handoff_cb(req)
            elif self.done_cb is not None:
                self.done_cb(req)

    def _kv_len(self, req: Request) -> int:
        """Cached KV depth for a live request: the whole prompt plus every
        generated token except the newest (written next decode step)."""
        return len(req.prompt) + max(len(req.generated) - 1, 0)

    def _report_kv(self) -> None:
        logical = sum(self._kv_len(r) for r in self.scheduler.running.values()
                      if r.state == RequestState.DECODE)
        if self.paged:
            self.metrics.on_kv(self.cache.kv_bytes_allocated(),
                               int(logical * self.cache.token_bytes),
                               self.cache.dense_reserved_bytes)
        else:
            self.metrics.on_kv(self.cache.kv_bytes,
                               int(logical * self.cache.token_bytes),
                               self.cache.kv_bytes)

    # ----------------------------------------------------------- resilience
    def _poison_dev(self, site: str) -> jax.Array:
        """Per-slot additive logit poison for this step (zeros unless the
        injector schedules NaN/Inf at ``site``)."""
        inj = self.resilience.injector
        if inj is not None:
            vec = inj.poison(site, inj.step, self.n_slots)
            if vec is not None:
                return jnp.asarray(vec)
        return self._zero_poison

    def _retry_eligible(self, req: Request) -> bool:
        """Quarantined requests wait out their backoff window; everyone
        else admits immediately. Passed to Scheduler.admit as the *skip*
        predicate (an ineligible request never blocks the queue)."""
        return req.retry_at_step <= self.step_count

    def _fail_request(self, req: Request, reason: str) -> None:
        """Terminal failure: free everything the request holds within this
        step and surface ``finish_reason`` through done_cb."""
        slot = req.slot
        if self.paged:
            try:
                self._prefill_queue.remove(req)
            except ValueError:
                pass
        req.finish_reason = reason
        self.scheduler.finish(req)
        self.metrics.on_abort(req.id, reason)
        if slot is not None:
            if self.paged:
                self.cache.free_slot(slot)
                if self.spec_active:
                    self.draft_cache.free_slot(slot)
            self._live[slot] = False
            if req.sampling.temperature > 0:
                self._dev = self._clear_slot(self._dev,
                                             jnp.asarray(slot, jnp.int32))
        if self.done_cb is not None:
            self.done_cb(req)
        log.warning("request %d failed: finish_reason=%s (%d retries, "
                    "%d tokens streamed)", req.id, reason,
                    req.n_fault_retries, len(req.generated))

    def _enforce_deadlines(self) -> None:
        """Abort any ``enforce_deadline`` request past its e2e SLO — pages
        freed within this step, finish_reason="deadline"."""
        now = self.metrics.clock()
        candidates = list(self.scheduler.running.values()) \
            + list(self.scheduler.waiting)
        for req in candidates:
            if not req.enforce_deadline or req.e2e_slo_s is None:
                continue
            rm = self.metrics.requests.get(req.id)
            if rm is None or now - rm.t_submit <= req.e2e_slo_s:
                continue
            self.n_deadline_aborts += 1
            self._fail_request(req, "deadline")

    def _quarantine(self, req: Request) -> None:
        """Non-finite logits in this slot only: free its pages, requeue it
        at its original arrival position with exponential backoff, and
        after ``max_fault_retries`` fail it with finish_reason="fault".
        Every other slot's state is untouched — the batch rows are
        independent, so survivors stay byte-identical to a fault-free run;
        the quarantined request regenerates deterministically on retry."""
        res = self.resilience
        res.note_fault()
        self.n_quarantines += 1
        self.metrics.on_quarantine(req.id)
        if req.n_fault_retries >= res.max_fault_retries:
            self.n_fault_failures += 1
            self._fail_request(req, "fault")
            return
        req.n_fault_retries += 1
        req.retry_at_step = self.step_count + res.backoff_steps(
            req.id, req.n_fault_retries)
        slot = req.slot
        if self.paged:
            self.cache.preempt_slot(slot)
            if self.spec_active:
                self.draft_cache.preempt_slot(slot)
        self._live[slot] = False
        if req.sampling.temperature > 0:
            self._dev = self._clear_slot(self._dev,
                                         jnp.asarray(slot, jnp.int32))
        self.scheduler.requeue(req)
        log.warning("quarantined request %d (slot %d, non-finite logits): "
                    "retry %d/%d no earlier than step %d", req.id, slot,
                    req.n_fault_retries, res.max_fault_retries,
                    req.retry_at_step)

    def _handle_step_fault(self, err: Exception) -> bool:
        """A decode dispatch failed before any state was assigned (the
        ``dev, caches = dispatch(...)`` pattern mutates nothing on an
        exception), so the next step() re-runs the identical work — a
        deterministic retry. Bounded: after ``max_consecutive_step_faults``
        the fault is treated as persistent and re-raised. Backoff is
        exponential with seeded jitter."""
        res = self.resilience
        res.note_fault()
        res.consecutive_step_faults += 1
        self.metrics.on_step_fault()
        if res.consecutive_step_faults > res.max_consecutive_step_faults:
            log.error("engine step faulted %d consecutive times — persistent "
                      "fault, giving up", res.consecutive_step_faults)
            raise err
        delay = min(0.001 * (2 ** (res.consecutive_step_faults - 1)), 0.05)
        rng = np.random.default_rng((res.seed, self.step_count))
        delay *= 1.0 + 0.25 * float(rng.random())
        log.warning("engine step fault (%s) — retrying next step after "
                    "%.1fms backoff (%d/%d)", err, delay * 1e3,
                    res.consecutive_step_faults,
                    res.max_consecutive_step_faults)
        time.sleep(delay)
        return True

    def _on_ladder_transition(self, old: int, new: int) -> None:
        self.metrics.on_degradation(new)
        log.warning("degradation ladder: %s -> %s", STAGE_NAMES[old],
                    STAGE_NAMES[new])
        if not self.paged:
            return
        if new >= 2 and old < 2:        # entering flush_prefix
            n = self.cache.flush_trie()
            self.cache.publish_enabled = False
            if self.spec_active:
                self.draft_cache.publish_enabled = False
            log.warning("flushed %d trie-only prefix nodes; prefix "
                        "publishing suspended", n)
        elif new < 2 and old >= 2:      # pressure cleared: re-enable
            self.cache.publish_enabled = True
            if self.spec_active:
                self.draft_cache.publish_enabled = True
            log.warning("prefix publishing re-enabled")

    def _apply_ladder(self, page_blocked: bool) -> None:
        """Feed this step's pressure signal into the ladder. Pool pressure
        is *contention*, not commitment: 1.0 when admission was actually
        page-blocked this step or nothing is obtainable from the pool;
        otherwise the committed fraction. Fault storms raise pressure
        through the resilience fault EWMA."""
        res = self.resilience
        if res.ladder is None:
            return
        if self.paged:
            cap = max(self.cache.pool.n_pages - 1, 1)
            avail = self.cache.available()
            util = 1.0 if (page_blocked or avail <= 0) \
                else 1.0 - min(avail, cap) / cap
        else:
            util = 1.0 if page_blocked else 0.0
        res.ladder.observe(res.pressure(util), self.step_count)

    @property
    def spec_suspended(self) -> bool:
        """True while the degradation ladder holds spec decoding off (the
        plain paged decode serves mid-flight; draft K/V goes stale for
        tokens generated meanwhile, costing acceptance — never
        correctness — after re-enable)."""
        ladder = self.resilience.ladder
        return self.spec_active and ladder is not None and ladder.spec_disabled

    # ------------------------------------------------------------- the step
    def step(self) -> bool:
        """One engine iteration: admit into free slots, (paged) run prefill
        chunks under the token budget, then one batched decode of all live
        slots. Returns True if any work was done. The resilience bracket
        wraps every path: injected slow-steps fire in begin_step, the
        step-time EWMA monitor and fault-rate decay in end_step."""
        res = self.resilience
        t0 = time.perf_counter()
        res.begin_step(self.step_count)
        try:
            with _span("engine.step", step=self.step_count), \
                    self._mesh_scope():
                return self._step_inner()
        finally:
            res.end_step(time.perf_counter() - t0)

    def _step_inner(self) -> bool:
        self._enforce_deadlines()
        page_blocked = False
        if self.paged:
            # one at a time: each admission consumes pages, and the pool
            # predicate for the next queue head must see that (spec mode:
            # in BOTH pools)
            def _can(r):
                nonlocal page_blocked
                ok = self.cache.can_admit(len(r.prompt), r.max_new_tokens,
                                          prompt=r.prompt)
                if ok and self.spec_active:
                    ok = self.draft_cache.can_admit(
                        len(r.prompt), r.max_new_tokens, prompt=r.prompt)
                if not ok:
                    page_blocked = True     # pressure signal for the ladder
                return ok
            admitted = []
            with _span("engine.admit") as span:
                while True:
                    pairs = self.scheduler.admit(
                        can_admit=_can, max_n=1,
                        eligible=self._retry_eligible)
                    if pairs:
                        self._admit_one_paged(*pairs[0])
                        admitted += pairs
                        continue
                    # head blocked (slot or page pressure): preempt the
                    # lowest-priority running request if it outranks, then
                    # retry — each eviction returns capacity the predicate
                    # re-checks
                    if not self._preempt_for_head():
                        break
                span.set_metadata(n=len(admitted))
            prefilled = self._prefill_chunks()
        else:
            with _span("engine.admit") as span:
                admitted = self.scheduler.admit(eligible=self._retry_eligible)
                for req, slot in admitted:
                    self._admit_one(req, slot)
                span.set_metadata(n=len(admitted))
            prefilled = False
        self.step_count += 1
        self.metrics.on_queue_depth(len(self.scheduler.waiting))
        self._apply_ladder(page_blocked)

        if not self._live.any():
            self.metrics.on_step(0, self.n_slots)
            self._report_kv()
            return bool(admitted) or prefilled

        if self.spec_active and not self.spec_suspended:
            return self._step_spec()

        res = self.resilience
        if self.paged:
            live_slots = np.nonzero(self._live)[0]
            with _span("engine.decode.prep") as span:
                # materialize this step's write pages and size the active
                # block-table width to the deepest live sequence
                needed, kv_tokens = 1, 0
                wpos_arr = np.zeros((self.n_slots,), np.int32)
                for slot in live_slots:
                    req = self.scheduler.running.get(int(slot))
                    if req is None:
                        continue
                    wpos = self._kv_len(req)
                    wpos_arr[slot] = wpos
                    kv_tokens += wpos + 1      # the kernel's length for it
                    self.cache.ensure_decode_page(int(slot), wpos)
                    needed = max(needed, self.cache.pages_used(int(slot),
                                                               wpos + 1))
                width = min(_next_pow2(needed), self.cache.max_pages)
                span.set_metadata(live=len(live_slots), width=width,
                                  kv_tokens=kv_tokens)
                bt = self._block_tables_dev(width)
                # live mask is load-bearing: mid-prefill slots hold real
                # block tables + carried state that an unmasked decode
                # would corrupt
                live = self._live_mask_dev()
                if self.spec_active:
                    # suspended-spec interlude: verify leaves cache ``pos``
                    # at the window entry depth, so the device counter the
                    # plain decode trusts is stale after a spec step —
                    # resync it to the host-authoritative accepted depth or
                    # this step writes K/V over accepted positions
                    self.cache.caches = self.model.set_paged_pos(
                        self.cache.caches, jnp.asarray(wpos_arr))
            try:
                with _span("engine.decode.dispatch"):
                    if res.injector is not None:
                        res.injector.check("engine_step")
                    self._dev, self.cache.caches, ok_dev = self._decode_paged(
                        self.params, self.cache.caches, self._dev, bt, live,
                        self._poison_dev("decode_logits"))
            except Exception as e:          # noqa: BLE001 — bounded retry
                return self._handle_step_fault(e)
        else:
            try:
                with _span("engine.decode.dispatch"):
                    if res.injector is not None:
                        res.injector.check("engine_step")
                    self._dev, self.cache.caches, ok_dev = self._decode(
                        self.params, self.cache.caches, self._dev,
                        self._poison_dev("decode_logits"))
            except Exception as e:          # noqa: BLE001 — bounded retry
                return self._handle_step_fault(e)
        res.consecutive_step_faults = 0
        with _span("engine.decode.sync"):
            next_np = np.asarray(self._dev["tokens"])
            ok_np = np.asarray(ok_dev)

        self.metrics.on_step(int(self._live.sum()), self.n_slots)
        if self.paged:
            self.metrics.on_kv_tokens_attended(kv_tokens)
        self._report_kv()
        live_slots = np.nonzero(self._live)[0]
        with _span("engine.emit", n=len(live_slots)):
            for slot in live_slots:
                req = self.scheduler.running.get(int(slot))
                if req is None:
                    continue
                if not ok_np[slot]:
                    self._quarantine(req)
                    continue
                self.metrics.on_decode_step(req.id, 1)
                self._emit(req, int(next_np[slot]))
        return True

    def _step_spec(self) -> bool:
        """The speculative decode step: materialize window pages in both
        pools, draft-propose (one scan dispatch), target-verify (one
        (k+1)-query dispatch), then emit 1..k+1 tokens per live slot with
        stop checks anywhere inside the accepted window, and roll both
        caches back to the accepted depth."""
        k = self.spec_k
        live_slots = np.nonzero(self._live)[0]
        with _span("engine.decode.prep") as span:
            pos0 = np.zeros((self.n_slots,), np.int32)
            needed, kv_tokens = 1, 0
            for slot in live_slots:
                req = self.scheduler.running.get(int(slot))
                if req is None:
                    continue
                wpos = self._kv_len(req)
                pos0[slot] = wpos
                kv_tokens += wpos + k + 1      # the verify kernel's length
                # target writes window positions wpos..wpos+k; the draft
                # only wpos..wpos+k-1 — materialize each range against the
                # slack reservation
                for t in range(k + 1):
                    self.cache.ensure_decode_page(int(slot), wpos + t)
                    if t < k:
                        self.draft_cache.ensure_decode_page(int(slot),
                                                            wpos + t)
                needed = max(needed, self.cache.pages_used(int(slot),
                                                           wpos + k + 1))
            width = min(_next_pow2(needed), self.cache.max_pages)
            span.set_metadata(live=len(live_slots), width=width,
                              kv_tokens=kv_tokens)
            bt = self._block_tables_dev(width)
            dbt = self._draft_block_tables_dev(width)
            live = self._live_mask_dev()
            pos0_dev = jnp.asarray(pos0)

        res = self.resilience
        try:
            with _span("engine.decode.dispatch"):
                if res.injector is not None:
                    res.injector.check("engine_step")
                # propose-then-verify retries as a unit: a fault after the
                # draft assignment only leaves rewritten draft window pages,
                # which the re-run re-scatters with identical values
                draft_toks, draft_q, self.draft_cache.caches = self._propose(
                    self.draft_params, self.draft_cache.caches, self._dev,
                    dbt, live, pos0_dev, self._poison_dev("draft_logits"))
                self._dev, self.cache.caches, out_dev, n_acc_dev, ok_dev = \
                    self._verify(self.params, self.cache.caches, self._dev,
                                 bt, live, pos0_dev, draft_toks, draft_q,
                                 self._poison_dev("decode_logits"))
        except Exception as e:              # noqa: BLE001 — bounded retry
            return self._handle_step_fault(e)
        res.consecutive_step_faults = 0
        with _span("engine.decode.sync"):
            out_np = np.asarray(out_dev)
            n_acc_np = np.asarray(n_acc_dev)
            ok_np = np.asarray(ok_dev)

        self.metrics.on_step(int(self._live.sum()), self.n_slots)
        self.metrics.on_kv_tokens_attended(kv_tokens)
        self._report_kv()
        with _span("engine.emit", n=len(live_slots)):
            for slot in live_slots:
                req = self.scheduler.running.get(int(slot))
                if req is None:
                    continue
                if not ok_np[slot]:
                    self._quarantine(req)
                    continue
                n = int(n_acc_np[slot])
                self.metrics.on_decode_step(req.id, n + 1, n_proposed=k,
                                            n_accepted=n)
                for i in range(n + 1):
                    self._emit(req, int(out_np[slot, i]))
                    if req.state == RequestState.DONE:
                        break       # EOS/max inside the window: drop the rest
                if req.state != RequestState.DONE:
                    # truncate both block tables to the accepted depth —
                    # pages past it hold rejected-window K/V (re-ensured
                    # next step)
                    keep = self._kv_len(req)
                    self.cache.rollback(int(slot), keep)
                    self.draft_cache.rollback(int(slot), keep)
        return True

    def run(self, requests: Sequence[Request],
            max_steps: int = 100_000) -> Dict[int, List[int]]:
        """Drive a fixed set of already-arrived requests to completion.
        Returns {request id: generated tokens}. (The streaming loop with
        wall-clock arrivals lives in ``repro.launch.serve``.)"""
        for r in requests:
            self.submit(r)
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("engine failed to drain the queue "
                                   f"within {max_steps} steps")
        return {r.id: list(r.generated) for r in requests}
