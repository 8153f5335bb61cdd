"""Continuous-batching serving engine over the paged KV cache.

Converts the one-shot batch serving path into a stateful multi-request
loop: ragged requests join fixed decode slots mid-stream (prefill-on-join),
decode runs in fused chunks of ``decode_chunk`` tokens over ALL slots with
a per-slot validity mask (one AOT executable for every occupancy pattern),
and slots free on EOS / token budget at harvest, at chunk granularity.

Anatomy of one engine cycle::

    poll ──> prefill-on-join ──> grow/preempt ──> fused chunk ──> harvest
     ^   (cached prefix shared,   (lazy pages;     (paged loop,      │
     │    only the suffix runs)    requeue on       donated cache)   │
     │                             pressure)                         │
     └──────────────────── free slots / pages on finish ─────────────┘

Joins with a prefix-cache hit map shared read-only pages and prefill only
the uncached suffix (chunked, through the paged verify sweep — see
docs/prefix_cache.md); cold prompts keep the classic bucketed prefill +
page inject.  In lazy mode (``EngineConfig.preempt``) pages grow
chunk-by-chunk and page pressure evicts the lowest-priority slot back to
the queue instead of stalling admission.

Telemetry: the engine itself is control-plane-agnostic — the launcher
passes an ``on_chunk`` hook that receives per-chunk :class:`ChunkStats`
(measured wall time, occupancy, useful-vs-computed tokens) and returns the
chunk's energy in joules (or ``None``).  Energy is attributed to requests
in proportion to their *kept* tokens, so J/token charges only occupied
slots — utilisation-honest under partial occupancy.  A
:class:`~repro.serving.spans.SpanRecorder` passed as ``spans`` records the
engine's phases (poll, join, page growth, each chunk and its steps) with
their counts, and annotates them on a ``jax.profiler`` trace; see
docs/serving_engine.md, "Spans".

KV storage is tiered (``EngineConfig.kv_dtype`` / ``host_tier``): int8
pages with per-row fp32 scales quarter the device footprint of a page
(dequant fused into the decode sweeps), and cold prefix-cache pages can
demote to a host-memory pool instead of being dropped — paged back in on
the next prefix hit, with the modelled D2H/H2D energy charged into the
same J/token ledger (see docs/prefix_cache.md, "KV memory hierarchy").

Speculative mode (``EngineConfig.spec_k > 0``): each chunk iteration
becomes a K+1-token verify step (draft -> verify -> accept -> commit,
in-scan, per-slot accepted counts — see docs/speculative_decoding.md), the
harvest consumes a *variable* number of tokens per slot per step, and the
report adds acceptance rate and J per *accepted* token, with rejected
drafts' compute charged as overhead.  The per-slot drafter history is one
more host mirror, seeded at prefill-on-join.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import quant
from repro.checkpoint.store import CheckpointManager, restore_pytree
from repro.kernels import ops
from repro.models import transformer as tfm
from repro.runtime.chaos import FaultInjector, corrupt_paged_kv
from repro.runtime.speculate import get_drafter
from repro.runtime.steps import (StepConfig, make_paged_decode_loop,
                                 make_paged_speculative_decode_loop,
                                 make_prefill_suffix_step, make_run_ctx,
                                 with_decode_policy)
from repro.serving.paged_kv import PagedKVCache
from repro.serving.request import Request, RequestResult
from repro.serving.scheduler import RequestQueue, Scheduler
from repro.serving.spans import SpanRecorder

_NO_SPAN = contextlib.nullcontext()


class EngineCrash(RuntimeError):
    """Injected engine-process death (chaos drills).  Carries the decode
    step at which the engine died so recovery latency can be reported;
    callers recover via ``ServeEngine.restore`` + ``resume``."""

    def __init__(self, step: int):
        super().__init__(f"engine crashed at step {step}")
        self.step = int(step)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine knobs orthogonal to the model config."""
    n_slots: int = 4
    page_size: int = 16
    max_len: int = 256            # per-request prompt + generation ceiling
    decode_chunk: int = 8
    n_pages: int | None = None    # None: fully provisioned (no page waits)
    greedy: bool = True
    temperature: float = 1.0
    sample_seed: int = 0
    cache_dtype: str = "bfloat16"
    min_prefill_bucket: int = 8   # prompts pad up to pow2 buckets >= this
    # speculative decoding: >0 turns each chunk iteration into a K+1-token
    # verify step (draft -> verify -> accept in-scan, per-slot counts)
    spec_k: int = 0
    drafter: str = "ngram"        # ngram | repeat (self-drafters)
    drafter_hist: int = 128       # ngram lookup history per slot
    # prefix sharing: admit_with_prefix maps cached prompt prefixes onto
    # shared read-only pages and only the uncached suffix is prefilled
    # (chunked, through the paged verify sweep).  Families whose pages ride
    # the main block tables only (dense/MoE GQA and MLA, deepseek's first
    # dense layers included); silently disabled elsewhere — windowed rings,
    # SSM state slots and multi-codebook keep the legacy cold-prefill path.
    prefix_cache: bool = True
    prefill_chunk: int = 16       # suffix tokens per chunked-prefill sweep
    # preemption: admit on prompt pages only, grow per chunk, and when the
    # pool runs dry evict the lowest-priority slot and re-queue it with
    # its generated tokens folded into the prompt (the prefix cache then
    # mostly restores the requeue for free).  False = reserve the whole
    # context at admission (the old hard-stall behaviour).
    preempt: bool = True
    # head-of-line fix: when the queue head cannot get pages, try up to
    # this many ready requests behind it (admitted order stays FIFO
    # otherwise)
    max_skip: int = 2
    # decode-sweep operating point: two-stage split-KV count ("auto" = the
    # ops.choose_kv_splits occupancy heuristic; 1 = single-stage sweep) and
    # the split-K block for the ring kernels / page-sized DMA elsewhere
    kv_splits: str | int = "auto"
    decode_k_chunk: int = 256
    # quantized KV pages: "int8" stores every page pool as int8 with
    # per-row fp32 scales and the dequant fused into the split-KV sweeps
    # (see docs/prefix_cache.md, "KV memory hierarchy").  Dense-GQA
    # families only — elsewhere the engine warns once (FallbackWarning) and
    # keeps cache_dtype.  The default leaves the decode path byte-identical
    # to unquantized serving.
    kv_dtype: str = "bfloat16"
    # host-memory page-out: cold trie-held pages demote to a host pool
    # instead of being dropped, and a later prefix hit pages them back in.
    # Each direction is charged at transfer_j_per_byte into the energy
    # ledger; with recompute_j_per_token set, a page is only demoted when
    # the round trip is cheaper than re-prefilling its rows.
    host_tier: bool = False
    host_pages: int | None = None       # None: unbounded host pool
    transfer_j_per_byte: float = 1e-9
    recompute_j_per_token: float | None = None


@dataclasses.dataclass(frozen=True)
class ChunkStats:
    """One fused chunk's telemetry, handed to the ``on_chunk`` hook."""
    step: int                     # chunk index
    wall_s: float                 # measured execution time (compile excluded)
    n_slots: int
    n_active: int                 # slots holding a live request
    tokens_kept: int              # useful tokens harvested this chunk
    tokens_computed: int          # n_active * chunk * (K+1) (incl. overrun)
    drafts_proposed: int = 0      # speculative mode only
    drafts_accepted: int = 0
    clock_step: int = 0           # engine decode-step clock at chunk end
    degrade_level: int = 0        # 0 healthy, 1 derate, 2 emergency cap


@dataclasses.dataclass
class EngineReport:
    """Run summary + per-request results.

    Ratio properties are guarded against empty runs (zero requests, zero
    kept tokens, zero wall) — they return 0.0 rather than leaking NaN /
    inf into benchmark CSVs."""
    results: list[RequestResult]
    n_chunks: int = 0
    decode_wall_s: float = 0.0
    tokens_kept: int = 0
    tokens_computed: int = 0
    energy_j: float = 0.0
    occupancy: float = 0.0        # mean active/slots over chunks
    spec_k: int = 0               # 0 = plain decode
    drafts_proposed: int = 0
    drafts_accepted: int = 0
    prompt_tokens: int = 0        # prompt tokens across every join (requeues too)
    prefill_tokens_saved: int = 0  # restored from the prefix cache, not computed
    n_preemptions: int = 0        # slots evicted + re-queued on page pressure
    # fault-tolerance accounting (docs/fault_tolerance.md)
    n_faults_injected: int = 0    # chaos faults applied to this engine
    n_restores: int = 0           # crash-restores this report survived
    degraded_steps: int = 0       # clock steps spent degraded (derate/cap)
    requeued_requests: int = 0    # in-flight requests recovered via requeue
    n_pages_quarantined: int = 0  # pages withheld after corruption repair
    # two-tier KV hierarchy: modelled page-out/page-in energy (already
    # included in energy_j; broken out so benchmarks can see the split)
    transfer_j: float = 0.0
    n_demotions: int = 0          # device pages paged out to the host tier
    n_promotions: int = 0         # host pages paged back in on a prefix hit
    # (token, expert) rows the held experts computed, counted on the device
    # and fetched with each chunk's tokens / each join's first token: pad
    # and parked-slot rows included, speculative chunks not counted
    expert_rows_decode: int = 0
    expert_rows_prefill: int = 0

    @property
    def tok_per_s(self) -> float:
        if self.tokens_kept <= 0 or self.decode_wall_s <= 0.0:
            return 0.0
        return self.tokens_kept / self.decode_wall_s

    @property
    def j_per_token(self) -> float:
        """Charges only tokens actually served — under partial occupancy
        this is the honest (higher) figure.  In speculative mode the kept
        tokens are the *accepted* ones, so rejected drafts' compute lands
        here as overhead (see ``j_per_accepted_token``)."""
        if self.tokens_kept <= 0:
            return 0.0
        return self.energy_j / self.tokens_kept

    @property
    def acceptance_rate(self) -> float:
        """Accepted / proposed drafts (0.0 when not speculating)."""
        if self.drafts_proposed <= 0:
            return 0.0
        return self.drafts_accepted / self.drafts_proposed

    @property
    def j_per_accepted_token(self) -> float:
        """The speculative serving figure of merit: every kept token is an
        accepted draft or the verify step's bonus token, and the chunk's
        full energy — including the sweeps spent scoring rejected drafts —
        is in the numerator.  Identical to ``j_per_token`` by construction;
        named so reports say what is being charged."""
        return self.j_per_token

    @property
    def tokens_per_step(self) -> float:
        """Mean useful tokens per slot-step — the effective-throughput
        multiplier admission control should see under speculation."""
        if self.n_chunks <= 0 or self.tokens_computed <= 0:
            return 0.0
        steps = self.tokens_computed / max(self.spec_k + 1, 1)
        return self.tokens_kept / max(steps, 1e-9)

    @property
    def prefix_hit_rate(self) -> float:
        """Fraction of prompt tokens restored from the prefix cache
        instead of being prefilled (0.0 on empty runs)."""
        if self.prompt_tokens <= 0:
            return 0.0
        return self.prefill_tokens_saved / self.prompt_tokens

    def latency_percentiles(self, qs=(50, 95)) -> dict[int, float]:
        lats = [r.latency_steps for r in self.results if r.finish_step >= 0]
        if not lats:
            return {q: 0.0 for q in qs}    # no finished requests: keep CSVs finite
        return {q: float(np.percentile(lats, q)) for q in qs}


class EnergyAwareAdmission:
    """Admission hook: admit while the predicted board draw at the
    *resulting* occupancy — under the cap currently in force — stays within
    a power budget.  Under a deep cap decode is memory-bound and occupancy
    is near-free, so the hook admits aggressively; at high caps it backs
    off, which is exactly the paper's serving trade expressed as admission
    control."""

    def __init__(self, device, workload_fn: Callable[[int], object],
                 budget_w: float, backend=None):
        self.device = device
        self.workload_fn = workload_fn        # n_active -> WorkloadProfile
        self.budget_w = float(budget_w)
        self.backend = backend                # CapBackend (current_cap())

    def __call__(self, request: Request, n_active_after: int) -> bool:
        cap = self.backend.current_cap() if self.backend is not None else 1.0
        est = self.device.estimate(self.workload_fn(n_active_after), cap)
        return est.power_w <= self.budget_w


class ServeEngine:
    """Drives the fused paged decode loop over live slots."""

    def __init__(self, cfg, engine_cfg: EngineConfig, params, *,
                 step_cfg: StepConfig | None = None, rules=None,
                 on_chunk: Callable[[ChunkStats], float | None] | None = None,
                 on_prefill: Callable[[int, int], float | None] | None = None,
                 admission=None,
                 injector: FaultInjector | None = None,
                 on_heartbeat: Callable[[int, float], None] | None = None,
                 on_fault: Callable[[object], None] | None = None,
                 snapshot_dir: str | None = None, snapshot_every: int = 0,
                 spans: SpanRecorder | None = None):
        self.cfg = cfg
        self.ecfg = engine_cfg
        self.params = params
        # quantized KV pages pair per-row scale leaves with full-length k/v
        # page pools; families whose pools are latent rows, private rings or
        # state slots warn once (naming the blocking feature) and keep the
        # unquantized layout
        kv_dtype = engine_cfg.cache_dtype
        if engine_cfg.kv_dtype == "int8":
            i8_block = tfm.int8_paged_blockers(cfg)
            if not i8_block:
                kv_dtype = "int8"
            else:
                ops.warn_kv_dtype_fallback(
                    cfg.name, f"int8 paged cache blocked by {i8_block[0]}")
        self.kv_dtype = kv_dtype
        # engine config owns the decode-sweep operating point: fold it onto
        # the kernel policy so every compiled loop (decode, verify, suffix
        # prefill) sees the same kv_splits / block / storage-dtype choice
        self.step_cfg = with_decode_policy(
            step_cfg or StepConfig(remat="none"),
            kv_splits=engine_cfg.kv_splits,
            decode_k_chunk=engine_cfg.decode_k_chunk,
            kv_dtype=kv_dtype)
        self.rules = rules
        self.on_chunk = on_chunk
        # on_prefill(n_computed, n_saved) -> J for one join's prefill (or
        # None): lets the launcher charge prefill compute into the same
        # J/token ledger — and see the joules the prefix cache avoided
        self.on_prefill = on_prefill
        # chaos: faults polled on the decode-step clock each cycle; kinds
        # the engine cannot act on itself (bus_drop/bus_delay) forward to
        # on_fault so the launcher can disturb its own telemetry transport
        self.injector = injector
        # on_heartbeat(clock_step, chunk_wall_s): liveness signal for a
        # serving supervisor; suppressed while a "stall" fault is active
        self.on_heartbeat = on_heartbeat
        self.on_fault = on_fault
        # None: no spans recorded, no profiler annotations
        self.spans = spans
        self.snapshot_every = int(snapshot_every)
        self._ckpt = CheckpointManager(snapshot_dir, keep=2) \
            if snapshot_dir else None
        self.kv = PagedKVCache(cfg, n_slots=engine_cfg.n_slots,
                               page_size=engine_cfg.page_size,
                               max_len=engine_cfg.max_len,
                               n_pages=engine_cfg.n_pages,
                               dtype=kv_dtype,
                               host_tier=engine_cfg.host_tier,
                               host_pages=engine_cfg.host_pages,
                               transfer_j_per_byte=engine_cfg.transfer_j_per_byte,
                               recompute_j_per_token=engine_cfg.recompute_j_per_token)
        # prefix sharing rides the chunked-prefill verify seam (suffix
        # chunks are scored by paged_verify_attention): any family whose
        # pages live on the main block tables qualifies — dense/MoE GQA and
        # MLA (deepseek's first dense layers included).  Windowed rings,
        # SSM state slots and the hybrid shared buffer keep the cold path.
        self._use_prefix = (engine_cfg.prefix_cache
                            and not tfm.chunked_prefill_blockers(cfg))
        self.scheduler = Scheduler(engine_cfg.n_slots, self.kv,
                                   admission=admission,
                                   max_skip=engine_cfg.max_skip,
                                   lazy=engine_cfg.preempt,
                                   prefix=self._use_prefix)
        self.cache = self.kv.make_cache()
        # unit subs whose page axis rides the main block tables — the only
        # pools the page-granular seams (host tier, CoW copy, page bytes)
        # may touch.  Windowed rings / SSM state slots are slot-indexed on
        # axis 1, so treating them as pages would corrupt other slots.
        self._table_subs = frozenset(
            f"sub{i}" for i in range(tfm.unit_size(cfg))
            if not cfg.uses_ssm
            and (cfg.use_mla or cfg.window_for_layer(i) == 0))
        self._tier_restore = None            # AOT page-in scatter (H2D)
        self._transfer_seen = 0.0            # kv.transfer_j folded so far
        if engine_cfg.host_tier:
            if self.kv.tables_active:
                self.kv.attach_tier(self._fetch_page, self._restore_page,
                                    self._cache_page_bytes())
            else:
                warnings.warn(
                    f"config {cfg.name!r}: host KV tier disabled: no page "
                    "pool rides the block tables (state-slot layout)",
                    ops.FallbackWarning, stacklevel=2)
        self._ctx = make_run_ctx(cfg, rules, self.step_cfg)
        # AOT-compiled paged chunk loops, keyed (chunk_len, speculative):
        # graceful degradation swaps in a shorter / non-speculative loop
        # under an emergency cap, each compiled once on first use
        self._loops: dict[tuple[int, bool], object] = {}
        self._prefills: dict[int, object] = {}   # bucket -> compiled prefill
        self._injects: dict[int, object] = {}    # bucket -> compiled inject
        self._suffix = None                  # AOT chunked-suffix prefill
        self._copy = None                    # AOT page-rows copy (CoW)
        self._pos = np.zeros((engine_cfg.n_slots,), np.int32)
        self._sample_key = jax.random.PRNGKey(engine_cfg.sample_seed)
        self._drafter = None
        self._dstate = None
        if engine_cfg.spec_k > 0:
            spec_block = (tfm.speculative_blockers(cfg)
                          or tfm.chunked_prefill_blockers(cfg))
            if spec_block:
                raise ValueError(f"{cfg.name}: speculative serving blocked "
                                 f"by {spec_block[0]}")
            self._drafter = get_drafter(engine_cfg.drafter, engine_cfg.spec_k,
                                        hist_len=engine_cfg.drafter_hist)
            # host mirror of the per-slot drafter state, synced like
            # pos/block_tables: seeded at prefill-on-join, carried through
            # the fused loop, read back at harvest
            self._dstate = self._drafter.init_state(engine_cfg.n_slots)
        # graceful-degradation state: level 0 = healthy, 1 = derate
        # (admission paused), 2 = emergency cap (+ shorter chunk, spec off)
        self._degrade_level = 0
        self._degrade_until = -1           # engine-clock step the window ends
        self._cap_frac = 1.0               # cap fraction in force (reporting)
        self._stall_until = -1             # heartbeat suppression window end
        self._eff_chunk = engine_cfg.decode_chunk

    def _span(self, name: str, rid: int | None = None):
        """A recorded span around a block, or nothing without a recorder."""
        return _NO_SPAN if self.spans is None else self.spans.span(name, rid)

    # -- compiled pieces (AOT so compile time never lands in measured walls) -
    def _chunk_loop(self, chunk: int, spec: bool, *args):
        key = (chunk, spec)
        if key not in self._loops:
            if spec:
                fn = jax.jit(make_paged_speculative_decode_loop(
                    self.cfg, self.step_cfg, self.rules,
                    chunk, drafter=self._drafter,
                    greedy=self.ecfg.greedy,
                    temperature=self.ecfg.temperature), donate_argnums=(1,))
            else:
                fn = jax.jit(make_paged_decode_loop(
                    self.cfg, self.step_cfg, self.rules,
                    chunk, greedy=self.ecfg.greedy,
                    temperature=self.ecfg.temperature), donate_argnums=(1,))
            self._loops[key] = fn.lower(*args).compile()
        return self._loops[key]

    def _prefill(self, bucket: int):
        if bucket not in self._prefills:
            cfg, ctx = self.cfg, self._ctx

            def prefill(params, inputs):
                # full_cache: keep windowed layers linear so the bucket's
                # pad rows can't wrap over the real window tail before the
                # inject scatter reads it
                return tfm.prefill_counted(params, inputs, cfg, ctx,
                                           max_len=bucket, full_cache=True)

            self._prefills[bucket] = jax.jit(prefill)
        return self._prefills[bucket]

    # -- host tier (two-tier KV hierarchy; docs/prefix_cache.md) -------------
    def _table_groups(self, cache) -> dict:
        """Pool groups whose axis 1 is the main page-id space: full-attention
        / MLA unit subs plus the stacked first-dense group.  Slot-indexed
        groups (windowed rings, SSM state, the hybrid shared buffer) are
        NOT pages and never appear here."""
        groups = {name: c for name, c in cache["units"].items()
                  if name in self._table_subs}
        if "dense" in cache:
            groups["dense"] = cache["dense"]
        return groups

    def _cache_page_bytes(self) -> int:
        """Device bytes of ONE page across every table-backed pool — scale
        pools included in int8 mode — the unit the transfer-energy model
        charges per page-out / page-in direction."""
        total = 0
        for c in self._table_groups(self.cache).values():
            for pool in c.values():                # (nu, P, ps, hkv, w)
                total += (pool.size // pool.shape[1]) * pool.dtype.itemsize
        return total

    def _fetch_page(self, page: int) -> dict:
        """D2H: copy one device page's rows out of every table-backed pool
        into host-memory numpy blobs (keys ``group/pool``)."""
        return {f"{name}/{key}": np.asarray(pool[:, page])
                for name, c in self._table_groups(self.cache).items()
                for key, pool in c.items()}

    def _restore_page(self, page: int, blob: dict) -> None:
        """H2D: scatter a fetched blob back into device page ``page``.
        One donated executable (page is a traced scalar) serves every
        promotion."""
        if self._tier_restore is None:
            tsubs = self._table_subs

            def tier_restore(cache, page, blob):
                def put(name, c):
                    return {key: pool.at[:, page].set(
                        blob[f"{name}/{key}"].astype(pool.dtype))
                        for key, pool in c.items()}

                units = {name: put(name, c) if name in tsubs else c
                         for name, c in cache["units"].items()}
                out = {**cache, "units": units}
                if "dense" in cache:
                    out["dense"] = put("dense", cache["dense"])
                return out

            self._tier_restore = jax.jit(tier_restore, donate_argnums=(0,))
        self.cache = self._tier_restore(
            self.cache, jnp.asarray(page, jnp.int32),
            {k: jnp.asarray(v) for k, v in blob.items()})

    def _sync_transfer(self) -> None:
        """Fold tier-transfer energy accrued in the paged-KV manager since
        the last sync into the run ledger.  Modelled, not measured: the
        manager charges bytes x J/byte as demotions/promotions happen; the
        engine surfaces the delta in ``energy_j`` (and breaks it out as
        ``transfer_j``) so J/token includes the cost of paging."""
        delta = self.kv.transfer_j - self._transfer_seen
        if delta > 0.0:
            self._transfer_seen = self.kv.transfer_j
            self._report.energy_j += delta
            self._report.transfer_j += delta

    def _inject(self, bucket: int):
        """Scatter a (padded) prefill cache into a slot's storage: one fused
        donated update across every pool group, keyed by per-group flat row
        ids from ``_inject_rows_tree`` (pad rows dropped).

        Per family: table-backed groups (k/v, MLA ``lat``, the stacked
        first-dense group) land on the slot's pages via
        ``PagedKVCache.inject_rows``; sliding-window groups scatter the
        prompt's last ``window`` rows into the slot's private ring pages;
        the hybrid shared buffer takes rows ``[0, L)`` of its per-slot
        linear span; SSM groups overwrite the slot's O(1) state slot
        outright (``slot`` is a traced scalar — one executable per bucket
        serves every slot).  Quantized pools ("k_scale" present) quantize
        the prefill rows on the way in — the same per-row int8 packing
        ``commit_spec_paged`` applies on the decode path, so cold-prefilled
        and decoded rows are indistinguishable."""
        if bucket not in self._injects:
            def inject(cache, pcache, rows, slot):
                def scatter(pool, vals, r):
                    nu = pool.shape[0]
                    flat = pool.reshape(nu, -1, *pool.shape[3:])
                    flat = flat.at[:, r].set(
                        vals.astype(flat.dtype), mode="drop")
                    return flat.reshape(pool.shape)

                def inject_group(c, src, r):
                    new = {}
                    for key in ("k", "v", "lat"):
                        if key not in c:
                            continue
                        vals = src[key][:, 0]  # (nu, bucket, ...)
                        if key + "_scale" in c:
                            vals, scales = quant.quantize_int8_rows(vals)
                            new[key + "_scale"] = scatter(
                                c[key + "_scale"], scales, r)
                        new[key] = scatter(c[key], vals, r)
                    return new

                units = {}
                for name, c in cache["units"].items():
                    src = pcache["units"][name]
                    if "conv" in c:       # SSM: overwrite the state slot
                        units[name] = {
                            "conv": c["conv"].at[:, slot].set(
                                src["conv"][:, 0].astype(c["conv"].dtype)),
                            "ssm": c["ssm"].at[:, slot].set(
                                src["ssm"][:, 0])}
                    else:
                        units[name] = inject_group(c, src, rows[name])
                out = {**cache, "units": units}
                if "shared" in cache:
                    out["shared"] = inject_group(
                        cache["shared"], pcache["shared"],
                        rows["__shared__"])
                if "dense" in cache:
                    # ring prefill keeps dense caches as a per-layer list
                    # (no unit axis); stack to the paged group's layout
                    src = {key: jnp.stack([c[key] for c in pcache["dense"]])
                           for key in cache["dense"]}
                    out["dense"] = inject_group(cache["dense"], src,
                                                rows["__dense__"])
                return out

            self._injects[bucket] = jax.jit(inject, donate_argnums=(0,))
        return self._injects[bucket]

    def _inject_rows_tree(self, slot: int, bucket: int, L: int) -> dict:
        """Per-group flat destination rows for ``_inject``: length-``bucket``
        arrays mapping prefill index ``p`` to a pool row, out-of-bounds
        (dropped) where ``p`` is padding or outside the group's retention.

        Table groups reuse ``PagedKVCache.inject_rows``; a window-``w``
        group keeps only ``[max(0, L - w), L)`` at ring offset ``p % Cw`` of
        the slot's private pages (older rows can never be attended again);
        the shared buffer is the slot's linear span."""
        cfg, kv = self.cfg, self.kv
        ps = kv.page_size
        main = np.asarray(kv.inject_rows(slot, bucket, L))
        rows = {}
        for i in range(tfm.unit_size(cfg)):
            name = f"sub{i}"
            if cfg.uses_ssm:
                continue                   # state slots need no row map
            w = 0 if cfg.use_mla else cfg.window_for_layer(i)
            if w <= 0:
                rows[name] = main
                continue
            nbw = -(-min(kv.max_blocks * ps, w) // ps)
            cw = nbw * ps
            p = np.arange(bucket)
            r = slot * cw + p % cw
            valid = (p >= max(0, L - w)) & (p < L)
            rows[name] = np.where(valid, r,
                                  self.ecfg.n_slots * cw).astype(np.int32)
        if cfg.family == "hybrid" and cfg.hybrid_attn_every:
            cs = kv.max_blocks * ps
            p = np.arange(bucket)
            rows["__shared__"] = np.where(
                p < L, slot * cs + p,
                self.ecfg.n_slots * cs).astype(np.int32)
        if cfg.first_dense_layers:
            rows["__dense__"] = main
        return rows

    def _bucket(self, L: int) -> int:
        b = self.ecfg.min_prefill_bucket
        while b < L:
            b *= 2
        return b

    def _page_copy(self):
        """Copy-on-write: rows ``0..n_rows-1`` of ``src_page`` duplicated
        into ``dst_page`` across every unit pool (one fused donated
        update; src/dst/n_rows are traced scalars, ONE executable)."""
        if self._copy is None:
            ps = self.ecfg.page_size
            tsubs = self._table_subs

            def copy_group(c, src, dst, n_rows):
                i = jnp.arange(ps)
                new = {}
                for key, pool in c.items():   # k/v/lat (+ scales in int8)
                    nu, P = pool.shape[0], pool.shape[1]
                    flat = pool.reshape(nu, P * ps, *pool.shape[3:])
                    vals = flat[:, src * ps + i]
                    rows = jnp.where(i < n_rows, dst * ps + i, P * ps)
                    flat = flat.at[:, rows].set(vals, mode="drop")
                    new[key] = flat.reshape(pool.shape)
                return new

            def page_copy(cache, src, dst, n_rows):
                units = {name: (copy_group(c, src, dst, n_rows)
                                if name in tsubs else c)
                         for name, c in cache["units"].items()}
                out = {**cache, "units": units}
                if "dense" in cache:
                    out["dense"] = copy_group(cache["dense"], src, dst,
                                              n_rows)
                return out

            self._copy = jax.jit(page_copy, donate_argnums=(0,))
        return self._copy

    def _suffix_step(self, args):
        if self._suffix is None:
            fn = jax.jit(make_prefill_suffix_step(
                self.cfg, self.step_cfg, self.rules), donate_argnums=(1,))
            self._suffix = fn.lower(*args).compile()
        return self._suffix

    def _prefill_suffix(self, slot: int, req: Request, m: int):
        """Chunked paged prefill of the uncached suffix ``prompt[m:]``:
        fixed-shape verify sweeps against the slot's (partly shared)
        pages, committed rows advancing ``pos`` in place.  Returns the
        logits row scoring the token after the prompt's last token, and
        each sweep's expert-row count (device scalars, or None)."""
        ecfg = self.ecfg
        L = req.prompt_len
        suffix = np.asarray(req.prompt[m:])
        qc = ecfg.prefill_chunk
        tok_shape = (ecfg.n_slots, qc) + suffix.shape[1:]
        logits, r, rows = None, 0, []
        for c0 in range(0, L - m, qc):
            r = min(qc, L - m - c0)
            tok = np.zeros(tok_shape, np.int32)
            tok[slot, :r] = suffix[c0:c0 + r]
            ncommit = np.zeros((ecfg.n_slots,), np.int32)
            ncommit[slot] = r
            pos = self._pos.copy()
            pos[slot] = m + c0
            self.cache = {**self.cache, "pos": jnp.asarray(pos),
                          "block_tables": jnp.asarray(self.kv.tables)}
            args = (self.params, self.cache, jnp.asarray(tok),
                    jnp.asarray(ncommit))
            logits, self.cache, n = self._suffix_step(args)(*args)
            rows.append(n)
        return logits[slot, r - 1], rows

    # -- join ----------------------------------------------------------------
    def _sweeps(self, L: int, m: int) -> int:
        """Chunked-prefill sweeps a join restoring ``m`` of ``L`` runs."""
        return -(-(L - m) // self.ecfg.prefill_chunk) if m > 0 else 0

    def _sample_first(self, logits_row, rid: int, rows):
        """Sample the prefill's token (greedy or temperature) — position
        prompt_len - 1 of the padded prefill logits — and fetch it with
        the join's expert-row counts (device scalars or None) in one
        transfer: (token, rows summed)."""
        if self.ecfg.greedy:
            nxt = jnp.argmax(logits_row, axis=-1)
        else:
            key = jax.random.fold_in(self._sample_key, (rid << 1) | 1)
            nxt = jax.random.categorical(
                key, logits_row / self.ecfg.temperature, axis=-1)
        nxt, rows = jax.device_get((nxt, rows))
        return (np.asarray(nxt, np.int32),
                sum(int(n) for n in rows if n is not None))

    def _join(self, slot: int, req: Request, m: int, copy, t0: float) -> int:
        """Join ``req`` into ``slot``; returns the expert rows its prefill
        computed."""
        L = req.prompt_len
        if L + req.max_new_tokens > self.ecfg.max_len:
            raise ValueError(f"request {req.rid}: prompt {L} + "
                             f"{req.max_new_tokens} new > max_len "
                             f"{self.ecfg.max_len}")
        if copy is not None:
            # CoW: the match ended inside a shared page — duplicate the
            # matched rows into the slot's private page before the suffix
            # prefill writes right behind them
            with self._span("page_copy"):
                self.cache = self._page_copy()(
                    self.cache, jnp.asarray(copy.src_page, jnp.int32),
                    jnp.asarray(copy.dst_page, jnp.int32),
                    jnp.asarray(copy.n_rows, jnp.int32))
                self.kv.copy_done(copy.src_page)
        if m > 0:
            # prefill ONLY the uncached suffix, through the paged verify
            # sweep (chunked, fixed-shape, in-place commit)
            with self._span("suffix_prefill") as s:
                logits_row, expert_rows = self._prefill_suffix(slot, req, m)
            if s is not None:
                s.counts["sweeps"] = self._sweeps(L, m)
        else:
            # cold prompt: classic bucketed prefill + page inject.  SSM
            # families prefill at the EXACT prompt length: attention caches
            # drop the bucket's pad rows at inject, but recurrent state is
            # a reduction over every fed token — pad tokens would poison
            # the state slots (costs one compile per distinct prompt
            # length instead of per bucket)
            bucket = L if self.cfg.uses_ssm else self._bucket(L)
            pad_shape = (1, bucket - L) + req.prompt.shape[1:]
            inputs = np.concatenate(
                [req.prompt[None], np.zeros(pad_shape, np.int32)], axis=1)
            with self._span("prefill"):
                logits, pcache, n = self._prefill(bucket)(
                    self.params, jnp.asarray(inputs))
                logits_row, expert_rows = logits[0, L - 1], [n]
            with self._span("inject"):
                rows = {k: jnp.asarray(v) for k, v in
                        self._inject_rows_tree(slot, bucket, L).items()}
                self.cache = self._inject(bucket)(
                    self.cache, pcache, rows, jnp.asarray(slot, jnp.int32))
        with self._span("sample_first"):     # the join's device sync
            first, n_rows = self._sample_first(logits_row, req.rid,
                                               expert_rows)
        self._report.expert_rows_prefill += n_rows
        self._pos[slot] = L
        if self._use_prefix:
            # index the prompt's (now fully written) pages for future joins
            with self._span("register_prefix"):
                self.kv.register_prefix(slot, np.asarray(req.prompt))
        if self._drafter is not None:
            self._drafter.seed_request(self._dstate, slot, req.prompt, first)
        state = self.scheduler.slots[slot]
        state.next_token = first
        state.tok_start = len(self._results[req.rid].tokens)
        res = self._results[req.rid]
        res.slot = slot
        if res.admit_step < 0:        # requeued joins keep first-admit stats
            res.admit_step = self._now
            res.admit_t = time.perf_counter() - t0
        res.prefill_tokens_saved += m
        self._report.prompt_tokens += L
        self._report.prefill_tokens_saved += m
        if self.on_prefill is not None:
            energy = self.on_prefill(L - m, m)
            if energy:
                self._report.energy_j += energy
                res.energy_j += energy
        res.tokens.append(first.tolist() if first.ndim else int(first))
        if req.eos_id is not None and first.ndim == 0 \
                and int(first) == req.eos_id:
            state.remaining = 0
            res.finish_reason = "eos"
        if state.remaining <= 0:                  # max_new 1, or instant EOS
            res.finish_reason = res.finish_reason or "max_new_tokens"
            res.finish_step = self._now
            res.finish_t = time.perf_counter() - t0
            self.scheduler.finish(slot)
            self._pos[slot] = 0
        return n_rows

    # -- preemption ----------------------------------------------------------
    def _preempt(self, slot: int, t0: float) -> None:
        """Evict ``slot`` on page pressure: re-queue its request with the
        tokens generated so far folded into the prompt (arrival = now),
        index its pages in the prefix cache (so the requeue mostly
        restores instead of recomputing), then free the slot."""
        state = self.scheduler.slots[slot]
        req = state.request
        res = self._results[req.rid]
        gen = np.asarray(res.tokens[state.tok_start:], np.int32)
        prompt = np.asarray(req.prompt, np.int32)
        if gen.size:
            prompt = np.concatenate([prompt, gen.reshape((-1,) +
                                                         prompt.shape[1:])])
        written = int(self._pos[slot])    # KV committed through written - 1
        if self._use_prefix:
            self.kv.register_prefix(slot, prompt[:written])
        new_req = dataclasses.replace(req, prompt=prompt,
                                      max_new_tokens=state.remaining,
                                      arrival_step=self._now)
        self.scheduler.finish(slot)
        self._pos[slot] = 0
        self._queue.push(new_req)
        res.n_preemptions += 1
        self._report.n_preemptions += 1

    def _grow_pages(self, t0: float, need: int | None = None) -> int:
        """Lazy-allocation mode: before a chunk, grow every active slot's
        pages to cover the chunk's writes, preempting the lowest-priority
        slot when the pool runs dry (``Scheduler.victim``: lowest
        priority, then most recently admitted).  Returns the pages grown
        on slots that stay."""
        ecfg = self.ecfg
        if need is None:
            need = ecfg.decode_chunk * (ecfg.spec_k + 1)
        slots = self.scheduler.slots
        order = sorted(self.scheduler.active_slots(),
                       key=lambda s: (-slots[s].request.priority,
                                      slots[s].seq))
        grown = 0
        for slot in order:
            if slots[slot] is None:
                continue                   # preempted earlier this pass
            # clamp the ask to the request's own context end: within-chunk
            # overrun past the budget writes scratch (contained), so pages
            # past ctx — or past the table width — are never needed
            req = slots[slot].request
            ctx = req.prompt_len + req.max_new_tokens - 1
            target = min(int(self._pos[slot]) + need, ctx, self.kv.max_len)
            had = len(self.kv.allocated[slot])
            while not self.kv.ensure(slot, target):
                victim = self.scheduler.victim()
                if victim == slot:
                    if self.scheduler.n_active <= 1:
                        raise RuntimeError(
                            f"request {slots[slot].request.rid}: page pool "
                            f"({self.kv.n_pages} pages) too small even at "
                            "zero concurrency; raise n_pages")
                    self._preempt(slot, t0)
                    break
                self._preempt(victim, t0)
            if slots[slot] is not None:
                grown += len(self.kv.allocated[slot]) - had
        return grown

    # -- harvest -------------------------------------------------------------
    def _harvest(self, toks: np.ndarray, t0: float) -> dict[int, int]:
        """Plain harvest — exactly the speculative harvest where every step
        yielded one token.  toks: (n_slots, chunk[, n_cb])."""
        counts = np.ones(toks.shape[:2], np.int32)
        return self._harvest_spec(toks[:, :, None], counts, t0)

    def _harvest_spec(self, toks: np.ndarray, counts: np.ndarray,
                      t0: float) -> dict[int, int]:
        """Append each active slot's kept tokens, finish on EOS / budget.

        Each step yields ``counts[slot, s]`` tokens (1 on the plain path;
        accepted drafts + the bonus token, 1..K+1, when speculating) —
        consumed in order at chunk granularity.  Returns kept (useful)
        token counts per request id for this chunk — the
        energy-attribution weights.  toks: (n_slots, steps, K+1[, n_cb])."""
        kept_by_rid: dict[int, int] = {}
        for slot in self.scheduler.active_slots():
            state = self.scheduler.slots[slot]
            req = state.request
            res = self._results[req.rid]
            kept = 0
            budget = state.remaining
            for s in range(toks.shape[1]):
                if res.finish_reason == "eos" or kept >= budget:
                    break
                for i in range(int(counts[slot, s])):
                    t = toks[slot, s, i]
                    res.tokens.append(t.tolist() if t.ndim else int(t))
                    kept += 1
                    if req.eos_id is not None and t.ndim == 0 \
                            and int(t) == req.eos_id:
                        res.finish_reason = "eos"
                        break
                    if kept >= budget:
                        break
            kept_by_rid[req.rid] = kept
            state.remaining = 0 if res.finish_reason == "eos" \
                else state.remaining - kept
            # the loop's carried token: last emitted token of the last step
            state.next_token = toks[slot, -1, max(int(counts[slot, -1]) - 1, 0)]
            if state.remaining == 0:
                res.finish_reason = res.finish_reason or "max_new_tokens"
                res.finish_step = self._now + self._eff_chunk
                res.finish_t = time.perf_counter() - t0
                self.scheduler.finish(slot)
                self._pos[slot] = 0
        return kept_by_rid

    # -- chaos + degradation -------------------------------------------------
    def degrade(self, level: int, *, steps: int, cap: float = 1.0) -> None:
        """Enter (or extend/deepen) a degradation window for ``steps``
        engine-clock steps.  Level 1 (derate) pauses admission; level 2
        (emergency cap) additionally halves the decode chunk and drops
        speculative K.  Called from fault injection and from the launcher
        when an ``EmergencyPower``/``NodeDerated`` event lands on the bus;
        the window clears itself when the clock passes its end."""
        self._degrade_level = max(self._degrade_level, int(level))
        self._degrade_until = max(self._degrade_until,
                                  self._now + max(int(steps), 1))
        if cap:
            self._cap_frac = min(self._cap_frac, float(cap))

    @property
    def degrade_level(self) -> int:
        return self._degrade_level

    def _apply_faults(self, t0: float) -> None:
        """Poll the injector on the decode-step clock and apply what came
        due.  ``engine_crash`` raises ``EngineCrash`` (the caller restores
        from the last snapshot); everything else is absorbed in place."""
        if self.injector is None:
            return
        for ev in self.injector.poll(self._now):
            self._report.n_faults_injected += 1
            if ev.kind == "engine_crash":
                raise EngineCrash(self._now)
            if ev.kind == "slot_crash":
                slot = int(ev.arg) % self.ecfg.n_slots
                if self.scheduler.slots[slot] is not None:
                    self._preempt(slot, t0)
                    self._report.requeued_requests += 1
            elif ev.kind == "page_corrupt":
                if corrupt_paged_kv(self.kv, self.injector.rng) is not None:
                    # audit + repair immediately: nothing may allocate on
                    # corrupted metadata
                    self.kv.verify_invariants(repair=True)
                    self._report.n_pages_quarantined = \
                        len(self.kv.quarantined)
            elif ev.kind == "stall":
                self._stall_until = self._now + \
                    max(ev.duration, self.ecfg.decode_chunk)
            elif ev.kind == "derate":
                self.degrade(1, steps=max(ev.duration, 1), cap=ev.arg)
            elif ev.kind == "emergency_cap":
                self.degrade(2, steps=max(ev.duration, 1), cap=ev.arg)
            elif self.on_fault is not None:
                self.on_fault(ev)     # bus_drop / bus_delay: launcher-owned

    # -- snapshot / restore --------------------------------------------------
    @staticmethod
    def _ser_req(req: Request) -> dict:
        p = np.asarray(req.prompt)
        return {"rid": req.rid, "prompt": p.tolist(), "dtype": str(p.dtype),
                "max_new_tokens": req.max_new_tokens,
                "arrival_step": req.arrival_step, "eos_id": req.eos_id,
                "priority": req.priority}

    @staticmethod
    def _de_req(rec: dict) -> Request:
        return Request(rid=int(rec["rid"]),
                       prompt=np.asarray(rec["prompt"], dtype=rec["dtype"]),
                       max_new_tokens=int(rec["max_new_tokens"]),
                       arrival_step=int(rec["arrival_step"]),
                       eos_id=rec["eos_id"], priority=int(rec["priority"]))

    def snapshot(self) -> dict:
        """Recoverable engine state as a checkpointable pytree: the device
        KV pools plus a JSON blob (uint8 leaf) holding the request queue,
        per-slot progress, results so far, report counters, and the
        paged-KV host metadata (block tables + trie).  Taken at chunk
        boundaries only, so every slot's KV is committed through its
        ``pos`` and the fold-into-prompt replay is exact."""
        slots = []
        for slot in self.scheduler.active_slots():
            state = self.scheduler.slots[slot]
            slots.append({"slot": slot,
                          "request": self._ser_req(state.request),
                          "remaining": int(state.remaining),
                          "tok_start": int(state.tok_start),
                          "written": int(self._pos[slot])})
        rep = {f.name: getattr(self._report, f.name)
               for f in dataclasses.fields(self._report)
               if f.name != "results"}
        meta = {"now": self._now, "chunk_idx": self._chunk_idx,
                "occ_sum": self._occ_sum,
                # an emergency-cap/derate window outlives the process that
                # crashed under it — the restored engine must stay degraded
                # until the window actually ends
                "degrade": {"level": self._degrade_level,
                            "until": self._degrade_until,
                            "cap": self._cap_frac,
                            "stall": self._stall_until},
                "req_order": self._req_order,
                "queue": [self._ser_req(r) for r in self._queue.pending()],
                "slots": slots,
                "results": {str(rid): dataclasses.asdict(res)
                            for rid, res in self._results.items()},
                "report": rep,
                "kv": self.kv.state_dict()}
        blob = np.frombuffer(json.dumps(meta).encode(), np.uint8).copy()
        return {"cache": self.cache, "meta": blob}

    def save_snapshot(self) -> None:
        if self._ckpt is None:
            raise ValueError("engine was built without snapshot_dir")
        with self._span("snapshot"):
            self._ckpt.save(self.snapshot(), self._now)

    @classmethod
    def restore(cls, cfg, engine_cfg: EngineConfig, params, snapshot_dir,
                *, step: int | None = None, **kwargs) -> "ServeEngine":
        """Rebuild an engine from the latest committed snapshot.

        The device pools and paged-KV metadata (incl. the prefix trie) come
        back verbatim — then every in-flight slot is converted into a
        requeued request with its generated tokens folded into the prompt
        (PR 5's preemption fold), so ``resume()`` re-admits it through
        ``admit_with_prefix`` against the restored trie: re-prefill is
        cheap and restored greedy streams are bit-identical to an
        uninterrupted run.  ``verify_invariants(repair=True)`` audits the
        restored pool, quarantining anything a crash corrupted."""
        eng = cls(cfg, engine_cfg, params, snapshot_dir=snapshot_dir,
                  **kwargs)
        like = {"cache": eng.cache, "meta": np.zeros((0,), np.uint8)}
        tree = restore_pytree(like, snapshot_dir, step)
        meta = json.loads(bytes(np.asarray(tree["meta"])))
        eng.cache = tree["cache"]
        eng.kv.load_state(meta["kv"])
        eng.kv.verify_invariants(repair=True)
        # transfer energy accrued before the crash is already inside the
        # restored report — only charge what happens from here on
        eng._transfer_seen = eng.kv.transfer_j
        eng._results = {int(rid): RequestResult(**rec)
                        for rid, rec in meta["results"].items()}
        eng._req_order = [int(r) for r in meta["req_order"]]
        eng._now = int(meta["now"])
        eng._chunk_idx = int(meta["chunk_idx"])
        eng._occ_sum = float(meta["occ_sum"])
        deg = meta["degrade"]
        eng._degrade_level = int(deg["level"])
        eng._degrade_until = int(deg["until"])
        eng._cap_frac = float(deg["cap"])
        eng._stall_until = int(deg["stall"])
        eng._report = EngineReport(results=[], **meta["report"])
        eng._report.n_restores += 1
        if eng.injector is not None:
            # the crash's own injection died with the process (snapshots
            # predate it) — the injector's log is authoritative
            eng._report.n_faults_injected = max(
                eng._report.n_faults_injected, eng.injector.n_injected)
            # derate/cap windows are EXTERNAL conditions: one that fired
            # after the last snapshot is one-shot (won't replay) but its
            # window may still be open — re-impose the remainder
            for ev in eng.injector.log:
                lvl = {"derate": 1, "emergency_cap": 2}.get(ev.kind)
                if lvl and ev.step + ev.duration > eng._now:
                    eng.degrade(lvl, steps=ev.step + ev.duration - eng._now,
                                cap=ev.arg)
        eng._report.n_pages_quarantined = len(eng.kv.quarantined)
        reqs = [eng._de_req(rec) for rec in meta["queue"]]
        for srec in meta["slots"]:
            req = eng._de_req(srec["request"])
            res = eng._results[req.rid]
            gen = np.asarray(res.tokens[int(srec["tok_start"]):], np.int32)
            prompt = np.asarray(req.prompt, np.int32)
            if gen.size:
                prompt = np.concatenate(
                    [prompt, gen.reshape((-1,) + prompt.shape[1:])])
            slot = int(srec["slot"])
            if eng._use_prefix and slot in eng.kv.allocated:
                # index the dead slot's written pages before releasing them
                # — the requeue then restores from the trie, not compute
                eng.kv.register_prefix(slot, prompt[:int(srec["written"])])
            reqs.append(dataclasses.replace(
                req, prompt=prompt, max_new_tokens=int(srec["remaining"]),
                arrival_step=eng._now))
            eng._report.requeued_requests += 1
        for slot in list(eng.kv.allocated):   # slots died with the process
            eng.kv.release(slot)
        eng._pos[:] = 0
        eng._queue = RequestQueue(reqs)
        return eng

    def resume(self) -> EngineReport:
        """Continue a restored engine to completion."""
        return self._drive()

    # -- main loop -----------------------------------------------------------
    def _begin(self, requests: list[Request]) -> None:
        self._queue = RequestQueue(requests)
        self._results = {r.rid: RequestResult(
            rid=r.rid, prompt_len=r.prompt_len, arrival_step=r.arrival_step,
            max_new_tokens=r.max_new_tokens) for r in requests}
        self._req_order = [r.rid for r in requests]
        self._now = 0
        self._chunk_idx = 0
        self._occ_sum = 0.0
        self._report = EngineReport(results=[], spec_k=self.ecfg.spec_k)
        self._transfer_seen = self.kv.transfer_j
        self._degrade_level = 0
        self._degrade_until = -1
        self._cap_frac = 1.0
        self._stall_until = -1
        self._eff_chunk = self.ecfg.decode_chunk

    def run(self, requests: list[Request]) -> EngineReport:
        self._begin(requests)
        if self._ckpt is not None and self.snapshot_every > 0:
            # step-0 snapshot: a crash BEFORE the first periodic save must
            # still restore (to the full queue), never lose the run
            self.save_snapshot()
        return self._drive()

    def _drive(self) -> EngineReport:
        ecfg = self.ecfg
        queue = self._queue
        report = self._report
        t0 = time.perf_counter()
        n_cb = self.cfg.n_codebooks
        tok_shape = (ecfg.n_slots, 1) + ((n_cb,) if n_cb else ())
        tok_in = np.zeros(tok_shape, np.int32)

        while len(queue) or self.scheduler.n_active:
            self._apply_faults(t0)           # may raise EngineCrash
            if self._degrade_level and self._now >= self._degrade_until:
                self._degrade_level = 0      # window cleared: full service
                self._cap_frac = 1.0
            degraded = self._degrade_level
            if not degraded:                 # degraded: admission paused
                with self._span("poll") as s:
                    joins = self.scheduler.poll(queue, self._now)
                if s is not None:
                    s.counts.update(admitted=len(joins),
                                    queue_depth=len(queue))
                for slot, req, m, copy in joins:
                    with self._span("join", req.rid) as s:
                        n_rows = self._join(slot, req, m, copy, t0)
                    if s is not None:
                        s.counts.update(prompt_tokens=req.prompt_len,
                                        restored=m,
                                        sweeps=self._sweeps(req.prompt_len,
                                                            m))
                        if self.cfg.uses_moe:
                            s.counts["expert_rows"] = n_rows
            # emergency cap: halve the decode chunk, drop speculation — the
            # chunk's compute shrinks instead of violating the cap
            eff_chunk = ecfg.decode_chunk if degraded < 2 \
                else max(ecfg.decode_chunk // 2, 1)
            spec = self._drafter is not None and degraded < 2
            eff_k = ecfg.spec_k if spec else 0
            self._eff_chunk = eff_chunk
            if ecfg.preempt:
                # grows/preempts but always leaves >= 1 slot active (the
                # last survivor raises rather than self-preempting)
                n_pre = report.n_preemptions
                with self._span("grow_pages") as s:
                    grown = self._grow_pages(t0, eff_chunk * (eff_k + 1))
                if s is not None:
                    s.counts.update(grown=grown,
                                    preempted=report.n_preemptions - n_pre)
            # joins may have paged prefixes back in, growth may have paged
            # cold pages out — fold the modelled transfer energy in now so
            # every ChunkStats-adjacent ledger read sees it
            self._sync_transfer()

            if self.scheduler.n_active == 0:
                if degraded:
                    # admission is paused and nothing is running: jump the
                    # clock to the window's end instead of spinning (or
                    # tripping the inadmissible-at-zero-load check below)
                    self._now = max(self._degrade_until, self._now + 1)
                    report.degraded_steps += eff_chunk
                    continue
                nxt = queue.next_arrival()
                if nxt is None:
                    break
                if nxt <= self._now:
                    # the head request is due but poll refused it on an EMPTY
                    # engine: pages/admission can never be satisfied — fail
                    # loudly instead of spinning the idle branch forever
                    raise RuntimeError(
                        f"request {queue.peek_ready(self._now).rid} not "
                        f"admissible at zero load (pool {self.kv.n_pages} "
                        "pages / admission hook); raise n_pages or the "
                        "power budget")
                self._now = nxt                   # idle: jump to next arrival
                continue

            sp = self.spans
            if sp is not None:
                sp.open("chunk")
                sp.open("upload")
            active = np.zeros((ecfg.n_slots,), np.int32)
            for slot in self.scheduler.active_slots():
                active[slot] = 1
                tok_in[slot, 0] = self.scheduler.slots[slot].next_token
            # sync host mirrors (membership may have changed since last chunk)
            self.cache = {**self.cache,
                          "pos": jnp.asarray(self._pos),
                          "block_tables": jnp.asarray(self.kv.tables)}
            args = [self.params, self.cache, jnp.asarray(tok_in),
                    jnp.asarray(active)]
            if spec:
                args.append({k: jnp.asarray(v)
                             for k, v in self._dstate.items()})
            if not ecfg.greedy:
                # even namespace: first-token keys live at (rid << 1) | 1
                args.append(jax.random.fold_in(self._sample_key,
                                               self._chunk_idx << 1))
            loop = self._chunk_loop(eff_chunk, spec, *args)
            # wall_s is exactly the dispatch and device_wait spans: the
            # three clock readings are shared
            t_c = time.perf_counter()
            if sp is not None:
                sp.switch("dispatch", t_c)
            rows = None
            if spec:
                toks, counts, self.cache, dstate = loop(*args)
            else:
                toks, self.cache, rows = loop(*args)
            t_d = time.perf_counter()
            if sp is not None:
                sp.switch("device_wait", t_d)
            toks, rows = jax.device_get((toks, rows))
            toks = np.asarray(toks)
            if spec:
                counts = np.asarray(counts)
                # np.array (not asarray): seed_row mutates this mirror on join
                self._dstate = {k: np.array(v) for k, v in dstate.items()}
            t_w = time.perf_counter()
            wall = t_w - t_c
            if sp is not None:
                sp.switch("harvest", t_w)

            n_active = int(active.sum())
            if spec:
                # device pos advanced by this chunk's per-slot emitted counts
                self._pos += counts.sum(axis=1).astype(np.int32)
                kept_by_rid = self._harvest_spec(toks, counts, t0)
                kept = sum(kept_by_rid.values())
                computed = n_active * eff_chunk * (eff_k + 1)
                proposed = n_active * eff_chunk * eff_k
                accepted = int(counts.sum()) - n_active * eff_chunk
            else:
                self._pos[active.astype(bool)] += eff_chunk
                kept_by_rid = self._harvest(toks, t0)
                kept = sum(kept_by_rid.values())
                computed = n_active * eff_chunk
                proposed = accepted = 0
            self._now += eff_chunk
            self._chunk_idx += 1
            if degraded:
                report.degraded_steps += eff_chunk
            if sp is not None:
                sp.switch("on_chunk")

            stats = ChunkStats(step=self._chunk_idx, wall_s=wall,
                               n_slots=ecfg.n_slots, n_active=n_active,
                               tokens_kept=kept, tokens_computed=computed,
                               drafts_proposed=proposed,
                               drafts_accepted=accepted,
                               clock_step=self._now,
                               degrade_level=degraded)
            energy = self.on_chunk(stats) if self.on_chunk is not None else None
            n_rows = {} if rows is None else {"expert_rows": int(rows)}
            report.expert_rows_decode += n_rows.get("expert_rows", 0)
            if sp is not None:
                sp.close()
                sp.close(n_active=n_active, tokens_kept=kept,
                         finished=n_active - self.scheduler.n_active,
                         **n_rows)
            report.n_chunks += 1
            report.decode_wall_s += wall
            report.tokens_kept += kept
            report.tokens_computed += stats.tokens_computed
            report.drafts_proposed += proposed
            report.drafts_accepted += accepted
            self._occ_sum += n_active / ecfg.n_slots
            if energy:
                report.energy_j += energy
                # charge occupied slots only, pro rata by kept tokens
                for rid, n in kept_by_rid.items():
                    if n > 0:
                        self._results[rid].energy_j += energy * n / max(kept, 1)
            if self.on_heartbeat is not None and self._now > self._stall_until:
                self.on_heartbeat(self._now, wall)
            if self._ckpt is not None and self.snapshot_every > 0 \
                    and self._chunk_idx % self.snapshot_every == 0:
                self.save_snapshot()

        # final poll: a fault due between the last chunk and run exit must
        # still fire (an engine_crash here restores + replays the tail —
        # results are only authoritative once this returns)
        self._apply_faults(t0)
        self._sync_transfer()
        report.n_demotions = self.kv.n_demotions
        report.n_promotions = self.kv.n_promotions
        report.occupancy = self._occ_sum / max(report.n_chunks, 1)
        report.results = [self._results[rid] for rid in self._req_order]
        return report
