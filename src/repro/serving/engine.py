"""Serving engine: continuous batching with batched prefill, async decode
and device-side routing capture.

The paper's system serves a single user; this engine generalizes to batched
requests while keeping the paper's structure visible, and makes the hot
loop production-shaped:

  * **Batched prefill** — every engine iteration admits *all* queued
    requests into free decode slots with ONE jit call: the full-batch
    prefill runs over a (max_batch, prefill_len) token matrix and the
    resulting caches are merged row-wise under an admit mask, so in-flight
    slots are untouched.  (``EngineConfig.batched_prefill=False`` restores
    the legacy one-jit-call-per-request scatter prefill as a reference /
    baseline mode.)
  * **Device-side routing capture** — the forward pass returns every MoE
    layer's actual top-k decision as an auxiliary output
    (``Model.prefill_routed`` / ``decode_step_routed``; see
    ``core/expert_parallel.moe_layer``), and ``LRUExpertTracker`` consumes
    those.  The decode hot loop performs **zero host-side router
    evaluations**; the paper's Table-1 statistic
    ``E[#exec experts/node/layer]`` is exact, not a layer-0 embedding
    proxy.
  * **Zero-copy hot loop** — every jit donates its cache operand
    (``EngineConfig.donate_buffers``), and the model updates the cache with
    ``dynamic_update_slice`` on a scan carry, so the donated buffer aliases
    in place: the steady-state decode step contains no full-cache-sized
    copy (the JAX analogue of the paper's C1 pre-allocated buffers;
    HLO-verified in tests/test_zero_copy.py).  Small decode batches
    additionally skip the fixed-capacity dispatch via the capacity-free
    fast path (``ModelConfig.gather_decode_max_tk``): a per-token
    expert-weight gather (core/moe.gather_moe) when T·K fits under
    E_local, or a one-hot dense compute when T is below the capacity
    floor — on those forms there is no round_capacity padding, no
    dispatch-plan argsort/scatter and no drops.  When T·K is under the
    threshold but neither form is cheaper (T·K > E_local and T at/above
    the capacity floor), the fixed-capacity dispatch still runs with its
    usual capacity semantics.
  * **Async stepping** — decode steps are dispatched without
    ``block_until_ready``; per-step tokens and routing stay on device in a
    pending buffer and the host syncs only at request-completion
    boundaries (or on ``flush()``), overlapping host scheduling with
    device compute.  Budget-based termination means doneness never depends
    on token *values*, so the host can run ahead freely.
    (``EngineConfig.async_steps=False`` syncs every step — reference
    mode.)

Other paper artifacts are unchanged: ``standby`` reproduces the keep-warm
summing touch (§4.2) and the tracker's LRU structure is the faithful L_R
host half.

  * **Unified token-budget step** (``EngineConfig.unified_step``, default
    on) — prefill and decode are ONE jit program
    (``Model.forward_routed``): every iteration packs the active decode
    rows *and* up to ``token_budget`` pending prefill-chunk tokens into a
    single (max_batch, chunk_len) block at per-row cache offsets.  Long
    prompts stream through the cache ``chunk_len`` tokens per iteration
    (no padding to ``prefill_len``, no truncation — prompts up to
    ``max_cache``), and admission never stalls in-flight decode rows: a
    decode slot advances one token every iteration regardless of how much
    prefill work is queued.  ``unified_step=False`` restores the
    two-program reference engine (padded whole-prompt prefill + one-token
    decode) for A/B token-equality and perf comparison.
  * **Per-request sampling** — ``Request.temperature`` / ``top_k`` are
    applied inside the jit step (greedy argmax when temperature=0, the
    default; otherwise per-row top-k Gumbel sampling with an RNG folded on
    (engine step, slot)).  Token-equality gates always run at
    temperature=0.
  * **Paged KV cache + prefix reuse** (``EngineConfig.paged``;
    docs/DESIGN.md §7) — the contiguous (max_batch, max_cache)
    slot-per-request cache is replaced by ONE donated page pool
    (num_pages, page_size, Hkv, hd) per layer plus per-row block tables:
    a request consumes only the pages its context needs, admission is
    gated on free pages (host-side free list + refcounts,
    serving/paging.py), and a radix prefix cache maps requests sharing a
    system prompt onto the same physical pages — their shared prefix is
    never re-prefilled (partial tail pages shared via copy-on-write).
    Token-for-token equal to the contiguous unified path under
    non-binding capacity; the donated paged program still contains no
    pool-sized copy (tests/test_zero_copy.py).

  * **Quantized weight store** (``ModelConfig.weight_quant``;
    docs/DESIGN.md §8) — params load as blockwise int8 / packed-int4
    ``QuantTensor`` leaves (payload + per-block fp32 scales as sibling
    arrays; router and embedding stay fp) via a one-time
    quantize-on-load pass, and every matmul site dequantizes through the
    ``core/quant.qdot`` policy point — the hot loop, donation, sharding
    and routing capture are representation-agnostic.
    ``memory_stats()`` reports the resulting device weight + KV pool
    bytes (int8 shrinks weights >= 3.5x, int4 >= 6x, at fp router).
    Correctness gate: token-identical to the fake-quant fp reference
    (tests/test_quant.py, CI perf-smoke).

Static-shape serving: the reference path right-pads requests to the slot
length; the unified path streams chunks through a fixed (max_batch,
chunk_len) block.  The scheduler packs arrivals into fixed decode slots
(continuous batching).

Batch-capacity semantics (``moe_strategy="dispatch"``): per-expert dispatch
capacity scales with the whole admitted batch, so requests batched together
share one capacity pool — garbage/inactive rows are dead-routed via a
``token_mask`` and consume none of it, but real rows can admit tokens a
batch-1 dispatch would have dropped.  Token-for-token equality between
batched and sequential prefill is therefore exact whenever capacity is not
binding (the engine's intended serving regime, and always for
``moe_strategy="dense"``); under capacity pressure the pooled dispatch is
the intended continuous-batching behaviour, not a bug.

Tracing (docs/DESIGN.md §12): every phase of a step runs inside a
``serving.<phase>`` span (``ServingEngine._span``) — a profiler
annotation on the trace's host clock whose host seconds also land in
``ServingEngine.step_times``, the engine's one timing source — and the
step program's operations carry ``jax.named_scope`` paths (``embed``,
``layer.*``, ``attn``, ``moe.*``, ``lm_head``, ``sample``).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import expert_parallel, quant
from repro.core.dynamic_load import LRUExpertTracker
from repro.models.model import build_model
from repro.serving import scheduler as sched
from repro.serving.faults import InjectedFault
from repro.serving.paging import PageAllocator, PrefixCache

Array = jax.Array

# Terminal request states: a request in one of these never transitions
# again (cancel() on it is a no-op returning False) and its pages are
# already released.
TERMINAL_STATES = ("done", "cancelled", "expired", "failed")


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 32
    # per-request sampling params (greedy when temperature == 0)
    temperature: float = 0.0
    top_k: int = 0                # 0 = no top-k cut (full vocab)
    # scheduling class (docs/DESIGN.md §10): higher admits first; ties
    # admit FIFO.  deadline_s is an absolute time.perf_counter() stamp
    # past which the request is expired instead of served.
    priority: int = 0
    deadline_s: float | None = None
    # filled by the engine
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    submit_s: float = 0.0         # wall clock at submit()
    first_token_s: float | None = None  # wall clock when token 1 harvested
    # scheduler state (docs/DESIGN.md §10): queued -> running <->
    # preempted -> done | cancelled | expired | failed
    status: str = "queued"
    seq: int = 0                  # submission order; kept across preemption
    preemptions: int = 0          # times this request lost its slot
    last_preempt_epoch: int = 0   # engine epoch of the last preemption
    # virtual prompt at re-admission: original prompt + every token
    # generated before the preemption (its cache pages live in the
    # prefix tree, so restore re-prefills at most one partial chunk)
    resume_tokens: np.ndarray | None = None
    nan_retries: int = 0          # consecutive quarantined steps


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_batch: int = 8            # decode slots
    prefill_len: int = 128        # reference mode: prompts padded to this
    max_cache: int = 256          # KV/state cache length
    track_experts: bool = True
    batched_prefill: bool = True  # False: legacy per-request prefill
    async_steps: bool = True      # False: block_until_ready every step
    # Unified token-budget forward pass (the production path): prefill and
    # decode share ONE jit program (Model.forward_routed); each iteration
    # packs active decode rows plus pending prefill chunks into a single
    # (max_batch, chunk_len) block at per-row cache offsets.  Prompts of
    # any length up to max_cache stream through the cache chunk_len tokens
    # per iteration — no prefill_len padding/truncation, and admission
    # never stalls decode.  False restores the two-program reference
    # engine (whole-prompt padded prefill + one-token decode) for A/B
    # token-equality and perf comparison.  Families without a unified
    # forward (ssm/hybrid/vlm/audio) silently fall back to the reference
    # path.
    unified_step: bool = True
    chunk_len: int = 32           # unified block width / prefill chunk size
    # Per-iteration cap on scheduled prefill tokens (0 = unlimited).
    # Decode rows are exempt: they always advance.  The budget throttles
    # how much prefill work shares an iteration with decode, bounding the
    # per-iteration latency a decode token can see.
    token_budget: int = 0
    sample_seed: int = 0          # RNG seed for stochastic decode
    # Paged KV cache (docs/DESIGN.md §7): replace the contiguous
    # (max_batch, max_cache) slot-per-request cache with ONE donated page
    # pool (num_pages, page_size, Hkv, hd) per layer plus per-row block
    # tables.  A request consumes ceil((prompt + max_new_tokens - 1) /
    # page_size) pages instead of reserving max_cache slots, admission is
    # gated on FREE PAGES (a host-side free list + refcounts,
    # serving/paging.PageAllocator), and a radix prefix cache maps
    # requests sharing a system prompt onto the same physical pages so
    # the shared prefix's prefill is skipped entirely (partial tail pages
    # shared via copy-on-write).  Requires the unified scheduler (paged
    # mode streams chunks; ring-cache archs keep the reference path).
    # Token-for-token equal to the contiguous unified path under
    # non-binding capacity (tests/test_paged_cache.py + CI perf-smoke).
    paged: bool = False
    page_size: int = 16           # tokens per page
    # Pool size in pages; 0 = auto (max_batch * ceil(max_cache /
    # page_size) — the same token capacity as the contiguous layout, so
    # paged-vs-contiguous A/Bs run at equal pool bytes).
    num_pages: int = 0
    # Overcommit the page pool (docs/DESIGN.md §10; requires paged):
    # admission allocates only the pages the CONTEXT needs (lazy decode
    # growth takes one page at a time as rows advance) instead of the
    # whole ceil((prompt + max_new - 1) / page_size) lifetime, so more
    # requests run concurrently at equal pool bytes.  When growth or a
    # higher-priority admission finds the pool short, a low-priority
    # row is PREEMPTED: its pages move into the prefix tree, the
    # request is requeued, and restore is a block-table remap plus at
    # most one partial-tail re-prefill chunk — greedy token streams are
    # identical to the unpreempted run (tests/test_resilience.py).
    # False keeps PR4's conservative whole-lifetime admission: an
    # admitted request can never hit pool OOM mid-generation.
    overcommit: bool = False
    # Paged-attention Pallas kernel (kernels/paged_attn.py; requires
    # paged): attention walks the block table page by page in VMEM —
    # online softmax, grouped GQA, in-kernel int8 dequant — instead of
    # gathering each row's pages into a (B, NB*page_size, Hkv, hd)
    # virtual cache.  Attention reads then scale with row lengths, not
    # pool size (docs/DESIGN.md §11); the gather path stays as the
    # reference (token-identical under greedy, gated in CI perf-smoke
    # and the chaos matrix).  Single-host only: mesh-sharded serving
    # keeps the gather path, whose XLA ops shard under GSPMD.
    paged_kernel: bool = False
    # NaN/Inf logit quarantine (serving/faults.py): when on, every
    # unified step reads back the jit's per-row finiteness flag
    # (_quarantine_check — a deliberate per-step device sync, the same
    # opt-in trade as async_steps=False) and withholds the host-state
    # advance of any non-finite row so it retries from its last durable
    # cache state.  None = auto: enabled iff a fault plan is installed.
    nan_guard: bool | None = None
    # consecutive non-finite steps before a quarantined row is failed
    nan_retry_limit: int = 3
    # Donate the cache operand of every jit in the hot loop (the JAX
    # analogue of the paper's C1 pre-allocated buffers): the model updates
    # the cache with dynamic_update_slice on a scan *carry*
    # (transformer._scan_stack_with_cache), so the donated buffer aliases in
    # place and the steady-state decode step performs no full-cache-sized
    # copy (HLO-verified in tests/test_zero_copy.py).  False restores the
    # copy-per-step baseline for A/B measurement.  Values are unaffected
    # either way; only ``last_tok``/routing stay undonated because async
    # mode's pending harvest buffer still references them after dispatch.
    donate_buffers: bool = True


@dataclasses.dataclass(frozen=True)
class _Pending:
    """One dispatched-but-unharvested device step.

    ``rows`` binds batch rows to their requests *at dispatch time* (slots
    may be re-assigned before the harvest sync).  ``tok`` is the post-step
    (B,) last-token vector; ``routing`` the (L, T, K) device capture (None
    for dense archs / disabled tracking).  ``routing_batch`` is the batch
    size of the dispatched call (1 for the legacy batch-1 prefill, whose
    capture row is always 0).  ``obs_rows`` lists the batch rows whose
    routing capture should feed the tracker (unified mixed batches observe
    mid-prefill rows that sample no token); None = the rows of ``rows``.
    ``stalled`` marks reference-mode prefill dispatched while decode rows
    were in flight (its device time is decode-stall time)."""
    kind: str                     # "prefill" | "decode" | "mixed"
    rows: tuple                   # ((row_in_routing, slot, Request), ...)
    tok: Any
    routing: Any
    routing_batch: int
    obs_rows: tuple | None = None
    stalled: bool = False


class _Span:
    """One ``serving.<name>`` span of a step (see ``ServingEngine._span``).
    ``seconds`` holds its host time once it has closed."""
    __slots__ = ("engine", "name", "stats", "seconds", "_ann", "_t0")

    def __init__(self, engine, name: str, stats: dict):
        self.engine = engine
        self.name = name
        self.stats = stats
        self.seconds = 0.0

    def __enter__(self):
        self._ann = jax.profiler.TraceAnnotation(
            f"serving.{self.name}", iter=self.engine._iter, **self.stats)
        self._ann.__enter__()
        self.engine._span_depth += 1
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        eng = self.engine
        eng._span_depth -= 1
        eng.step_times[self.name] = eng.step_times.get(self.name, 0.0) + dt
        if eng._span_depth == 0 and self.name != "sync":
            # host time the device waits on: every outermost span but the
            # block on the device itself
            eng.stats["host_ns"] += int(dt * 1e9)
        return False


class ServingEngine:
    """Continuous-batching engine over the pure-functional Model API."""

    def __init__(self, cfg_model, engine_cfg: EngineConfig | None = None,
                 params=None, rng=None, mesh=None, fault_plan=None):
        self.cfg = cfg_model
        self.ecfg = engine_cfg or EngineConfig()
        self.mesh = mesh
        self.model = build_model(cfg_model)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.params = params if params is not None else self.model.init(rng)
        # quantize-on-load (docs/DESIGN.md §8): convert eligible weight
        # kinds to blockwise QuantTensor leaves BEFORE device placement —
        # the one-time preprocessing step of the weight store (idempotent:
        # params restored from an already-quantized checkpoint pass
        # through untouched; weight_quant="none" is the identity)
        if getattr(cfg_model, "weight_quant", "none") != "none":
            self.params = quant.quantize_params(self.params, cfg_model)
        if mesh is not None:
            from repro.launch import sharding as sharding_lib
            spec = sharding_lib.params_pspec(cfg_model, mesh, self.params,
                                             mode="serve")
            self.params = jax.device_put(
                self.params, sharding_lib.named(mesh, spec))
        self.tracker = (LRUExpertTracker(cfg_model.num_layers,
                                         cfg_model.num_experts)
                        if cfg_model.is_moe and self.ecfg.track_experts
                        else None)
        self.queue = sched.AdmissionQueue()
        self.slots: list[Request | None] = [None] * self.ecfg.max_batch
        # per-slot admission context: the token sequence the occupant is
        # prefilling against — req.prompt on first admission, the longer
        # resume_tokens (prompt + pre-preemption generation) on restore
        self.slot_ctx: list[np.ndarray | None] = [None] * self.ecfg.max_batch
        self._all: dict[int, Request] = {}
        self._uid = 0
        self._seq = 0                 # submission sequence for FIFO ties
        self._iter = 0                # step() count; fault-plan step key
        # host seconds of each serving.* span in the current step() (by
        # span name, summed over repeats; cleared as each step() starts)
        self.step_times: dict[str, float] = {}
        self._span_depth = 0
        self._has_deadlines = False   # skip the sweep until one exists
        self._preempt_epoch = 0       # bumps per preemption (fairness key)
        self.preempt_log: list = []   # (iter, uid, running-snapshot) tuples
        self.faults = fault_plan
        if fault_plan is not None and not (self.ecfg.unified_step):
            raise ValueError("fault injection requires the unified engine "
                             "path (unified_step=True)")
        self._guard = (self.ecfg.nan_guard if self.ecfg.nan_guard is not None
                       else fault_plan is not None)
        if self.ecfg.overcommit and not self.ecfg.paged:
            raise ValueError("overcommit requires the paged KV cache "
                             "(EngineConfig.paged=True)")
        b, c = self.ecfg.max_batch, self.ecfg.max_cache
        self.lengths = np.zeros((b,), np.int32)
        self.budgets = np.zeros((b,), np.int32)
        self.last_tok = jnp.zeros((b,), jnp.int32)
        # resilience scratch: the all-clear poison vector (finite = no
        # injection) and the no-guard quarantine answer, built once so
        # the fault-free hot loop allocates nothing per step
        self._poison0 = np.zeros((b,), np.float32)
        self._no_bad = np.zeros((b,), bool)
        self._pending: list[_Pending] = []
        # unified-step scheduler state: per-slot prefill progress (prompt
        # tokens already streamed into the cache) and sampling params
        if self.ecfg.chunk_len < 1 or self.ecfg.token_budget < 0:
            raise ValueError(
                f"chunk_len must be >= 1 and token_budget >= 0, got "
                f"chunk_len={self.ecfg.chunk_len} "
                f"token_budget={self.ecfg.token_budget}")
        # the unified block step needs a token-input attention family and a
        # LINEAR cache: a ring cache (sliding window == cache length) only
        # takes width-1 writes (attention.attn_block_step), so sliding-
        # window archs keep the two-program reference path
        from repro.models.transformer import effective_window
        win = (effective_window(cfg_model, self.ecfg.max_cache)
               if cfg_model.family in ("dense", "moe") else None)
        # transformer.stack_cache_spec clips the cache to the window, so
        # any window <= max_cache means the allocated cache is a ring
        ring = win is not None and win <= self.ecfg.max_cache
        self.unified = (self.ecfg.unified_step and not ring
                        and cfg_model.family in ("dense", "moe"))
        # block width: a chunk can never exceed the cache it streams into
        self.chunk_len = min(self.ecfg.chunk_len, self.ecfg.max_cache)
        # paged KV cache state (EngineConfig.paged; docs/DESIGN.md §7):
        # one donated page pool + host-side allocator / prefix tree /
        # per-slot block tables.  The pool replaces the per-slot cache.
        self.paged = bool(self.ecfg.paged)
        if self.paged:
            if not self.unified:
                raise ValueError(
                    "paged KV cache requires the unified engine path "
                    "(token-input attention family, non-ring cache, "
                    "unified_step=True)")
            if self.ecfg.page_size < 1:
                raise ValueError(
                    f"page_size must be >= 1, got {self.ecfg.page_size}")
            if self.ecfg.paged_kernel and mesh is not None:
                raise ValueError(
                    "paged_kernel is single-host: mesh-sharded serving "
                    "keeps the gather reference path (docs/DESIGN.md §11)")
            self.page_size = self.ecfg.page_size
            self.max_blocks = -(-c // self.page_size)
            self.num_pages = (self.ecfg.num_pages
                              or b * self.max_blocks)
            self.cache = self.model.init_paged_cache(self.num_pages,
                                                     self.page_size)
            self.block_tables = np.zeros((b, self.max_blocks), np.int32)
            self.allocator = PageAllocator(self.num_pages)
            self.prefix = PrefixCache(self.page_size, self.allocator)
            self.slot_pages: list[list[int]] = [[] for _ in range(b)]
            self._jit_copy_pages = jax.jit(
                self._copy_pages,
                donate_argnums=(0,) if self.ecfg.donate_buffers else ())
        elif self.ecfg.paged_kernel:
            raise ValueError(
                "paged_kernel requires paged=True (it attends through "
                "the page pool's block tables)")
        else:
            self.cache = self.model.init_cache(b, c)
        self.prefill_pos = np.zeros((b,), np.int64)
        self.temps = np.zeros((b,), np.float32)
        self.topks = np.zeros((b,), np.int32)
        self._sample_key = jax.random.PRNGKey(self.ecfg.sample_seed)
        self._step_idx = 0
        self._admit_stalled = False
        # Retrace accounting (analysis rule R3): each jit body bumps its
        # counter at TRACE time, so this Counter records how many programs
        # XLA specialized since engine birth.  The documented steady-state
        # set: unified traces at widths chunk_len and 1 (the pure-decode
        # block), reference mode traces prefill once and decode once, paged
        # mode adds one copy_pages trace, and flipping the static sampling
        # flag doubles each — anything beyond that is a silent recompile
        # eating dispatch latency.  Note ``.lower()`` on a jit also traces.
        self.trace_counts: collections.Counter = collections.Counter()
        # cache is argument 1 of every jit body; self.cache is rebound to the
        # output before the next dispatch, so donating it is always safe.
        donate = (1,) if self.ecfg.donate_buffers else ()
        # the trailing ``sampling`` flag is STATIC: greedy-only workloads
        # trace a pure-argmax program; the first stochastic submit() flips
        # the flag and retraces once with the Gumbel/top-k sampler inlined
        self._jit_prefill_batch = jax.jit(self._prefill_batch,
                                          donate_argnums=donate,
                                          static_argnums=(8,))
        self._jit_prefill_one = jax.jit(self._prefill_one,
                                        donate_argnums=donate,
                                        static_argnums=(8,))
        self._jit_decode = jax.jit(self._decode, donate_argnums=donate,
                                   static_argnums=(8,))
        self._jit_unified = jax.jit(self._unified, donate_argnums=donate,
                                    static_argnums=(13,))
        self._sampling = False
        self._grouped_blocks: dict[tuple[int, int], bool] = {}
        self.stats = {"prefill_tokens": 0, "prefill_pad_tokens": 0,
                      "decode_steps": 0, "decode_tokens": 0,
                      # per-phase token counts of MIXED iterations only —
                      # throughput() apportions mixed_s by their share
                      # (satellite fix: mixed time was double-counted in
                      # both per-phase denominators)
                      "mixed_prefill_tokens": 0, "mixed_decode_tokens": 0,
                      "prefill_s": 0.0, "decode_s": 0.0, "mixed_s": 0.0,
                      "stall_s": 0.0,
                      # device programs dispatched, and host ns spent in
                      # the outermost serving.* spans but serving.sync
                      # (_Span): the host's share of each step
                      "dispatches": 0, "host_ns": 0,
                      # dispatched programs whose MoE layers run the
                      # dropless grouped form (core/moe.grouped_moe)
                      "grouped_moe_steps": 0,
                      # paged-mode counters (0 when paged=False)
                      "prefix_lookups": 0, "prefix_hits": 0,
                      "prefix_hit_tokens": 0, "cow_copies": 0,
                      "pages_hwm": 0,
                      # resilience counters (docs/DESIGN.md §10)
                      "preemptions": 0, "restores": 0,
                      "restore_hit_tokens": 0, "cancelled": 0,
                      "expired": 0, "failed": 0,
                      "alloc_stalls": 0, "dispatch_failures": 0,
                      "nan_quarantines": 0, "active_hwm": 0}

    # -- jit bodies ---------------------------------------------------------

    def _sample_next(self, logits: Array, temps: Array, topks: Array,
                     step_idx: Array, sampling: bool) -> Array:
        """Per-row sampling inside the jit step: greedy argmax where
        temperature == 0 (the default, keeping every token-equality gate
        exact), otherwise temperature-scaled top-k Gumbel sampling with an
        RNG folded on (engine step, slot) so replays with the same
        ``sample_seed`` are deterministic.

        ``sampling`` is a TRACE-TIME flag (static jit argument): it stays
        False until the first stochastic request is submitted, so purely
        greedy workloads never trace the (B, V) sort / Gumbel draws into
        the hot loop — the all-greedy program is pure argmax.

        logits: (B, V_padded) fp32; temps: (B,) fp32; topks: (B,) int32
        (0 = full vocab); step_idx: () int32."""
        v = self.cfg.vocab_size
        logits = logits[:, :v].astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not sampling:
            return greedy
        b = logits.shape[0]
        key = jax.random.fold_in(self._sample_key, step_idx)
        keys = jax.vmap(jax.random.fold_in, (None, 0))(key, jnp.arange(b))
        gum = jax.vmap(lambda k: jax.random.gumbel(k, (v,), jnp.float32))(keys)
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        kth = jnp.take_along_axis(
            -jnp.sort(-scaled, axis=-1),                  # descending sort
            (jnp.clip(topks, 1, v) - 1)[:, None], axis=-1)
        keep = (scaled >= kth) | (topks[:, None] <= 0)
        samp = jnp.argmax(jnp.where(keep, scaled, -1e30) + gum,
                          axis=-1).astype(jnp.int32)
        return jnp.where(temps > 0, samp, greedy)

    def _unified(self, params, cache, tokens, last_tok, lengths, seg_lens,
                 block_tables, is_decode, sample_mask, temps, topks,
                 poison, step_idx, sampling):
        """ONE jit program for prefill chunks, decode rows, and any mix.

        tokens: (B, chunk_len) host-scheduled block — decode rows take their
        input token from device-resident ``last_tok`` instead (column 0), so
        the decode feedback loop never syncs to the host.  ``seg_lens``
        gives each row's valid-token count at cache offset ``lengths``;
        ``sample_mask`` marks rows whose last valid logit becomes a
        generated token (decode rows and final prefill chunks — mid-prompt
        chunks keep ``last_tok`` untouched).  ``block_tables`` is None on
        the contiguous cache and the (B, max_blocks) page map on the paged
        pool (an undonated host snapshot, like ``lengths``).

        ``poison`` is the fault-injection vector (serving/faults.py): a
        (B,) fp32 whose non-finite entries overwrite that row's logits
        (finite entries — the steady state — inject nothing; the vector is
        a runtime value, so injection never retraces).  The step always
        returns a per-row ``bad`` finiteness flag and refuses to let a
        non-finite row overwrite ``last_tok`` — the device half of the
        NaN quarantine, active whether or not the host guard reads it.

        Returns (last_tok', cache', routing (L, B*chunk_len, K),
        bad (B,) bool)."""
        self.trace_counts["unified"] += 1
        with jax.named_scope("embed"):
            tok0 = jnp.where(is_decode, last_tok, tokens[:, 0])
            tokens = jnp.concatenate([tok0[:, None], tokens[:, 1:]], axis=1)
        # context_len pins the windowing decision to the LOGICAL context
        # (max_cache) in both layouts: the paged pool's block-table reach
        # rounds up to whole pages, and letting effective_window() see the
        # rounded value could flip the long-context SWA variant on in
        # paged mode but not contiguous — breaking token equality exactly
        # at ragged page sizes
        logits, cache, routing = self.model.forward_routed(
            params, {"tokens": tokens, "lengths": lengths,
                     "seg_lens": seg_lens, "block_tables": block_tables},
            cache, self.mesh, context_len=self.ecfg.max_cache,
            paged_kernel=self.ecfg.paged_kernel)
        with jax.named_scope("sample"):
            logits = jnp.where(jnp.isfinite(poison)[:, None], logits,
                               poison[:, None].astype(logits.dtype))
            bad = ~jnp.all(jnp.isfinite(
                logits[:, :self.cfg.vocab_size].astype(jnp.float32)), axis=-1)
            nxt = self._sample_next(logits, temps, topks, step_idx, sampling)
            last_tok = jnp.where(sample_mask & ~bad, nxt, last_tok)
        return last_tok, cache, routing, bad

    def _copy_pages(self, cache, src, dst):
        """Device half of copy-on-write (serving/paging): duplicate pool
        pages ``src`` into ``dst`` across every layer and cache leaf.  The
        copy moves ``n * page_size`` rows — page-sized traffic, never a
        pool-sized buffer — and the pool stays donated/aliased."""
        self.trace_counts["copy_pages"] += 1
        return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), cache)

    def _prefill_batch(self, params, cache, tokens, admit_mask, last_tok,
                       temps, topks, step_idx, sampling):
        """Admit up to max_batch requests in ONE call.

        tokens: (B, prefill_len) — zeros on non-admitted rows;
        admit_mask: (B,) bool.  The full-batch prefill recomputes every row
        (static shapes, one XLA program); the cache is then merged row-wise
        so in-flight slots keep their state.  Returns (last_tok', cache',
        routing) with last_tok' holding each admitted row's first sampled
        token."""
        self.trace_counts["prefill_batch"] += 1
        tmask = jnp.broadcast_to(admit_mask[:, None], tokens.shape)
        logits, new_cache, routing = self.model.prefill_routed(
            params, {"tokens": tokens, "token_mask": tmask}, cache, self.mesh)
        nxt = self._sample_next(logits[:, -1], temps, topks, step_idx,
                                sampling)

        def merge(old, new):
            if old.ndim < 2:      # scalar bookkeeping leaves, if any
                return new
            m = admit_mask.reshape((1, old.shape[1]) + (1,) * (old.ndim - 2))
            return jnp.where(m, new, old)

        cache = jax.tree.map(merge, cache, new_cache)
        last_tok = jnp.where(admit_mask, nxt, last_tok)
        return last_tok, cache, routing

    def _prefill_one(self, params, cache, tokens, slot, last_tok,
                     temps, topks, step_idx, sampling):
        """Legacy reference path: batch-1 prefill scattered into ``slot``.

        The batch-1 working cache is *sliced* out of the full cache rather
        than zero-materialized: the old ``jnp.zeros`` + scatter pattern
        allocated a fresh per-slot cache copy every admit, while the slice
        reads one row and (under donation) scatters it back in place.
        Prefill overwrites the whole prompt region and decode masks by
        ``lengths``, so any stale tail beyond the prompt is never attended —
        the same invariant the batched path relies on when it recomputes
        in-flight rows under the admit mask."""
        self.trace_counts["prefill_one"] += 1
        one_cache = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1)
            if a.ndim >= 2 else a, cache)
        logits, one_cache, routing = self.model.prefill_routed(
            params, {"tokens": tokens}, one_cache, self.mesh)
        cache = jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_index_in_dim(
                full, one[:, 0], slot, axis=1), cache, one_cache)
        nxt = self._sample_next(logits[:, -1], jnp.take(temps, slot)[None],
                                jnp.take(topks, slot)[None], step_idx,
                                sampling)  # (1,)
        last_tok = jax.lax.dynamic_update_index_in_dim(
            last_tok, nxt[0], slot, axis=0)
        return last_tok, cache, routing

    def _decode(self, params, cache, last_tok, lengths, active_mask,
                temps, topks, step_idx, sampling):
        self.trace_counts["decode"] += 1
        logits, cache, routing = self.model.decode_step_routed(
            params, cache, {"tokens": last_tok[:, None], "lengths": lengths,
                            "token_mask": active_mask[:, None]},
            self.mesh)
        nxt = self._sample_next(logits[:, -1], temps, topks, step_idx,
                                sampling)
        last_tok = jnp.where(active_mask, nxt, last_tok)
        return last_tok, cache, routing

    # -- public API ---------------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0,
               priority: int = 0, deadline_ms: float | None = None) -> int:
        """Queue a request.  ``temperature``/``top_k`` select per-request
        sampling inside the jit step (greedy when temperature=0).

        ``priority`` orders admission (higher first, FIFO within a class;
        under ``EngineConfig.overcommit`` a higher-priority arrival may
        preempt strictly-lower-priority running rows).  ``deadline_ms``
        is a wall-clock budget from submit: a request still unfinished
        when it elapses is expired and its pages released.

        Prompt-length contract: the unified engine streams prompts through
        the cache in chunks, so anything up to ``max_cache`` is served
        without truncation; the reference (``unified_step=False``) path
        pads whole prompts to ``prefill_len`` and REJECTS longer ones
        instead of silently dropping the prefix (the seed engine's
        behaviour)."""
        prompt = np.asarray(prompt, np.int32)
        if len(prompt) == 0:
            # a zero-length prompt has no defined context: the unified
            # scheduler would classify it as a decode row seeded from the
            # slot's STALE last_tok (the previous occupant's final token)
            raise ValueError("empty prompt")
        limit = (self.ecfg.max_cache if self.unified
                 else self.ecfg.prefill_len)
        if len(prompt) > limit:
            mode = "unified" if self.unified else "reference"
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the {mode} "
                f"engine's limit of {limit} "
                f"({'max_cache' if self.unified else 'prefill_len'}); "
                f"refusing to silently truncate")
        # decode step i writes generated token i at slot context+i; past
        # max_cache those writes clamp/drop and later tokens are generated
        # against a context missing their predecessors — reject instead of
        # silently corrupting.  The reference path always decodes from
        # offset prefill_len (the padded program), the unified path from
        # the real prompt length.
        context = len(prompt) if self.unified else self.ecfg.prefill_len
        if context + max_new_tokens - 1 > self.ecfg.max_cache:
            raise ValueError(
                f"context of {context} tokens + {max_new_tokens} new "
                f"tokens does not fit the {self.ecfg.max_cache}-slot cache; "
                f"lower max_new_tokens or raise max_cache")
        if self.paged:
            blocks = -(-(context + max_new_tokens - 1) // self.page_size)
            if blocks > self.num_pages:
                raise ValueError(
                    f"request needs {blocks} pages but the pool holds only "
                    f"{self.num_pages}; raise num_pages or lower "
                    f"max_new_tokens")
        self._uid += 1
        self._seq += 1
        if temperature > 0:
            self._sampling = True    # one-time retrace with the sampler
        now = time.perf_counter()
        req = Request(self._uid, prompt, max_new_tokens,
                      temperature=float(temperature), top_k=int(top_k),
                      priority=int(priority), submit_s=now, seq=self._seq,
                      deadline_s=(now + deadline_ms / 1e3
                                  if deadline_ms is not None else None))
        if req.deadline_s is not None:
            self._has_deadlines = True
        self.queue.append(req)
        self._all[req.uid] = req
        return self._uid

    def _pad_prompt(self, req: Request) -> np.ndarray:
        assert len(req.prompt) <= self.ecfg.prefill_len  # enforced at submit
        pad = np.zeros((self.ecfg.prefill_len,), np.int32)
        pad[:len(req.prompt)] = req.prompt
        return pad

    def _admit(self) -> None:
        free = [i for i, r in enumerate(self.slots) if r is None]
        if not free or not self.queue:
            return
        # reference-mode stall: any in-flight decode slot sits idle for the
        # whole separate prefill program (the unified path has no such
        # window — decode rows ride every iteration)
        self._admit_stalled = any(r is not None for r in self.slots)
        if self.ecfg.batched_prefill:
            self._admit_batched(free)
        else:
            self._admit_sequential(free)

    def _post_admit(self, rows, routing, routing_batch: int) -> None:
        for _, slot, req in rows:
            self.slots[slot] = req
            self.slot_ctx[slot] = req.prompt
            req.status = "running"
            self.lengths[slot] = self.ecfg.prefill_len
            self.budgets[slot] = req.max_new_tokens - 1
            # real prompt tokens vs the padding the fixed-length program
            # recomputes anyway (satellite fix: tok/s counts real work)
            self.stats["prefill_tokens"] += len(req.prompt)
            self.stats["prefill_pad_tokens"] += (self.ecfg.prefill_len
                                                 - len(req.prompt))
        self._pending.append(_Pending("prefill", tuple(rows), self.last_tok,
                                      routing, routing_batch,
                                      stalled=self._admit_stalled))
        if not self.ecfg.async_steps:
            self._harvest()

    def _admit_batched(self, free: list[int]) -> None:
        rows = []
        tokens = np.zeros((self.ecfg.max_batch, self.ecfg.prefill_len),
                          np.int32)
        admit = np.zeros((self.ecfg.max_batch,), bool)
        for slot in free:
            if not self.queue:
                break
            req = self.queue.popleft()
            tokens[slot] = self._pad_prompt(req)
            admit[slot] = True
            self.temps[slot] = req.temperature
            self.topks[slot] = req.top_k
            rows.append((slot, slot, req))
        with self._span("dispatch", kind="prefill") as sp:
            step_idx = self._next_step_idx()
            # tokens/admit are freshly built per call and never mutated
            # after dispatch (see the transfer note in step())
            self.last_tok, self.cache, routing = self._jit_prefill_batch(
                self.params, self.cache, jnp.asarray(tokens),
                jnp.asarray(admit), self.last_tok,
                jnp.asarray(self.temps.copy()),
                jnp.asarray(self.topks.copy()), step_idx, self._sampling)
            self._count_dispatch(*tokens.shape)
        dt = sp.seconds
        if not self.ecfg.async_steps:
            with self._span("sync") as sp:
                self.last_tok.block_until_ready()
            dt += sp.seconds
        self.stats["prefill_s"] += dt
        if self._admit_stalled:
            self.stats["stall_s"] += dt
        self._post_admit(rows, routing, self.ecfg.max_batch)

    def _admit_sequential(self, free: list[int]) -> None:
        for slot in free:
            if not self.queue:
                break
            # re-check per dispatch: request N's separate prefill program
            # stalls the requests admitted earlier in this same round too
            self._admit_stalled = any(r is not None for r in self.slots)
            req = self.queue.popleft()
            tokens = self._pad_prompt(req)[None]
            self.temps[slot] = req.temperature
            self.topks[slot] = req.top_k
            with self._span("dispatch", kind="prefill") as sp:
                step_idx = self._next_step_idx()
                self.last_tok, self.cache, routing = self._jit_prefill_one(
                    self.params, self.cache, jnp.asarray(tokens), slot,
                    self.last_tok, jnp.asarray(self.temps.copy()),
                    jnp.asarray(self.topks.copy()), step_idx, self._sampling)
                self._count_dispatch(*tokens.shape)
            dt = sp.seconds
            if not self.ecfg.async_steps:
                with self._span("sync") as sp:
                    self.last_tok.block_until_ready()
                dt += sp.seconds
            self.stats["prefill_s"] += dt
            if self._admit_stalled:
                self.stats["stall_s"] += dt
            self._post_admit([(0, slot, req)], routing, 1)

    def _count_dispatch(self, b: int, s: int) -> None:
        """Count a dispatched program over a (b, s) token block, and
        whether its MoE layers run the grouped form: the same shape rule
        (``expert_parallel.block_form``) the layers apply when traced."""
        self.stats["dispatches"] += 1
        if (b, s) not in self._grouped_blocks:
            self._grouped_blocks[(b, s)] = (
                self.cfg.family == "moe" and expert_parallel.block_form(
                    self.cfg, self.mesh, b, s,
                    self.params["blocks"]["experts"]) == "grouped")
        self.stats["grouped_moe_steps"] += self._grouped_blocks[(b, s)]

    def _span(self, name: str, **stats) -> _Span:
        """``with self._span(name, **stats):`` — the ``serving.<name>``
        span: a ``jax.profiler.TraceAnnotation`` carrying ``iter`` and
        ``stats``, on the host clock the profiler aligns device time to
        (inert while no trace is being taken), whose host seconds add to
        ``step_times[name]``.  Names never start with ``bench.``."""
        return _Span(self, name, stats)

    def _next_step_idx(self) -> Any:
        """Monotone per-dispatch counter feeding the sampling RNG fold
        (handed to the jit as a 0-d device array so it traces once)."""
        i = self._step_idx
        self._step_idx += 1
        return jnp.asarray(i, jnp.int32)

    def step(self) -> int:
        """One engine iteration.  Returns the number of rows that did work.

        Unified mode: admit (state-only), then pack decode rows + prefill
        chunks into ONE mixed-batch jit call (``_step_unified``).
        Reference mode: admit (separate whole-prompt prefill programs,
        stalling in-flight decodes) + one decode step.

        In async mode the device step is only *dispatched* here; tokens are
        appended to requests at the next harvest boundary (a request
        finishing, ``flush()``, or sync mode)."""
        self._iter += 1
        self.step_times = {}
        self._sweep_deadlines()
        if self.unified:
            return self._step_unified()
        self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return 0
        mask = np.zeros((self.ecfg.max_batch,), bool)
        mask[active] = True
        # NB: self.lengths is handed to the device as a host-side SNAPSHOT
        # (.copy()) that nothing mutates afterwards.  The host→device
        # transfer is itself deferred on jaxlib 0.4.x CPU — even
        # jnp.array's copy can read the source buffer *after* the
        # `self.lengths[i] += 1` below, which under CPU load produced
        # stale-length decodes (KV written over the previous slot,
        # repeated tokens).  mask/tokens buffers are freshly built per
        # call and never mutated after dispatch, so they are safe as-is.
        with self._span("dispatch", kind="decode") as sp:
            step_idx = self._next_step_idx()
            self.last_tok, self.cache, routing = self._jit_decode(
                self.params, self.cache, self.last_tok,
                jnp.asarray(self.lengths.copy()), jnp.asarray(mask),
                jnp.asarray(self.temps.copy()),
                jnp.asarray(self.topks.copy()), step_idx, self._sampling)
            self._count_dispatch(self.ecfg.max_batch, 1)
        dt = sp.seconds
        if not self.ecfg.async_steps:
            with self._span("sync") as sp:
                self.last_tok.block_until_ready()
            dt += sp.seconds
        self.stats["decode_s"] += dt
        self.stats["decode_steps"] += 1
        rows = tuple((i, i, self.slots[i]) for i in active)
        self._pending.append(_Pending("decode", rows, self.last_tok, routing,
                                      self.ecfg.max_batch))
        finishing = False
        for i in active:
            self.lengths[i] = min(self.lengths[i] + 1, self.ecfg.max_cache)
            self.stats["decode_tokens"] += 1
            self.budgets[i] -= 1
            if self.budgets[i] <= 0:
                # budget-based completion is host-known at dispatch time:
                # free the slot now, collect the tokens at the harvest below
                self._finish_slot(i)
                finishing = True
        if finishing or not self.ecfg.async_steps:
            self._harvest()
        return len(active)

    # -- unified token-budget iteration -------------------------------------

    def _step_unified(self) -> int:
        """One token-budget iteration of the unified engine.

        Admission only binds a request to a slot (no device work), so
        arrivals NEVER stall in-flight decode rows.  The iteration then
        schedules, in one (max_batch, chunk_len) block at per-row cache
        offsets: (a) every decode row — one token each, exempt from the
        budget; (b) pending prefill chunks, oldest slot first, until
        ``token_budget`` (0 = unlimited) is exhausted.  A row whose chunk
        completes its prompt samples its first generated token from that
        chunk's last logit — the prefill→decode transition costs no extra
        program.

        Resilience hooks (docs/DESIGN.md §10): paged decode rows secure
        the page their next token writes BEFORE anything is scheduled
        (lazy growth under overcommit — a row that cannot get one either
        idles for the iteration or preempts a peer); an injected
        dispatch fault aborts the iteration before any host bookkeeping
        mutates, so the identical iteration re-dispatches next step; and
        with the NaN guard on, rows whose logits came back non-finite
        are withheld from every host-state advance (lengths /
        prefill_pos / budgets / token record) and retried from their
        last durable cache state — the re-dispatched block writes are
        idempotent, so neighbours never see the fault."""
        with self._span("schedule"):
            blk = self._schedule()
        if blk is None:
            return 0
        tokens, seg, is_dec, sample, decode_rows, prefill_rows = blk
        b = self.ecfg.max_batch
        kind = ("decode" if not prefill_rows
                else "prefill" if not decode_rows else "mixed")
        prefill_tokens = int(sum(int(seg[i]) for i in prefill_rows))
        with self._span("transfer") as xfer:
            poison = self._poison0
            if self.faults is not None:
                f = self.faults.poll(self._iter, "nan")
                if f is not None:
                    poison = poison.copy()
                    poison[list(f.rows) if f.rows else range(b)] = f.value
            # lengths/temps/topks/block-table snapshots: same
            # deferred-transfer race rule as the reference decode path
            # (see step())
            bt = (jnp.asarray(self.block_tables.copy()) if self.paged
                  else None)
            tokens_d, lengths_d = (jnp.asarray(tokens),
                                   jnp.asarray(self.lengths.copy()))
            seg_d, is_dec_d, sample_d = (jnp.asarray(seg),
                                         jnp.asarray(is_dec),
                                         jnp.asarray(sample))
            temps_d, topks_d, poison_d = (jnp.asarray(self.temps.copy()),
                                          jnp.asarray(self.topks.copy()),
                                          jnp.asarray(poison))
        with self._span("dispatch", kind=kind, decode_rows=len(decode_rows),
                        prefill_tokens=prefill_tokens) as disp:
            try:
                if self.faults is not None:
                    # raised in place of the backend failing the launch:
                    # nothing host-side has mutated yet (not even the RNG
                    # step index), so the identical iteration
                    # re-dispatches on the next step()
                    self.faults.maybe_raise(self._iter, "dispatch")
                step_idx = self._next_step_idx()
                out = self._jit_unified(
                    self.params, self.cache, tokens_d, self.last_tok,
                    lengths_d, seg_d, bt, is_dec_d, sample_d, temps_d,
                    topks_d, poison_d, step_idx, self._sampling)
            except InjectedFault:
                self.stats["dispatch_failures"] += 1
                return 0
            self._count_dispatch(*tokens.shape)
        self.last_tok, self.cache, routing, bad = out
        dt = xfer.seconds + disp.seconds
        if not self.ecfg.async_steps:
            with self._span("sync") as sp:
                self.last_tok.block_until_ready()
            dt += sp.seconds
        self.stats[{"decode": "decode_s", "prefill": "prefill_s",
                    "mixed": "mixed_s"}[kind]] += dt
        with self._span("apply"):
            if kind == "mixed":
                # per-phase token counts so throughput() can apportion
                # mixed_s by token share instead of double-counting it
                self.stats["mixed_decode_tokens"] += len(decode_rows)
                self.stats["mixed_prefill_tokens"] += prefill_tokens
            bad_host = (self._quarantine_check(bad) if self._guard
                        else self._no_bad)
            rows = []
            finishing = False
            for i in decode_rows:
                if bad_host[i]:
                    finishing |= self._quarantine(i)
                    continue
                self.slots[i].nan_retries = 0
                self.lengths[i] = min(self.lengths[i] + 1,
                                      self.ecfg.max_cache)
                self.stats["decode_tokens"] += 1
                self.budgets[i] -= 1
                rows.append((i, i, self.slots[i]))
                if self.budgets[i] <= 0:
                    self._finish_slot(i)
                    finishing = True
            if decode_rows:
                self.stats["decode_steps"] += 1
            for i in prefill_rows:
                if bad_host[i]:
                    finishing |= self._quarantine(i)
                    continue
                self.slots[i].nan_retries = 0
                n = int(seg[i])
                self.lengths[i] += n
                self.prefill_pos[i] += n
                self.stats["prefill_tokens"] += n
                if sample[i]:             # prompt complete: token 1 sampled
                    if self.paged:
                        # the prompt's pages are final from this dispatch
                        # on: cache them for prefix reuse BEFORE any release
                        self._prefix_insert(i)
                    rows.append((i, i, self.slots[i]))
                    self.budgets[i] -= 1
                    if self.budgets[i] <= 0:
                        self._finish_slot(i)
                        finishing = True
            self._pending.append(_Pending(
                kind, tuple(rows), self.last_tok, routing, b,
                obs_rows=tuple(i for i in range(b)
                               if seg[i] and not bad_host[i])))
        if finishing or not self.ecfg.async_steps:
            self._harvest()
        return len(decode_rows) + len(prefill_rows)

    def _schedule(self):
        """Host half of a unified iteration: admission (``_admit_paged``'s
        prefix lookup included), lazy page growth, then the step's block:
        (tokens, seg, is_dec, sample, decode_rows, prefill_rows), or None
        when no row has work."""
        b, t = self.ecfg.max_batch, self.chunk_len
        for i in range(b):
            if self.slots[i] is None and self.queue:
                if self.paged:
                    # page-gated admission: priority order, stop at the
                    # first request the pool cannot hold (never skip
                    # ahead within the queue)
                    if not self._admit_paged(i):
                        break
                    continue
                req = self.queue.popleft()
                self.slots[i] = req
                self.slot_ctx[i] = req.prompt
                req.status = "running"
                self.lengths[i] = 0
                self.prefill_pos[i] = 0
                self.budgets[i] = req.max_new_tokens
                self.temps[i] = req.temperature
                self.topks[i] = req.top_k
        self.stats["active_hwm"] = max(
            self.stats["active_hwm"],
            sum(1 for s in self.slots if s is not None))
        if self.paged:
            # lazy-growth pass: every decode-phase row secures the page
            # its next token writes BEFORE any row enters this
            # iteration's dispatch — growth may preempt a peer (or the
            # grower itself), and a preempted row must never already be
            # scheduled when its pages are released
            for i in range(b):
                req = self.slots[i]
                if (req is not None
                        and self.prefill_pos[i] >= len(self.slot_ctx[i])):
                    self._ensure_decode_page(i)
        tokens = np.zeros((b, t), np.int32)
        seg = np.zeros((b,), np.int32)
        is_dec = np.zeros((b,), bool)
        sample = np.zeros((b,), bool)
        budget = self.ecfg.token_budget or (b * t + b)   # 0 = unlimited
        decode_rows, prefill_rows = [], []
        for i, req in enumerate(self.slots):
            if req is not None and self.prefill_pos[i] >= len(self.slot_ctx[i]):
                if self.paged and not self._covered(i):
                    # page-starved (alloc fault / exhausted pool with no
                    # victim): the row idles this iteration with all its
                    # state intact and retries next step
                    continue
                seg[i] = 1
                is_dec[i] = sample[i] = True
                decode_rows.append(i)   # budget-exempt: decode never starves
        for i, req in enumerate(self.slots):
            if req is None or is_dec[i] or budget <= 0:
                continue
            ctx = self.slot_ctx[i]
            pos = int(self.prefill_pos[i])
            n = min(t, len(ctx) - pos, budget,
                    self.ecfg.max_cache - int(self.lengths[i]))
            if n <= 0:
                continue
            tokens[i, :n] = ctx[pos:pos + n]
            seg[i] = n
            budget -= n
            sample[i] = pos + n == len(ctx)
            prefill_rows.append(i)
        if not decode_rows and not prefill_rows:
            return None
        # decode-only iterations shrink the block to width 1: the unified
        # program is length-agnostic, so the same jit body retraces once at
        # (B, 1) and the steady-state decode iteration costs exactly a
        # decode step — never chunk_len columns of dead compute
        if not prefill_rows:
            tokens = tokens[:, :1]
        return tokens, seg, is_dec, sample, decode_rows, prefill_rows

    # -- paged-cache bookkeeping (EngineConfig.paged; docs/DESIGN.md §7) ----

    def _admit_paged(self, slot: int) -> bool:
        """Map the queue head into ``slot`` if the pool can hold its page
        entitlement, minus every page shared through the prefix cache.

        Entitlement: the whole lifetime — ceil((context + remaining_new
        - 1) / page_size) blocks — by default (PR4's conservative
        admission: an admitted request can never hit pool OOM
        mid-generation), or only the CONTEXT's pages under
        ``EngineConfig.overcommit``, where lazy decode growth
        (``_ensure_decode_page``) takes the rest one page at a time.

        Restore is the same operation (docs/DESIGN.md §10): a preempted
        request's ``resume_tokens`` (prompt + everything generated
        before preemption) is its context, and its own evicted pages ARE
        the prefix hit — so restore is a block-table remap plus at most
        one partial-tail re-prefill chunk, and the greedy token stream
        continues exactly where it stopped.  Under overcommit a short
        pool preempts strictly-lower-priority running rows (least
        recently preempted first) until the head fits or nobody lesser
        remains.

        Returns False with the queue untouched (priority order
        preserved) when pages stay short even after LRU eviction and
        preemption."""
        req = self.queue[0]
        if self.faults is not None and self.faults.poll(self._iter, "alloc"):
            # injected pool exhaustion: admission sees nothing free and
            # nothing reclaimable this iteration — the request just
            # stays queued (no refcount was taken)
            self.stats["alloc_stalls"] += 1
            return False
        ctx = (req.resume_tokens if req.resume_tokens is not None
               else req.prompt)
        remaining = req.max_new_tokens - len(req.generated)
        lifetime = sched.lifetime_pages(len(ctx), remaining, self.page_size)
        upfront = (sched.pages_for(len(ctx), self.page_size)
                   if self.ecfg.overcommit else lifetime)
        hit = self.prefix.lookup(ctx)
        need = upfront - len(hit.pages)
        if self.allocator.free_pages < need:
            # evict only when it can actually close the gap: a request
            # merely waiting for in-flight pages must NOT drain the tree
            # (it retries every iteration — unconditional eviction would
            # destroy the cached system prompt while freeing nothing)
            if (self.allocator.free_pages + self.prefix.reclaimable_pages()
                    >= need):
                self.prefix.evict(need)
        while self.allocator.free_pages < need and self.ecfg.overcommit:
            # priority preemption: a victim's pages land in the prefix
            # tree (reclaimable once its row references drop), so each
            # preemption is followed by another gap-closing eviction
            victim = sched.select_victim(self._running_rows(),
                                         below=req.priority)
            if victim is None:
                break
            self._preempt_slot(victim)
            if (self.allocator.free_pages + self.prefix.reclaimable_pages()
                    >= need):
                self.prefix.evict(need)
        if self.allocator.free_pages < need:
            # hand the lookup references back; the request stays queued
            # (retried next iteration — not counted as a prefix lookup,
            # so hit-rate stats count requests, not retries)
            self.allocator.free(hit.pages)
            if hit.tail_page >= 0:
                self.allocator.free([hit.tail_page])
            return False
        self.stats["prefix_lookups"] += 1
        new_pages = self.allocator.alloc(need)
        pages = list(hit.pages) + new_pages
        if hit.tail_len:
            # copy-on-write the shared partial tail page: its owner may
            # still be appending decode tokens to the original, so this
            # request copies the page (one page-sized device op) and
            # overwrites the divergent suffix as it writes
            dst = new_pages[0]
            self.cache = self._jit_copy_pages(
                self.cache, jnp.asarray([hit.tail_page], jnp.int32),
                jnp.asarray([dst], jnp.int32))
            self.allocator.free([hit.tail_page])   # drop the lookup ref
            self.stats["cow_copies"] += 1
        self.queue.popleft()
        self.slots[slot] = req
        self.slot_ctx[slot] = ctx
        self.slot_pages[slot] = pages
        self.block_tables[slot] = 0
        self.block_tables[slot, :len(pages)] = pages
        # the shared prefix is already in the cache: prefill starts at
        # hit.tokens, skipping exactly that much prefill work
        self.lengths[slot] = hit.tokens
        self.prefill_pos[slot] = hit.tokens
        self.budgets[slot] = remaining
        self.temps[slot] = req.temperature
        self.topks[slot] = req.top_k
        if req.status == "preempted":
            self.stats["restores"] += 1
            self.stats["restore_hit_tokens"] += hit.tokens
        req.status = "running"
        if hit.tokens:
            self.stats["prefix_hits"] += 1
            self.stats["prefix_hit_tokens"] += hit.tokens
        self.stats["pages_hwm"] = max(self.stats["pages_hwm"],
                                      self.allocator.pages_in_use)
        return True

    def _covered(self, i: int) -> bool:
        """Row ``i``'s next decode write (cache position ``lengths[i]``)
        has a page under its block table."""
        return int(self.lengths[i]) < len(self.slot_pages[i]) * self.page_size

    def _running_rows(self) -> list:
        """Victim candidates for sched.select_victim: every occupied
        slot with its scheduling keys."""
        return [sched.RunningRow(i, r.priority, r.last_preempt_epoch, r.seq)
                for i, r in enumerate(self.slots) if r is not None]

    def _ensure_decode_page(self, i: int) -> bool:
        """Lazy decode-page growth (docs/DESIGN.md §10): make sure row
        ``i``'s block table covers the position its next token writes.

        Whole-lifetime admission always covers it (the fast path).  An
        overcommitted row takes one page at a time: evict LRU prefix
        entries if that closes the gap; if the pool is still dry,
        preempt the least-entitled running row — possibly row ``i``
        itself, which then yields instead of starving a peer.  Returns
        False when the row cannot advance this iteration (preempted, or
        page-starved under an injected alloc fault / a pool with no
        eligible victim)."""
        if self._covered(i):
            return True
        if self.faults is not None and self.faults.poll(self._iter, "alloc"):
            self.stats["alloc_stalls"] += 1
            return False
        if (self.allocator.free_pages < 1
                and self.allocator.free_pages
                + self.prefix.reclaimable_pages() >= 1):
            self.prefix.evict(1)
        while self.allocator.free_pages < 1 and self.ecfg.overcommit:
            victim = sched.select_victim(self._running_rows())
            if victim is None:
                break
            self._preempt_slot(victim)
            if victim == i:
                return False
            if (self.allocator.free_pages
                    + self.prefix.reclaimable_pages() >= 1):
                self.prefix.evict(1)
        got = self.allocator.alloc(1)
        if got is None:
            self.stats["alloc_stalls"] += 1
            return False
        self.slot_pages[i].append(got[0])
        self.block_tables[i, len(self.slot_pages[i]) - 1] = got[0]
        self.stats["pages_hwm"] = max(self.stats["pages_hwm"],
                                      self.allocator.pages_in_use)
        return True

    def _preempt_slot(self, i: int) -> None:
        """Evict row ``i`` to the prefix cache and requeue its request —
        the preemption protocol (docs/DESIGN.md §10).

        Order matters: (1) harvest, so every in-flight token of the row
        is on the host and the virtual prompt (prompt + generated) is
        final; (2) insert the row's durable cache state — ``lengths[i]``
        tokens: every full page plus the partial tail — into the prefix
        tree as an ordinary entry; (3) free the row's own page
        references (the tree's references keep the state alive,
        LRU-evictable under later pressure); (4) requeue with
        ``resume_tokens`` = the virtual prompt and the ORIGINAL
        submission seq, so the request re-enters ahead of later
        same-priority arrivals.  Restore (``_admit_paged``) then finds
        its own pages as a prefix hit and re-prefills at most one
        partial chunk: greedy token streams are identical to the
        unpreempted run."""
        self._harvest()
        req = self.slots[i]
        full = np.concatenate([req.prompt,
                               np.asarray(req.generated, np.int32)])
        n = int(self.lengths[i])
        ps = self.page_size
        k, tail = n // ps, n % ps
        pages = self.slot_pages[i]
        if n:
            self.prefix.insert(full[:n], pages[:k],
                               pages[k] if tail else -1, tail)
        self.allocator.free(pages)
        self.slot_pages[i] = []
        self.block_tables[i] = 0
        self.slots[i] = None
        self.slot_ctx[i] = None
        self._preempt_epoch += 1
        req.resume_tokens = full
        req.status = "preempted"
        req.preemptions += 1
        req.last_preempt_epoch = self._preempt_epoch
        self.stats["preemptions"] += 1
        self.preempt_log.append(
            (self._iter, req.uid,
             tuple((r.uid, r.priority) for r in self.slots
                   if r is not None)))
        self.queue.append(req)

    def preempt(self, uid: int) -> bool:
        """Preempt the running request ``uid`` now (public policy hook;
        also how analysis R3's drive_engine pushes a preemption through
        the trace-budget audit).  Its pages move into the prefix tree
        and the request restores through normal admission.  Returns
        False if ``uid`` is not currently in a slot."""
        if not self.paged:
            raise ValueError("preemption requires the paged KV cache "
                             "(EngineConfig.paged=True)")
        for i, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                self._preempt_slot(i)
                return True
        return False

    def _release_slot(self, i: int) -> None:
        """Free slot ``i``'s pages and binding (exactly once: the page
        list is emptied, so a second call is a no-op).  Paged mode drops
        the request's page references — pages the prefix tree also holds
        stay resident for future hits; the rest return to the free
        list."""
        if self.paged and self.slot_pages[i]:
            self.allocator.free(self.slot_pages[i])
            self.slot_pages[i] = []
            self.block_tables[i] = 0
        self.slots[i] = None
        self.slot_ctx[i] = None

    def _finish_slot(self, i: int) -> None:
        """Normal completion: the budget is exhausted and the final token
        is already in flight to the harvest (which flips ``done`` when
        the token count lands)."""
        req = self.slots[i]
        if req.status == "running":
            req.status = "done"
        self._release_slot(i)

    def _prefix_insert(self, i: int) -> None:
        """Record row ``i``'s freshly prefilled context in the prefix tree
        (called when its prefill completes — the pages' contents are final
        from that dispatch on, in dispatch order).  Full page-aligned
        chunks become radix nodes; a non-aligned remainder becomes the
        node's partial-tail record, shareable via copy-on-write.  For a
        restored request the context is ``resume_tokens`` (prompt + the
        pre-preemption generation), so its re-entered state is shareable
        too."""
        ctx = self.slot_ctx[i]
        ps = self.page_size
        k = len(ctx) // ps
        pages = [int(p) for p in self.block_tables[i, :k]]
        tail_len = len(ctx) - k * ps
        tail_page = int(self.block_tables[i, k]) if tail_len else -1
        self.prefix.insert(ctx, pages, tail_page, tail_len)

    # -- cancellation, deadlines, quarantine (docs/DESIGN.md §10) -----------

    def _terminate_req(self, req: Request, status: str) -> None:
        """Move ``req`` to a terminal state (its pages must already be
        released).  ``done`` flips so waiters see it finished; ``status``
        says why."""
        req.status = status
        req.done = True
        self.stats[status] += 1

    def _terminate_slot(self, i: int, status: str) -> None:
        req = self.slots[i]
        self._release_slot(i)
        self._terminate_req(req, status)

    def cancel(self, uid: int) -> bool:
        """Abandon request ``uid``, queued or in-flight (satellite fix:
        previously a submitted request held its slot and pages until
        ``max_new_tokens`` completed, no matter what).

        Page references are dropped exactly once (``_release_slot``
        empties the page list) and only the ROW's references — pages the
        prefix tree shares stay cached for other requests.  In-flight
        tokens are harvested first, so ``generated`` holds everything
        the request produced before the cancel.  Returns True if the
        request was live and is now cancelled; False if unknown or
        already terminal (a second cancel is a no-op)."""
        req = self._all.get(uid)
        if req is None or req.done or req.status in TERMINAL_STATES:
            return False
        # flush pending device steps: a record in flight may complete the
        # request (then cancel is too late and reports False), and the
        # bookkeeping below needs ``generated`` final
        self._harvest()
        if req.done:
            return False
        if self.queue.remove(uid) is not None:      # queued or preempted
            self._terminate_req(req, "cancelled")
            return True
        for i, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                self._terminate_slot(i, "cancelled")
                return True
        return False

    def _sweep_deadlines(self) -> None:
        """Expire every request whose deadline passed (runs at the top of
        each step; ``_now`` is monkeypatchable in tests).  Queued
        requests just leave the queue; in-flight rows release their
        pages through the same exactly-once path as cancel."""
        if not self._has_deadlines:
            return
        now = self._now()
        for r in list(self.queue):
            if r.deadline_s is not None and now >= r.deadline_s:
                if self.queue.remove(r.uid) is not None:
                    self._terminate_req(r, "expired")
        for i, req in enumerate(self.slots):
            if (req is not None and req.deadline_s is not None
                    and now >= req.deadline_s):
                self._harvest()
                if not req.done:
                    self._terminate_slot(i, "expired")

    def _now(self) -> float:
        return time.perf_counter()

    def _quarantine(self, i: int) -> bool:
        """Row ``i``'s logits came back non-finite (NaN guard): withhold
        every host-state advance so the row re-dispatches from its last
        durable cache state next iteration (the repeated block write is
        idempotent; in-jit, ``last_tok`` was already shielded).  After
        ``nan_retry_limit`` consecutive bad steps the row is failed and
        its pages released instead of spinning forever.  Returns True
        when the row was failed (the caller's harvest boundary)."""
        req = self.slots[i]
        self.stats["nan_quarantines"] += 1
        req.nan_retries += 1
        if req.nan_retries > self.ecfg.nan_retry_limit:
            self._terminate_slot(i, "failed")
            return True
        return False

    def _quarantine_check(self, bad) -> np.ndarray:
        """THE quarantine sync point (``EngineConfig.nan_guard``): fetch
        the step's per-row finiteness verdict.  Deliberately a blocking
        device->host read in the hot loop — the guard trades the async
        pipeline's run-ahead for per-step integrity, the same opt-in
        trade as ``async_steps=False`` — so it lives OUTSIDE the R4
        host-sync scan's hot-method set as a documented boundary, like
        ``_harvest``."""
        return np.asarray(jax.device_get(bad))

    def resilience_stats(self) -> dict:
        """Scheduler + fault-guard counters for reporting (launch/serve,
        benchmarks/serving_engine, the chaos harness)."""
        s = self.stats
        out = {k: s[k] for k in
               ("preemptions", "restores", "restore_hit_tokens",
                "cancelled", "expired", "failed", "alloc_stalls",
                "dispatch_failures", "nan_quarantines", "active_hwm")}
        out["preempt_log_len"] = len(self.preempt_log)
        return out

    def paged_stats(self) -> dict:
        """Page-pool / prefix-cache counters for reporting (launch/serve,
        benchmarks).  ``{"paged": False}`` on the contiguous cache."""
        if not self.paged:
            return {"paged": False}
        s = self.stats
        return {
            "paged": True,
            "paged_kernel": self.ecfg.paged_kernel,
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "pages_in_use": self.allocator.pages_in_use,
            "pages_hwm": s["pages_hwm"],
            "pool_utilization": s["pages_hwm"] / self.num_pages,
            "prefix_lookups": s["prefix_lookups"],
            "prefix_hits": s["prefix_hits"],
            "prefix_hit_rate": s["prefix_hits"] / max(s["prefix_lookups"], 1),
            "prefix_hit_tokens": s["prefix_hit_tokens"],
            "prefix_cached_pages": self.prefix.cached_pages,
            "prefix_evictions": self.prefix.evictions,
            "cow_copies": s["cow_copies"],
        }

    def memory_stats(self) -> dict:
        """Device-memory report (satellite of docs/DESIGN.md §8): total
        GLOBAL weight bytes of the params pytree (QuantTensor leaves
        count their int8/int4 payload + fp32 scales — the number the
        quantized store shrinks), KV pool bytes (contiguous slots or page
        pool), and their sum.  On a single node this IS the per-node
        budget ``perf_model.fits_in_memory`` checks; on an expert-parallel
        mesh the arrays here are global (each node holds only its expert
        shard plus the replicated rest — ``perf_model.
        per_node_weight_bytes`` models that split)."""
        weight = quant.tree_bytes(self.params)
        pool = quant.tree_bytes(self.cache)
        return {
            "weight_bytes": weight,
            "kv_pool_bytes": pool,
            "total_bytes": weight + pool,
            "weight_quant": getattr(self.cfg, "weight_quant", "none"),
        }

    # -- harvest: the only device sync in the loop --------------------------

    def _harvest(self) -> None:
        """Fetch all pending step outputs and apply them to requests/tracker
        in dispatch order.  Each record is fetched in its own
        ``serving.fetch`` span — computations complete in dispatch order,
        so the per-record wait IS that step's remaining device time, giving
        an honest prefill/decode split of the async pipeline's wall
        clock."""
        if not self._pending:
            return
        recs, self._pending = self._pending, []
        with self._span("harvest"):
            for rec in recs:
                with self._span("fetch", kind=rec.kind) as sp:
                    tok, routing = jax.device_get((rec.tok, rec.routing))
                self.stats[{"prefill": "prefill_s", "decode": "decode_s",
                            "mixed": "mixed_s"}[rec.kind]] += sp.seconds
                if rec.stalled:
                    self.stats["stall_s"] += sp.seconds
                now = time.perf_counter()
                for _, slot, req in rec.rows:
                    req.generated.append(int(tok[slot]))
                    if req.first_token_s is None:
                        req.first_token_s = now
                    if len(req.generated) >= req.max_new_tokens:
                        req.done = True
                        if req.status not in TERMINAL_STATES:
                            req.status = "done"
                with self._span("observe_routing"):
                    self._observe_routing(rec, routing)

    def _observe_routing(self, rec: _Pending, routing) -> None:
        """Feed the tracker from the device capture (host does NO routing)."""
        if self.tracker is None or routing is None:
            return
        # prefill/unified: (L, B*S, K) -> (L, B, S*K); decode: (L, B, K)
        per_row = routing.reshape(routing.shape[0], rec.routing_batch, -1)
        row_ids = (list(rec.obs_rows) if rec.obs_rows is not None
                   else [row for row, _, _ in rec.rows])
        for layer in range(self.cfg.num_layers):
            ids = per_row[layer, row_ids]
            # unified blocks dead-route invalid tokens to the E_pad
            # sentinel; those entries are scheduling padding, not executed
            # experts — drop them before they reach the tracker
            self.tracker.observe(layer, ids[ids < self.cfg.num_experts])
        self.tracker.tick()

    def flush(self) -> None:
        """Sync: harvest every dispatched-but-unapplied step."""
        self._harvest()

    def run_until_done(self, max_steps: int = 10_000) -> list[Request]:
        done: list[Request] = []
        seen: set[int] = set()
        pending = lambda: self.queue or any(s is not None for s in self.slots)
        steps = 0
        while pending() and steps < max_steps:
            self.step()
            steps += 1
            for r in self._all.values():
                if r.done and r.uid not in seen:
                    seen.add(r.uid)
                    done.append(r)
        self.flush()
        for r in self._all.values():
            if r.done and r.uid not in seen:
                seen.add(r.uid)
                done.append(r)
        return done

    # -- paper policy artifacts ---------------------------------------------

    def standby(self) -> Array:
        """The paper's between-request keep-warm: a summing touch over every
        expert weight (§4.2 'standby calculation')."""
        if not self.cfg.is_moe:
            return jnp.zeros(())
        ex = self.params["blocks"]["experts"]
        return sum(jnp.sum(w.astype(jnp.float32)) for w in jax.tree.leaves(ex))

    def expected_experts_per_node(self, n_nodes: int) -> float:
        """Measured Table-1 statistic from the tracker (exact: computed from
        the device-captured routing decisions of every served step)."""
        if self.tracker is None:
            return float("nan")
        self.flush()
        return self.tracker.mean_executed_per_node(n_nodes)

    def throughput(self) -> dict:
        """Per-phase tok/s.  ``prefill_s``/``decode_s``/``mixed_s`` hold
        dispatch time plus each phase's harvest wait (see _harvest), so the
        split is meaningful in async mode too; ``total`` is the combined
        rate over all three buckets (unified iterations that mix prefill
        chunks with decode rows land in ``mixed_s``).

        Mixed-iteration time is APPORTIONED between the two per-phase
        denominators by each phase's token share of those iterations
        (``mixed_prefill_tokens`` / ``mixed_decode_tokens``) — the
        satellite fix: charging all of ``mixed_s`` to *both* phases
        systematically deflated both rates (their reciprocals summed to
        more than the measured wall time).

        ``prefill_tokens`` counts REAL prompt tokens only;
        ``prefill_padding_overhead`` is the fraction of prefill positions
        the reference path spent recomputing padding (0 in unified mode —
        the satellite fix for the seed's inflated prefill tok/s).
        ``decode_stall_s`` is reference-mode device time during which
        in-flight decode rows sat idle behind a separate prefill program
        (0 by construction in unified mode)."""
        s = self.stats
        work_s = s["prefill_s"] + s["decode_s"] + s["mixed_s"]
        pad = s["prefill_pad_tokens"]
        mp, md = s["mixed_prefill_tokens"], s["mixed_decode_tokens"]
        p_share = mp / (mp + md) if (mp + md) else 0.0
        prefill_den = s["prefill_s"] + s["mixed_s"] * p_share
        decode_den = s["decode_s"] + s["mixed_s"] * (1.0 - p_share)
        return {
            "prefill_tok_per_s": s["prefill_tokens"] / max(prefill_den,
                                                           1e-9),
            "decode_tok_per_s": s["decode_tokens"] / max(decode_den, 1e-9),
            "total_tok_per_s": (s["prefill_tokens"] + s["decode_tokens"])
                               / max(work_s, 1e-9),
            "prefill_padding_overhead": pad / max(pad + s["prefill_tokens"],
                                                  1),
            "decode_stall_s": s["stall_s"],
        }

    def ttft(self, since: float = 0.0) -> dict:
        """Time-to-first-token stats over completed requests (seconds,
        harvest-boundary resolution — honest for sync stepping; async mode
        coalesces harvests, so pair with ``async_steps=False`` when TTFT is
        the metric under study).  ``since`` drops requests submitted before
        that ``time.perf_counter()`` stamp (e.g. compile-time warmups)."""
        ts = sorted(r.first_token_s - r.submit_s for r in self._all.values()
                    if r.first_token_s is not None and r.submit_s >= since)
        if not ts:
            return {"n": 0, "p50": float("nan"), "p95": float("nan")}
        pct = lambda p: ts[min(int(p * (len(ts) - 1) + 0.5), len(ts) - 1)]
        return {"n": len(ts), "p50": pct(0.50), "p95": pct(0.95),
                "mean": sum(ts) / len(ts)}
