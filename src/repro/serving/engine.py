"""Serving engine: continuous per-slot batched decode over the morphable
substrate, with CHUNKED admission prefill and a fault-tolerance layer.

The engine owns `slots` cache rows and runs one decode step per iteration for
the whole batch. Every slot progresses independently — `KVCache.pos` is a
per-row vector — so a finished slot is refilled from the queue IMMEDIATELY
while the other slots keep decoding, instead of the old wave-synchronous
scheme where a whole wave stalled until its slowest member finished. This is
the serving-side analogue of the paper's morphable MAC array: one substrate,
independently progressing lanes.

Admission is CHUNKED: a new prompt advances in fixed `prefill_chunk`-token
right-padded slices, one chunk launch per engine step, INTERLEAVED with the
decode launches — resident slots keep generating while a long prompt admits,
so admission no longer head-of-line-blocks every in-flight request for the
whole prompt. Rows mid-decode pass `lengths == 0` through a chunk launch and
keep their caches; admitted rows advance only by their true token count, so
pad keys sit beyond every row's causal frontier and are never attended. The
chunk shape is FIXED, so prefill traces ONCE instead of once per pow2 bucket
(the old `_bucket` ladder is gone), and under a pallas backend the chunk
dispatches to the varlen flash-prefill kernel, which prunes q-blocks and
KV-blocks to each row's real tokens (`prefill_route()` reports the path).
Greedy outputs are byte-identical to one-shot admission (tested).

With `paged=True` the KV residency is a BLOCK POOL instead of per-slot
stripes: every cache layer holds `pool_blocks` fixed-size KV blocks and a
per-row block table maps each row's logical cache positions onto pool
blocks (the flash kernels indirect through the scalar-prefetched table; the
ref path gathers pages). A host-side refcounted allocator reserves a row's
whole block budget at admission, shares fully-covered prompt-prefix blocks
copy-on-write through a prompt-hash prefix registry (a matching system
prompt prefills ONCE; the one partially-covered boundary block is forked to
a private copy before the row writes into it), evicts cold registry-only
prefixes LRU under pool pressure, and DEFERS admission at the queue head
when the pool cannot hold the reservation — queue backpressure then
surfaces through the same bounded-queue REJECTED path. Greedy outputs are
byte-identical to the per-slot engine (tested: dense, GQA, int8-KV).

Pool pressure degrades GRACEFULLY instead of cliffing into deferral: past
a high watermark (`swap_watermark`, fraction of the pool an admission may
fill), the admission policy PREEMPTS resident rows of strictly lower
priority — victims ordered by (priority, deadline slack, blocks freed) —
and spills each victim's private blocks to a host-side numpy store
(`serving/swap.py`), codes+scales for quantized layouts. Blocks the victim
shares with the prefix registry or other rows are NOT swapped (the shared
bytes stay resident either way); the swap entry keeps their references.
The preempted request moves to a PREEMPTED state that re-admits AHEAD of
fresh admissions: swap-in reserves fresh blocks, scatters the host bytes
back (`write_pool_blocks` — the same fixed-width sentinel-padded scatter
discipline as the CoW fork) and rewinds the row to its saved frontier — no
prefill recompute, greedy output byte-identical to an uncontended run
(tested). Every transfer happens at the already-synchronizing scheduler
boundary; the jitted step stays transfer-free (`repro.analysis` HL206).
Equal priorities never preempt each other — the hysteresis that prevents
two rows from thrashing each other's residency.

Architectures with recurrent state (mamba / mlstm / slstm blocks) advance
strictly one token at a time; their prefill and decode MERGE into a single
l=1 launch per step — prefilling rows feed their next prompt token while
decoding rows feed their last sampled one.

Greedy outputs are byte-identical to serving each request alone (tested),
except MoE archs whose capacity-factor routing couples batch rows by design.

Fault tolerance (the hyperscale-serving posture of §VI):

* The step program carries a fused NUMERIC-HEALTH output — one per-row
  `all(isfinite(logits))` reduction folded into the SAME traced program as
  the decode step, so the guard costs no extra launch and
  `step_trace_count()` stays at the fixed two shapes. Health is fetched only
  at launches whose logits the host was already syncing on (decode, merged,
  finishing prefill); a slot whose logits go non-finite is QUARANTINED: its
  cache row is scrubbed (`scrub_slots` — values AND positions, because a NaN
  riding an additive attention mask is not neutral the way finite stale
  values are) and its request replays from its retained prompt,
  byte-identically, up to `max_replays` times before it fails terminally.
* A kernel-launch failure (a real pallas error, or an injected
  `faults.KernelLaunchError`) DEMOTES the engine: the pinned
  ExecutionPolicy is re-pinned to the reference backend
  (`ExecutionPolicy.demoted()`), the step jits rebuild, and the SAME step
  retries down the safe route — the software analogue of reconfiguring the
  morphable array back to its safe dataflow. `degraded_routes()` reports
  every demotion event.
* Requests carry per-request deadlines: `deadline_steps` (engine steps —
  deterministic) and `ttl_s` (wall clock); expiry finishes them with
  status "TIMEOUT". Admission is BOUNDED: with `max_queue` set, `submit()`
  refuses further requests (returns False, status "REJECTED") instead of
  queueing without limit.
* `snapshot()` / `restore()` persist the whole engine state — cache pytree,
  per-slot bookkeeping, queue, stats — through `repro.checkpoint.store`, so
  a run recovers mid-stream and finishes byte-identically (tested).

All of it is exercised by `repro.serving.faults` — a seeded, deterministic
fault-injection plan armed via `arm_fault_plan()`; production pays zero cost
when no plan is armed (one `is None` check per step).

Under the JAX profiler each step writes host spans into the trace, on the
device's clock: `engine.step`, and inside it `engine.admit`,
`engine.launch` (a launch's inputs and dispatch), `engine.sync` (the host
blocking on the tokens and the health flags) and `engine.emit` (the token
bookkeeping). With the profiler off a span costs about a microsecond.
`EngineStats.admissions` / `queue_wait_s` count each request's wait from
`submit()` to its first slot.

Multi-tenant serving stacks one engine per tenant on its mesh partition
(tenancy/scheduler.py — the §VI-C scenario); engines report per-slot
occupancy through `occupancy()` for the scheduler's utilization view.
"""
from __future__ import annotations

import bisect
import contextlib
import dataclasses
import hashlib
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import api
from ..models import transformer as T
from ..models.layers import apply_norm
from ..models.transformer import _block_apply, _sinusoid
from . import faults as faultlib
from .swap import HostBlockStore

__all__ = ["Request", "ServingEngine", "EngineStats", "EngineStalledError",
           "TERMINAL_STATES"]

PAD = 0

_RECURRENT_KINDS = ("mamba", "mlstm", "slstm")

# Request.status values once a request leaves the engine for good.
TERMINAL_STATES = ("done", "TIMEOUT", "REJECTED", "FAILED")


def _span(name: str, args: Optional[Callable[[], dict]] = None):
    """A host span in the profiler's trace (`jax.profiler.TraceAnnotation`),
    on the device trace's clock. `args` gives the span's arguments and is
    called only while the profiler records: with it off a span costs about
    a microsecond and builds no argument."""
    if args is not None and jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler.TraceAnnotation(name, **args())
    return jax.profiler.TraceAnnotation(name)


def _encode_memory(params, frames, cfg):
    """Run the audio encoder stack once (prefill of the cross-attn memory)."""
    mem = frames + _sinusoid(frames.shape[1], cfg.d_model, frames.dtype)
    for i in range(cfg.encoder_layers):
        p_i = jax.tree.map(lambda a: a[i], params["encoder"])
        mem, _, _ = _block_apply("enc", p_i, mem, cfg)
    return apply_norm(cfg.norm, params["enc_norm"], mem)


class EngineStalledError(RuntimeError):
    """`run_until_drained` hit its step budget with work still in flight.

    Carries the diagnosis instead of a bare step count: which slots are
    stuck (their occupancy dicts) and how deep the admission queue is."""

    def __init__(self, msg: str, *, stuck=(), queue_depth: int = 0):
        self.stuck = list(stuck)
        self.queue_depth = int(queue_depth)
        super().__init__(
            f"{msg}; {len(self.stuck)} stuck slot(s): {self.stuck!r}; "
            f"queue depth {self.queue_depth}")


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                # (L,) int32
    max_new_tokens: int = 16
    out_tokens: Optional[List[int]] = None
    done: bool = False
    # --- lifecycle / fault-tolerance state ---
    status: str = "new"               # queued | active | PREEMPTED ->
    #                                   TERMINAL_STATES
    deadline_steps: Optional[int] = None   # engine steps from submit (determ.)
    ttl_s: Optional[float] = None          # wall seconds from submit
    replays: int = 0                  # quarantine replays consumed so far
    priority: int = 0                 # preemption rank: higher admits first
    #                                   under pressure and may swap out
    #                                   strictly-lower rows; equal never
    #                                   preempts equal
    _submit_step: int = 0
    _submit_t: float = 0.0


@dataclasses.dataclass
class EngineStats:
    """Model-invocation accounting (the serving_bench comparison currency),
    plus the fault-surface counters the bench and launcher surface."""
    prefill_chunk_calls: int = 0      # chunk-shaped batched prefill launches
    prefill_token_steps: int = 0      # merged l=1 launches (recurrent archs)
    prefill_tokens: int = 0           # valid prompt tokens prefilled
    decode_steps: int = 0             # batch decode launches
    generated_tokens: int = 0
    # --- fault counters ---
    quarantines: int = 0              # poisoned slots evicted + scrubbed
    demotions: int = 0                # pallas->ref route demotions
    timeouts: int = 0                 # requests expired (deadline/TTL)
    rejected_submits: int = 0         # submits refused by the bounded queue
    failed_requests: int = 0          # replay budget exhausted -> FAILED
    # --- memory-pressure counters (paged engines) ---
    preemptions: int = 0              # resident rows preempted under pressure
    swap_outs: int = 0                # preemptions that moved blocks to host
    swap_ins: int = 0                 # preempted rows restored byte-identically
    # --- admission: a request's first slot, and its wait for it ---
    admissions: int = 0               # queued requests given their first slot
    queue_wait_s: float = 0.0         # sum over those of slot minus submit()

    @property
    def model_calls(self) -> int:
        return self.prefill_chunk_calls + self.prefill_token_steps + \
            self.decode_steps


class ServingEngine:
    """Continuous per-slot batching over `slots` preallocated cache rows."""

    def __init__(self, cfg: T.ModelConfig, params, *, slots: int = 4,
                 max_len: int = 512, eos_id: Optional[int] = None,
                 frames: Optional[np.ndarray] = None,
                 policy: Optional[api.ExecutionPolicy] = None,
                 weight_format: Optional[str] = None,
                 prefill_chunk: int = 32,
                 max_queue: Optional[int] = None,
                 max_replays: int = 2,
                 deadline_steps: Optional[int] = None,
                 ttl_s: Optional[float] = None,
                 paged: bool = False,
                 block_size: int = 16,
                 pool_blocks: Optional[int] = None,
                 swap_watermark: float = 1.0):
        """frames: (slots, frontend_len, d_model) audio features for enc-dec
        archs — encoded once, cross-attended by every decode step.

        policy: an ExecutionPolicy governing every op the engine traces
        (backend/format/tiling); one engine = one policy, so the jit caches
        stay coherent. A kernel-launch failure re-pins it to the ref backend
        (`demoted()`) and rebuilds the jits.

        weight_format: make the Linear weights RESIDENT in this AIO format
        (int4/int8/fp8a/fp8b): `quantize_params` converts the pytree once at
        construction and every covered matmul dispatches through
        `api.ops.matmul_codes` — greedy outputs stay byte-identical to the
        fake-quant path (tested). Other format names (incl. "bf16") raise,
        they are not residency formats. The conversion here does NOT donate
        the caller's dense params (they may be shared across engines); the
        serve launcher quantizes with donation before handing the codes
        over.

        prefill_chunk: tokens a new prompt advances per admission launch.
        Small chunks keep resident decode slots generating smoothly (low
        inter-token stall) at the cost of more launches per admitted prompt;
        a chunk >= the longest prompt degenerates to one-shot admission.
        Greedy outputs are identical either way (tested).

        max_queue: bound on the admission queue; beyond it `submit()`
        REJECTS (returns False) instead of queueing — backpressure the
        caller can see. None = unbounded (the historical behavior).

        max_replays: quarantine replays a request may consume before it is
        failed terminally (status "FAILED") instead of re-queued.

        deadline_steps / ttl_s: default per-request deadlines applied at
        submit() to requests that don't carry their own.

        paged / block_size / pool_blocks: block-pool KV residency. Every KV
        cache layer becomes a pool of `pool_blocks` blocks of `block_size`
        tokens (default pool: slots x (max_len / block_size) — the same
        token capacity as the per-slot stripes) plus a (slots, nblk) block
        table the host allocator owns. block_size doubles as the kernels'
        KV tile, so it wants the usual pallas tile alignment; it must
        divide max_len.

        swap_watermark: high-watermark fraction of the pool (0, 1] an
        admission may fill before the engine starts reclaiming: first LRU
        registry eviction, then PREEMPTION of strictly-lower-priority
        resident rows (their private blocks spill to the host block store
        and the request resumes byte-identically on re-admission). 1.0 (the
        default) reclaims only on hard exhaustion; below 1.0 the engine
        keeps `pool*(1-watermark)` blocks of headroom so a priority burst
        admits without deferral. Preemption needs victims of strictly lower
        priority — with uniform priorities the watermark only drives
        registry eviction."""
        if weight_format not in (None, "none"):
            params = T.quantize_params(params, weight_format)
        rfmt = T.resident_format(params)
        if rfmt is not None and (cfg.quant.weights != rfmt
                                 or not cfg.quant.resident):
            # pin the model policy to the residency format so the linears the
            # pass leaves dense fall back to the SAME fake-quant plane
            cfg = dataclasses.replace(
                cfg, quant=dataclasses.replace(cfg.quant, weights=rfmt,
                                               resident=True))
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk ({prefill_chunk}) must be >= 1")
        # a chunk wider than the cache can never fill: clamp so small-cache
        # engines work under the default without the caller minding the knob
        prefill_chunk = min(prefill_chunk, max_len)
        self.cfg = cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.policy = policy
        self.prefill_chunk = prefill_chunk
        self.max_queue = max_queue
        self.max_replays = max_replays
        self.deadline_steps = deadline_steps
        self.ttl_s = ttl_s
        self.queue: Deque[Request] = deque()
        self.finished: List[Request] = []
        self.stats = EngineStats()
        self.memory = None
        if cfg.family == "audio":
            assert frames is not None, "enc-dec serving needs audio frames"
            with self._policy_ctx():
                self.memory = jax.jit(
                    lambda p, f: _encode_memory(p, f, cfg))(params,
                                                            jnp.asarray(frames))
        # chunked prefill only works where every cache is positional (KV);
        # recurrent states advance one token per launch (the merged path)
        self._recurrent = any(k in _RECURRENT_KINDS
                              for k in cfg.block_kinds())
        # --- paged KV pool (block allocator + prefix registry) ---
        self._paged = bool(paged)
        if self._paged:
            if max_len % block_size:
                raise ValueError(
                    f"block_size ({block_size}) must divide max_len "
                    f"({max_len})")
            self._pg_bs = int(block_size)
            self._pg_nblk = max_len // block_size
            self._pg_pool = int(pool_blocks) if pool_blocks is not None \
                else slots * self._pg_nblk
            if self._pg_pool < self._pg_nblk:
                raise ValueError(
                    f"pool_blocks ({self._pg_pool}) cannot hold even one "
                    f"full row ({self._pg_nblk} blocks)")
            self._pg_free: List[int] = list(range(self._pg_pool))
            self._pg_ref = np.zeros(self._pg_pool, np.int64)
            self._pg_rows: List[List[int]] = [[] for _ in range(slots)]
            self._pg_table = np.zeros((slots, self._pg_nblk), np.int32)
            # prefix registry: sha1(prompt) -> {tokens, blocks, reg_tokens,
            # last_used}; entries hold their own block refs so a prefix
            # outlives its donor request until LRU eviction reclaims it
            self._pg_registry: Dict[str, dict] = {}
            self._pg_clock = 0
            self._pg_admits = 0
            self._pg_hits = 0
            self._pg_shared_tokens = 0
            self._pg_cow_copies = 0
            self._pg_evictions = 0
            self._pg_deferred = 0
            self._pg_evict_skips = 0
            if not (0.0 < swap_watermark <= 1.0):
                raise ValueError(
                    f"swap_watermark ({swap_watermark}) must be in (0, 1]")
            self._swap_watermark = float(swap_watermark)
            # free blocks held in reserve past the watermark: an admission
            # that would leave fewer free triggers reclaim (evict/preempt)
            self._pg_headroom = self._pg_pool - int(
                self._swap_watermark * self._pg_pool)
            # blocks a pool_pressure fault is holding off the free list:
            # [release_step | None, [block ids]] per unexpired squeeze
            self._pg_holds: List[list] = []
        # preemption/swap state (live only for paged engines, but always
        # present so pending()/snapshot() can consult it unconditionally).
        # Recurrent archs keep per-row state outside the block pool, so a
        # swapped row could not resume byte-identically — swap stays off.
        self._swap_enabled = self._paged and not self._recurrent
        self._preempted: List[Request] = []
        self._swap_entries: Dict[int, dict] = {}
        self._swap_store = HostBlockStore()
        # slots filled during the CURRENT _admit pass — never preemption
        # victims until their device state has actually materialized
        self._admit_protect: set = set()
        self._build_step_fns()
        # per-slot runtime state
        self.caches = T.init_caches(
            cfg, batch=slots, max_len=max_len,
            paged=(self._pg_pool, self._pg_bs) if self._paged else None)
        self._slot_req: List[Optional[Request]] = [None] * slots
        self._last = np.zeros((slots, 1), np.int32)
        self._remaining = np.zeros(slots, np.int64)
        self._prefilling = np.zeros(slots, bool)
        self._prefill_off = np.zeros(slots, np.int64)
        # fault-tolerance state
        self._step_no = 0
        self._fault_plan: Optional[faultlib.FaultPlan] = None
        self._degraded: List[dict] = []
        self._has_deadlines = deadline_steps is not None or ttl_s is not None

    def _step_program(self, p, c, t, lens, m):
        """The ONE traced step program: decode_step plus the fused numeric-
        health reduction. Health is a (slots,) bool — True where every logit
        of the row is finite — computed INSIDE the same jit so the guard is
        a fused reduction over values already in registers, never an extra
        launch or a host round-trip (`repro.analysis` HL205 pins this)."""
        logits, caches = T.decode_step(p, c, t, self.cfg, memory=m,
                                       lengths=lens)
        with jax.named_scope("health"):
            health = jnp.all(jnp.isfinite(logits), axis=(1, 2))
        return logits, caches, health

    def _build_step_fns(self):
        """(Re)build the step/reset/scrub jits. ONE traced step program
        serves decode (l=1) and chunk prefill (l=prefill_chunk): both are
        decode_step with a per-row `lengths` validity vector, so the jit
        cache holds exactly the two chunk shapes for the engine's whole
        lifetime. The cache pytree is donated on every call: the engine is
        the sole owner and always rebinds self.caches to the output, so XLA
        updates the (B, Hkv, max_len, D)-per-layer buffers in place instead
        of copying the whole KV residency each step. (On backends without
        donation support this is a no-op.) Called again after a route
        demotion: the policy is read at TRACE time, so a re-pinned policy
        needs fresh jits — a stale compiled step would keep the old route."""
        self._step_fn = jax.jit(self._step_program, donate_argnums=(1,))
        self._reset_fn = jax.jit(T.reset_slots, donate_argnums=(0,))
        self._scrub_fn = jax.jit(T.scrub_slots, donate_argnums=(0,))
        if getattr(self, "_paged", False):
            self._table_fn = jax.jit(T.set_block_tables, donate_argnums=(0,))
            self._cow_fn = jax.jit(T.copy_pool_blocks, donate_argnums=(0,))
            # swap-in scatter: fixed-width (nblk) slabs + sentinel-padded
            # dst, so restores trace once like the CoW copy
            self._swapin_fn = jax.jit(T.write_pool_blocks,
                                      donate_argnums=(0,))

    def _policy_ctx(self):
        return api.policy(self.policy) if self.policy is not None \
            else contextlib.nullcontext()

    def _merged_mode(self) -> bool:
        """Recurrent archs (and chunk=1 engines) advance prefill one token
        per launch — prefill and decode share a single l=1 launch."""
        return self._recurrent or self.prefill_chunk == 1

    # ------------------------------------------------------------ admission
    def submit(self, req: Request) -> bool:
        """Queue a request; True if admitted to the queue.

        Malformed requests raise immediately with a clear diagnostic instead
        of failing later inside a trace: empty or non-1-D prompts and
        non-integer prompt dtypes (ValueError/TypeError), non-int or
        negative max_new_tokens (0 is legal: emit nothing), and requests
        whose prompt + budget can never fit the preallocated cache rows —
        which also covers absurd max_new_tokens values.

        With `max_queue` set, a full queue REJECTS the request: status
        "REJECTED", `submit()` returns False, nothing is queued — the
        backpressure signal callers retry on."""
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1:
            raise ValueError(
                f"request {req.rid}: prompt must be a 1-D token-id vector, "
                f"got shape {tuple(prompt.shape)}")
        if prompt.shape[0] == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if not np.issubdtype(prompt.dtype, np.integer):
            raise TypeError(
                f"request {req.rid}: prompt dtype {prompt.dtype} is not an "
                f"integer token dtype")
        m = req.max_new_tokens
        if isinstance(m, bool) or not isinstance(m, (int, np.integer)):
            raise TypeError(
                f"request {req.rid}: max_new_tokens must be an int, got "
                f"{type(m).__name__} ({m!r})")
        if m < 0:
            raise ValueError(f"request {req.rid}: max_new_tokens < 0")
        p = req.priority
        if isinstance(p, bool) or not isinstance(p, (int, np.integer)):
            raise TypeError(
                f"request {req.rid}: priority must be an int, got "
                f"{type(p).__name__} ({p!r})")
        plen = int(prompt.shape[0])
        if plen + m > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt_len ({plen}) + max_new_tokens "
                f"({m}) exceeds the engine's max_len "
                f"({self.max_len}); shorten the request or grow the cache")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            req.status = "REJECTED"
            req.done = True
            self.stats.rejected_submits += 1
            return False
        req.prompt = prompt
        req.out_tokens = []
        req.done = False
        req.status = "queued"
        if req.deadline_steps is None:
            req.deadline_steps = self.deadline_steps
        if req.ttl_s is None:
            req.ttl_s = self.ttl_s
        req._submit_step = self._step_no
        req._submit_t = time.monotonic()
        if req.deadline_steps is not None or req.ttl_s is not None:
            self._has_deadlines = True
        self.queue.append(req)
        return True

    def _finish(self, slot: int, status: str = "done"):
        req = self._slot_req[slot]
        req.done = True
        req.status = status
        self.finished.append(req)
        self._slot_req[slot] = None
        self._remaining[slot] = 0
        self._prefilling[slot] = False
        if self._paged:
            self._pg_release_row(slot)

    def _admit(self, newly_finished: List[Request]) -> List[int]:
        """Assign queued requests to free slots and reset their cache rows;
        returns the slots filled.
        NO model call happens here — the prompts advance chunk by chunk in
        subsequent step()s, interleaved with everyone else's decode.

        Paged engines additionally RESERVE each request's whole block budget
        here (prefix-shared blocks counted out), fork the one partial
        boundary block copy-on-write, install the updated block tables and
        rewind the admitted rows to their shared-prefix frontier. A request
        whose reservation cannot be met even after LRU prefix eviction is
        DEFERRED at the queue head — FIFO order is preserved, and sustained
        pressure backs up into the bounded queue's REJECTED path.

        PREEMPTED rows re-admit FIRST, ahead of every fresh admission
        (highest priority first, preemption order within a priority): their
        swap-in reserves fresh blocks for the host-held portion, scatters
        the saved bytes back and rewinds the row to its saved frontier — no
        recompute, byte-identical resume."""
        admitted = []
        new_pos = np.zeros(self.slots, np.int32)
        cow_src: List[int] = []
        cow_dst: List[int] = []
        restores: List[tuple] = []        # (req, entry, dst blocks)
        deferred = False
        self._admit_protect = set()
        for s in range(self.slots):
            if deferred:
                break
            while self._slot_req[s] is None and \
                    (self._preempted or self.queue):
                if self._preempted:
                    i = self._best_preempted()
                    req = self._preempted[i]
                    got = self._pg_swap_in(s, req)
                    if got is None:
                        # still no room: the row keeps its place AHEAD of
                        # fresh admissions, and admission stops entirely
                        self._pg_deferred += 1
                        deferred = True
                        break
                    self._preempted.pop(i)
                    entry, dst = got
                    restores.append((req, entry, dst))
                    req.status = "active"
                    self._slot_req[s] = req
                    self._prefilling[s] = entry["prefilling"]
                    self._prefill_off[s] = entry["prefill_off"]
                    self._remaining[s] = entry["remaining"]
                    self._last[s, 0] = entry["last"]
                    new_pos[s] = entry["pos"]
                    admitted.append(s)
                    self._admit_protect.add(s)
                    continue
                req = self.queue.popleft()
                if req.max_new_tokens == 0:
                    # emit nothing: respect the limit without spending a
                    # single prefill launch on it
                    req.done = True
                    req.status = "done"
                    self.finished.append(req)
                    newly_finished.append(req)
                    continue
                covered = 0
                if self._paged:
                    got = self._pg_admit(s, req)
                    if got is None:
                        # pool can't hold the reservation: put the request
                        # back at the HEAD and stop admitting entirely so
                        # later (smaller) requests can't starve it
                        self.queue.appendleft(req)
                        self._pg_deferred += 1
                        deferred = True
                        break
                    covered, pairs = got
                    new_pos[s] = covered
                    for src, dst in pairs:
                        cow_src.append(src)
                        cow_dst.append(dst)
                req.status = "active"
                self._slot_req[s] = req
                if req.replays == 0:
                    self.stats.admissions += 1
                    self.stats.queue_wait_s += time.monotonic() - \
                        req._submit_t
                self._prefilling[s] = True
                self._prefill_off[s] = covered
                self._remaining[s] = req.max_new_tokens
                admitted.append(s)
                self._admit_protect.add(s)
        if admitted:
            reset = np.zeros(self.slots, bool)
            reset[admitted] = True
            if self._paged:
                if cow_src:
                    # fixed-width copy vectors (sentinel == pool size pads)
                    # so the jitted copy traces once, not once per fan-out
                    pad = np.full(self.slots, self._pg_pool, np.int32)
                    pad[:len(cow_src)] = cow_src
                    dst = np.full(self.slots, self._pg_pool, np.int32)
                    dst[:len(cow_dst)] = cow_dst
                    self.caches = self._cow_fn(self.caches, jnp.asarray(pad),
                                               jnp.asarray(dst))
                    self._pg_cow_copies += len(cow_src)
                self.caches = self._table_fn(self.caches,
                                             jnp.asarray(self._pg_table))
                self.caches = self._reset_fn(self.caches, jnp.asarray(reset),
                                             jnp.asarray(new_pos))
                # scatter swapped-out bytes back AFTER the table/pos install
                # so the restored frontier bounds exactly the restored bytes
                for req, entry, dst in restores:
                    self._pg_restore_blocks(entry, dst)
                    del self._swap_entries[req.rid]
                    self.stats.swap_ins += 1
            else:
                self.caches = self._reset_fn(self.caches, jnp.asarray(reset))
        return admitted

    # ------------------------------------------------------ paged block pool
    def _pg_key(self, prompt: np.ndarray) -> str:
        return hashlib.sha1(
            np.ascontiguousarray(prompt, np.int32).tobytes()).hexdigest()

    def _pg_release_row(self, slot: int):
        """Drop the slot's references; blocks nobody else holds go back to
        the free list (kept sorted so allocation order is deterministic)."""
        for b in self._pg_rows[slot]:
            self._pg_ref[b] -= 1
            if self._pg_ref[b] == 0:
                bisect.insort(self._pg_free, b)
        self._pg_rows[slot] = []

    def _pg_evict(self, target_free: int, protect=None):
        """LRU-evict registry prefixes until `target_free` blocks are free.
        Only the registry's own references are dropped — blocks still shared
        with an active row stay resident until that row finishes. An entry
        whose blocks are ALL pinned by in-flight sharers is SKIPPED, not
        evicted: dropping it would free nothing now and destroy sharing a
        resident row is actively using, while a colder-but-unpinned prefix
        further down the LRU order can actually yield blocks (skips are
        counted in pool_stats). `protect` shields the entry the current
        admission is about to share from being reclaimed out from under it."""
        order = sorted(self._pg_registry.items(),
                       key=lambda kv: kv[1]["last_used"])
        for key, ent in order:
            if len(self._pg_free) >= target_free:
                break
            if ent is protect:
                continue
            if all(self._pg_ref[b] > 1 for b in ent["blocks"]):
                self._pg_evict_skips += 1
                continue
            for b in ent["blocks"]:
                self._pg_ref[b] -= 1
                if self._pg_ref[b] == 0:
                    bisect.insort(self._pg_free, b)
            del self._pg_registry[key]
            self._pg_evictions += 1

    def _pg_lookup(self, prompt: np.ndarray):
        """Longest usable shared prefix across the registry: (entry, covered)
        with covered capped at prompt_len - 1 so the admitted row always
        prefills at least its last prompt token (its first sampled logits
        must come from its own launch), or (None, 0)."""
        plen = int(prompt.shape[0])
        best, best_cov = None, 0
        for ent in self._pg_registry.values():
            toks = ent["tokens"]
            n = min(len(toks), plen)
            neq = np.flatnonzero(toks[:n] != prompt[:n])
            common = int(neq[0]) if neq.size else n
            cov = min(common, plen - 1, ent["reg_tokens"])
            if cov > best_cov:
                best, best_cov = ent, cov
        return best, best_cov

    def _pg_admit(self, slot: int, req: Request):
        """Reserve the row's whole block budget: shared prefix blocks by
        reference, the partial boundary block by copy-on-write fork, the
        rest fresh. Returns (covered, [(src, dst) copies]) or None when the
        pool cannot hold the reservation even after eviction."""
        bs = self._pg_bs
        prompt = np.asarray(req.prompt)
        plen = int(prompt.shape[0])
        total = -(-(plen + int(req.max_new_tokens)) // bs)
        total = min(total, self._pg_nblk)
        ent, covered = self._pg_lookup(prompt)
        shared_full = covered // bs
        fresh_needed = total - shared_full
        # soft target = the reservation plus the watermark headroom: past
        # the high watermark, reclaim cold registry prefixes first, then
        # preempt strictly-lower-priority residents to host memory. The
        # HARD gate stays fresh_needed — the watermark is best-effort, an
        # admission that fits is never deferred just to keep headroom.
        want_free = fresh_needed + self._pg_headroom
        if len(self._pg_free) < want_free:
            self._pg_evict(want_free, protect=ent)
            if len(self._pg_free) < want_free:
                self._pg_preempt_for(req.priority, want_free)
        if len(self._pg_free) < fresh_needed:
            return None
        blocks: List[int] = []
        pairs: List[tuple] = []
        if ent is not None and covered > 0:
            for b in ent["blocks"][:shared_full]:
                self._pg_ref[b] += 1
                blocks.append(b)
            if covered % bs:
                # the boundary block is only PARTLY covered by the prefix —
                # this row will write positions >= covered into it, so it
                # gets a private fork (the copy-on-write in "prefix sharing
                # is copy-on-write": the one shared block a row would ever
                # write is forked before any write can land)
                src = ent["blocks"][shared_full]
                dst = self._pg_free.pop(0)
                self._pg_ref[dst] = 1
                blocks.append(dst)
                pairs.append((src, dst))
            ent["last_used"] = self._pg_clock
            self._pg_clock += 1
            self._pg_hits += 1
            self._pg_shared_tokens += covered
        while len(blocks) < total:
            b = self._pg_free.pop(0)
            self._pg_ref[b] = 1
            blocks.append(b)
        self._pg_rows[slot] = blocks
        # unreserved tail entries repeat the first block: the kernels never
        # read past the reservation (pos bounds the visited blocks), but
        # scrub derives its block mask from the WHOLE table row, so padding
        # must point at blocks this row owns, never at a neighbour's
        row = np.full(self._pg_nblk, blocks[0], np.int32)
        row[:len(blocks)] = blocks
        self._pg_table[slot] = row
        self._pg_admits += 1
        return covered, pairs

    def _pg_register(self, slot: int):
        """Register a freshly-prefilled prompt in the prefix registry: the
        blocks covering [0, prompt_len) gain a registry reference so the
        prefix survives its donor request. Decode tokens the donor appends
        beyond prompt_len may land in the registered tail block — harmless,
        a future sharer forks that block and re-prefills past `covered`."""
        req = self._slot_req[slot]
        prompt = np.asarray(req.prompt)
        key = self._pg_key(prompt)
        ent = self._pg_registry.get(key)
        if ent is not None:
            ent["last_used"] = self._pg_clock
            self._pg_clock += 1
            return
        nb = -(-int(prompt.shape[0]) // self._pg_bs)
        blocks = list(self._pg_rows[slot][:nb])
        for b in blocks:
            self._pg_ref[b] += 1
        self._pg_registry[key] = {
            "tokens": prompt.astype(np.int32).copy(),
            "blocks": blocks,
            "reg_tokens": int(prompt.shape[0]),
            "last_used": self._pg_clock,
        }
        self._pg_clock += 1

    def _pg_extend_bad(self, bad_slots) -> np.ndarray:
        """Close a quarantine set over block sharing: scrubbing a bad row
        zeroes every block its table references, including prefix blocks
        OTHER rows share — those rows are corrupted too and must replay.
        Registry entries touching a scrubbed block are dropped (their
        values are gone). Returns (closed slot list, scrubbed block set) —
        the caller also invalidates swap entries whose KEPT blocks got
        scrubbed."""
        bad = set(int(s) for s in bad_slots
                  if self._slot_req[int(s)] is not None)
        scrubbed = set()
        for s in bad:
            scrubbed.update(self._pg_rows[s])
        changed = True
        while changed:
            changed = False
            for s in range(self.slots):
                if s in bad or self._slot_req[s] is None:
                    continue
                if scrubbed.intersection(self._pg_rows[s]):
                    bad.add(s)
                    scrubbed.update(self._pg_rows[s])
                    changed = True
        for key in [k for k, ent in self._pg_registry.items()
                    if scrubbed.intersection(ent["blocks"])]:
            ent = self._pg_registry.pop(key)
            for b in ent["blocks"]:
                self._pg_ref[b] -= 1
                if self._pg_ref[b] == 0:
                    bisect.insort(self._pg_free, b)
        return np.asarray(sorted(bad), np.int64), scrubbed

    # --------------------------------------------- swap-out / preemption
    def _pg_row_pos(self, slot: int) -> int:
        """The row's device-side write frontier — read from the first paged
        cache leaf (identical across layers). Ground truth for the resume
        point: works mid-prefill, mid-decode, and for merged-mode steps."""
        for c in jax.tree_util.tree_leaves(
                self.caches, is_leaf=lambda x: isinstance(x, T._PAGED_TYPES)):
            if isinstance(c, T._PAGED_TYPES):
                return int(np.asarray(c.pos)[0, slot])
        raise RuntimeError("paged engine has no paged cache leaf")

    def _pg_swap_template(self):
        """(treedef, leaf avals) of a single-block gather over THIS engine's
        caches — the layout every host-stored block must match. Snapshot
        restore uses it to rebuild (and reject mismatched) swap-store
        contents."""
        t = T.gather_pool_blocks(self.caches, jnp.zeros((1,), jnp.int32))
        return (jax.tree.structure(t),
                [(tuple(a.shape), str(a.dtype)) for a in jax.tree.leaves(t)])

    def _pg_victims(self, prio: int) -> List[int]:
        """Resident rows preemptible by an admission at priority `prio`,
        cheapest-to-evict first. Only STRICTLY lower priorities qualify —
        equal never preempts equal, the hysteresis that keeps two rows from
        thrashing each other in and out of residency. Order: lowest
        priority, then most deadline slack (no deadline sorts as infinite
        slack), then most immediately-freeable blocks."""
        cands = []
        for s in range(self.slots):
            r = self._slot_req[s]
            if r is None or r.priority >= prio:
                continue
            if s in self._admit_protect:
                # admitted IN THIS admission pass: its device state (reset,
                # CoW, restore scatter, prefill) has not materialized yet,
                # so a swap-out would gather stale bytes — and instantly
                # preempting a row just admitted is thrash anyway
                continue
            freeable = sum(1 for b in self._pg_rows[s]
                           if self._pg_ref[b] == 1)
            slack = (float("inf") if r.deadline_steps is None
                     else r.deadline_steps - (self._step_no - r._submit_step))
            cands.append(((r.priority, -slack, -freeable), s))
        return [s for _, s in sorted(cands)]

    def _pg_preempt_for(self, prio: int, want_free: int):
        """Swap out strictly-lower-priority resident rows until `want_free`
        blocks are free or no eligible victims remain."""
        if not self._swap_enabled:
            return
        for s in self._pg_victims(prio):
            if len(self._pg_free) >= want_free:
                break
            self._pg_swap_out(s)

    def _best_preempted(self) -> int:
        """Index of the next PREEMPTED request to re-admit: highest
        priority first, preemption order within a priority."""
        return max(range(len(self._preempted)),
                   key=lambda i: (self._preempted[i].priority, -i))

    def _pg_swap_out(self, slot: int):
        """Preempt the resident row: gather its PRIVATE blocks device->host
        (outside the jitted step — the step trace stays transfer-free,
        HL206) into the host block store and free them; blocks shared with
        the registry or other rows are NOT swapped (their bytes stay
        resident either way — swapping would duplicate them and eviction
        could then tear them from under the sharers), the swap entry just
        keeps holding the row's reference on them. The request parks in
        PREEMPTED state and re-admits ahead of fresh admissions."""
        req = self._slot_req[slot]
        blocks = self._pg_rows[slot]
        kept: List[tuple] = []        # (logical j, physical block)
        priv_j: List[int] = []
        priv_b: List[int] = []
        for j, b in enumerate(blocks):
            if self._pg_ref[b] > 1:
                kept.append((j, int(b)))
            else:
                priv_j.append(j)
                priv_b.append(int(b))
        hids: List[int] = []
        if priv_b:
            ids = jnp.asarray(np.asarray(priv_b, np.int32))
            slabs = jax.device_get(T.gather_pool_blocks(self.caches, ids))
            hids = self._swap_store.put(slabs, len(priv_b))
            self.stats.swap_outs += 1
        self._swap_entries[req.rid] = {
            "kept": kept, "js": priv_j, "hids": hids,
            "total": len(blocks),
            "pos": self._pg_row_pos(slot),
            "prefilling": bool(self._prefilling[slot]),
            "prefill_off": int(self._prefill_off[slot]),
            "remaining": int(self._remaining[slot]),
            "last": int(self._last[slot, 0]),
        }
        for b in priv_b:
            self._pg_ref[b] -= 1
            bisect.insort(self._pg_free, b)
        self._pg_rows[slot] = []
        self._slot_req[slot] = None
        self._remaining[slot] = 0
        self._prefilling[slot] = False
        self._prefill_off[slot] = 0
        req.status = "PREEMPTED"
        self._preempted.append(req)
        self.stats.preemptions += 1

    def _pg_swap_in(self, slot: int, req: Request):
        """Reserve residency for a PREEMPTED row's host-held portion —
        registry eviction, then preemption of rows strictly below
        `req.priority`, may run to make room — and rebuild the row's
        logical block list around the references it kept. Returns
        (entry, dst blocks) for the caller's scatter, or None when the pool
        still can't hold it (the row defers, still ahead of fresh
        admissions)."""
        entry = self._swap_entries[req.rid]
        fresh_needed = len(entry["js"])
        want_free = fresh_needed + self._pg_headroom
        if len(self._pg_free) < want_free:
            self._pg_evict(want_free)
            if len(self._pg_free) < want_free:
                self._pg_preempt_for(req.priority, want_free)
        if len(self._pg_free) < fresh_needed:
            return None
        row_blocks: List[int] = [-1] * entry["total"]
        for j, b in entry["kept"]:
            row_blocks[j] = b
        dst: List[int] = []
        for j in entry["js"]:
            b = self._pg_free.pop(0)
            self._pg_ref[b] = 1
            row_blocks[j] = b
            dst.append(b)
        self._pg_rows[slot] = row_blocks
        row = np.full(self._pg_nblk, row_blocks[0], np.int32)
        row[:len(row_blocks)] = row_blocks
        self._pg_table[slot] = row
        return entry, dst

    def _pg_restore_blocks(self, entry: dict, dst: List[int]):
        """Scatter the host-held block bytes into the freshly reserved
        physical blocks — ONE fixed-width jitted scatter (slabs padded to
        nblk, dst sentinel-padded, same discipline as the CoW copy, so
        restores trace once) — then drop them from the host store."""
        if not dst:
            return
        slabs = self._swap_store.get(entry["hids"])
        pad_n = self._pg_nblk - len(dst)
        if pad_n:
            slabs = jax.tree.map(
                lambda a: np.concatenate(
                    [a, np.zeros(a.shape[:1] + (pad_n,) + a.shape[2:],
                                 a.dtype)], axis=1), slabs)
        dvec = np.full(self._pg_nblk, self._pg_pool, np.int32)
        dvec[:len(dst)] = dst
        self.caches = self._swapin_fn(self.caches, slabs, jnp.asarray(dvec))
        self._swap_store.free(entry["hids"])

    def _drop_swap_entry(self, req: Request):
        """Release everything a PREEMPTED request holds: its kept block
        references and its host-store bytes. Used when the request expires
        or its kept blocks get scrubbed by a quarantine."""
        entry = self._swap_entries.pop(req.rid, None)
        if entry is None:
            return
        for _, b in entry["kept"]:
            self._pg_ref[b] -= 1
            if self._pg_ref[b] == 0:
                bisect.insort(self._pg_free, b)
        self._swap_store.free(entry["hids"])

    def _pg_apply_pressure(self, fault) -> bool:
        """pool_pressure fault: squeeze the effective free list down to
        `fault.blocks` blocks by holding the rest aside (released after
        `fault.duration` steps; None = held forever) — the deterministic
        lever that forces the eviction/preemption/swap path on demand."""
        if not self._paged:
            return False
        keep = max(0, int(fault.blocks))
        n_hold = max(0, len(self._pg_free) - keep)
        if n_hold == 0:
            return False
        # pop from the tail so the held set is deterministic and the
        # low-numbered blocks the allocator prefers stay available
        held = [self._pg_free.pop() for _ in range(n_hold)]
        release = None if fault.duration is None \
            else self._step_no + int(fault.duration)
        self._pg_holds.append([release, held])
        return True

    def _pg_release_pressure(self):
        """Return expired pool_pressure holds to the free list."""
        keep = []
        for release, held in self._pg_holds:
            if release is not None and self._step_no >= release:
                for b in held:
                    bisect.insort(self._pg_free, b)
            else:
                keep.append([release, held])
        self._pg_holds = keep

    def pool_stats(self) -> dict:
        """Block-pool utilization + prefix-sharing counters (the BENCH_kv
        currency). Zeros-shaped dict for non-paged engines so callers can
        report unconditionally."""
        if not self._paged:
            return {"paged": False}
        used = self._pg_pool - len(self._pg_free)
        return {
            "paged": True,
            "pool_blocks": self._pg_pool,
            "block_size": self._pg_bs,
            "used_blocks": used,
            "free_blocks": len(self._pg_free),
            "occupancy": used / self._pg_pool,
            "registry_entries": len(self._pg_registry),
            "admitted": self._pg_admits,
            "prefix_hits": self._pg_hits,
            "prefix_hit_rate": (self._pg_hits / self._pg_admits
                                if self._pg_admits else 0.0),
            "shared_tokens": self._pg_shared_tokens,
            "cow_copies": self._pg_cow_copies,
            "evictions": self._pg_evictions,
            "eviction_skips": self._pg_evict_skips,
            "deferred_admissions": self._pg_deferred,
            # --- memory-pressure / swap surface ---
            "swap_watermark": self._swap_watermark,
            "watermark_blocks": self._pg_pool - self._pg_headroom,
            "preemptions": self.stats.preemptions,
            "swap_outs": self.stats.swap_outs,
            "swap_ins": self.stats.swap_ins,
            "preempted_now": len(self._preempted),
            "host_blocks": len(self._swap_store),
            "host_bytes": self._swap_store.nbytes(),
            "swap_bytes_out": self._swap_store.bytes_out,
            "swap_bytes_in": self._swap_store.bytes_in,
            "pressure_held": sum(len(h) for _, h in self._pg_holds),
        }

    # -------------------------------------------------------- fault surface
    def arm_fault_plan(self, plan: Optional[faultlib.FaultPlan]):
        """Arm (or disarm, with None) a fault-injection plan. The engine
        consults it at step start (latency, kv/weight poison) and at every
        launch (launch faults, logits poison)."""
        self._fault_plan = plan
        return self

    @property
    def step_no(self) -> int:
        """Engine steps taken so far — the fault plan's step coordinate.
        Advances on EVERY step(), including idle ones, so a plan's future
        coordinates are always reachable."""
        return self._step_no

    def degraded_routes(self) -> tuple:
        """Every route-demotion event so far, oldest first: dicts of the
        step, the error, and the decode/prefill routes before and after."""
        return tuple(self._degraded)

    def _inject_pre_step(self, plan: faultlib.FaultPlan, step: int):
        """Host-side faults due before this step's launches: latency stalls
        and device-state poison (KV rows, shared weights)."""
        for f in plan.take("latency", step):
            f.tripped = True
            time.sleep(f.delay_s)
        for f in plan.take("poison", step, target="kv"):
            if f.slot is None:
                continue
            self.caches = faultlib.poison_caches(self.caches, int(f.slot),
                                                 f.value)
            f.tripped = True
        for f in plan.take("poison", step, target="weight"):
            self.params = faultlib.poison_weights(self.params, f.value)
            f.tripped = True
        for f in plan.take("pool_pressure", step):
            f.tripped = self._pg_apply_pressure(f)

    def _launch(self, toks, lens, consumed=None):
        """Every model launch funnels through here: the kernel-launch fault
        boundary, the demote-and-retry recovery, and logits poison.

        Returns (logits, health) DEVICE arrays; rebinds self.caches only on
        a successful launch (a failed trace never consumes the donated
        buffers, so the retry reuses them safely). On failure the engine
        demotes its policy to the ref backend and retries the SAME step
        once; a failure with no safe route left propagates.

        `consumed` is the per-slot "this launch's logits are read for this
        row" mask — logits-poison faults fire only on a consuming launch so
        every injected fault is observable."""
        plan = self._fault_plan
        step = self._step_no
        raise_fault = hook_fault = None
        if plan is not None:
            for f in plan.take("launch", step):
                if f.boundary == "dispatch":
                    hook_fault = f
                else:
                    f.tripped = True
                    raise_fault = f
        for attempt in (0, 1):
            try:
                if raise_fault is not None and attempt == 0:
                    raise faultlib.KernelLaunchError(
                        f"injected kernel-launch failure at step {step} "
                        f"({raise_fault.describe()})")
                ctx = contextlib.nullcontext()
                if hook_fault is not None and attempt == 0:
                    ctx = api.dispatch_intercepted(
                        _dispatch_raiser(hook_fault))
                with ctx, self._policy_ctx():
                    logits, caches, health = self._step_fn(
                        self.params, self.caches, toks, lens, self.memory)
                self.caches = caches
                break
            except Exception as err:
                if attempt == 1 or not self._demote(err):
                    raise
        if plan is not None and consumed is not None:
            poisoned = plan.take_due(
                "poison", step, target="logits",
                pred=lambda f: f.slot is not None and bool(consumed[f.slot]))
            for f in poisoned:
                logits = faultlib.poison_logits(logits, int(f.slot), f.value)
                f.tripped = True
            if poisoned:
                health = jnp.all(jnp.isfinite(logits), axis=(1, 2))
        return logits, health

    def _demote(self, err: Exception) -> bool:
        """Re-pin the engine's policy to the safe (ref) route after a launch
        failure and rebuild the step jits. False when there is no route
        below the current one (already ref) — the caller re-raises."""
        pol = self.policy if self.policy is not None else api.default_policy
        if not pol.use_pallas():
            return False
        event = {
            "step": int(self._step_no),
            "error": f"{type(err).__name__}: {err}",
            "from": {"decode": self.decode_route(),
                     "prefill": self.prefill_route()},
        }
        self.policy = pol.demoted()
        self._build_step_fns()
        event["to"] = {"decode": self.decode_route(),
                       "prefill": self.prefill_route()}
        self._degraded.append(event)
        self.stats.demotions += 1
        return True

    def _quarantine(self, bad_slots, newly: List[Request]):
        """Evict poisoned slots: scrub their cache rows (values AND
        positions — see `scrub_slots`) and replay each request from its
        retained prompt at the FRONT of the queue, byte-identically; a
        request whose replay budget is spent fails terminally instead.

        Paged engines first CLOSE the bad set over block sharing (scrubbing
        a row's blocks corrupts every co-sharing row) and drop registry
        prefixes whose blocks get scrubbed — a quarantined NaN must never
        leak through a shared block into another tenant's row. A PREEMPTED
        request whose KEPT (still-resident shared) blocks get scrubbed
        loses its resume point the same way: its swap entry is dropped and
        it replays from its prompt."""
        scrubbed = set()
        if self._paged:
            bad_slots, scrubbed = self._pg_extend_bad(bad_slots)
        mask = np.zeros(self.slots, bool)
        for s in bad_slots:
            req = self._slot_req[s]
            if req is None:
                continue
            mask[s] = True
            self.stats.quarantines += 1
            self._slot_req[s] = None
            self._remaining[s] = 0
            self._prefilling[s] = False
            self._prefill_off[s] = 0
            self._last[s, 0] = 0
            if self._paged:
                # host bookkeeping only — the DEVICE table still points at
                # the blocks, which is exactly what scrub_slots needs to
                # derive its block mask below
                self._pg_release_row(s)
            req.replays += 1
            if req.replays > self.max_replays:
                req.status = "FAILED"
                req.done = True
                self.stats.failed_requests += 1
                self.finished.append(req)
                newly.append(req)
            else:
                req.out_tokens = []
                req.status = "queued"
                self.queue.appendleft(req)
        if scrubbed:
            for req in [r for r in self._preempted
                        if scrubbed.intersection(
                            b for _, b in self._swap_entries[r.rid]["kept"])]:
                self._preempted.remove(req)
                self._drop_swap_entry(req)
                self.stats.quarantines += 1
                req.replays += 1
                if req.replays > self.max_replays:
                    req.status = "FAILED"
                    req.done = True
                    self.stats.failed_requests += 1
                    self.finished.append(req)
                    newly.append(req)
                else:
                    req.out_tokens = []
                    req.status = "queued"
                    self.queue.appendleft(req)
        if mask.any():
            self.caches = self._scrub_fn(self.caches, jnp.asarray(mask))

    def _expired(self, req: Request, now: float) -> bool:
        if req.deadline_steps is not None and \
                self._step_no - req._submit_step >= req.deadline_steps:
            return True
        if req.ttl_s is not None and now - req._submit_t > req.ttl_s:
            return True
        return False

    def _expire_deadlines(self, newly: List[Request]):
        """Finish expired requests with status TIMEOUT — queued ones (never
        reached a slot in time), resident ones (slot freed, cache row
        reclaimed by the next admit's reset), and PREEMPTED ones (kept
        block references and host-store bytes released)."""
        now = time.monotonic()
        kept_p: List[Request] = []
        for req in self._preempted:
            if self._expired(req, now):
                self._drop_swap_entry(req)
                req.status = "TIMEOUT"
                req.done = True
                self.stats.timeouts += 1
                self.finished.append(req)
                newly.append(req)
            else:
                kept_p.append(req)
        self._preempted = kept_p
        kept: Deque[Request] = deque()
        while self.queue:
            req = self.queue.popleft()
            if self._expired(req, now):
                req.status = "TIMEOUT"
                req.done = True
                self.stats.timeouts += 1
                self.finished.append(req)
                newly.append(req)
            else:
                kept.append(req)
        self.queue = kept
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is not None and self._expired(req, now):
                self.stats.timeouts += 1
                self._finish(s, status="TIMEOUT")
                newly.append(req)

    # -------------------------------------------------------------- stepping
    def _emit_first(self, s: int, tok: int, newly: List[Request]):
        """Record a freshly-completed prefill's first sampled token."""
        req = self._slot_req[s]
        if self._paged:
            # the prompt's K/V is fully resident NOW — register the prefix
            # before the finish check so even a max_new_tokens == 1 request
            # donates its prompt to future admissions
            self._pg_register(s)
        req.out_tokens.append(tok)
        self.stats.generated_tokens += 1
        self._remaining[s] -= 1
        self._last[s, 0] = tok
        if self._remaining[s] <= 0 or (self.eos_id is not None
                                       and tok == self.eos_id):
            self._finish(s)
            newly.append(req)

    def _occupied(self) -> np.ndarray:
        return np.asarray([r is not None for r in self._slot_req])

    def _prefill_chunk_step(self, newly: List[Request]):
        """ONE chunk-shaped prefill launch: every prefilling row advances by
        up to `prefill_chunk` prompt tokens (right-padded, `lengths` marking
        the real count); decoding/free rows ride along with lengths == 0 and
        keep their caches untouched."""
        c = self.prefill_chunk
        with _span("engine.launch", lambda: {"kind": "chunk"}):
            toks = np.full((self.slots, c), PAD, np.int32)
            lens = np.zeros(self.slots, np.int32)
            finishing = []
            for s, r in enumerate(self._slot_req):
                if r is None or not self._prefilling[s]:
                    continue
                off = int(self._prefill_off[s])
                take = min(c, len(r.prompt) - off)
                toks[s, :take] = r.prompt[off:off + take]
                lens[s] = take
                if off + take >= len(r.prompt):
                    finishing.append(s)
            consumed = np.zeros(self.slots, bool)
            consumed[finishing] = True
            logits, health_dev = self._launch(jnp.asarray(toks),
                                              jnp.asarray(lens),
                                              consumed=consumed)
        self.stats.prefill_chunk_calls += 1
        self.stats.prefill_tokens += int(lens.sum())
        bad = np.zeros(self.slots, bool)
        if finishing:
            # only launches that COMPLETE a prompt consume logits; mid-prompt
            # chunks skip the sync + transfer entirely (health rides the same
            # rule: a poisoned row surfaces at its finishing launch, where
            # the NaN has propagated through attention). Gather + argmax run
            # ON DEVICE: only (slots,) int32 crosses to host, never a logits
            # block
            with _span("engine.sync"):
                idx = jnp.asarray(np.clip(lens - 1, 0, c - 1))
                last = jnp.take_along_axis(logits, idx[:, None, None],
                                           axis=1)[:, 0]
                first_tok = np.asarray(jnp.argmax(last, axis=-1))
                bad = self._occupied() & ~np.asarray(health_dev)
        with _span("engine.emit"):
            for s, r in enumerate(self._slot_req):
                if r is None or not self._prefilling[s]:
                    continue
                self._prefill_off[s] += lens[s]
            if bad.any():
                self._quarantine(np.flatnonzero(bad), newly)
            for s in finishing:
                if bad[s] or self._slot_req[s] is None:
                    continue
                self._prefilling[s] = False
                self._emit_first(s, int(first_tok[s]), newly)

    def _decode_launch(self, newly: List[Request]):
        """ONE batched decode launch for every mid-generation slot;
        prefilling/free rows pass lengths == 0 and sit the launch out."""
        active = np.asarray(
            [r is not None and not self._prefilling[s]
             for s, r in enumerate(self._slot_req)])
        if not active.any():
            return
        with _span("engine.launch", lambda: {"kind": "decode"}):
            logits, health_dev = self._launch(
                jnp.asarray(self._last), jnp.asarray(active.astype(np.int32)),
                consumed=active)
        self.stats.decode_steps += 1
        # the guard consumes health at this already-syncing point: any
        # occupied row gone non-finite (its own logits, or a poisoned cache
        # surfacing through a ride-along row) is quarantined, its token
        # never emitted
        with _span("engine.sync"):
            nxt = np.asarray(jnp.argmax(logits[:, -1],
                                        axis=-1)).astype(np.int32)
            bad = self._occupied() & ~np.asarray(health_dev)
        with _span("engine.emit"):
            if bad.any():
                self._quarantine(np.flatnonzero(bad), newly)
            for s in range(self.slots):
                req = self._slot_req[s]
                if req is None or not active[s] or bad[s]:
                    continue
                tok = int(nxt[s])
                req.out_tokens.append(tok)
                self.stats.generated_tokens += 1
                self._remaining[s] -= 1
                if self._remaining[s] <= 0 or (self.eos_id is not None
                                               and tok == self.eos_id):
                    self._finish(s)
                    newly.append(req)
                else:
                    self._last[s, 0] = tok

    def _merged_step(self, newly: List[Request]):
        """Recurrent archs / chunk=1: ONE l=1 launch advances everything —
        prefilling rows feed their next prompt token, decoding rows their
        last sampled one. Counted as a decode step when any row decoded,
        else as a prefill token step."""
        with _span("engine.launch", lambda: {"kind": "merged"}):
            toks = np.full((self.slots, 1), PAD, np.int32)
            lens = np.zeros(self.slots, np.int32)
            consumed = np.zeros(self.slots, bool)
            n_prefill = n_decode = 0
            for s, r in enumerate(self._slot_req):
                if r is None:
                    continue
                lens[s] = 1
                if self._prefilling[s]:
                    toks[s, 0] = r.prompt[int(self._prefill_off[s])]
                    consumed[s] = self._prefill_off[s] + 1 >= len(r.prompt)
                    n_prefill += 1
                else:
                    toks[s, 0] = self._last[s, 0]
                    consumed[s] = True
                    n_decode += 1
            logits, health_dev = self._launch(jnp.asarray(toks),
                                              jnp.asarray(lens),
                                              consumed=consumed)
        if n_decode:
            self.stats.decode_steps += 1
        else:
            self.stats.prefill_token_steps += 1
        self.stats.prefill_tokens += n_prefill
        # argmax ON DEVICE: only (slots,) int32 crosses to host — the first
        # token of a finishing prefill row IS its argmax, same as a decode
        # row's, so one vector serves both
        with _span("engine.sync"):
            nxt = np.asarray(jnp.argmax(logits[:, 0],
                                        axis=-1)).astype(np.int32)
            bad = self._occupied() & ~np.asarray(health_dev)
        with _span("engine.emit"):
            if bad.any():
                self._quarantine(np.flatnonzero(bad), newly)
            for s in range(self.slots):
                req = self._slot_req[s]
                if req is None or bad[s]:
                    continue
                if self._prefilling[s]:
                    self._prefill_off[s] += 1
                    if self._prefill_off[s] >= len(req.prompt):
                        self._prefilling[s] = False
                        self._emit_first(s, int(nxt[s]), newly)
                    continue
                tok = int(nxt[s])
                req.out_tokens.append(tok)
                self.stats.generated_tokens += 1
                self._remaining[s] -= 1
                if self._remaining[s] <= 0 or (self.eos_id is not None
                                               and tok == self.eos_id):
                    self._finish(s)
                    newly.append(req)
                else:
                    self._last[s, 0] = tok

    # --------------------------------------------------------------- driving
    def step(self) -> List[Request]:
        """Admit into free slots, then advance every in-flight request once:
        one chunk-prefill launch for admitting rows (when any) interleaved
        with one batched decode launch for generating rows (when any).
        Returns the requests that finished during this step (including ones
        that TIMED OUT or FAILED). The step counter advances on every call,
        busy or idle."""
        with _span("engine.step", lambda: {"step": self._step_no}):
            newly: List[Request] = []
            plan = self._fault_plan
            if plan is not None:
                self._inject_pre_step(plan, self._step_no)
            if self._paged and self._pg_holds:
                self._pg_release_pressure()
            if self._has_deadlines:
                self._expire_deadlines(newly)
            with _span("engine.admit") as span:
                admitted = self._admit(newly)
                if admitted and span.is_enabled():
                    span.set_metadata(rids=" ".join(
                        str(self._slot_req[s].rid) for s in admitted))
            if any(r is not None for r in self._slot_req):
                if self._merged_mode():
                    self._merged_step(newly)
                else:
                    if self._prefilling.any():
                        self._prefill_chunk_step(newly)
                    self._decode_launch(newly)
            self._step_no += 1
        return newly

    def pending(self) -> bool:
        return bool(self.queue) or bool(self._preempted) \
            or any(r is not None for r in self._slot_req)

    def run_until_drained(self, max_steps: int = 100000) -> List[Request]:
        for _ in range(max_steps):
            if not self.pending():
                break
            self.step()
        else:
            raise EngineStalledError(
                f"engine not drained after {max_steps} steps",
                stuck=[o for o in self.occupancy() if o is not None],
                queue_depth=len(self.queue))
        return self.finished

    def warmup(self) -> "ServingEngine":
        """Trace + compile the engine's step programs BEFORE the first
        request: one decode-shaped (l=1) and — for chunked archs — one
        chunk-shaped launch with every row idle (`lengths == 0` keeps all
        cache values and positions bitwise intact), so the first real
        request doesn't eat the compile stall. Idempotent; returns self."""
        zeros = jnp.zeros((self.slots,), jnp.int32)
        widths = (1,) if self._merged_mode() else (self.prefill_chunk, 1)
        with self._policy_ctx():
            for w in widths:
                tok = jnp.zeros((self.slots, w), jnp.int32)
                _, self.caches, _ = self._step_fn(self.params, self.caches,
                                                  tok, zeros, self.memory)
        return self

    # ------------------------------------------------------- snapshot/restore
    def snapshot(self, ckpt_dir, *, step: Optional[int] = None,
                 include_params: bool = False) -> str:
        """Persist the full engine state through `repro.checkpoint.store`:
        the cache pytree as the checkpoint tree (plus the params when
        `include_params` — the recovery lever for weight corruption), and
        every piece of host bookkeeping — per-slot requests, queue, stats,
        last-token vector — as the JSON `extra`. Atomic (tmp dir + rename),
        same as training checkpoints. Returns the checkpoint path."""
        from ..checkpoint import store
        tree = {"caches": self.caches}
        if include_params:
            tree["params"] = self.params

        def reqstate(r: Request) -> dict:
            return {"rid": r.rid, "prompt": np.asarray(r.prompt).tolist(),
                    "max_new_tokens": int(r.max_new_tokens),
                    "out_tokens": list(r.out_tokens or []),
                    "status": r.status, "replays": int(r.replays),
                    "deadline_steps": r.deadline_steps,
                    "ttl_s": r.ttl_s, "priority": int(r.priority),
                    "submit_step": int(r._submit_step)}

        extra = {"engine": {
            "step_no": int(self._step_no),
            "include_params": include_params,
            "last": self._last.tolist(),
            "remaining": self._remaining.tolist(),
            "prefilling": self._prefilling.tolist(),
            "prefill_off": self._prefill_off.tolist(),
            "slots": [reqstate(r) if r is not None else None
                      for r in self._slot_req],
            "queue": [reqstate(r) for r in self.queue],
            "stats": dataclasses.asdict(self.stats),
        }}
        if self._paged:
            extra["engine"]["paged"] = {
                "block_size": self._pg_bs,
                "pool_blocks": self._pg_pool,
                "free": list(self._pg_free),
                "ref": self._pg_ref.tolist(),
                "rows": [list(r) for r in self._pg_rows],
                "table": self._pg_table.tolist(),
                "registry": [
                    {"tokens": ent["tokens"].tolist(),
                     "blocks": list(ent["blocks"]),
                     "reg_tokens": ent["reg_tokens"],
                     "last_used": ent["last_used"]}
                    for ent in self._pg_registry.values()],
                "clock": self._pg_clock,
                "counters": [self._pg_admits, self._pg_hits,
                             self._pg_shared_tokens, self._pg_cow_copies,
                             self._pg_evictions, self._pg_deferred],
                "evict_skips": self._pg_evict_skips,
                "swap_watermark": self._swap_watermark,
                "preempted": [reqstate(r) for r in self._preempted],
                "swap_entries": {
                    str(rid): {"kept": [[j, b] for j, b in e["kept"]],
                               "js": list(e["js"]),
                               "hids": list(e["hids"]),
                               "total": e["total"], "pos": e["pos"],
                               "prefilling": e["prefilling"],
                               "prefill_off": e["prefill_off"],
                               "remaining": e["remaining"],
                               "last": e["last"]}
                    for rid, e in self._swap_entries.items()},
                # the host store PARTICIPATES in the snapshot: preempted
                # rows' spilled bytes round-trip so they can still resume
                # byte-identically after a restore
                "swap_store": self._swap_store.state_dict(),
            }
        return store.save(ckpt_dir,
                          step if step is not None else self._step_no,
                          tree, extra=extra)

    def restore(self, ckpt_dir, step: Optional[int] = None) -> int:
        """Load a `snapshot()` back into THIS engine (same cfg/slots/
        max_len — the cache template must match; shape drift raises).
        In-flight generation resumes byte-identically: caches, positions,
        last tokens and replay/queue bookkeeping all round-trip. Wall-clock
        TTLs restart at restore time (the monotonic clock does not survive a
        process), and `finished` resets — requests completed before the
        snapshot were already delivered to the caller. Returns the restored
        step number."""
        from ..checkpoint import store
        tree, extra, got = store.restore(ckpt_dir, step=step,
                                         tree_like={"caches": self.caches})
        eng = extra["engine"]
        if eng["include_params"]:
            tree, _, _ = store.restore(
                ckpt_dir, step=step,
                tree_like={"caches": self.caches, "params": self.params})
            self.params = jax.tree.map(jnp.asarray, tree["params"])
        self.caches = jax.tree.map(jnp.asarray, tree["caches"])
        if len(eng["last"]) != self.slots:
            raise ValueError(
                f"snapshot has {len(eng['last'])} slots, engine has "
                f"{self.slots}")

        now = time.monotonic()

        def rebuild(st: dict) -> Request:
            r = Request(rid=st["rid"],
                        prompt=np.asarray(st["prompt"], np.int32),
                        max_new_tokens=st["max_new_tokens"],
                        out_tokens=list(st["out_tokens"]),
                        status=st["status"], replays=st["replays"],
                        deadline_steps=st["deadline_steps"],
                        ttl_s=st["ttl_s"],
                        priority=int(st.get("priority", 0)))
            r._submit_step = st["submit_step"]
            r._submit_t = now
            return r

        self._step_no = int(eng["step_no"])
        self._last = np.asarray(eng["last"], np.int32)
        self._remaining = np.asarray(eng["remaining"], np.int64)
        self._prefilling = np.asarray(eng["prefilling"], bool)
        self._prefill_off = np.asarray(eng["prefill_off"], np.int64)
        self._slot_req = [rebuild(st) if st is not None else None
                          for st in eng["slots"]]
        self.queue = deque(rebuild(st) for st in eng["queue"])
        self.finished = []
        self.stats = EngineStats(**eng["stats"])
        self._has_deadlines = self._has_deadlines or any(
            r is not None and (r.deadline_steps is not None
                               or r.ttl_s is not None)
            for r in list(self._slot_req) + list(self.queue))
        pg = eng.get("paged")
        if (pg is not None) != self._paged:
            raise ValueError(
                "snapshot and engine disagree on paged mode: snapshot "
                f"{'has' if pg is not None else 'lacks'} a block pool, "
                f"engine paged={self._paged}")
        if self._paged:
            if (pg["block_size"] != self._pg_bs
                    or pg["pool_blocks"] != self._pg_pool):
                raise ValueError(
                    f"snapshot pool geometry ({pg['pool_blocks']} blocks x "
                    f"{pg['block_size']} tokens) does not match the "
                    f"engine's ({self._pg_pool} x {self._pg_bs})")
            self._pg_free = list(pg["free"])
            self._pg_ref = np.asarray(pg["ref"], np.int64)
            self._pg_rows = [list(r) for r in pg["rows"]]
            self._pg_table = np.asarray(pg["table"], np.int32)
            self._pg_registry = {}
            for ent in pg["registry"]:
                toks = np.asarray(ent["tokens"], np.int32)
                self._pg_registry[self._pg_key(toks)] = {
                    "tokens": toks, "blocks": list(ent["blocks"]),
                    "reg_tokens": int(ent["reg_tokens"]),
                    "last_used": int(ent["last_used"])}
            self._pg_clock = int(pg["clock"])
            (self._pg_admits, self._pg_hits, self._pg_shared_tokens,
             self._pg_cow_copies, self._pg_evictions,
             self._pg_deferred) = [int(x) for x in pg["counters"]]
            self._pg_evict_skips = int(pg.get("evict_skips", 0))
            self._pg_holds = []
            self._preempted = [rebuild(st)
                               for st in pg.get("preempted", [])]
            self._swap_entries = {
                int(rid): {"kept": [(int(j), int(b)) for j, b in e["kept"]],
                           "js": [int(j) for j in e["js"]],
                           "hids": [int(h) for h in e["hids"]],
                           "total": int(e["total"]), "pos": int(e["pos"]),
                           "prefilling": bool(e["prefilling"]),
                           "prefill_off": int(e["prefill_off"]),
                           "remaining": int(e["remaining"]),
                           "last": int(e["last"])}
                for rid, e in pg.get("swap_entries", {}).items()}
            self._swap_store = HostBlockStore()
            st = pg.get("swap_store")
            if st is not None:
                # rebuild against THIS engine's single-block gather layout:
                # a snapshot from a different cache geometry is rejected,
                # not reinterpreted
                treedef, avals = self._pg_swap_template() \
                    if st["blocks"] else (None, None)
                self._swap_store.load_state(st, treedef, avals)
            self._has_deadlines = self._has_deadlines or any(
                r.deadline_steps is not None or r.ttl_s is not None
                for r in self._preempted)
        return got

    # ---------------------------------------------------------- introspection
    def step_widths(self) -> tuple:
        """Token widths the ONE step program is traced at over the engine's
        lifetime: (1,) for merged-mode engines, else (1, prefill_chunk)."""
        return (1,) if self._merged_mode() else (1, self.prefill_chunk)

    def step_trace(self, width: int):
        """ClosedJaxpr of the engine's step program at token width `width`,
        traced abstractly (no compile, no execution) against the engine's
        live params/caches/memory under its pinned policy — what
        `repro.analysis` audits for host callbacks, donation aliasing,
        quantized-path upcasts and the fused numeric-health guard (HL205).
        This traces `_step_program` — the REAL program the engine jits,
        health reduction included — not the bare decode_step."""
        tok = jnp.zeros((self.slots, width), jnp.int32)
        lens = jnp.zeros((self.slots,), jnp.int32)
        with self._policy_ctx():
            return jax.make_jaxpr(self._step_program)(
                self.params, self.caches, tok, lens, self.memory)

    def step_hlo(self, width: int) -> str:
        """Compiled HLO text of the step program at token width `width`.
        Each instruction's `metadata={op_name=...}` carries the model's
        named scopes (`kv_cache`, `attention`, `linear`, ...), which is how
        a profiler trace's device ops are attributed to them. Compiles
        unless the persistent compile cache holds the program."""
        tok = jnp.zeros((self.slots, width), jnp.int32)
        lens = jnp.zeros((self.slots,), jnp.int32)
        with self._policy_ctx():
            return self._step_fn.lower(self.params, self.caches, tok, lens,
                                       self.memory).compile().as_text()

    def donated_avals(self) -> list:
        """(shape, dtype) of every leaf the step donates (the cache pytree),
        in tree order — the buffers XLA must alias to step outputs."""
        return [(tuple(x.shape), jnp.asarray(x).dtype)
                for x in jax.tree_util.tree_leaves(self.caches)]

    def step_trace_count(self) -> int:
        """Distinct traces the step jit cache currently holds. After warmup
        (or any real traffic) this must equal len(step_widths()) — more
        means a shape leak retracing the hot loop."""
        return self._step_fn._cache_size()

    def weight_route(self) -> str:
        """How the Linear weights reach the matmul plane: "resident-<fmt>"
        (codes pytree through api.ops.matmul_codes), "fake-quant-<fmt>"
        (dense f32 re-quantized per call), or "dense"."""
        rfmt = T.resident_format(self.params)
        if rfmt is not None:
            return f"resident-{rfmt}"
        if self.cfg.quant.weights != "none":
            return f"fake-quant-{self.cfg.quant.weights}"
        return "dense"

    def decode_route(self) -> str:
        """Attention impl the engine's decode steps dispatch to under its
        pinned policy: "pallas-decode" (flash-decode kernel), or "ref"."""
        with self._policy_ctx():
            return api.ops.attention_route(
                lq=1, lk=self.max_len, causal=True, offset_ndim=1,
                quantized=self.cfg.kv_quant, policy=self.policy)

    def prefill_route(self) -> str:
        """Attention impl the engine's admission prefill dispatches to under
        its pinned policy: "pallas-prefill" (varlen flash-prefill kernel;
        any chunk > 1), "pallas-decode" (merged-mode engines — recurrent
        archs and chunk == 1 — whose prefill is l=1 launches), or "ref"."""
        lq = 1 if self._merged_mode() else self.prefill_chunk
        with self._policy_ctx():
            return api.ops.attention_route(
                lq=lq, lk=self.max_len, causal=True, offset_ndim=1,
                quantized=self.cfg.kv_quant, policy=self.policy)

    def occupancy(self) -> List[Optional[dict]]:
        """Per-slot view: None for a free slot, else the resident request's
        {rid, generated, remaining} — the scheduler's utilization signal."""
        return [None if r is None else
                {"rid": r.rid, "generated": len(r.out_tokens),
                 "remaining": int(self._remaining[s])}
                for s, r in enumerate(self._slot_req)]

    def utilization(self) -> float:
        """Fraction of slots currently serving a request."""
        busy = sum(r is not None for r in self._slot_req)
        return busy / self.slots if self.slots else 0.0


def _dispatch_raiser(fault: faultlib.Fault):
    """The registry hook a dispatch-boundary launch fault installs: raise at
    the first (matching) op dispatch crossed while the step traces."""
    def hook(op_name: str, impl: str):
        if fault.op is not None and op_name != fault.op:
            return
        fault.tripped = True
        raise faultlib.KernelLaunchError(
            f"injected dispatch failure at op {op_name!r} ({impl})")
    return hook
