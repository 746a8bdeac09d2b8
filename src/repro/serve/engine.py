"""ServeEngine: continuous-batching inference over a paged KV cache.

The engine owns a shared **page pool** per layer (``[num_pages,
page_size, ...]``) plus a per-slot **block table** (``[max_slots,
max_pages] int32``, vLLM-style): a sequence's KV lives in whatever
physical pages its table points at, so ``max_slots x max_len`` can
exceed the physically backed cache (set ``num_pages`` below the
full-backing default to overcommit).  Admission allocates pages on
demand from a free list, prefill writes page-aligned chunks straight
into the pool, ``_finish_slot`` returns a sequence's pages to the free
list, and the decode step gathers K/V through the block table inside the
flash-decode kernel (``kernels/ops.decode_attention_paged``) — the grid
is bucketed to the pages actually in use, so short sequences never pay
for ``max_len``.  The pools are stacked over the scanned layers and
lane-aligned in their head dim (``models/lm.py``), so every program takes
and returns them in the one layout the kernels read and each step
updates them in place.  ``kv_layout="contiguous"`` keeps the older
layout (one ``[max_slots, max_len]`` row per slot, vector-length kernel)
as the benchmark baseline.

Admission is *continuous*: whenever a slot is free and a request is
queued, the request binds to the slot and its pages are reserved;
prefill then proceeds in **bounded chunks** interleaved with decode
(Sarathi/vLLM-style chunked prefill).  Each ``step()`` spends at most
``prefill_chunk_tokens`` prompt tokens across the currently-prefilling
slots — one jitted ragged cache-writing forward
(``make_prefill_chunk_step``, the prefill kernel in
``kernels/prefill_attention.py``) appends every row's chunk at its own
offset straight into the pool/slot cache — and then runs one fused
decode over the slots whose prefill already finished.  A long prompt
therefore stalls in-flight decode tails by at most one chunk per step
instead of its whole length, which is what bounds the inter-token stall
tail (each request's worst gap, the global p99) under mixed long/short
workloads.  This retires the old
whole-prompt prefill scratch (``[nb, prompt_bucket]`` rows packed into
pages after the fact) and the unbounded per-prompt-bucket jit cache: the
chunk step writes in place, and its jitted variants are keyed by chunk
bucket in a small LRU (``prefill_fns_cached`` in ``stats()``).
Sampling is per-slot (temperature / top-k / seeded PRNG streams; greedy
default is bit-identical to argmax), finished sequences free their slot
and pages, and freed capacity is refilled on the next step.  A
static-batch baseline (``continuous=False``: admit only when every slot
is free) exists for the serving benchmark's comparison; passing
``prefill_chunk_tokens=None`` keeps admission whole-prompt (one chunk
covers the prompt) as the chunking baseline.

The engine is also a *service task body* for the pilot runtime
(``run_service``): driven through a :class:`~repro.core.task.ServiceControl`,
it pulls requests from the control inbox, and cooperates with priority
preemption — when the agent requests preemption it checkpoints its slot
state (page pool, block tables, free list, per-slot PRNG keys, bound
requests, queue), releases everything, and raises
:class:`~repro.core.task.ServicePreempted`; the agent re-queues the task
and the next attempt restores from the checkpoint and keeps serving.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.params import init_params, is_param
from repro.configs.base import ModelConfig, RunConfig
from repro.core.resilience import faults as rfaults
from repro.core.task import ServiceControl, ServicePreempted
from repro.models.lm import lm_cache_specs, lm_paged_cache_specs
from repro.serve.handoff import KVHandoff
from repro.serve.request import Request, RequestState
from repro.serve.sampling import make_slot_key, sample_tokens
from repro.train.state import model_specs
from repro.train.step import make_decode_step, make_prefill_chunk_step

_engine_uid = itertools.count()

# the sampler's device operations carry the ``sample`` scope in every
# program that samples (the decode step and the first-token sampler)
_sample_tokens = jax.named_scope("sample")(sample_tokens)


def _entry_submitted_at(entry) -> float:
    """Submission time of a queue entry (Request or migrated KVHandoff)."""
    return (entry.request.submitted_at if isinstance(entry, KVHandoff)
            else entry.submitted_at)


def _bucket(n: int, lo: int = 2) -> int:
    """Next power-of-two >= n (floored at ``lo``) — bounds jit retraces.
    The floor is 2, not 8: with 1-2 occupied prefill rows an 8-floor pads
    every admission to batch 8; the engine counts actual retraces in
    ``stats()`` so the bucketing/retrace tradeoff stays observable."""
    p = lo
    while p < n:
        p *= 2
    return p


def _map_cache(fn_b0, fn_b1, *trees):
    """Map over LM cache trees, batch-axis aware: ``head_layers`` /
    ``tail_layers`` leaves are ``[batch, ...]`` (``fn_b0``) while the
    scanned ``unit`` leaves are ``[layers, batch, ...]`` (``fn_b1``)."""
    out = {k: jax.tree.map(fn_b0, *(t[k] for t in trees))
           for k in ("head_layers", "tail_layers") if k in trees[0]}
    if "unit" in trees[0]:
        out["unit"] = jax.tree.map(fn_b1, *(t["unit"] for t in trees))
    return out


def _tree_bytes(tree) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))


class ServeEngine:
    """Paged continuous-batching engine for token-LM archs.

    Drive it either directly (``submit`` + ``step``/``run_until_drained``,
    the benchmark/test mode) or as a service stage under the pilot runtime
    (``run_service(control=...)``).
    """

    # jitted chunk-step variants kept per chunk bucket; small because the
    # chunk budget bounds the bucket count to log2(budget) + 1
    _PREFILL_FN_CAP = 8

    def __init__(self, cfg: ModelConfig, run_cfg: Optional[RunConfig] = None,
                 *, max_slots: int = 4, max_len: int = 128,
                 params: Any = None, seed: int = 0,
                 continuous: bool = True, idle_wait_s: float = 0.005,
                 kv_layout: str = "paged", page_size: int = 16,
                 num_pages: Optional[int] = None,
                 decode_impl: Optional[str] = None,
                 prefill_chunk_tokens: Optional[int] = 64,
                 prefill_only: bool = False,
                 name: Optional[str] = None,
                 device: Optional[jax.Device] = None):
        if cfg.is_encoder_decoder or cfg.input_kind != "tokens":
            raise NotImplementedError("ServeEngine targets token-LM archs")
        if cfg.mrope_sections:
            raise NotImplementedError(
                "M-RoPE position streams are not supported by the slot cache")
        if max_slots < 1 or max_len < 2:
            raise ValueError("need max_slots >= 1 and max_len >= 2")
        if kv_layout not in ("paged", "contiguous"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        if prefill_only and kv_layout != "paged":
            raise ValueError("prefill_only engines require kv_layout="
                             "'paged' (handoff ships page blocks)")
        if decode_impl is not None:
            cfg = cfg.with_overrides(decode_impl=decode_impl)
        self.cfg = cfg
        self.run_cfg = run_cfg or RunConfig()
        self.uid = name or f"engine{next(_engine_uid):03d}"
        # prefill-specialised role: finished prompts are exported as
        # KVHandoff page blocks instead of decoding in place
        self.prefill_only = prefill_only
        self.max_slots = max_slots
        self.max_len = max_len
        self.continuous = continuous
        self.idle_wait_s = idle_wait_s
        if prefill_chunk_tokens is not None and prefill_chunk_tokens < 1:
            raise ValueError("prefill_chunk_tokens must be >= 1 (or None "
                             "for whole-prompt prefill)")
        self.paged = kv_layout == "paged"
        self.page_size = page_size
        self.max_pages = -(-max_len // page_size)
        # per-step prompt-token budget for chunked prefill; None = each
        # prompt prefills in one chunk (the unchunked baseline)
        self.prefill_chunk_tokens = prefill_chunk_tokens
        # full backing by default; pass a smaller num_pages to overcommit
        # (max_slots x max_len of *logical* capacity over fewer physical
        # pages — admission backpressures on the free list)
        self.num_pages = (num_pages if num_pages is not None
                          else max_slots * self.max_pages)
        # the device holding this engine's params and KV cache (None:
        # JAX's default device); fleet engines each get their own
        self.device = device
        with jax.default_device(device):
            self.params = (params if params is not None
                           else init_params(jax.random.PRNGKey(seed),
                                            model_specs(cfg)))
        if device is not None:
            self.params = jax.device_put(self.params, device)
        if self.paged:
            # raises at construction for unsupported archs: paged caches
            # need attention-family temporal blocks
            lm_paged_cache_specs(cfg, 1, page_size)
        # raises at construction for archs the ragged chunked prefill
        # cannot serve (recurrent state caches, windowed ring caches)
        self._prefill_chunk = make_prefill_chunk_step(cfg, self.run_cfg)
        # chunk-bucket -> jitted chunk step, LRU-capped (satellite of the
        # old unbounded per-prompt-bucket cache this replaced)
        self._prefill_fns: "collections.OrderedDict[int, Any]" = (
            collections.OrderedDict())
        decode = make_decode_step(cfg, self.run_cfg)
        self._sample = jax.jit(_sample_tokens)

        # ``sampling`` is a static flag: an all-greedy batch (the default)
        # keeps the old argmax-only hot path — no full-vocab sort, no
        # Gumbel draws, no key advancement.  Greedy slots never consume
        # their keys, so skipping the sampler when no occupied slot
        # samples cannot change any stream.
        if self.paged:

            def _step(params, tokens, cache, lengths, active, keys, temps,
                      topks, block_table, *, sampling):
                greedy, logits, new_cache = decode(
                    params, tokens[:, None], cache, lengths, block_table)
                if sampling:
                    toks, new_keys = _sample_tokens(logits[:, -1], keys,
                                                    temps, topks)
                else:
                    toks, new_keys = greedy, keys
                # inactive slots: their block-table rows are all-sentinel,
                # so their junk appends already dropped inside the kernel
                return (jnp.where(active, toks, 0),
                        jnp.where(active[:, None], new_keys, keys),
                        new_cache)

        else:

            def _step(params, tokens, cache, lengths, active, keys, temps,
                      topks, *, sampling):
                greedy, logits, new_cache = decode(params, tokens[:, None],
                                                   cache, lengths)
                if sampling:
                    toks, new_keys = _sample_tokens(logits[:, -1], keys,
                                                    temps, topks)
                else:
                    toks, new_keys = greedy, keys

                # freeze unoccupied slots: restore their cache rows so junk
                # writes never accumulate
                def keep_b0(new, old):
                    a = active.reshape((-1,) + (1,) * (new.ndim - 1))
                    return jnp.where(a, new, old)

                def keep_b1(new, old):  # scanned unit: [layers, batch, ...]
                    a = active.reshape((1, -1) + (1,) * (new.ndim - 2))
                    return jnp.where(a, new, old)

                return (jnp.where(active, toks, 0),
                        jnp.where(active[:, None], new_keys, keys),
                        _map_cache(keep_b0, keep_b1, new_cache, cache))

        self._decode = jax.jit(_step, donate_argnums=(2,),
                               static_argnames=("sampling",))

        # _lock guards the state shared with submitter/monitor threads
        # (queue, stats, retrace tracking).  The slot/page fields below
        # (cache, lengths, slots, free_pages, slot_pages, block_table, ...)
        # are owned by the engine thread that calls step(); checkpoint()/
        # restore()/_release_state() snapshot them under _lock.
        self._lock = threading.Lock()
        self.queue: Deque[Any] = collections.deque()  # guarded-by: _lock
        # finished prefills parked for the router's handoff mover
        self._outbox: Deque[KVHandoff] = collections.deque()  # guarded-by: _lock
        self.cache = None
        self.lengths = np.zeros(max_slots, np.int32)
        self.last_tok = np.zeros(max_slots, np.int32)
        self.slots: List[Optional[Request]] = [None] * max_slots
        self._stats: Dict[str, int] = collections.defaultdict(int)  # guarded-by: _lock
        self._seen_shapes: Dict[str, set] = collections.defaultdict(set)  # guarded-by: _lock
        self._steps = itertools.count()  # step() calls, for its trace span
        self._init_state()
        self._page_bytes = 0
        self._cache_bytes = _tree_bytes(self.cache)
        if self.paged:
            self._page_bytes = self._cache_bytes // self.num_pages

    # -- state lifecycle -----------------------------------------------------

    def _init_state(self) -> None:
        if self.paged:
            specs = lm_paged_cache_specs(self.cfg, self.num_pages,
                                         self.page_size)
            # per-slot block tables; sentinel num_pages = unallocated
            self.block_table = np.full((self.max_slots, self.max_pages),
                                       self.num_pages, np.int32)
            self.free_pages: List[int] = list(range(self.num_pages))
            self.slot_pages: List[List[int]] = [[] for _ in
                                                range(self.max_slots)]
        else:
            specs = lm_cache_specs(self.cfg, self.max_slots, self.max_len)
        self.cache = jax.tree.map(
            lambda p: jnp.zeros(p.shape, p.dtype, device=self.device),
            specs, is_leaf=is_param)
        self.lengths = np.zeros(self.max_slots, np.int32)
        self.last_tok = np.zeros(self.max_slots, np.int32)
        self.slots = [None] * self.max_slots
        self.slot_keys = np.zeros((self.max_slots, 2), np.uint32)
        self.slot_temp = np.zeros(self.max_slots, np.float32)
        self.slot_topk = np.zeros(self.max_slots, np.int32)
        # chunked-prefill progress: tokens of the prompt already written
        # into the cache, or -1 once the slot is decoding / free
        self.prefill_pos = np.full(self.max_slots, -1, np.int32)
        self.slot_prompt: List[Optional[np.ndarray]] = (
            [None] * self.max_slots)

    def checkpoint(self) -> Dict[str, Any]:
        """Snapshot the full serving state (page pool + block tables +
        free list for paged, slot cache otherwise; per-slot lengths and
        sampling PRNG keys; bound and queued requests).  Cache arrays are
        copied so the snapshot survives later donated decode steps."""
        with self._lock:
            state = {
                "cache": jax.tree.map(jnp.copy, self.cache),
                "lengths": self.lengths.copy(),
                "last_tok": self.last_tok.copy(),
                "slots": list(self.slots),
                "queue": list(self.queue),
                "outbox": list(self._outbox),
                "stats": dict(self._stats),
                "slot_keys": self.slot_keys.copy(),
                "slot_temp": self.slot_temp.copy(),
                "slot_topk": self.slot_topk.copy(),
                "prefill_pos": self.prefill_pos.copy(),
                "slot_prompt": list(self.slot_prompt),
            }
            if self.paged:
                state.update({
                    "block_table": self.block_table.copy(),
                    "free_pages": list(self.free_pages),
                    "slot_pages": [list(p) for p in self.slot_pages],
                })
            return state

    def restore(self, state: Dict[str, Any]) -> None:
        with self._lock:
            # copy: the live cache is donated by decode/pack, and ``state``
            # may be the agent's stashed resume_state which a later retry
            # re-uses — aliasing it here would hand that retry deleted
            # buffers
            self.cache = jax.tree.map(jnp.copy, state["cache"])
            self.lengths = state["lengths"].copy()
            self.last_tok = state["last_tok"].copy()
            self.slots = list(state["slots"])
            self.queue = collections.deque(state["queue"])
            self._outbox = collections.deque(state.get("outbox", ()))
            self._stats = collections.defaultdict(int, state["stats"])
            self.slot_keys = state["slot_keys"].copy()
            self.slot_temp = state["slot_temp"].copy()
            self.slot_topk = state["slot_topk"].copy()
            self.prefill_pos = state["prefill_pos"].copy()
            self.slot_prompt = list(state["slot_prompt"])
            if self.paged:
                self.block_table = state["block_table"].copy()
                self.free_pages = list(state["free_pages"])
                self.slot_pages = [list(p) for p in state["slot_pages"]]

    def _release_state(self) -> None:
        """Drop the live slot state (after checkpointing): the preempted
        engine holds no cache while higher-priority work runs."""
        with self._lock:
            self.cache = None
            self.slots = [None] * self.max_slots
            self.lengths = np.zeros(self.max_slots, np.int32)
            self.last_tok = np.zeros(self.max_slots, np.int32)
            self.queue = collections.deque()
            self._outbox = collections.deque()
            self.slot_keys = np.zeros((self.max_slots, 2), np.uint32)
            self.slot_temp = np.zeros(self.max_slots, np.float32)
            self.slot_topk = np.zeros(self.max_slots, np.int32)
            self.prefill_pos = np.full(self.max_slots, -1, np.int32)
            self.slot_prompt = [None] * self.max_slots
            if self.paged:
                self.block_table = np.full(
                    (self.max_slots, self.max_pages), self.num_pages,
                    np.int32)
                self.free_pages = list(range(self.num_pages))
                self.slot_pages = [[] for _ in range(self.max_slots)]

    def place(self, device: jax.Device) -> None:
        """Move params and KV cache onto ``device``: a pilot-mode engine is
        built before the pilot that runs it is chosen."""
        with self._lock:
            self.device = device
            self.params = jax.device_put(self.params, device)
            if self.cache is not None:
                self.cache = jax.device_put(self.cache, device)

    def lowered_steps(self) -> Dict[str, Any]:
        """This engine's own jitted decode step and its prefill-chunk step
        at the full chunk width, lowered on its current params and cache
        (greedy, every slot active): the programs ``step`` dispatches."""
        S = self.max_slots
        T = _bucket(self.prefill_chunk_tokens or self.max_len)

        def ints(*shape):
            return jnp.zeros(shape, jnp.int32)

        bt = jnp.asarray(self.block_table) if self.paged else None
        decode_args = (self.params, ints(S), self.cache, ints(S),
                       jnp.ones(S, bool), jnp.asarray(self.slot_keys),
                       jnp.asarray(self.slot_temp),
                       jnp.asarray(self.slot_topk))
        if self.paged:
            decode_args += (bt,)
        return {
            "decode": self._decode.lower(*decode_args, sampling=False),
            "prefill_chunk": self._get_prefill(T).lower(
                self.params, ints(S, T), ints(S), ints(S), self.cache,
                bt),
        }

    # -- client side ---------------------------------------------------------

    def submit(self, request, **kw) -> Request:
        """Queue a request (a :class:`Request`, a raw prompt array, or a
        migrated :class:`KVHandoff` from a prefill engine)."""
        if isinstance(request, KVHandoff):
            if not self.paged:
                raise ValueError(
                    "KVHandoff import needs a paged engine")
            if request.page_size != self.page_size:
                raise ValueError(
                    f"handoff page_size {request.page_size} != engine "
                    f"page_size {self.page_size}")
            with self._lock:
                self.queue.append(request)
            return request.request
        if not isinstance(request, Request):
            request = Request(np.asarray(request, np.int32), **kw)
        with self._lock:
            self.queue.append(request)
        return request

    def take_handoffs(self) -> List[KVHandoff]:
        """Pop every exported prefill (the router's handoff mover ships
        these through the transport into a decode engine)."""
        with self._lock:
            out = list(self._outbox)
            self._outbox.clear()
        return out

    def steal_queued(self) -> List[Any]:
        """Pop every queued-but-unbound entry so a router can re-route
        it away from a draining or preempted engine.  Bound slots are
        not touched — they finish here or ride the preemption
        checkpoint."""
        with self._lock:
            out = list(self.queue)
            self.queue.clear()
        return out

    def recover_outstanding(self) -> List[Any]:
        """Crash recovery (the router's circuit-breaker path): collect
        every accepted-but-unfinished entry — bound slots, queued
        entries, parked handoffs — and return them for re-routing
        instead of failing them.  Bound requests lose their in-pool KV
        with the crashed state, so they are reset to QUEUED and
        re-enter as plain prompts (:meth:`Request.reset_for_retry`
        documents why the regenerated output is reproducible).  Queued
        entries and exported handoffs return as-is — a handoff's page
        blocks are host-side copies independent of the dead engine
        state.  The slot state is released; the next ``run_service``
        starts fresh."""
        with self._lock:
            bound = [r for r in self.slots if r is not None]
            queued, self.queue = list(self.queue), collections.deque()
            handed, self._outbox = list(self._outbox), collections.deque()
        for req in bound:
            if not req.done():
                req.reset_for_retry()
        self._release_state()
        recovered = bound + queued + handed
        if recovered:
            self._bump("recovered", len(recovered))
        return recovered

    def has_work(self) -> bool:
        with self._lock:
            return bool(self.queue) or any(r is not None for r in self.slots)

    def occupancy(self) -> int:
        with self._lock:  # cross-thread monitoring read
            return sum(r is not None for r in self.slots)

    def pages_in_use(self) -> int:
        with self._lock:  # cross-thread monitoring read
            return self.num_pages - len(self.free_pages) if self.paged else 0

    def admission_signals(self) -> Dict[str, Any]:
        """One-lock snapshot of the signals a fleet router admits on:
        slot occupancy, page-pool pressure, and queue depth/age.  For
        contiguous engines the page figures degrade to free slots (each
        slot owns its full row, so slots are the only capacity axis)."""
        with self._lock:
            now = time.time()
            occupied = sum(r is not None for r in self.slots)
            return {
                "engine": self.uid,
                "prefill_only": self.prefill_only,
                "occupied": occupied,
                "max_slots": self.max_slots,
                "queue_depth": len(self.queue),
                "oldest_queued_age_s": (
                    now - min(_entry_submitted_at(e) for e in self.queue)
                    if self.queue else 0.0),
                "free_pages": (len(self.free_pages) if self.paged
                               else self.max_slots - occupied),
                "num_pages": (self.num_pages if self.paged
                              else self.max_slots),
            }

    def _bump(self, key: str, n: int = 1) -> None:
        with self._lock:
            self._stats[key] += n

    # -- page bookkeeping ----------------------------------------------------

    def _count_retrace(self, kind: str, key) -> None:
        with self._lock:
            seen = self._seen_shapes[kind]
            if key not in seen:
                seen.add(key)
                self._stats["retraces"] += 1
                self._stats[f"retraces_{kind}"] += 1

    def _alloc_pages(self, slot: int, n: int) -> bool:
        """Append ``n`` fresh pages to a slot's block table (False if the
        pool cannot supply them — caller backpressures or fails)."""
        if len(self.free_pages) < n:
            return False
        base = len(self.slot_pages[slot])
        if base + n > self.max_pages:
            return False
        for j in range(n):
            pid = self.free_pages.pop()
            self.slot_pages[slot].append(pid)
            self.block_table[slot, base + j] = pid
        used = self.pages_in_use()
        with self._lock:
            if used > self._stats.get("peak_pages", 0):
                self._stats["peak_pages"] = used
        return True

    def _free_slot_pages(self, slot: int) -> None:
        self.free_pages.extend(self.slot_pages[slot])
        self.slot_pages[slot] = []
        self.block_table[slot, :] = self.num_pages

    def _ensure_decode_pages(self) -> None:
        """Every active slot appends K/V at position ``lengths[i]`` this
        step — allocate the covering page if the sequence just crossed a
        page boundary.  A slot the pool cannot serve fails (its own pages
        return to the free list, which may unblock the remaining slots).
        Slots still prefilling are skipped: their prompt pages were
        reserved at admission and they do not decode yet."""
        for i, req in enumerate(self.slots):
            if req is None or self.prefill_pos[i] >= 0:
                continue
            lp = int(self.lengths[i]) // self.page_size
            if lp < len(self.slot_pages[i]):
                continue
            if not self._alloc_pages(i, 1):
                self._finish_slot(
                    i, RequestState.FAILED,
                    f"page pool exhausted ({self.num_pages} pages of "
                    f"{self.page_size}); lower the load or raise num_pages")

    # -- engine core ---------------------------------------------------------

    def _finish_slot(self, i: int, state: RequestState,
                     error: Optional[str] = None) -> None:
        req = self.slots[i]
        self.slots[i] = None
        self.lengths[i] = 0
        self.last_tok[i] = 0
        self.slot_temp[i] = 0.0
        self.slot_topk[i] = 0
        self.slot_keys[i] = 0
        self.prefill_pos[i] = -1
        self.slot_prompt[i] = None
        if self.paged:
            self._free_slot_pages(i)
        req._finish(state, error)
        self._bump("completed" if state is RequestState.DONE else "failed")

    def _pad_pids(self, pids: np.ndarray) -> np.ndarray:
        """Pad a page-id list to its power-of-two bucket by repeating the
        last id: the gather/scatter XLA shapes stay bounded to
        ``log2(max_pages) + 1`` variants instead of one per distinct page
        count (an eager compile inside the serving hot path otherwise).
        Duplicate ids are safe — every duplicate carries the same block,
        so scatter order cannot change the result."""
        b = min(_bucket(max(len(pids), 1), lo=1), self.max_pages)
        if b == len(pids):
            return pids
        return np.concatenate(
            [pids, np.full(b - len(pids), pids[-1], np.int32)])

    def _export_slot(self, i: int) -> None:
        """Prefill-only handoff: gather exactly the slot's own pages out
        of the pool (a block copy addressed by the block-table row — the
        pool itself never ships) and park them in the outbox as a
        :class:`KVHandoff`.  The slot unbinds WITHOUT finishing the
        request: it stays RUNNING and completes on the importing decode
        engine."""
        req = self.slots[i]
        pids = np.asarray(self.slot_pages[i], np.int32)
        n = len(pids)
        padded = jnp.asarray(self._pad_pids(pids))
        self._count_retrace("handoff_gather", int(padded.shape[0]))
        # gather at the bucketed width, ship only the owned pages
        pages = _map_cache(lambda l: np.asarray(l[padded])[:n],
                           lambda l: np.asarray(l[:, padded])[:, :n],
                           self.cache)
        hand = KVHandoff(
            request=req, length=int(self.lengths[i]),
            last_tok=int(self.last_tok[i]),
            slot_key=self.slot_keys[i].copy(),
            temperature=float(self.slot_temp[i]),
            top_k=int(self.slot_topk[i]), pages=pages,
            n_pages=len(self.slot_pages[i]), page_size=self.page_size,
            kv_bytes=_tree_bytes(pages), source=self.uid)
        self.slots[i] = None
        self.lengths[i] = 0
        self.last_tok[i] = 0
        self.slot_temp[i] = 0.0
        self.slot_topk[i] = 0
        self.slot_keys[i] = 0
        self.prefill_pos[i] = -1
        self.slot_prompt[i] = None
        self._free_slot_pages(i)
        with self._lock:
            self._outbox.append(hand)
            self._stats["handoffs_exported"] += 1
            self._stats["handoff_bytes_exported"] += hand.kv_bytes

    def _fail_outstanding(self, error: str) -> None:
        """Terminate every accepted-but-unfinished request (hard stop):
        waiters block on Request.wait(), so abandoning them silently would
        hang clients forever."""
        for i, req in enumerate(self.slots):
            if req is not None:
                self._finish_slot(i, RequestState.FAILED, error)
        with self._lock:
            queued, self.queue = list(self.queue), collections.deque()
            handed, self._outbox = list(self._outbox), collections.deque()
        for entry in queued + handed:
            # _finish runs callbacks — keep it outside the lock
            req = entry.request if isinstance(entry, KVHandoff) else entry
            req._finish(RequestState.FAILED, error)
        if queued or handed:
            self._bump("failed", len(queued) + len(handed))

    def _should_stop(self, req: Request, tok: int, length: int) -> bool:
        return (len(req.tokens) >= req.max_new_tokens
                or (req.stop_token is not None and tok == req.stop_token)
                or length >= self.max_len)

    def _get_prefill(self, chunk_t: int):
        """Jitted chunk-step per chunk bucket, LRU-capped at
        ``_PREFILL_FN_CAP`` — evicting an entry drops its whole compiled
        family (the paged page-bucket variants live inside one entry's
        jit cache).  The chunk budget bounds live buckets to
        ``log2(budget) + 1``, so eviction only fires when callers mix
        many chunk settings on one engine."""
        fn = self._prefill_fns.get(chunk_t)
        if fn is None:
            fn = jax.jit(self._prefill_chunk, donate_argnums=(4,))
            self._prefill_fns[chunk_t] = fn
            if len(self._prefill_fns) > self._PREFILL_FN_CAP:
                self._prefill_fns.popitem(last=False)
                self._bump("prefill_fns_evicted")
        else:
            self._prefill_fns.move_to_end(chunk_t)
        return fn

    def _admit(self) -> List[Any]:
        """Bind queued requests to free slots (reserving their prompt
        pages); the actual prompt processing happens chunk-by-chunk in
        ``_prefill_step``.  Returns the entries admitted this call."""
        free = [i for i, r in enumerate(self.slots) if r is None]
        with self._lock:
            if not free or not self.queue:
                return []
            if not self.continuous and len(free) < self.max_slots:
                return []  # static batching: wait for the whole batch to end
            batch: List[Any] = []
            reserved = 0
            while self.queue and len(batch) < len(free):
                req = self.queue[0]
                if isinstance(req, KVHandoff):
                    # migrated prefill: its own pages plus one
                    # decode-growth page (same rule as a fresh prompt)
                    need = min(req.n_pages + 1, self.max_pages)
                    if need > self.num_pages:
                        self.queue.popleft()
                        req.request._finish(
                            RequestState.FAILED,
                            f"handoff needs {need} pages of "
                            f"{self.page_size} but the pool only has "
                            f"{self.num_pages}")
                        self._stats["failed"] += 1
                        continue
                    if reserved + need > len(self.free_pages):
                        break  # FIFO backpressure, same as prompts
                    reserved += need
                    batch.append(self.queue.popleft())
                    continue
                if req.prompt_len > self.max_len - 1:
                    self.queue.popleft()
                    req._finish(RequestState.FAILED,
                                f"prompt ({req.prompt_len} tokens) does not "
                                f"fit max_len={self.max_len}")
                    self._stats["failed"] += 1
                    continue
                if self.paged:
                    # reserve the prompt's pages plus one decode-growth
                    # page (capped at what the sequence can ever address)
                    need = min(-(-req.prompt_len // self.page_size) + 1,
                               self.max_pages)
                    if need > self.num_pages:
                        # no amount of recycling can ever serve this
                        # request — fail it now, or it livelocks the
                        # whole FIFO queue behind it
                        self.queue.popleft()
                        req._finish(
                            RequestState.FAILED,
                            f"prompt needs {need} pages of "
                            f"{self.page_size} but the pool only has "
                            f"{self.num_pages}")
                        self._stats["failed"] += 1
                        continue
                    if reserved + need > len(self.free_pages):
                        # transient shortage: FIFO backpressure — the
                        # head waits for pages to recycle rather than
                        # being skipped
                        break
                    reserved += need
                batch.append(self.queue.popleft())
        if not batch:
            return batch
        nb = len(batch)
        now = time.time()
        for j, req in enumerate(batch):
            i = free[j]
            if isinstance(req, KVHandoff):
                self._import_handoff(i, req, now)
                continue
            if self.paged:
                n_pages = -(-req.prompt_len // self.page_size)
                if not self._alloc_pages(i, n_pages):
                    raise RuntimeError(
                        "page reservation failed after admission check")
            self.slots[i] = req
            self.lengths[i] = 0  # becomes prompt_len when prefill finishes
            self.prefill_pos[i] = 0
            self.slot_prompt[i] = np.asarray(req.prompt, np.int32)
            self.slot_keys[i] = make_slot_key(req.seed)
            self.slot_temp[i] = req.temperature
            self.slot_topk[i] = req.top_k
            req.state = RequestState.RUNNING
            req.admitted_at = now
        with self._lock:
            self._stats["admitted"] += nb
            self._stats["prefill_batches"] += 1
        return batch

    def _import_handoff(self, i: int, hand: KVHandoff,
                        now: float) -> None:
        """Bind a migrated prefill: allocate exactly its page count,
        scatter the shipped blocks into this engine's pool (a
        block-table rewrite — page ids change, intra-page offsets do
        not), and enter decode directly: ``prefill_pos`` stays -1, the
        prompt never replays."""
        if not self._alloc_pages(i, hand.n_pages):
            raise RuntimeError(
                "page reservation failed after admission check")
        raw = np.asarray(self.slot_pages[i], np.int32)
        padded = self._pad_pids(raw)
        b = len(padded)
        self._count_retrace("handoff_scatter", b)

        def _pad_rows(d, axis):
            # repeat the last shipped block out to the bucket width: the
            # duplicate page ids then write identical data, so the scatter
            # stays deterministic while the XLA shape stays bucketed
            n = d.shape[axis]
            if n == b:
                return d
            last = d[-1:] if axis == 0 else d[:, -1:]
            return np.concatenate([d, np.repeat(last, b - n, axis=axis)],
                                  axis=axis)

        pids = jnp.asarray(padded)
        self.cache = _map_cache(
            lambda l, d: l.at[pids].set(jnp.asarray(_pad_rows(d, 0), l.dtype)),
            lambda l, d: l.at[:, pids].set(
                jnp.asarray(_pad_rows(d, 1), l.dtype)),
            self.cache, hand.pages)
        req = hand.request
        self.slots[i] = req
        self.lengths[i] = hand.length
        self.last_tok[i] = hand.last_tok
        self.prefill_pos[i] = -1
        self.slot_prompt[i] = None
        self.slot_keys[i] = hand.slot_key
        self.slot_temp[i] = hand.temperature
        self.slot_topk[i] = hand.top_k
        req.state = RequestState.RUNNING
        if req.admitted_at is None:
            req.admitted_at = now
        with self._lock:
            self._stats["handoffs_imported"] += 1
            self._stats["handoff_bytes_imported"] += hand.kv_bytes

    def _prefill_step(self) -> bool:
        """Spend up to ``prefill_chunk_tokens`` prompt tokens across the
        slots still prefilling: ONE jitted ragged chunk forward appends
        each participating row's next chunk at its own cache offset
        (inert rows ride with ``chunk_lens == 0``).  Rows whose prompt
        completes sample their first token here and hand off to decode."""
        taking: Dict[int, int] = {}
        budget = (self.prefill_chunk_tokens if self.prefill_chunk_tokens
                  is not None else self.max_len)
        used = 0
        for i, req in enumerate(self.slots):
            if req is None or self.prefill_pos[i] < 0 or used >= budget:
                continue
            take = min(req.prompt_len - int(self.prefill_pos[i]),
                       budget - used)
            if take > 0:
                taking[i] = take
                used += take
        if not taking:
            return False
        with jax.profiler.TraceAnnotation("engine.prefill") as span:
            # bucket the chunk width so jit retraces stay bounded; rows not
            # taking tokens this step ride with chunk_lens 0 (inert in the
            # ragged kernel — no writes, zero output)
            T = _bucket(max(taking.values()))
            tokens = np.zeros((self.max_slots, T), np.int32)
            base = np.zeros(self.max_slots, np.int32)
            clens = np.zeros(self.max_slots, np.int32)
            for i, take in taking.items():
                pos = int(self.prefill_pos[i])
                tokens[i, :take] = self.slot_prompt[i][pos:pos + take]
                base[i] = pos
                clens[i] = take
            if self.paged:
                # bucket the table to the PREFILLING rows' own page
                # frontier (base + chunk), not the global pages-in-use:
                # tying the prefill shape to other slots' decode growth
                # would recompile mid-serve whenever an admission lands on
                # a grown pool
                need = max(-(-(int(base[i]) + take) // self.page_size)
                           for i, take in taking.items())
                mb = min(_bucket(need, lo=1), self.max_pages)
                self._count_retrace("prefill", (T, mb))
                bt = jnp.asarray(self.block_table[:, :mb])
                span.set_metadata(mb=mb)
            else:
                self._count_retrace("prefill", (T,))
                bt = None
            span.set_metadata(T=T, rows=len(taking), tokens=used)
            prefill = self._get_prefill(T)
            next_tok, last_logits, self.cache = prefill(
                self.params, jnp.asarray(tokens), jnp.asarray(base),
                jnp.asarray(clens), self.cache, bt)
            done = [i for i, take in taking.items()
                    if int(self.prefill_pos[i]) + take
                    >= self.slots[i].prompt_len]
            for i, take in taking.items():
                self.prefill_pos[i] += take
            # first token for rows that just finished their prompt:
            # per-request sampling params + the slot's seeded stream
            # (all-greedy rows keep the chunk step's argmax — no sampler
            # call)
            if done:
                with jax.profiler.TraceAnnotation("engine.prefill.fetch"):
                    if any(self.slot_temp[i] > 0 for i in done):
                        first_tok, new_keys = self._sample(
                            last_logits, jnp.asarray(self.slot_keys),
                            jnp.asarray(self.slot_temp),
                            jnp.asarray(self.slot_topk))
                        toks = np.asarray(first_tok)
                        new_keys = np.asarray(new_keys)
                        for i in done:
                            if self.slot_temp[i] > 0:
                                self.slot_keys[i] = new_keys[i]
                    else:
                        toks = np.asarray(next_tok)
                now = time.time()
                for i in done:
                    req = self.slots[i]
                    self.lengths[i] = req.prompt_len
                    self.prefill_pos[i] = -1
                    self.slot_prompt[i] = None
                    req.first_token_at = now
                    tok = int(toks[i])
                    req.tokens.append(tok)
                    req.token_times.append(now)
                    self.last_tok[i] = tok
                    if self._should_stop(req, tok, int(self.lengths[i])):
                        self._finish_slot(i, RequestState.DONE)
                    elif self.prefill_only:
                        self._export_slot(i)
        with self._lock:
            self._stats["prefill_chunks"] += 1
            self._stats["prefill_tokens"] += used
        return True

    def step(self) -> bool:
        """Admit what fits, spend one bounded prefill chunk, then run one
        fused decode over every slot whose prefill already finished.
        Returns False when there was nothing to do."""
        with jax.profiler.TraceAnnotation("engine.step",
                                          step=next(self._steps)):
            inj = rfaults.active()
            if inj is not None and self.has_work():
                # chaos site (FaultPlan.crash_engine): only steps with work
                # count, so the Nth firing is a logical point in the
                # workload, not a function of idle-spin timing
                act = inj.fire("engine.step", engine=self.uid)
                if act is not None and act.get("action") == "crash":
                    raise rfaults.InjectedFault(
                        f"injected crash at {self.uid} step")
            with jax.profiler.TraceAnnotation("engine.admit") as span:
                admitted = self._admit()
                if admitted:
                    span.set_metadata(admitted=len(admitted), rids=" ".join(
                        (r.request if isinstance(r, KVHandoff) else r).rid
                        for r in admitted))
            progressed = self._prefill_step() or bool(admitted)
            if self.paged:
                self._ensure_decode_pages()
            return self._decode_step() or progressed

    def _decode_step(self) -> bool:
        """One fused decode over every slot whose prefill already
        finished; False when there is none."""
        active = np.array([r is not None and self.prefill_pos[i] < 0
                           for i, r in enumerate(self.slots)])
        if not active.any():
            return False
        sampling = bool((self.slot_temp[active] > 0).any())
        with jax.profiler.TraceAnnotation(
                "engine.decode", active=int(active.sum()),
                sampling=int(sampling)) as span:
            args = (self.params, jnp.asarray(self.last_tok), self.cache,
                    jnp.asarray(self.lengths), jnp.asarray(active),
                    jnp.asarray(self.slot_keys), jnp.asarray(self.slot_temp),
                    jnp.asarray(self.slot_topk))
            if self.paged:
                # bucket the block table (and with it the kernel grid) to
                # the pages actually in use — short sequences never pay
                # max_len
                mb = min(_bucket(max(len(p) for p in self.slot_pages), lo=1),
                         self.max_pages)
                span.set_metadata(mb=mb)
                self._count_retrace("decode", (mb, sampling))
                # mid-prefill slots hold REAL allocated pages but must not
                # decode: mask their table rows to the sentinel so the
                # decode step's junk appends drop instead of clobbering
                # their prompt
                bt_step = self.block_table[:, :mb].copy()
                bt_step[~active] = self.num_pages
                args = args + (jnp.asarray(bt_step),)
            else:
                self._count_retrace("decode", (self.max_len, sampling))
            next_tok, new_keys, self.cache = self._decode(
                *args, sampling=sampling)
            with jax.profiler.TraceAnnotation("engine.decode.fetch"):
                toks = np.asarray(next_tok)
                self.slot_keys = np.array(new_keys)  # writable copy
            self.lengths = self.lengths + active.astype(np.int32)
            # memory-per-token accounting (what the serving benchmark
            # reports): paged holds only its allocated pages, contiguous
            # always holds the full [max_slots, max_len] rows
            bytes_now = (self.pages_in_use() * self._page_bytes
                         if self.paged else self._cache_bytes)
            with self._lock:
                self._stats["decode_steps"] += 1
                self._stats["decode_slot_steps"] += int(active.sum())
                self._stats["kv_bytes_step_sum"] += bytes_now
                self._stats["kv_tokens_step_sum"] += int(
                    self.lengths[active].sum())
            with jax.profiler.TraceAnnotation("engine.emit") as emit:
                generated = 0
                finished = []
                now = time.time()
                for i, req in enumerate(self.slots):
                    if req is None or not active[i]:
                        continue
                    tok = int(toks[i])
                    req.tokens.append(tok)
                    req.token_times.append(now)
                    self.last_tok[i] = tok
                    generated += 1
                    if self._should_stop(req, tok, int(self.lengths[i])):
                        finished.append(req.rid)
                        self._finish_slot(i, RequestState.DONE)
                emit.set_metadata(tokens=generated,
                                  finished=" ".join(finished))
            if generated:
                self._bump("tokens_generated", generated)
        return True

    def run_until_drained(self, max_steps: int = 100_000) -> None:
        """Synchronous drive: step until queue and slots are empty."""
        steps = 0
        while self.has_work():
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")

    # -- service-stage body --------------------------------------------------

    def run_service(self, control: Optional[ServiceControl] = None,
                    resume_state: Any = None) -> Dict[str, Any]:
        """Long-running service loop (the body of a ``service=True`` stage).

        Pulls requests from the control inbox, steps the engine, and
        cooperates with the runtime: ``stop()`` exits immediately,
        ``drain()`` exits once every accepted request finished, and a
        preemption request checkpoints + yields via ServicePreempted.
        """
        if resume_state is not None:
            self.restore(resume_state)
            self._bump("resumes")
        if self.cache is None:
            self._init_state()
        while True:
            if control is not None:
                with jax.profiler.TraceAnnotation("service.take") as span:
                    taken = control.take_requests()
                    for req in taken:
                        self.submit(req)
                    span.set_metadata(taken=len(taken))
                if control.stop_requested():
                    # hard stop: sweep any request that raced in after the
                    # take above, then fail everything outstanding so
                    # Request.wait() callers are released, not hung
                    for req in control.take_requests():
                        self.submit(req)
                    self._fail_outstanding("service stopped before completion")
                    break
                if control.preempt_requested():
                    self._bump("preemptions")  # before the snapshot
                    # so the count survives restore()
                    state = self.checkpoint()
                    self._release_state()
                    raise ServicePreempted(state)
            if not self.step():
                if control is None:
                    break
                if (control.drain_requested()
                        and control.pending_requests() == 0):
                    break
                with jax.profiler.TraceAnnotation("service.wait"):
                    control.wait_for_work(self.idle_wait_s)
        return self.stats()

    # -- reporting -----------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        # the router's admission signals (queue depth/age, free pages,
        # occupancy) are snapshotted under ONE _lock acquisition so they
        # are mutually consistent
        with self._lock:
            out = dict(self._stats)
            now = time.time()
            queued = len(self.queue)
            oldest = (now - min(_entry_submitted_at(e)
                                for e in self.queue)
                      if self.queue else 0.0)
            free_pages = len(self.free_pages) if self.paged else 0
            occupied = sum(r is not None for r in self.slots)
        in_use = self.num_pages - free_pages
        out.update({
            "engine": self.uid,
            "max_slots": self.max_slots,
            "max_len": self.max_len,
            "continuous": self.continuous,
            "prefill_only": self.prefill_only,
            "kv_layout": "paged" if self.paged else "contiguous",
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "prefill_fns_cached": len(self._prefill_fns),
            "queued": queued,
            "queue_depth": queued,
            "oldest_queued_age_s": oldest,
            "occupied": occupied,
            "kv_cache_bytes": (in_use * self._page_bytes
                               if self.paged else self._cache_bytes),
            "kv_cache_capacity_bytes": (
                self.num_pages * self._page_bytes if self.paged
                else self._cache_bytes),
        })
        if self.paged:
            out.setdefault("peak_pages", 0)
            out.update({
                "page_size": self.page_size,
                "num_pages": self.num_pages,
                "pages_in_use": in_use,
                "free_pages": free_pages,
                "kv_cache_peak_bytes": (out.get("peak_pages", 0)
                                        * self._page_bytes),
            })
        out.setdefault("retraces", 0)
        d = out.get("decode_steps", 0)
        out["slot_occupancy"] = (
            out.get("decode_slot_steps", 0) / (d * self.max_slots)
            if d else 0.0)
        # mean cache bytes held per live token across decode steps — the
        # memory-efficiency figure the serving benchmark asserts on
        out["kv_bytes_per_token"] = (
            out.get("kv_bytes_step_sum", 0)
            / max(out.get("kv_tokens_step_sum", 0), 1))
        return out

    def reset_stats(self) -> None:
        with self._lock:
            self._stats = collections.defaultdict(int)
