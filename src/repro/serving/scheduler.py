"""Continuous-batching serving scheduler.

Production serving does not run prefill/decode on fixed request batches: it
keeps a fixed number of SLOTS (the compiled decode batch size), admits new
requests into free slots as running ones finish, and runs one fused decode
step per tick for whatever is resident.  That keeps the compiled decode
shape static (one XLA program) while the request mix churns — the same
design as production LLM servers, adapted to this framework's
``ServeState``.

Mechanics:

- One decode program of batch = ``num_slots`` is compiled once.  Empty
  slots carry a pad token and their outputs are ignored.
- Prefill runs per admitted request (batch 1) and its cache is scattered
  into the slot's rows of the shared stacked cache.
- Sampled tokens come back to the host in one read per decode step and
  are fed back through a host mirror of the decode input (one upload per
  step): no device op or wait per live slot.  Freed and never-used slots
  keep the last token they held.
- Per-request stopping: max_new_tokens or an EOS token id.
- Fairness/occupancy stats for capacity planning.

The scatter uses ``jax.tree.map`` over the cache pytree with a dynamic
batch-row update — O(cache_row) per admission, no recompile.
"""

from __future__ import annotations

import time
import warnings
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import PagedKVCache
from repro.models.model import Model
from repro.obs import trace as obs_trace
from repro.serving.paging import PagedPlan
from repro.train.serve_step import ServeState, jitted_steps, sample_token
from repro.utils.config import RunConfig


class PromptTooLong(ValueError):
    """A submitted request can never fit its serving deployment: prompt plus
    worst-case generation exceeds the dense ``cache_len`` or the paged slot
    capacity / page pool.  Carries the offending request uid and the limit so
    callers can report or reject-and-count (``on_too_long="reject"``)."""

    def __init__(self, uid: int, needed: int, limit: int, what: str):
        super().__init__(
            f"request {uid} needs {needed} cache tokens but the {what} "
            f"holds {limit}; it would silently truncate — reject it or "
            f"deploy a larger geometry")
        self.uid = uid
        self.needed = needed
        self.limit = limit


class DrainStall(RuntimeError):
    """A drain loop (real scheduler or the workload simulator) hit its tick
    budget with requests still queued or resident — a stall, not a completed
    run.  Carries the progress made so callers can report it."""

    def __init__(self, msg: str, *, completed: int, pending: int):
        super().__init__(msg)
        self.completed = completed
        self.pending = pending


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                  # (prompt_len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    extras: Dict[str, np.ndarray] = field(default_factory=dict)


@dataclass
class RequestState:
    request: Request
    slot: int
    generated: List[int] = field(default_factory=list)
    admitted_at: float = 0.0
    finished_at: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.finished_at is not None


def _scatter_rows(dst_tree, src_tree, slot: int):
    """Write src (batch-1 state rows) into dst at batch row `slot`.

    Cache leaves are stacked (layers, batch, ...); lengths are (batch,).
    The batch dim is located as the first axis whose size equals the slot
    count — for stacked leaves that is axis 1, for flat leaves axis 0.
    """
    def one(dst, src):
        if dst.ndim == src.ndim and dst.shape == src.shape:
            return dst  # shared/static (e.g. vision_kv broadcast) — keep
        if dst.ndim >= 2 and src.ndim == dst.ndim and \
                src.shape[0] == dst.shape[0] and src.shape[1] == 1:
            # stacked (layers, 1, ...) -> row `slot` of (layers, B, ...)
            return jax.lax.dynamic_update_slice_in_dim(dst, src, slot, axis=1)
        if src.ndim == dst.ndim and src.shape[0] == 1:
            return jax.lax.dynamic_update_slice_in_dim(dst, src, slot, axis=0)
        raise ValueError(f"unscatterable leaf {src.shape} -> {dst.shape}")

    return jax.tree.map(one, dst_tree, src_tree)


def _scatter_paged_rows(dst_tree, src_tree, slot: int, pages: List[int],
                        page_size: int, pages_per_slot_max: int,
                        scratch_page: int):
    """Write a dense batch-1 prefill state into slot ``slot`` of a paged
    decode state: KV rows land in the slot's reserved pool ``pages`` (the
    first ``len(pages) * page_size`` dense rows, page-reshaped into the
    pool's (page, head, row) layout once, at admission), the page
    table row is rewritten wholesale (tail entries pinned to the scratch
    page — valid and owned by nobody), and recurrent (SSM) leaves scatter
    exactly like the dense path."""
    table_row = np.full((pages_per_slot_max,), scratch_page, np.int32)
    table_row[:len(pages)] = pages
    table_row = jnp.asarray(table_row)
    pages_arr = jnp.asarray(pages, jnp.int32)

    def one(dst, src):
        if isinstance(dst, PagedKVCache):
            n = len(pages)

            def paged(dense):  # (nsb, 1, Hkv, S, D) -> (nsb, n, Hkv, ps, D)
                nsb, _, hkv, _, d = dense.shape
                rows = dense[:, 0, :, :n * page_size]
                return rows.reshape(nsb, hkv, n, page_size, d).transpose(
                    0, 2, 1, 3, 4)

            k_pages = dst.k_pages.at[:, pages_arr].set(paged(src.k))
            v_pages = dst.v_pages.at[:, pages_arr].set(paged(src.v))
            table = dst.page_table.at[:, slot].set(table_row[None])
            length = dst.length.at[:, slot].set(src.length[:, 0])
            return PagedKVCache(k_pages, v_pages, table, length)
        return _scatter_rows(dst, src, slot)

    return jax.tree.map(one, dst_tree, src_tree,
                        is_leaf=lambda x: isinstance(x, PagedKVCache))


class ContinuousBatcher:
    def __init__(self, model: Model, run: RunConfig, params, *,
                 num_slots: int = 8, cache_len: int = 512,
                 eos_token: Optional[int] = None, seed: int = 0,
                 launch_config: Optional[Dict[str, Any]] = None,
                 interleave: str = "eager",
                 paged: Optional[PagedPlan] = None,
                 on_too_long: str = "raise"):
        if interleave not in ("eager", "drain"):
            raise ValueError(
                f"unknown interleave policy {interleave!r}; "
                f"known: ['drain', 'eager']")
        if on_too_long not in ("raise", "reject"):
            raise ValueError(f"on_too_long must be 'raise' or 'reject', "
                             f"got {on_too_long!r}")
        self.model = model
        self.run = run
        self.params = params
        self.num_slots = num_slots
        self.eos_token = eos_token
        self.interleave = interleave
        self.on_too_long = on_too_long
        self._key = jax.random.PRNGKey(seed)

        self.paged = paged if (paged is not None and paged.paging) else None
        if self.paged is not None:
            if model.init_paged_decode_state is None:
                raise NotImplementedError(
                    f"model family {model.cfg.family!r} has no paged decode "
                    f"state; serve it dense (pages.paging=off)")
            # the compiled decode shape is the (pool, page) geometry — the
            # per-slot capacity is a page-table property, not a cache axis,
            # so `cache_len` is superseded by page_size * pages_per_slot_max
            self.cache_len = self.paged.slot_capacity
            caches = model.init_paged_decode_state(
                num_slots, self.paged.pool_pages, self.paged.page_size,
                self.paged.pages_per_slot_max)
            self._free_pages: List[int] = list(range(self.paged.pool_pages))
            self._slot_pages: List[List[int]] = [[] for _ in range(num_slots)]
            # pages hold attention KV, not only bookkeeping for recurrent
            # state: the ticks then count the pages held
            self._kv_paged = any(
                isinstance(c, PagedKVCache) for c in jax.tree.leaves(
                    caches, is_leaf=lambda x: isinstance(x, PagedKVCache)))
        else:
            self._kv_paged = False
            self.cache_len = cache_len
            caches = model.init_decode_state(num_slots, cache_len)

        # a tuned kernel-launch optimum (e.g. TuneResult.launch_config) is
        # baked into the traces; the shared cache means several batchers on
        # one model reuse the compilation.  Prefill always runs dense — for
        # paged deployments at the slot capacity, then page-scattered.
        self._prefill, self._decode = jitted_steps(
            model, run, cache_len=self.cache_len, launch_config=launch_config)

        self.state = ServeState(
            caches=caches,
            lengths=jnp.zeros((num_slots,), jnp.int32),
            extras={})
        # the decode step's token input, kept on the host: the only place
        # per-slot tokens are written
        self._host_tokens = np.zeros((num_slots,), np.int32)
        self._slots: List[Optional[RequestState]] = [None] * num_slots
        self.queue: List[Request] = []
        self.completed: List[RequestState] = []
        # chunked prefill in flight: [request, tokens_done, slot, pages]
        self._prefilling: Optional[List[Any]] = None
        self.rejected_too_long = 0
        self.prefill_chunks = 0
        self.ticks = 0
        self.stalled = False
        self._occupancy_sum = 0
        # per-decode-tick paged mediators (mirror the simulator's counters)
        self._pool_occ_sum = 0.0
        self._chunks_inflight_sum = 0.0
        # lifetime wall time inside prefill vs decode launches — replay
        # reports diff these to get a per-replay prefill/decode split
        self.prefill_s = 0.0
        self.decode_s = 0.0
        # request-lifecycle tracing: submit timestamps (tracer us) per uid,
        # populated only while a tracer is active — the disabled path never
        # touches it, so tokens/counters stay bit-identical
        self._submit_ts: Dict[int, float] = {}

    # -- admission ----------------------------------------------------------

    def _worst_case_tokens(self, request: Request) -> int:
        """Cache rows this request can ever occupy: the prompt plus every
        decode-tick write (the first token is sampled from prefill and costs
        no extra row)."""
        return len(request.prompt) + max(request.max_new_tokens - 1, 0)

    def submit(self, request: Request) -> None:
        """Enqueue a request, rejecting (or raising, per ``on_too_long``) any
        that could never fit the deployed geometry — dense caches silently
        drop overflow rows, which corrupts decoding rather than failing."""
        needed = self._worst_case_tokens(request)
        if self.paged is not None:
            limit = min(self.paged.slot_capacity,
                        self.paged.pool_pages * self.paged.page_size)
            what = "paged slot"
        else:
            limit = self.cache_len
            what = "dense cache"
        if needed > limit:
            if self.on_too_long == "raise":
                raise PromptTooLong(request.uid, needed, limit, what)
            self.rejected_too_long += 1
            tr = obs_trace.active()
            if tr is not None:
                tr.instant("reject_too_long", cat="request",
                           uid=request.uid, needed=needed, limit=limit)
            return
        tr = obs_trace.active()
        if tr is not None:
            self._submit_ts[request.uid] = tr.now_us()
            tr.async_begin("request", request.uid,
                           prompt_len=len(request.prompt),
                           max_new=request.max_new_tokens)
        self.queue.append(request)

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s is None]

    def _prefill_and_seat(self, req: Request, slot: int,
                          pages: Optional[List[int]]) -> int:
        """Run the (dense, batch-1) prefill and seat the request in ``slot``
        — scattered into its reserved ``pages`` for paged deployments.
        Returns the number of times the host waited on the device."""
        tr = obs_trace.active()
        if tr is not None:
            # admission closes the queue phase begun at submit
            sub_ts = self._submit_ts.pop(req.uid, None)
            if sub_ts is not None:
                tr.complete("serve.queue", sub_ts, tr.now_us() - sub_ts,
                            cat="request", uid=req.uid)
        syncs = 0
        with obs_trace.span(
                "serve.admit", cat="request", uid=req.uid, slot=slot,
                pages=len(pages) if pages is not None else 0,
                free=len(self._free_pages) if pages is not None else 0
        ) as admit:
            prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
            batch = {"tokens": prompt}
            for k, v in req.extras.items():
                batch[k] = jnp.asarray(v)[None]
            t0 = time.perf_counter()
            with obs_trace.span("serve.prefill", cat="request", uid=req.uid,
                                prompt_len=len(req.prompt)):
                one_state, logits = self._prefill(self.params, batch)
                jax.block_until_ready(logits)
                syncs += 1
            self.prefill_s += time.perf_counter() - t0
            with obs_trace.span("serve.scatter", cat="request", uid=req.uid):
                if pages is not None:
                    caches = _scatter_paged_rows(
                        self.state.caches, one_state.caches, slot, pages,
                        self.paged.page_size, self.paged.pages_per_slot_max,
                        scratch_page=self.paged.pool_pages)
                else:
                    caches = _scatter_rows(self.state.caches,
                                           one_state.caches, slot)
                self.state = ServeState(
                    caches=caches,
                    lengths=self.state.lengths.at[slot].set(
                        one_state.lengths[0]),
                    extras=self.state.extras)
            with obs_trace.span("serve.first_token", cat="request",
                                uid=req.uid):
                self._key, sub = jax.random.split(self._key)
                tok = int(sample_token(logits, sub, req.temperature)[0])
                syncs += 1
                rs = RequestState(req, slot, admitted_at=time.perf_counter())
                rs.generated.append(tok)
                self._host_tokens[slot] = tok
                self._slots[slot] = rs
                self._maybe_finish(rs, tok)
            admit.set(syncs=syncs)
        return syncs

    def _admit(self) -> Tuple[int, int]:
        """Seat queued requests in free slots; returns how many were seated
        and the host's waits on the device that took."""
        if self.interleave == "drain" and \
                any(s is not None for s in self._slots):
            # drain policy: only refill once the resident batch empties —
            # the same admission gate the workload simulator prices
            return 0, 0
        if self.paged is not None and self.paged.prefill_chunk > 0:
            return self._admit_chunked()
        admitted = syncs = 0
        for slot in self._free_slots():
            if not self.queue:
                break
            if self.paged is not None:
                # reserve the worst case up front: unlike the simulator the
                # real batcher never grows a resident mid-flight (and so
                # never evicts) — exhausted pool defers admission instead
                need = self.paged.pages_for(
                    self._worst_case_tokens(self.queue[0]))
                if need > len(self._free_pages):
                    obs_trace.instant("defer", cat="request",
                                      uid=self.queue[0].uid, need=need,
                                      free=len(self._free_pages))
                    break
                pages = [self._free_pages.pop(0) for _ in range(need)]
                self._slot_pages[slot] = pages
            else:
                pages = None
            req = self.queue.pop(0)
            syncs += self._prefill_and_seat(req, slot, pages)
            admitted += 1
        return admitted, syncs

    def _admit_chunked(self) -> Tuple[int, int]:
        """Chunked-prefill admission: one prompt chunk per tick, decode
        ticking underneath.  The jitted prefill still runs once, over the
        full prompt, when the last chunk lands — chunking is a *scheduling*
        decision (when prefill work occupies the accelerator), so generated
        tokens stay bit-identical to the unchunked batcher."""
        if self._prefilling is not None:
            req, done, slot, pages = self._prefilling
            done += min(self.paged.prefill_chunk, len(req.prompt) - done)
            self.prefill_chunks += 1
            obs_trace.instant("prefill_chunk", cat="request", uid=req.uid,
                              done=done, prompt_len=len(req.prompt))
            if done >= len(req.prompt):
                self._prefilling = None
                return 1, self._prefill_and_seat(req, slot, pages)
            self._prefilling[1] = done
            return 0, 0
        free = self._free_slots()
        if not self.queue or not free:
            return 0, 0
        need = self.paged.pages_for(self._worst_case_tokens(self.queue[0]))
        if need > len(self._free_pages):
            obs_trace.instant("defer", cat="request", uid=self.queue[0].uid,
                              need=need, free=len(self._free_pages))
            return 0, 0
        slot = free[0]
        pages = [self._free_pages.pop(0) for _ in range(need)]
        self._slot_pages[slot] = pages
        self._prefilling = [self.queue.pop(0), 0, slot, pages]
        return 0, 0

    # -- stepping -----------------------------------------------------------

    def _maybe_finish(self, rs: RequestState, tok: int) -> None:
        if rs.done:
            return
        if (self.eos_token is not None and tok == self.eos_token) or \
                len(rs.generated) >= rs.request.max_new_tokens:
            rs.finished_at = time.perf_counter()
            self.completed.append(rs)
            self._slots[rs.slot] = None
            tr = obs_trace.active()
            if tr is not None:
                tr.async_end("request", rs.request.uid,
                             generated=len(rs.generated))
            if self.paged is not None:
                self._free_pages.extend(self._slot_pages[rs.slot])
                self._slot_pages[rs.slot] = []
                self._park_slot(rs.slot)

    def _park_slot(self, slot: int) -> None:
        """Point a freed slot's page-table rows back at the scratch page.
        Its pages return to the pool and may be reallocated immediately, but
        the empty slot keeps scattering pad-token K/V every decode tick (the
        compiled step has no notion of emptiness) — those writes must not
        land on pages a later owner holds."""
        scratch = jnp.full((self.paged.pages_per_slot_max,),
                           self.paged.pool_pages, jnp.int32)

        def one(dst):
            if isinstance(dst, PagedKVCache):
                return dst._replace(
                    page_table=dst.page_table.at[:, slot].set(scratch[None]))
            return dst

        self.state = self.state._replace(caches=jax.tree.map(
            one, self.state.caches,
            is_leaf=lambda x: isinstance(x, PagedKVCache)))

    @property
    def _tokens(self) -> jax.Array:
        """The decode input on the device, (num_slots,) int32: each live
        slot's last token; freed and never-used slots keep what they held."""
        # a copy: the CPU backend may alias a host buffer it is given
        return jnp.asarray(self._host_tokens.copy())

    def tick(self) -> int:
        """Admit + one decode step for all resident requests.
        Returns the number of live requests stepped."""
        with obs_trace.span("serve.tick", cat="serve") as span:
            admitted, syncs = self._admit()
            live = [s for s in self._slots if s is not None]
            if live:
                syncs += self._step(live)
            span.set(live=len(live), admitted=admitted, syncs=syncs)
            if self._kv_paged:
                span.set(kv_pages_held=(self.paged.pool_pages
                                        - len(self._free_pages)))
        return len(live)

    def _step(self, live: List[RequestState]) -> int:
        """One decode step for the ``live`` slots: decode, sample, read every
        slot's token in one transfer and feed each back through the host
        mirror.  Returns the host's waits on the device (two)."""
        self.ticks += 1
        self._occupancy_sum += len(live)
        if self.paged is not None:
            self._pool_occ_sum += ((self.paged.pool_pages
                                    - len(self._free_pages))
                                   / self.paged.pool_pages)
            self._chunks_inflight_sum += (
                1.0 if self._prefilling is not None else 0.0)
        tr = obs_trace.active()
        if tr is not None:
            tr.counter("queue_depth", len(self.queue))
        syncs = 0
        t0 = time.perf_counter()
        with obs_trace.span("serve.decode", cat="serve", live=len(live),
                            tick=self.ticks):
            new_state, logits = self._decode(
                self.params, self.state,
                jnp.asarray(self._host_tokens[:, None].copy()))
            jax.block_until_ready(logits)
            syncs += 1
        self.decode_s += time.perf_counter() - t0
        self.state = new_state
        with obs_trace.span("serve.sample", cat="serve"):
            self._key, sub = jax.random.split(self._key)
            # per-slot temperatures: requests with different sampling
            # settings share one decode step, so each resident row decodes
            # at its own temperature (empty slots sample greedily into
            # ignored outputs); the all-greedy batch — the common replay
            # case — keeps the scalar argmax-only fast path
            if any(rs.request.temperature > 0.0 for rs in live):
                temps = np.zeros((self.num_slots,), np.float32)
                for rs in live:
                    temps[rs.slot] = rs.request.temperature
                toks = sample_token(logits, sub, jnp.asarray(temps))
            else:
                toks = sample_token(logits, sub, 0.0)
        with obs_trace.span("serve.feedback", cat="serve"):
            sampled = np.asarray(toks)        # every slot's token, one read
            syncs += 1
            for rs in live:
                tok = int(sampled[rs.slot])
                rs.generated.append(tok)
                self._host_tokens[rs.slot] = tok
                self._maybe_finish(rs, tok)
        return syncs

    def run_until_drained(self, max_ticks: int = 10_000,
                          on_limit: str = "raise") -> List[RequestState]:
        """Tick until every submitted request finishes or ``max_ticks`` ticks
        (counted from this call) elapse.  Hitting the limit with work still
        pending is a stall, never silently partial results: ``on_limit`` is
        ``"raise"`` (:class:`DrainStall`, the default) or ``"warn"`` (emit a
        ``RuntimeWarning``, set :attr:`stalled`, return what completed)."""
        if on_limit not in ("raise", "warn"):
            raise ValueError(f"on_limit must be 'raise' or 'warn', "
                             f"got {on_limit!r}")
        self.stalled = False
        start = self.ticks
        while self.queue or self._prefilling is not None or \
                any(s is not None for s in self._slots):
            if self.ticks - start >= max_ticks:
                pending = (len(self.queue) + sum(
                    s is not None for s in self._slots)
                    + (self._prefilling is not None))
                msg = (f"batcher not drained after {max_ticks} ticks: "
                       f"{len(self.completed)} completed, {pending} pending")
                if on_limit == "raise":
                    raise DrainStall(msg, completed=len(self.completed),
                                     pending=pending)
                warnings.warn(msg, RuntimeWarning, stacklevel=2)
                self.stalled = True
                break
            if self.tick() == 0 and not self.queue and \
                    self._prefilling is None:
                break
        return self.completed

    # -- stats ----------------------------------------------------------------

    @property
    def mean_occupancy(self) -> float:
        return self._occupancy_sum / max(self.ticks, 1)
