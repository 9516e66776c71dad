"""Autoregressive LM serving engines — the paper's "E"(stimate) hot loop.

Two interchangeable decode engines behind one facade:

  * :class:`PagedDecodeEngine` — continuous batching over a **paged KV
    cache**: requests borrow fixed-size blocks from a shared pool
    (serving/blocks.py) under a unified token-budget scheduler
    (serving/scheduler.py).  Every engine step is one token-budgeted batch
    mixing multi-token prefill chunks and single-token decodes through one
    compiled ``paged_step`` path; identical prompt prefixes are shared
    copy-on-write through the manager's prefix cache instead of being
    re-prefilled.  Memory is committed per block actually used, so at equal
    memory budget it admits far more concurrent requests than dense
    per-slot slabs.
  * :class:`SlotDecodeEngine` — the dense reference: one ``cache_len`` slab
    per lane, kept for model families whose decode state is O(1) recurrent
    (ssm/hybrid/audio) and as the equivalence oracle for the paged path.

``DecodeEngine(api, params, ...)`` picks the paged engine whenever the
model family supports it (transformer-backed: dense / moe / vlm) and the
dense-slot engine otherwise — the public surface (``submit`` /
``step`` / ``run_until_drained``) is identical.
"""
from __future__ import annotations

import logging
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.serving import trace
from repro.serving.batch import RaggedBatch, padded_pow2
from repro.serving.blocks import KVCacheManager
from repro.serving.scheduler import (Request, RequestState, Scheduler,
                                     SchedulerConfig, StepDecision)
from repro.serving.spec import NgramProposer, Proposer

PyTree = Any
_log = logging.getLogger(__name__)


def _emit_token(engine, req: "Request", tok: int) -> bool:
    """THE shared step-completion emission — every engine class routes
    token emission through here, so ``t_first_token`` / ``t_done`` are
    stamped exactly once and from the engine's clock abstraction
    (``engine._now()``: wall time, or a shared SimClock in disaggregated /
    open-loop runs — which is what makes TTFT rows comparable across
    engine kinds).  Appends the token, bumps the decode counter, stamps
    the latency marks, and fires the engine's ``on_token`` streaming
    callback.  Returns True when the request just finished (hit its
    ``max_new_tokens`` or the EOS token)."""
    req.generated.append(tok)
    req.feed.append(tok)
    engine.tokens_decoded += 1
    if req.t_first_token == 0.0:
        req.t_first_token = engine._now()
    finished = (len(req.generated) >= req.max_new_tokens
                or tok == engine.eos)
    if finished:
        req.t_done = engine._now()
    if engine.on_token is not None:
        engine.on_token(req.request_id, tok, finished)
    return finished


def _mesh_dp_tp(mesh):
    """(data-parallel degree, tensor-parallel degree) of a serving mesh:
    tp is the "model" axis, dp the product of everything else."""
    from repro.launch.mesh import mesh_axis_sizes
    sizes = mesh_axis_sizes(mesh)
    tp = sizes.get("model", 1)
    dp = 1
    for name, n in sizes.items():
        if name != "model":
            dp *= n
    return dp, tp


def DecodeEngine(model_api, params: PyTree, *, paged: Optional[bool] = None,
                 mesh=None, **kw):
    """Facade: the paged engine when the model family supports it, the
    dense-slot engine otherwise.  ``paged=True/False`` forces the choice.

    ``mesh`` (a ``jax.sharding.Mesh``) serves across every device it
    holds: the "model" axis shards one engine tensor-parallel, while any
    data axis > 1 routes to :class:`ShardedDecodeEngine` — one full paged
    engine per data slice.  Mesh serving requires the paged path.
    """
    if paged is None:
        paged = getattr(model_api, "supports_paged", False)
    if mesh is not None:
        if not paged:
            raise ValueError(
                f"{model_api.cfg.family} models have no paged-KV decode "
                "path; mesh serving shards the paged engine only")
        dp, _ = _mesh_dp_tp(mesh)
        if dp > 1:
            return ShardedDecodeEngine(model_api, params, mesh=mesh, **kw)
        return PagedDecodeEngine(model_api, params, mesh=mesh, **kw)
    cls = PagedDecodeEngine if paged else SlotDecodeEngine
    return cls(model_api, params, **kw)


# ---------------------------------------------------------------------------
class PagedDecodeEngine:
    """Continuous-batching decode over a block-paged KV pool.

    ``n_slots`` is the number of concurrent lanes the jitted step batches
    over; ``cache_len`` caps one request's logical KV length.  The physical
    pool defaults to the dense-equivalent size (``n_slots`` full sequences,
    plus the null block) — pass a smaller ``num_blocks`` to oversubscribe
    memory and exercise preemption, or a larger one to admit more lanes
    than dense slabs could.

    Batch layout (``ragged``, default True for families providing
    ``ragged_step``): every step's scheduled tokens are flattened into one
    1-D stream with per-token (lane, position, KV-slot) metadata — a mixed
    prefill+decode step costs ~``sum(q_len)`` tokens of model work.
    ``ragged=False`` pins the legacy rectangular ``(n_slots, chunk_width)``
    layout, where one lane prefilling a wide chunk pads every decoding
    lane to the same width (``lanes * max(q_len)`` work) — kept as the PR 2
    baseline and for the padding-tax comparison in bench_serving.

    Attention grid (``tiled``, default True under ``ragged``): the flat
    stream is segment-tiled (``tile`` q rows per window, split at segment
    boundaries — serving/batch.py::TileMap), so the paged-attention read
    sweeps each lane's KV blocks once per q-tile instead of once per
    token.  ``tiled=False`` pins the per-token ``(token, head, block)``
    grid as the measured baseline.

    Speculative decode (``spec``, default True wherever the multi-token
    step exists): each decode lane schedules up to ``draft_k`` proposer
    drafts as one ``1 + k``-token segment per step; the step's per-row
    greedy argmax verifies them, the longest matching draft prefix plus
    one bonus token is accepted (always >= 1 token — zero acceptance
    degrades exactly to the plain decode step), and the KV cache is
    rewound past the rejected slots (``KVCacheManager.rewind``).  Greedy
    outputs are token-identical to ``spec=False`` (which pins the
    one-token-per-step decode) for ANY proposer; the default
    :class:`~repro.serving.spec.NgramProposer` drafts from each request's
    own token history, so acceptance is free on the repetitive tails long
    generations settle into.  This is the one path where a request
    advances a *variable* number of tokens per engine iteration —
    positions, slot mapping, budget accounting, and preemption all ride
    the same multi-token segment bookkeeping chunked prefill uses.
    """

    def __init__(self, model_api, params: PyTree, *, n_slots: int,
                 cache_len: int, eos_token: int = -1, window: int = 0,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 token_budget: int = 0, chunk_tokens: int = 16,
                 prefix_cache: bool = True, ragged: Optional[bool] = None,
                 tiled: Optional[bool] = None, tile: int = 16,
                 spec: bool = True, draft_k: int = 4,
                 proposer: Optional[Proposer] = None,
                 host_swap: bool = True,
                 host_swap_blocks: Optional[int] = None,
                 ttft_target: float = 0.0, tpot_target: float = 0.0,
                 clock=None,
                 mesh=None, cache_dtype=None, compute_dtype=None) -> None:
        """Build the paged engine: block pool, scheduler, jitted steps.

        ``ragged``/``tiled`` default to on where supported; ``spec=True``
        wires the speculative path with an :class:`NgramProposer` unless
        ``proposer`` overrides it.  ``num_blocks`` defaults to the pool
        that matches ``n_slots * cache_len`` tokens.

        ``host_swap`` (on wherever the prefix cache is) backs the device
        pool with a host-side block tier: a registered block evicted from
        the device — a preempted sequence's prefix, or a cold cached
        chain — parks its payload in host memory instead of being lost,
        and a later admission swaps it back into a fresh device block
        rather than recomputing it.  ``host_swap_blocks`` caps the tier
        (LRU-dropped beyond it; default unbounded).

        ``ttft_target`` / ``tpot_target`` (seconds, 0 = off) arm the
        scheduler's SLO-aware admission: chunk-shrink and admission
        shedding when observed decode TPOT slips past target (see
        :class:`~repro.serving.scheduler.SchedulerConfig`).  ``clock``
        (a :class:`~repro.core.simclock.SimClock`) replaces wall time for
        every latency stamp — the disaggregated engine installs its
        shared clock on both sides so TTFT rows stay comparable.

        ``cache_dtype=jnp.int8`` stores the paged KV pools quantized
        (per-(block, slot, kv-head) symmetric scales ride in parallel
        ``k_scale``/``v_scale`` pools) — half/quarter the pool bytes, with
        dequantization fused into the attention read.

        ``mesh`` (a ``jax.sharding.Mesh`` whose data axes are size 1)
        runs this one engine tensor-parallel over the mesh's "model"
        axis: parameters take the serving rule table
        (:func:`repro.launch.sharding.serving_param_specs`), the KV pools
        shard their kv-head dim (:func:`paged_pool_specs` — replicating
        when GQA heads don't divide), and every host-built metadata array
        is committed replicated, so the compiled step partitions by GSPMD
        propagation alone.  Scheduler, block pool, CoW, speculation, and
        transfer logic are untouched — they address logical block ids,
        which are identical on every shard.
        """
        if not getattr(model_api, "supports_paged", False):
            raise ValueError(
                f"{model_api.cfg.family} models have no paged-KV decode "
                "path; use DecodeEngine (it falls back to dense slots)")
        self.api = model_api
        self.params = params
        self.mesh = mesh
        self.tp = 1
        self._repl = None               # replicated sharding for metadata
        self._pool_shardings = None     # canonical NamedShardings per pool
        if mesh is not None:
            dp, self.tp = _mesh_dp_tp(mesh)
            if dp > 1:
                raise ValueError(
                    f"mesh has a data-parallel extent of {dp}; "
                    "PagedDecodeEngine shards ONE engine tensor-parallel — "
                    "use ShardedDecodeEngine (or DecodeEngine(mesh=...)) "
                    "for data-parallel slices")
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.eos = eos_token
        self.window = window
        self.block_size = block_size
        if chunk_tokens < 1:
            # unlike the raw SchedulerConfig, the engine compiles one step
            # per pow2 chunk width, so an "unlimited" chunk is not meaningful
            raise ValueError("chunk_tokens must be >= 1 "
                             "(1 = one-token-per-step prefill)")
        if getattr(model_api, "paged_step", None) is None:
            chunk_tokens = 1          # legacy q_len=1 step: no chunking
            spec = False              # q_len=1: no multi-token verification
        # ragged flat-token batching is the default whenever the model
        # family provides the flat step; ``ragged=False`` pins the legacy
        # rectangular (n_slots, chunk_width) layout (the PR 2 baseline)
        ragged_fn = getattr(model_api, "ragged_step", None)
        if ragged is None:
            ragged = ragged_fn is not None
        if ragged and ragged_fn is None:
            raise ValueError(
                f"{model_api.cfg.family} models have no ragged_step; "
                "pass ragged=False for the rectangular paged path")
        self.ragged = ragged
        # the segment-tiled attention grid is the ragged default; tiled=False
        # pins the per-token (token, head, block) grid as the baseline
        if tiled is None:
            tiled = ragged
        if tiled and not ragged:
            raise ValueError("tiled=True requires the ragged flat-token "
                             "layout (pass ragged=True)")
        if tile < 1 or tile & (tile - 1):
            raise ValueError(f"tile must be a positive power of two, "
                             f"got {tile}")
        self.tiled = tiled
        self.tile = tile
        self.chunk_tokens = chunk_tokens
        if draft_k < 0:
            raise ValueError(f"draft_k must be >= 0, got {draft_k}")
        self.spec = bool(spec) and draft_k > 0
        self.draft_k = draft_k if self.spec else 0
        if self.spec and proposer is None:
            proposer = NgramProposer()
        self.proposer = proposer if self.spec else None
        self.max_blocks = -(-cache_len // block_size)
        if num_blocks is None:
            num_blocks = n_slots * self.max_blocks + 1   # +1: null block
        self.num_blocks = num_blocks
        self.kv = KVCacheManager(num_blocks, block_size,
                                 max_blocks_per_seq=self.max_blocks,
                                 enable_prefix_cache=prefix_cache)
        # device->host swap tier: digest -> {"parent", "tokens", "payload"},
        # LRU-ordered.  Installed as the manager's host_has/on_swap_out
        # hooks so eviction parks payloads here and admission plans
        # swap-ins against it.
        self.host_swap = bool(host_swap) and prefix_cache
        self.host_swap_blocks = host_swap_blocks
        self._host_tier: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
        # digests mid-import whose device payload write has not landed yet:
        # the swap-out hook must not capture their (garbage) device bytes
        self._swap_quarantine: set = set()
        self.host_swap_outs = 0
        self.host_swap_ins = 0
        self.host_swap_drops = 0
        if self.host_swap:
            self.kv.host_has = self._host_tier.__contains__
            self.kv.on_swap_out = self._swap_out_block
        self.scheduler = Scheduler(
            SchedulerConfig(n_lanes=n_slots, token_budget=token_budget,
                            chunk_tokens=self.chunk_tokens,
                            fill_to_bucket=self.ragged,
                            draft_k=self.draft_k, proposer=self.proposer,
                            ttft_target=ttft_target,
                            tpot_target=tpot_target),
            self.kv)
        # clock abstraction: latency stamps (t_submit / t_first_token /
        # t_done, and the scheduler's SLO deadlines) read self._now() —
        # wall time by default, a shared SimClock when one is installed
        self.clock = None
        self.set_clock(clock)
        # per-token streaming hook: on_token(request_id, token, finished),
        # fired from the step thread by the shared emission helper
        self.on_token = None
        kw = {"num_blocks": num_blocks, "block_size": block_size,
              "max_blocks_per_lane": self.max_blocks}
        if cache_dtype is not None:
            kw["dtype"] = cache_dtype

        def init_cache():
            cache = model_api.init_paged_cache(n_slots, **kw)
            if self.ragged:
                # ragged_step tracks per-token positions, not per-lane
                # "pos"; drop it so the first step's cache signature
                # matches every later one (no pointless retrace per bucket)
                cache.pop("pos", None)
            return cache

        self.kv_heads_sharded = False
        if mesh is None:
            self.cache = init_cache()
        else:
            from repro.launch import sharding as shlib
            from repro.launch.mesh import mesh_axis_sizes
            axes = mesh_axis_sizes(mesh)
            pspecs = shlib.serving_param_specs(params, axes)
            self.params = jax.device_put(params,
                                         shlib.to_named(pspecs, mesh))
            cspecs = shlib.paged_pool_specs(jax.eval_shape(init_cache), axes)
            self._pool_shardings = shlib.to_named(cspecs, mesh)
            # materialize the pools straight into their shardings: a
            # slice's pool never passes through the default device
            self.cache = jax.jit(init_cache,
                                 out_shardings=self._pool_shardings)()
            self._repl = NamedSharding(mesh, P())
            self.kv_heads_sharded = any(
                "model" in s for s in jax.tree.leaves(
                    cspecs, is_leaf=lambda x: isinstance(x, P)))
        step_kw = {"window": window}
        if self.ragged and self.tiled:
            step_kw["tile"] = tile     # static TileMap q-window rows
        if compute_dtype is not None:
            step_kw["compute_dtype"] = compute_dtype
        # the Pallas attention kernel runs on a TPU backend; a
        # multi-device mesh pins it off (below)
        self.use_kernel = jax.default_backend() == "tpu"
        if mesh is not None and mesh.devices.size > 1:
            # shard-local dispatch: the partitioned step must lower to the
            # GSPMD-partitionable jnp reference attention on every shard —
            # the Pallas kernel is a single-device lowering (its scalar
            # prefetch and pool indexing assume the whole pool is local)
            step_kw["use_kernel"] = False
            self.use_kernel = False
            _log.warning("PagedDecodeEngine: Pallas attention kernel off on "
                         "this %d-device mesh; attention runs the jnp "
                         "reference", mesh.devices.size)
        # donate the cache: the KV pool is updated in place rather than
        # double-buffered (decisive for pool size = device memory on TPU).
        # Rectangular: one jitted step per pow2 chunk width (O(log
        # chunk_tokens) retraces, decode-only steps stay at width 1).
        # Ragged: one jitted step per pow2 *total token count* (O(log
        # token_budget) retraces) — the flat stream has no per-lane width
        # at all, so a mixed prefill+decode step does work proportional to
        # the real scheduled tokens.
        if self.ragged:
            step_fn = ragged_fn
        else:
            step_fn = model_api.resolve_paged_step() \
                if hasattr(model_api, "resolve_paged_step") \
                else (getattr(model_api, "paged_step", None)
                      or model_api.paged_decode_step)
        self._step = jax.jit(
            lambda p, c, t: step_fn(p, c, t, **step_kw),
            donate_argnums=(1,))
        self._cow = jax.jit(self._apply_copies, donate_argnums=(0,))
        self._finished: List[Request] = []
        self._next_id = 0
        self.tokens_decoded = 0
        self.tokens_prefilled = 0
        # cancellation / SLO-shed accounting
        self.cancelled = 0
        self.shed = 0
        self.host_purged = 0            # host-tier entries cancel reclaimed
        self.cow_block_copies = 0
        self.steps = 0
        # padding-tax accounting: real scheduled tokens vs flat/rect slots
        # the compiled step actually processed
        self.scheduled_tokens = 0
        self.padded_tokens = 0
        # (tile, KV block) visits of the tiled attention, per layer
        self.kv_block_visits = 0
        # speculative-decode accounting: drafted vs accepted draft tokens,
        # and per-verification emitted counts (always >= 1: the bonus)
        self.tokens_drafted = 0
        self.draft_tokens_accepted = 0
        self.spec_verifications = 0       # decode emissions that had drafts
        self.spec_tokens_emitted = 0      # tokens those emissions produced
        # mesh accounting: collectives in ONE compiled step (counted from
        # the first bucket's optimized HLO, lazily) and their running total
        self._collectives_per_step: Optional[int] = None
        self.collective_ops = 0
        self._last_tokens = None        # the last step's token array

    # ------------------------------------------------------------------
    def _put(self, x):
        """Commit a host-built array to the device — replicated across the
        mesh in mesh mode, so GSPMD partitions the step from the sharded
        params/pools alone (the replicated-metadata contract: block
        tables, per-token lane/pos/slot metadata, and tile maps are
        identical bytes on every shard)."""
        x = jnp.asarray(x)
        if self._repl is None:
            return x
        return jax.device_put(x, self._repl)

    def step_hlo(self, tokens=None) -> str:
        """Optimized HLO text of the compiled step for ``tokens``' bucket
        (default: the last step's) against the current cache — where a
        caller reads which collectives the step holds and whether the
        Pallas kernel (``tpu_custom_call``) is in it."""
        if tokens is None:
            tokens = self._last_tokens
        return self._step.lower(self.params, self.cache,
                                tokens).compile().as_text()

    def _count_collectives(self, tokens) -> int:
        """Collectives per compiled step, from the optimized HLO of the
        current bucket (counted once)."""
        import re
        return len(re.findall(
            r"\b(?:all-reduce|all-gather|reduce-scatter"
            r"|collective-permute|all-to-all)(?:-start)?\(",
            self.step_hlo(tokens)))

    # ------------------------------------------------------------------
    def set_clock(self, clock) -> None:
        """Install a :class:`~repro.core.simclock.SimClock` as the source
        of every latency stamp (``None`` restores wall time).  The
        scheduler's SLO deadlines follow the same clock, so virtual-time
        open-loop runs and wall-clock serving share one admission
        policy."""
        self.clock = clock
        self.scheduler.now_fn = self._now

    def _now(self) -> float:
        """Current time on the engine's clock: the installed SimClock's
        sim time, else the process wall clock."""
        return self.clock.now if self.clock is not None \
            else time.perf_counter()

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               priority: int = 0) -> int:
        """Queue a request; returns its id.  Rejects requests whose total
        length (prompt + new tokens) can never fit the pool.  ``priority``
        is the scheduler's admission/preemption class (higher admits
        first, evicted last; default 0 keeps plain FIFO)."""
        prompt = np.asarray(prompt, np.int32)
        total = len(prompt) + max_new_tokens
        usable = min(self.max_blocks, self.num_blocks - 1)
        if self.kv.blocks_needed(total) > usable:
            raise ValueError(
                f"request of {total} tokens needs "
                f"{self.kv.blocks_needed(total)} blocks; engine can serve "
                f"at most {usable} per request")
        rid = self._next_id
        self._next_id += 1
        req = Request(rid, prompt, max_new_tokens, priority=priority)
        req.t_submit = self._now()
        self.scheduler.add(req)
        return rid

    def cancel(self, request_id: int) -> bool:
        """Abort a queued or mid-flight request between steps, freeing
        everything it holds: its lane, its KV blocks, its prefix-cache
        registrations no other live sequence shares
        (:meth:`~repro.serving.blocks.KVCacheManager.release_seq`), any
        queued host->device swap-ins, and the host-tier payloads of its
        now-unregistered chain — so a cancel-everything drain returns the
        pool AND the host tier to empty.  The cancelled request lands in
        the finished list with ``cancelled=True`` and whatever tokens it
        had emitted.  Returns False when the id is unknown or already
        finished (cancelling a completed request is a harmless no-op).

        Only legal between steps — the async frontend serializes cancels
        with ``step()`` on its step thread, which is what makes
        mid-*stream* disconnects safe."""
        req = next((r for r in self.scheduler.running
                    if r.request_id == request_id), None)
        if req is None:
            req = next((r for r in self.scheduler.waiting
                        if r.request_id == request_id), None)
        if req is None:
            return False
        # the feed whose chain residue must be reclaimed: the live feed
        # for a running sequence, prompt + generated for a waiting one
        # (a preempted victim's KV may live on only in the host tier)
        feed = req.feed if req.state is RequestState.RUNNING and req.feed \
            else [int(t) for t in req.prompt] + list(req.generated)
        self.scheduler.abort(req)
        purge: List[str] = []
        if self.kv.has_seq(request_id):
            purge += self.kv.release_seq(request_id)
        purge += self.kv.release_chain(feed)
        for d in purge:
            if self._host_tier.pop(d, None) is not None:
                self.host_purged += 1
        req.t_done = self._now()
        self._finished.append(req)
        self.cancelled += 1
        return True

    # ------------------------------------------------------------------
    @staticmethod
    def _apply_copies(cache: Dict, src: jax.Array, dst: jax.Array) -> Dict:
        """Copy-on-write block copies: pool[dst] = pool[src] for every
        pool leaf — K and V, plus the scale planes of quantized pools
        (every leaf carries the block axis at dim 1; padding pairs are
        (0, 0) — a null-block self-copy no-op)."""
        out = dict(cache)
        for part in ("scan", "head"):
            if part in cache:
                out[part] = {name: arr.at[:, dst].set(arr[:, src])
                             for name, arr in cache[part].items()}
        return out

    def _run_rect(self, decision: StepDecision):
        """The rectangular (n_slots, chunk_width) step: every lane is
        padded to the widest scheduled chunk.  Returns ``greedy(req, j)``,
        the step's argmax token at row ``j`` of ``req``'s chunk."""
        with trace.span(trace.PREPARE):
            sched_ids = {r.request_id for r in decision.scheduled}
            width = padded_pow2(max(
                [decision.num_scheduled[r.request_id]
                 for r in decision.scheduled] or [1]))
            tokens = np.zeros((self.n_slots, width), np.int32)
            pos = np.zeros((self.n_slots,), np.int32)
            q_lens = np.zeros((self.n_slots,), np.int32)
            tables = np.zeros((self.n_slots, self.max_blocks), np.int32)
            # paused (budget-deferred) lanes keep q_lens = 0: their writes
            # are routed to the null block and their logits ignored
            for r in self.scheduler.running:
                pos[r.lane] = r.cursor
                tables[r.lane] = self.kv.padded_table(r.request_id)
                if r.request_id in sched_ids:
                    n = decision.num_scheduled[r.request_id]
                    q_lens[r.lane] = n
                    tokens[r.lane, :n] = decision.segment_tokens(r)
            self.cache["block_tables"] = self._put(tables)
            self.cache["pos"] = self._put(pos)
            self.cache["q_lens"] = self._put(q_lens)
            dev_tokens = self._put(tokens)
            if self.mesh is not None and self._collectives_per_step is None:
                self._collectives_per_step = self._count_collectives(
                    dev_tokens)
        real = int(q_lens.sum())
        with trace.span(trace.DISPATCH, tokens=self.n_slots * width,
                        real_tokens=real,
                        prefill_tokens=decision.n_prefill_tokens,
                        decode_lanes=decision.n_decode):
            logits, self.cache = self._step(self.params, self.cache,
                                            dev_tokens)
        self._last_tokens = dev_tokens
        self.collective_ops += self._collectives_per_step or 0
        self.scheduled_tokens += real
        self.padded_tokens += self.n_slots * width
        with trace.span(trace.SAMPLE):
            if decision.drafts:
                # speculative verification reads every row of a draft
                # segment — but still only those: gather them (plus each
                # lane's last row) before the argmax instead of reducing
                # all (slots, C)
                flat = logits.reshape(self.n_slots * width, -1)
                return self._gather_greedy(
                    decision, flat, lambda r: r.lane * width)
            # only each lane's last real chunk row can emit — gather those
            # (n_slots, V) rows before the argmax instead of reducing all C
            last = jnp.asarray(np.maximum(q_lens - 1, 0))
            lane_tok = np.asarray(jnp.argmax(
                logits[jnp.arange(self.n_slots), last], axis=-1))  # (slots,)
        return lambda r, j: int(lane_tok[r.lane])

    def _gather_greedy(self, decision: StepDecision, flat_logits,
                       seg_start):
        """Argmax only the rows verification can read: for each scheduled
        request, rows ``base-1 .. n-1`` of its segment (the draft
        verification window — just the emitting row when it has no
        drafts).  ``seg_start(req)`` maps a request to its segment's
        first flat row.  Returns ``greedy(req, j)`` over those rows."""
        offsets: Dict[int, int] = {}
        rows: List[int] = []
        for r in decision.scheduled:
            n = decision.num_scheduled[r.request_id]
            first = n - 1 - len(decision.drafts.get(r.request_id, ()))
            offsets[r.request_id] = len(rows) - first
            start = seg_start(r)
            rows.extend(range(start + first, start + n))
        toks = np.asarray(jnp.argmax(
            flat_logits[jnp.asarray(np.asarray(rows, np.int32))], axis=-1))
        return lambda r, j: int(toks[offsets[r.request_id] + j])

    def _run_ragged(self, decision: StepDecision):
        """The flat-token step: all scheduled tokens as one 1-D stream with
        per-token lane/pos/slot metadata — work proportional to the real
        token count, ~sum(q_len) instead of lanes * max(q_len).  Returns
        ``greedy(req, j)``, the step's argmax token at row ``j`` of
        ``req``'s segment."""
        with trace.span(trace.PREPARE):
            batch = RaggedBatch.build(decision, self.kv, self.n_slots,
                                      self.block_size,
                                      cap=self.scheduler._budget())
            tables = np.zeros((self.n_slots, self.max_blocks), np.int32)
            for r in self.scheduler.running:
                tables[r.lane] = self.kv.padded_table(r.request_id)
            self.cache["block_tables"] = self._put(tables)
            self.cache["token_lane"] = self._put(batch.token_lane)
            self.cache["token_pos"] = self._put(batch.token_pos)
            self.cache["slot_mapping"] = self._put(batch.slot_mapping)
            tile_args = {}
            if self.tiled:
                # segment-tile the stream: tile capacity is a pure function
                # of the pow2 bucket (windows + n_slots), so the jitted step
                # still retraces per bucket only
                tiles = batch.tiles(self.n_slots, self.tile)
                self.cache["tile_meta"] = self._put(tiles.meta)
                self.cache["row_tile"] = self._put(tiles.row_tile)
                tile_args["kv_blocks"] = tiles.kv_blocks(self.block_size,
                                                         self.window)
                self.kv_block_visits += tile_args["kv_blocks"]
            dev_tokens = self._put(batch.tokens)
            if self.mesh is not None and self._collectives_per_step is None:
                self._collectives_per_step = self._count_collectives(
                    dev_tokens)
        with trace.span(trace.DISPATCH, tokens=batch.padded_tokens,
                        real_tokens=batch.total_tokens,
                        prefill_tokens=decision.n_prefill_tokens,
                        decode_lanes=decision.n_decode, **tile_args):
            logits, self.cache = self._step(self.params, self.cache,
                                            dev_tokens)
        self._last_tokens = dev_tokens
        self.collective_ops += self._collectives_per_step or 0
        self.scheduled_tokens += batch.total_tokens
        self.padded_tokens += batch.padded_tokens
        with trace.span(trace.SAMPLE):
            if decision.drafts:
                # speculative verification reads every row of a draft
                # segment — but still only those: gather them (plus each
                # lane's last row) before the argmax instead of reducing
                # all T
                starts = batch.q_starts
                return self._gather_greedy(decision, logits,
                                           lambda r: starts[r.request_id])
            # only each lane's final segment row can emit — gather those
            # (n_slots, V) rows before the argmax instead of reducing all T
            lane_tok = np.asarray(jnp.argmax(
                logits[jnp.asarray(batch.last_row)], axis=-1))  # (slots,)
        return lambda r, j: int(lane_tok[r.lane])

    def step(self) -> StepDecision:
        """One engine iteration: one token-budgeted batch mixing prefill
        chunks, decodes, and (``spec``) speculative draft segments.

        Propose -> verify -> accept: the scheduler attached each decode
        lane's drafts (``decision.drafts``); the model step verified them
        by producing per-row greedy argmax; here the longest matching
        draft prefix plus one bonus token is accepted per lane, and the
        KV cache is rewound past the rejected draft slots so the next
        step's appends land where the accepted sequence actually ends."""
        t0 = time.perf_counter()
        with trace.step_span(self.steps):
            decision = self.scheduler.schedule()
            with trace.span(trace.KV_COPY):
                self._copy_blocks()
            greedy = (self._run_ragged(decision) if self.ragged
                      else self._run_rect(decision))
            self.steps += 1
            with trace.span(trace.EMIT):
                emitted = self._accept(decision, greedy)
                shed = self.scheduler.take_shed()
                if shed:
                    self._finished.extend(shed)
                    self.shed += len(shed)
                # feed the SLO admission loop: real wall seconds per decode
                # token (consistent with SimClock.measure, which also
                # charges real time)
                self.scheduler.observe_step(time.perf_counter() - t0,
                                            emitted)
        return decision

    def _copy_blocks(self) -> None:
        """The block copies a step's KV writes must follow: the queued
        host->device swap-ins, then the copy-on-write copies."""
        # host->device swap-ins FIRST: a swapped-in block must hold its
        # payload before a CoW copy reads it (a fully-matched prompt can
        # fork a block this very admission just swapped in) and before
        # the step attends over it
        if self.host_swap:
            swapins = self.kv.take_swap_ins()
            if swapins:
                self._apply_swap_ins(swapins)
            if self.host_swap_blocks is not None:
                # trim AFTER the swap-ins land: a queued swap-in's payload
                # must never be dropped between planning and application
                while len(self._host_tier) > self.host_swap_blocks:
                    self._host_tier.popitem(last=False)
                    self.host_swap_drops += 1
        # apply queued copy-on-write copies BEFORE this step's KV writes
        # land in the forked blocks
        copies = self.kv.take_copy_ops()
        if copies:
            n = padded_pow2(len(copies))
            src = np.zeros((n,), np.int32)
            dst = np.zeros((n,), np.int32)
            for i, (s, d) in enumerate(copies):
                src[i], dst[i] = s, d
            self.cache = self._cow(self.cache, self._put(src),
                                   self._put(dst))
            self.cow_block_copies += len(copies)

    def _accept(self, decision: StepDecision, greedy) -> int:
        """Accept each scheduled request's tokens from the step's argmax
        ``greedy``, emit them, and retire or rewind the request; returns
        the number of tokens emitted."""
        emitted = 0
        for r in list(decision.scheduled):
            n = decision.num_scheduled[r.request_id]
            drafts = decision.drafts.get(r.request_id, [])
            base = n - len(drafts)              # fed (non-draft) tokens
            emitting = r.cursor + base == len(r.feed)
            if not emitting:
                r.cursor += n                   # mid-prompt prefill chunk
                self.tokens_prefilled += n
                continue
            self.tokens_prefilled += base - 1
            # greedy rows base-1 .. n-1 predict the tokens at positions
            # cursor+base .. cursor+n: accept the longest draft prefix the
            # argmax reproduces, plus the bonus token at the first
            # mismatching (or final) row — with no drafts this is exactly
            # the old single-token emission
            m = 0
            while m < len(drafts) and greedy(r, base - 1 + m) == drafts[m]:
                m += 1
            new_toks = [int(t) for t in drafts[:m]] + [greedy(r, base - 1 + m)]
            if drafts:
                self.tokens_drafted += len(drafts)
                self.draft_tokens_accepted += m
                self.spec_verifications += 1
            kept = 0
            finished = False
            for tok in new_toks:
                kept += 1
                finished = _emit_token(self, r, tok)
                if finished:
                    break
            emitted += kept
            if drafts:
                self.spec_tokens_emitted += kept
            # cursor counts feed tokens resident in KV: the fed base plus
            # the accepted drafts that stayed (the bonus token is never in
            # KV — it is fed next step like any fresh decode token)
            r.cursor += base + min(kept, m)
            if finished:
                self.scheduler.finish(r)
                self._finished.append(r)
            elif len(drafts) > m:
                # roll back the rejected draft slots (and free any block
                # that only held rejected tokens) so the KV watermark
                # matches the accepted sequence exactly
                self.kv.rewind(r.request_id, r.cursor)
        return emitted

    def has_work(self) -> bool:
        """True while requests are queued or running (uniform across the
        engine classes, incl. the sharded front)."""
        return self.scheduler.has_work()

    def run_until_drained(self, max_steps: int = 100_000) -> List[Request]:
        """Step until no work remains; returns (and hands off) the requests
        finished since the last call."""
        for _ in range(max_steps):
            if not self.scheduler.has_work():
                break
            decision = self.step()
            if not decision.scheduled and self.scheduler.waiting:
                raise RuntimeError(
                    "serving stalled: waiting requests cannot be admitted "
                    f"({self.kv.num_free_blocks} free blocks)")
        return self.take_finished()

    def take_finished(self) -> List[Request]:
        """Hand off (and clear) the requests finished since the last call —
        the non-blocking collection path the async frontend polls."""
        out, self._finished = self._finished, []
        return out

    # ------------------------------------------------------------------
    # KV transfer / persistence (see repro.serving.transfer)
    # ------------------------------------------------------------------
    def cached_digests(self) -> frozenset:
        """Chain digests of every full block the prefix cache holds — the
        receiver-side set a sender dedups shipments against."""
        return self.kv.cached_digests()

    def _read_block_payload(self, blk: int) -> Dict:
        """Read one physical block's slice of every device pool leaf, as
        host arrays keyed ``part -> {"k", "v", ...}`` (the wire payload
        layout; int8 pools add their ``k_scale``/``v_scale`` planes)."""
        out: Dict[str, Dict[str, np.ndarray]] = {}
        for part in ("scan", "head"):
            if part in self.cache:
                out[part] = {name: np.asarray(arr[:, blk])
                             for name, arr in self.cache[part].items()}
        return out

    def _write_block_payloads(self, blocks: List[int],
                              payloads: List[Dict]) -> None:
        """Scatter host block payloads into the device pools at ``blocks``
        (block axis 1 of every pool leaf), restoring the canonical pool
        shardings afterwards in mesh mode."""
        idx = self._put(np.asarray(blocks, np.int32))
        for part in ("scan", "head"):
            if part not in self.cache:
                continue
            pools = self.cache[part]
            for p in payloads:
                if part not in p or set(p[part]) != set(pools):
                    raise ValueError(
                        f"payload pool-name mismatch on '{part}': got "
                        f"{sorted(p.get(part, {}))}, engine pools are "
                        f"{sorted(pools)} (fp and int8 pools do not mix)")
            new = {}
            for name, arr in pools.items():
                want = arr.shape[:1] + arr.shape[2:]
                for p in payloads:
                    if p[part][name].shape != want:
                        raise ValueError(
                            f"payload KV geometry mismatch on "
                            f"'{part}/{name}': got {p[part][name].shape}, "
                            f"engine pool expects {want}")
                # stack along the block axis: (layers, n_new, ...)
                stack = self._put(np.stack([p[part][name]
                                            for p in payloads], axis=1))
                new[name] = arr.at[:, idx].set(stack.astype(arr.dtype))
            self.cache[part] = new
        if self._pool_shardings is not None:
            # the eager scatter above mixes replicated payloads into
            # head-sharded pools; re-commit the canonical sharding so
            # the per-shard pool invariant survives the write
            for part in ("scan", "head"):
                if part in self.cache:
                    self.cache[part] = jax.device_put(
                        self.cache[part], self._pool_shardings[part])

    # ------------------------------------------------------------------
    # device->host swap tier (tiered KV; see docs/ARCHITECTURE.md)
    # ------------------------------------------------------------------
    def _swap_out_block(self, digest: str, blk: int, parent: str,
                        tokens) -> None:
        """Eviction hook (``KVCacheManager.on_swap_out``): park an evicted
        registered block's device payload in the host tier.

        Skips digests the tier already holds — a swapped-in block being
        re-evicted before its device write landed would capture garbage,
        and the host copy is bit-identical anyway (full blocks are
        immutable once registered) — and quarantined digests mid-import,
        whose payload write is still pending."""
        if digest in self._host_tier:
            self._host_tier.move_to_end(digest)
            return
        if digest in self._swap_quarantine:
            return
        self._host_tier[digest] = {"parent": parent,
                                   "tokens": tuple(int(t) for t in tokens),
                                   "payload": self._read_block_payload(blk)}
        self.host_swap_outs += 1

    def _apply_swap_ins(self, ops: List[Tuple[str, int]]) -> None:
        """Write queued host->device swap-ins into the KV pools.  Runs
        before CoW copies and before the step's own writes; an op whose
        target block was evicted (or re-registered to a different block)
        between planning and application is dropped — the current
        registration, if any, carries its own op."""
        blocks: List[int] = []
        payloads: List[Dict] = []
        for digest, blk in ops:
            if self.kv.digest_block(digest) != blk:
                continue
            ent = self._host_tier.get(digest)
            if ent is None:
                # the planner only swaps in digests host_has() confirmed,
                # and the tier is never trimmed with an op in flight — a
                # miss here would leave a garbage block attached to a
                # live sequence, so fail loudly rather than serve it
                raise RuntimeError(
                    f"swap-in payload for block digest {digest[:12]} "
                    "missing from the host tier")
            self._host_tier.move_to_end(digest)
            blocks.append(blk)
            payloads.append(ent["payload"])
        if not blocks:
            return
        self._write_block_payloads(blocks, payloads)
        self.host_swap_ins += len(blocks)

    def export_kv_prefix(self, feed: np.ndarray):
        """Package the cached KV prefix of ``feed`` as a
        :class:`~repro.serving.transfer.KVShipment`.

        Exports the longest chain of cached full blocks covering the
        feed's prefix — each with its device KV payload and checksum —
        plus the remaining tokens as the payload-free partial tail.  The
        usual source is a just-prefilled prompt (every full block was
        registered as prefill completed it, and registrations survive the
        sequence's ``free`` via the cache's own hold), but any feed whose
        prefix is cached exports the same way.
        """
        from repro.serving.transfer import (KVBlockRecord, KVShipment,
                                            payload_checksum)
        chain = self.kv.export_chain(feed)
        blocks = []
        for digest, parent, blk, tokens in chain:
            payload = self._read_block_payload(blk)
            blocks.append(KVBlockRecord(
                digest=digest, parent=parent, tokens=tokens,
                payload=payload, checksum=payload_checksum(payload)))
        covered = len(chain) * self.block_size
        return KVShipment(block_size=self.block_size, blocks=blocks,
                          partial_tokens=[int(t) for t in feed[covered:]])

    def import_kv_shipment(self, shipment) -> Dict[str, int]:
        """Attach a (verified) shipment's blocks to this engine's cache.

        Each block is registered with the prefix cache under its chain
        digest and its payload written into the device KV pools, so the
        next ``submit`` of the matching prompt attaches the chain as an
        ordinary prefix hit.  Blocks already cached are skipped (the dedup
        contract: a stripped payload must be one of these — anything else
        raises :class:`~repro.serving.transfer.TransferIntegrityError`).
        Imported blocks are immediately evictable, so a shipment can
        never starve live sequences; when the pool genuinely has no room
        the remainder of the chain is dropped (counted, not fatal — the
        decode side just recomputes more).  Returns
        ``{"imported", "dedup_skipped", "dropped_no_space",
        "tokens_attachable"}``.
        """
        from repro.serving.transfer import TransferIntegrityError
        if shipment.block_size != self.block_size:
            raise ValueError(
                f"shipment block_size {shipment.block_size} != engine "
                f"block_size {self.block_size}")
        imported: List[Tuple[str, int]] = []
        payloads: List[Dict] = []
        skipped = dropped = 0
        try:
            for rec in shipment.blocks:
                if self.kv.has_digest(rec.digest):
                    skipped += 1
                    continue
                if rec.payload is None:
                    raise TransferIntegrityError(
                        f"block {rec.digest[:12]} arrived without a payload "
                        "but is not in this engine's cache — dedup stripped "
                        "a block the receiver does not hold")
                # quarantine until the payload write lands: a later
                # import_block can LRU-evict this block, and the swap-out
                # hook must not capture its still-unwritten device bytes
                self._swap_quarantine.add(rec.digest)
                try:
                    blk = self.kv.import_block(rec.parent, rec.tokens,
                                               digest=rec.digest)
                except RuntimeError:
                    # pool full of live sequences: drop the chain's tail
                    dropped = sum(1 for b in shipment.blocks
                                  if not self.kv.has_digest(b.digest))
                    break
                if blk is not None:
                    imported.append((rec.digest, blk))
                    payloads.append(rec.payload)
            # importing can itself evict an earlier import of this very
            # shipment (and recycle its block): write only payloads whose
            # registration survived, into their still-registered blocks
            live = [(b, p) for (d, b), p in zip(imported, payloads)
                    if self.kv.digest_block(d) == b]
            if live:
                self._write_block_payloads([b for b, _ in live],
                                           [p for _, p in live])
        finally:
            for rec in shipment.blocks:
                self._swap_quarantine.discard(rec.digest)
        return {"imported": len(imported), "dedup_skipped": skipped,
                "dropped_no_space": dropped,
                "tokens_attachable": (len(imported) + skipped)
                * self.block_size}

    def save_prefix_cache(self, path: str) -> int:
        """Persist every cached full block to ``path`` and return the
        bytes written.  The on-disk format IS the wire format
        (:class:`~repro.serving.transfer.KVShipment`), so a restarted
        engine reloads with :meth:`load_prefix_cache` and warm prompts hit
        the cache exactly as before the restart."""
        from repro.serving.transfer import (KVBlockRecord, KVShipment,
                                            payload_checksum)
        blocks = []
        for digest, parent, blk, tokens in self.kv.export_all_cached():
            payload = self._read_block_payload(blk)
            blocks.append(KVBlockRecord(
                digest=digest, parent=parent, tokens=tokens,
                payload=payload, checksum=payload_checksum(payload)))
        data = KVShipment(block_size=self.block_size, blocks=blocks,
                          partial_tokens=[]).serialize()
        with open(path, "wb") as f:
            f.write(data)
        return len(data)

    def load_prefix_cache(self, path: str) -> Dict[str, int]:
        """Restore a :meth:`save_prefix_cache` snapshot (verifying every
        checksum and chain digest) into this engine's prefix cache.
        Returns the :meth:`import_kv_shipment` stats."""
        from repro.serving.transfer import KVShipment
        with open(path, "rb") as f:
            data = f.read()
        return self.import_kv_shipment(KVShipment.deserialize(data))

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Counters for benchmarks: token throughput, padding efficiency,
        prefix-cache and speculative-decode accounting."""
        return {
            "steps": self.steps,
            "tokens_decoded": self.tokens_decoded,
            "tokens_prefilled": self.tokens_prefilled,
            "active": len(self.scheduler.running),
            "waiting": len(self.scheduler.waiting),
            "preemptions": self.scheduler.total_preemptions,
            "block_utilization": self.kv.utilization(),
            "prefix_hits": self.kv.prefix_hits,
            "prefix_tokens_reused": self.kv.prefix_tokens_reused,
            "cow_copies": self.kv.cow_copies,
            "cache_evictions": self.kv.evictions,
            "ragged": int(self.ragged),
            "tiled": int(self.tiled),
            "padding_efficiency": (self.scheduled_tokens
                                   / max(self.padded_tokens, 1)),
            "kv_block_visits": self.kv_block_visits,
            "spec": int(self.spec),
            "kv_rewinds": self.kv.rewinds,
            "kv_tokens_rewound": self.kv.tokens_rewound,
            "tokens_drafted": self.tokens_drafted,
            "draft_tokens_accepted": self.draft_tokens_accepted,
            "spec_verifications": self.spec_verifications,
            # accepted drafts + bonus per verification; 1.0 = speculation
            # never pays off, k+1 = every draft lands
            "accepted_per_spec_step": (self.spec_tokens_emitted
                                       / max(self.spec_verifications, 1)),
            "draft_acceptance_rate": (self.draft_tokens_accepted
                                      / max(self.tokens_drafted, 1)),
            # host swap tier (zeros when host_swap=False)
            "host_swap": int(self.host_swap),
            "swap_outs": self.host_swap_outs,
            "swap_ins": self.host_swap_ins,
            "swapped_in_tokens": self.kv.swapped_in_tokens,
            "host_tier_blocks": len(self._host_tier),
            "host_swap_drops": self.host_swap_drops,
            "preempt_swap_outs": self.scheduler.total_swap_outs,
            # cancellation / SLO admission accounting
            "cancelled": self.cancelled,
            "shed": self.shed,
            "released_seqs": self.kv.released_seqs,
            "swap_ins_dropped": self.kv.swap_ins_dropped,
            "host_purged": self.host_purged,
            # mesh / tensor-parallel accounting (tp=1, zeros off-mesh)
            "tp": self.tp,
            "kv_heads_sharded": int(self.kv_heads_sharded),
            "collectives_per_step": self._collectives_per_step or 0,
            "attention_kernel": int(self.use_kernel),
            "collective_ops": self.collective_ops,
        }


# ---------------------------------------------------------------------------
class ShardedDecodeEngine:
    """Data-parallel serving front: one full paged engine per mesh slice.

    The mesh's data axes are cut into ``dp`` slices of ``tp`` devices
    (:func:`repro.launch.mesh.mesh_slices`); each slice runs a complete
    :class:`PagedDecodeEngine` — scheduler, block pool, prefix cache,
    CoW, speculation, transfer — tensor-parallel over its own "model"
    axis.  Requests are routed to the least-loaded slice by outstanding
    tokens (lowest index breaks ties), so open-loop arrivals never queue
    on one slice while another idles; the global output remains a
    deterministic function of the submission sequence (greedy decode per
    request is schedule-independent — the same property the
    single-device differential harness relies on).  Slices share no
    device state; with more than one slice their steps are dispatched
    from a thread pool, overlapping per-slice XLA executions.

    ``n_slots`` (and the pool size derived from it) is PER SLICE — the
    front scales capacity with the mesh rather than splitting a fixed
    budget.
    """

    def __init__(self, model_api, params: PyTree, *, mesh=None,
                 **engine_kw) -> None:
        """Split ``mesh`` (default: all devices, pure data-parallel) into
        slices and build one :class:`PagedDecodeEngine` per slice;
        ``engine_kw`` is forwarded to every slice unchanged."""
        from repro.launch.mesh import make_host_mesh, mesh_slices
        if mesh is None:
            mesh = make_host_mesh()
        self.mesh = mesh
        slices = mesh_slices(mesh)
        self.engines = [PagedDecodeEngine(model_api, params, mesh=m,
                                          **engine_kw)
                        for m in slices]
        self.api = model_api
        self.n_slices = len(self.engines)
        # global request id -> (slice index, slice-local id); slice-local
        # finished requests are handed back under their global id
        self._route: Dict[int, tuple] = {}
        self._gid_of: Dict[tuple, int] = {}
        self._next_id = 0
        self._finished: List[Request] = []
        self._on_token = None
        self.clock = None
        self._pool = (ThreadPoolExecutor(max_workers=self.n_slices)
                      if self.n_slices > 1 else None)

    # ------------------------------------------------------------------
    @staticmethod
    def _outstanding(eng: PagedDecodeEngine) -> int:
        """Tokens a slice still owes: remaining feed plus unemitted budget
        of its running requests, and the full prompt + budget of queued
        ones — the backlog measure least-loaded routing balances."""
        sched = eng.scheduler
        load = 0
        for r in sched.running:
            load += (r.remaining_feed
                     + (r.max_new_tokens - len(r.generated)))
        for r in sched.waiting:
            load += (len(r.prompt) + len(r.generated)
                     + (r.max_new_tokens - len(r.generated)))
        return load

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               priority: int = 0) -> int:
        """Queue a request on the least-loaded slice (by outstanding
        tokens; lowest slice index breaks ties, so a fresh fleet fills in
        slice order); returns its global id."""
        gid = self._next_id
        i = min(range(self.n_slices),
                key=lambda k: (self._outstanding(self.engines[k]), k))
        local = self.engines[i].submit(prompt, max_new_tokens,
                                       priority=priority)
        self._next_id += 1
        self._route[gid] = (i, local)
        self._gid_of[(i, local)] = gid
        return gid

    def cancel(self, request_id: int) -> bool:
        """Abort a queued or mid-flight request by global id, delegating
        to its slice (which frees blocks, host-tier entries, and pending
        swap-ins); returns False if unknown or already finished."""
        loc = self._route.get(request_id)
        if loc is None:
            return False
        i, local = loc
        ok = self.engines[i].cancel(local)
        if ok:
            self._collect()
        return ok

    def set_clock(self, clock) -> None:
        """Install one virtual clock on every slice so latency stamps are
        comparable fleet-wide (and against disaggregated rows)."""
        self.clock = clock
        for e in self.engines:
            e.set_clock(clock)

    @property
    def on_token(self):
        """Streaming callback ``(global_id, token, finished)``; setting it
        installs per-slice wrappers that rewrite local ids to global."""
        return self._on_token

    @on_token.setter
    def on_token(self, cb) -> None:
        """Install (or clear, with None) the fleet-wide streaming hook."""
        self._on_token = cb
        for i, e in enumerate(self.engines):
            if cb is None:
                e.on_token = None
            else:
                e.on_token = (lambda rid, tok, fin, _i=i:
                              cb(self._gid_of[(_i, rid)], tok, fin))

    def _collect(self) -> None:
        """Move every slice's finished requests into the global list,
        rewriting their ids back to the global namespace."""
        for i, eng in enumerate(self.engines):
            done, eng._finished = eng._finished, []
            for r in done:
                r.request_id = self._gid_of[(i, r.request_id)]
                self._finished.append(r)

    def has_work(self) -> bool:
        """True while any slice still holds queued or running requests."""
        return any(e.scheduler.has_work() for e in self.engines)

    def step(self) -> None:
        """One iteration of every slice that has work — concurrently when
        there is more than one (each slice's XLA execution releases the
        GIL, so slices genuinely overlap on CPU and on real meshes)."""
        active = [e for e in self.engines if e.scheduler.has_work()]
        if self._pool is not None and len(active) > 1:
            list(self._pool.map(lambda e: e.step(), active))
        else:
            for e in active:
                e.step()
        self._collect()

    def run_until_drained(self, max_steps: int = 100_000) -> List[Request]:
        """Step all slices until no work remains; returns (and hands off)
        the requests finished since the last call, under global ids."""
        for _ in range(max_steps):
            if not self.has_work():
                break
            self.step()
        return self.take_finished()

    def take_finished(self) -> List[Request]:
        """Hand off (and clear) finished requests under global ids."""
        self._collect()
        out, self._finished = self._finished, []
        return out

    # aggregate counters, so callers written against one engine (the
    # launcher's summary line, bench helpers) read the fleet totals
    @property
    def steps(self) -> int:
        """Max per-slice step count (slices step concurrently)."""
        return max((e.steps for e in self.engines), default=0)

    @property
    def tokens_decoded(self) -> int:
        """Total decoded tokens across all slices."""
        return sum(e.tokens_decoded for e in self.engines)

    @property
    def tokens_prefilled(self) -> int:
        """Total prefilled tokens across all slices."""
        return sum(e.tokens_prefilled for e in self.engines)

    # ------------------------------------------------------------------
    # KV transfer / persistence across the slice set
    # ------------------------------------------------------------------
    def cached_digests(self) -> frozenset:
        """Digests EVERY slice holds — the safe dedup set: a sender may
        strip exactly the blocks no possible receiving slice would miss."""
        out = None
        for e in self.engines:
            d = e.cached_digests()
            out = d if out is None else (out & d)
        return out if out is not None else frozenset()

    def export_kv_prefix(self, feed: np.ndarray):
        """Export ``feed``'s cached prefix from the slice covering the
        most of it (slices cache independently; load-based routing means
        any one slice may hold the longest chain)."""
        best = max(self.engines,
                   key=lambda e: len(e.kv.export_chain(feed)))
        return best.export_kv_prefix(feed)

    def import_kv_shipment(self, shipment) -> Dict[str, int]:
        """Broadcast a shipment into every slice (each has its own pool),
        summing the per-slice stats — so a warmed prefix is a hit no
        matter which slice later serves the matching prompt."""
        total: Dict[str, int] = {}
        for e in self.engines:
            for k, v in e.import_kv_shipment(shipment).items():
                total[k] = total.get(k, 0) + v
        return total

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        """Aggregated counters plus the per-slice/per-shard breakdown the
        bench and SLO work read imbalance from."""
        per = [e.stats() for e in self.engines]
        agg: Dict[str, Any] = {
            "slices": self.n_slices,
            "tp": per[0]["tp"] if per else 1,
            "steps": max((p["steps"] for p in per), default=0),
            "tokens_decoded": sum(p["tokens_decoded"] for p in per),
            "tokens_prefilled": sum(p["tokens_prefilled"] for p in per),
            "active": sum(p["active"] for p in per),
            "waiting": sum(p["waiting"] for p in per),
            "preemptions": sum(p["preemptions"] for p in per),
            "cancelled": sum(p["cancelled"] for p in per),
            "shed": sum(p["shed"] for p in per),
            "collective_ops": sum(p["collective_ops"] for p in per),
            "collectives_per_step": (per[0]["collectives_per_step"]
                                     if per else 0),
            "padding_efficiency": (
                sum(e.scheduled_tokens for e in self.engines)
                / max(sum(e.padded_tokens for e in self.engines), 1)),
            "tokens_decoded_per_slice": [p["tokens_decoded"] for p in per],
            "tokens_prefilled_per_slice": [p["tokens_prefilled"]
                                           for p in per],
            "collective_ops_per_slice": [p["collective_ops"] for p in per],
            "per_slice": per,
        }
        return agg


# ---------------------------------------------------------------------------
class SlotDecodeEngine:
    """Continuous-batching LM decode over a fixed dense slot grid.

    The cache has ``n_slots`` request slots of ``cache_len`` tokens each;
    every engine step decodes one token for every active slot.  Finished
    slots are freed and refilled from the admission queue; prompts are fed
    token-by-token (prefill-as-decode, correct for every family incl.
    recurrent/SSM models).  For transformer-family KV caches, a slot's
    positions/write-cursor are reset on reuse so a new occupant starts at
    RoPE position 0 and never attends to its predecessor's stale KV.
    """

    def __init__(self, model_api, params: PyTree, *, n_slots: int,
                 cache_len: int, eos_token: int = -1,
                 window: int = 0, cache_dtype=None, compute_dtype=None,
                 **_paged_opts) -> None:
        """Build the dense-slot engine (paged-only options are ignored)."""
        self.api = model_api
        self.params = params
        self.n_slots = n_slots
        self.cache_len = cache_len
        self.eos = eos_token
        self.window = window
        kw = {"window": window}
        if cache_dtype is not None:
            kw["dtype"] = cache_dtype
        self.cache = model_api.init_cache(n_slots, cache_len, **kw)
        step_kw = {"window": window}
        if compute_dtype is not None:
            step_kw["compute_dtype"] = compute_dtype
        self._step = jax.jit(
            lambda p, c, t: model_api.decode_step(p, c, t, **step_kw),
            donate_argnums=(1,))
        self.active: List[Optional[Request]] = [None] * n_slots
        self.queue: List[Request] = []
        self._finished: List[Request] = []
        # rolling KV buffers hold min(window, cache_len) slots per lane
        self._slots_per_lane = min(window, cache_len) if window else cache_len
        self._next_id = 0
        self.tokens_decoded = 0
        self.steps = 0
        self.scheduled_tokens = 0
        self.padded_tokens = 0
        self.cancelled = 0
        self.clock = None
        self.on_token = None

    # ------------------------------------------------------------------
    def set_clock(self, clock) -> None:
        """Install a virtual clock for latency stamps (None = wall clock)."""
        self.clock = clock

    def _now(self) -> float:
        """Current time on the engine's clock abstraction."""
        return self.clock.now if self.clock is not None \
            else time.perf_counter()

    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               priority: int = 0) -> int:
        """Queue a request; returns its request id (``priority`` is
        recorded for interface parity — the slot queue stays FIFO)."""
        rid = self._next_id
        self._next_id += 1
        req = Request(rid, np.asarray(prompt, np.int32), max_new_tokens,
                      priority=priority)
        req.t_submit = self._now()
        self.queue.append(req)
        return rid

    def cancel(self, request_id: int) -> bool:
        """Abort a queued or active request, freeing its slot; returns
        False if unknown or already finished."""
        for i, req in enumerate(self.queue):
            if req.request_id == request_id:
                del self.queue[i]
                break
        else:
            for slot, req in enumerate(self.active):
                if req is not None and req.request_id == request_id:
                    self.active[slot] = None
                    break
            else:
                return False
        req.done = True
        req.cancelled = True
        req.t_done = self._now()
        self._finished.append(req)
        self.cancelled += 1
        return True

    def _admit(self) -> None:
        for slot in range(self.n_slots):
            if self.active[slot] is None and self.queue:
                req = self.queue.pop(0)
                req.begin_run(slot)
                req.t_admit = self._now()
                self.active[slot] = req
                if "slot_positions" in self.cache and "scan" in self.cache:
                    # transformer-family rolling KV (pure cache, no recurrent
                    # state): invalidate the previous occupant's entries and
                    # restart the write cursor, so the new request starts at
                    # position 0 and never sees stale KV.  Families with
                    # recurrent state (zamba/xlstm/encdec) keep the seed
                    # behaviour — their lane state cannot be row-reset.
                    self.cache["slot_positions"] = \
                        self.cache["slot_positions"].at[slot].set(-1)
                    self.cache["pos"] = self.cache["pos"].at[slot].set(0)

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One engine iteration: one token per active slot."""
        self._admit()
        tokens = np.zeros((self.n_slots, 1), np.int32)
        for slot, req in enumerate(self.active):
            if req is not None:
                tokens[slot, 0] = req.feed[req.cursor]
        self.scheduled_tokens += sum(1 for a in self.active if a is not None)
        self.padded_tokens += self.n_slots
        logits, self.cache = self._step(self.params, self.cache,
                                        jnp.asarray(tokens))
        next_tokens = np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        self.steps += 1

        for slot, req in enumerate(self.active):
            if req is None:
                continue
            emitting = req.cursor >= len(req.feed) - 1
            req.cursor += 1
            if emitting:
                tok = int(next_tokens[slot])
                if _emit_token(self, req, tok):
                    req.done = True
                    self.active[slot] = None
                    self._finished.append(req)

    def has_work(self) -> bool:
        """True while requests are queued or occupy a slot."""
        return bool(self.queue) or any(a is not None for a in self.active)

    def run_until_drained(self, max_steps: int = 100_000) -> List[Request]:
        """Step until no work remains; returns (and hands off) the requests
        finished since the last call."""
        for _ in range(max_steps):
            if not self.queue and all(a is None for a in self.active):
                break
            self.step()
        return self.take_finished()

    def take_finished(self) -> List[Request]:
        """Hand off (and clear) the requests finished since the last call."""
        out, self._finished = self._finished, []
        return out

    def stats(self) -> Dict[str, float]:
        """Engine counters: steps, tokens, occupancy, padding efficiency."""
        n_active = sum(1 for a in self.active if a is not None)
        used = sum(min(r.cursor, self._slots_per_lane)
                   for r in self.active if r is not None)
        return {
            "steps": self.steps,
            "tokens_decoded": self.tokens_decoded,
            "active": n_active,
            "waiting": len(self.queue),
            "preemptions": 0,
            "block_utilization": used / max(
                self.n_slots * self._slots_per_lane, 1),
            "padding_efficiency": (self.scheduled_tokens
                                   / max(self.padded_tokens, 1)),
        }
