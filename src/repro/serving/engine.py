"""BuddyMoE serving engine — batched decode with an offloaded expert cache.

Mirrors the paper's Fig. 3 pipeline on the simulation substrate:

  step t:  jitted decode_step runs with the CURRENT residency mask (experts
           whose transfers have ARRIVED — in-flight prefetches are misses);
           the in-graph BuddyMoE layer substitutes/flags per-slot (Alg. 1)
  between: the host cache manager replays the step on the event-driven PCIe
           timeline (runtime/transfers.py): compute advances layer by layer,
           in-flight transfers overlap the compute of earlier layers, a miss
           stalls only the layer that needs it, and prefetches for layer
           l+lookahead are issued while layer l computes.

Stall attribution (the Fig. 8 / Tables 2-4 measurement substrate):
  demand stall        cold miss, nothing in flight — full fetch wait
  late-prefetch stall predicted but not yet arrived — the transfer is
                      escalated to demand priority and the layer waits only
                      for its remaining tail (buddy substitution absorbs
                      these misses entirely under policy=buddy)
  overlapped          transfer time hidden under compute — bytes, no latency
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import quantize
from repro.core.buddies import BuddyTables
from repro.core.policy import BuddyPolicy
from repro.models import transformer
from repro.models.moe import BuddyState
from repro.runtime.cache import ExpertCache
from repro.runtime.costs import MissCostModel, best_resident_q
from repro.runtime.memory import (DEFAULT_HW, HardwareModel, TransferLedger,
                                  expert_nbytes)
from repro.runtime.paged_kv import PagedKVPool
from repro.runtime.telemetry import ExpertStats, Telemetry
from repro.runtime.tiers import TIER_BITS, TieredExpertStore
from repro.runtime.trace import host_span
from repro.runtime.transfers import TransferScheduler, make_ici_links
from repro.serving.prefix import PrefixTree


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    tokens: int = 0
    sim_time_s: float = 0.0
    compute_s: float = 0.0
    stall_s: float = 0.0
    n_sub: int = 0
    n_miss_fetch: int = 0
    n_hit: int = 0
    n_late_prefetch: int = 0
    n_prefetch_issued: int = 0
    n_prefetch_cancelled: int = 0
    n_miss_drop: int = 0        # misses the cost argmin dropped (renorm)
    n_upgrade_issued: int = 0   # degraded-then-upgrade background fetches

    @property
    def tokens_per_s(self) -> float:
        return self.tokens / self.sim_time_s if self.sim_time_s else 0.0


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *,
                 tables: Optional[BuddyTables] = None,
                 policy: BuddyPolicy = BuddyPolicy(),
                 cache: Optional[ExpertCache] = None,
                 predictor=None,
                 prefetch_k: int = 0,
                 lookahead: int = 1,
                 hw: HardwareModel = DEFAULT_HW,
                 window: int = -1,
                 seed: int = 0,
                 latency_cfg: Optional[ModelConfig] = None,
                 tier: Optional[TieredExpertStore] = None,
                 upgrade_degraded: Optional[bool] = None,
                 prefetch_min_saving: Optional[float] = None,
                 telemetry: Optional[Telemetry] = None,
                 n_devices: int = 1,
                 ici_gbps: Optional[float] = None,
                 peer_borrow: bool = True,
                 paged_kv: bool = False,
                 kv_block: int = 16,
                 kv_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 placement=None):
        """latency_cfg: full-scale config whose expert sizes / active params
        drive the transfer + compute latency model (the accuracy testbed can
        be a reduced model while latencies reflect the deployment target —
        e.g. the real DeepSeek-V2-Lite). Defaults to cfg itself.

        tier: a TieredExpertStore enabling the degraded miss fallback. The
        engine quantizes every MoE expert into the tier's precision (the
        replicas ride the params pytree as a ``quant`` sub-dict), calibrates
        the per-expert fidelity scores, and uses the tier's displaced-budget
        cache. ``policy.quant_tier`` must name the same precision (it is the
        static jit switch for the mixed-precision dispatch).

        upgrade_degraded: degraded-then-upgrade — every slot served from the
        quant tier enqueues a background 'upgrade' transfer of the TRUE
        expert (prefetch priority, exempt from stale-prediction cancels), so
        later steps compute it at full precision once it lands. None (auto)
        enables it exactly when the unified cost policy is on
        (policy.miss_policy='cost') and a tier is attached; precedence-mode
        engines keep the pre-upgrade behavior bit-identical.

        prefetch_min_saving: with cost-ranked prefetch (miss_policy='cost' +
        a predict_proba predictor), candidates whose expected stall saved —
        P(use) x unified miss cost — is <= this many seconds are not worth
        their PCIe bytes and are skipped; the count of worthwhile candidates
        feeds (and can cap) the adaptive budget controller. None (auto): 1%
        of a full expert transfer — a prefetch occupies the link for
        ~transfer_time, so a saving far below that cannot pay for its own
        bytes (misses a good buddy or replica absorbs score ~stall_per_
        quality x their tiny quality loss and fall under this bar).

        n_devices: expert-parallel mesh size (1-8). Each layer's experts
        are sharded round-robin across the devices (owner[e] = e % D);
        device 0 is the simulated compute device, devices 1..D-1 are peer
        HBM pools reachable over per-device ICI links (one TransferScheduler
        per link, hop-priced on a 2D grid). A miss on an expert a peer
        holds can be resolved by BORROWING it over ICI — the fifth miss
        outcome, usually ~100x cheaper than a host PCIe fetch — and the
        borrowed expert is inserted into device 0's cache on landing.
        n_devices=1 (default) is bit-identical to the single-device engine.

        ici_gbps: ICI per-link bandwidth override in GB/s (None: hw.ici_bw).

        peer_borrow: gate the peer-borrow outcome (mesh misses fall back to
        the four single-device outcomes when False) — the ablation arm of
        the mesh benchmark.

        telemetry: an optional runtime.telemetry.Telemetry bundle. When
        attached, the engine emits flight-recorder spans on the simulated
        clock, maintains per-expert hit/miss/degraded EMAs, records miss-
        cost calibration samples (predicted vs realized stall per outcome
        class), and feeds the prefetch precision/recall meter — all read-
        only observers of engine state (no PRNG draws, no timeline
        mutation), so a telemetry=None run is bit-identical.

        paged_kv: replace the per-slot ring-buffer KV with a shared pool of
        fixed-size blocks (runtime/paged_kv.py) addressed through per-row
        block tables. Attention-only stacks, no sliding window. paged_kv=
        False (default) is bit-identical to the pre-paged engine (frozen-
        capture test in tests/test_paged.py).

        kv_block: tokens per KV block (paged mode). kv_blocks: pool size
        override; None sizes the pool to exactly the ring footprint —
        batch x ceil(capacity / kv_block) blocks — so paged-vs-ring A/Bs
        run at equal HBM.

        prefix_cache: radix-tree prefix reuse over the paged pool
        (serving/prefix.py): ContinuousScheduler admission matches each
        prompt against previously-served prefixes, adopts the shared block
        chain (refcount bump + copy-on-write at the write frontier), and
        prefills only the novel suffix. Requires paged_kv.

        placement: an optional runtime.placement.PlacementController — the
        live traffic→placement loop. When attached, the engine feeds it
        per-layer activity each step and ticks it on the simulated clock
        every refresh_interval_s: tier coverage re-picks, background
        'replicate' fetches of persistently-hot experts, and (D>1) pushes
        of hot experts to underloaded peers. placement=None (default) is
        bit-identical to the pre-placement engine (frozen-capture test in
        tests/test_placement.py)."""
        assert cfg.is_moe, "ServeEngine's expert cache applies to MoE archs"
        assert lookahead >= 1, "lookahead: layers ahead to prefetch (>= 1)"
        self.cfg = cfg
        self.policy = policy
        self.num_moe_layers = sum(r for k, r in cfg.stack() if k == "attn_moe")
        e = cfg.moe.num_experts
        self.tier = tier
        if tier is not None:
            assert policy.quant_tier != "off", \
                "a TieredExpertStore needs policy.quant_tier='int8'/'int4'"
            assert TIER_BITS[policy.quant_tier] == tier.bits, \
                f"policy tier {policy.quant_tier} != store bits {tier.bits}"
            assert cache is None or cache is tier.cache, \
                "pass the cache through the tier (it owns the budget split)"
            params, fid = quantize.attach_quant_tier(cfg, params, tier.bits)
            tier.attach_fidelity(fid)
            cache = tier.cache
        else:
            assert policy.quant_tier == "off", \
                "policy.quant_tier is on but no TieredExpertStore was given"
        self.params = params
        self.cache = cache or ExpertCache(self.num_moe_layers, e, 1.0)
        self.predictor = predictor
        self.prefetch_k = prefetch_k
        self.lookahead = lookahead
        self.hw = hw
        self.ledger = TransferLedger(hw)
        self.scheduler = TransferScheduler(hw)
        # residency commits and byte counts are driven by the same timeline
        self.scheduler.add_listener(self.cache.on_transfer_event)
        self.ledger.attach(self.scheduler)
        assert 1 <= int(n_devices) <= 8, "n_devices: 1-8 device mesh"
        self.n_devices = int(n_devices)
        self.peer_borrow = bool(peer_borrow)
        self._ici_bw = (hw.ici_bw if ici_gbps is None
                        else float(ici_gbps) * 1e9)
        self.cache.enable_mesh(self.n_devices)
        self.peer_links = self._build_peer_links()
        self._n_peer_borrow = 0
        if tier is not None:
            self.ledger.tier_upload(tier.quant_bytes)
        self.stats = EngineStats()
        self.window = window
        ref_cfg = latency_cfg or cfg
        self._expert_bytes = expert_nbytes(ref_cfg.d_model, ref_cfg.moe.d_ff)
        self._latency_cfg = ref_cfg
        self._active_params = ref_cfg.active_param_count()
        self._key = jax.random.PRNGKey(seed)
        self._last_used: dict = {}
        self._cost_mode = policy.miss_policy == "cost"
        self.costs = MissCostModel(
            self.num_moe_layers, e, expert_bytes=self._expert_bytes, hw=hw,
            stall_per_quality=policy.stall_per_quality,
            drop_loss=policy.drop_loss)
        self.upgrade_degraded = (self._cost_mode and tier is not None
                                 if upgrade_degraded is None
                                 else bool(upgrade_degraded))
        if prefetch_min_saving is None:
            prefetch_min_saving = 0.01 * hw.transfer_time(self._expert_bytes)
        self.prefetch_min_saving = float(prefetch_min_saving)
        self.last_prefetch_worthwhile: Optional[int] = None
        self._step_worthwhile: Optional[int] = None
        self.telemetry = telemetry
        self._wire_telemetry()
        self.placement = placement
        if placement is not None:
            placement.attach(self)

        self._paged = bool(paged_kv)
        self._kv_block = int(kv_block)
        self._kv_blocks = kv_blocks
        self._prefix_on = bool(prefix_cache)
        self.kv_pool = None
        self.prefix_tree = None
        self._prefix_hits = 0
        self._prefix_hit_tokens = 0
        self._prefix_novel_tokens = 0
        if self._paged:
            assert self._kv_block >= 1, "kv_block must be >= 1"
            assert all(k in ("attn_dense", "attn_moe")
                       for k, _ in cfg.stack()), \
                f"paged KV needs an attention-only stack, got {cfg.stack()}"
            assert cfg.sliding_window == 0 and self.window <= 0, \
                "paged KV blocks never wrap; sliding-window decode is " \
                "ring-only"
            assert cfg.num_cond_tokens == 0, \
                "paged KV does not model conditioning-prefix positions"
        else:
            assert not prefix_cache, "prefix_cache requires paged_kv"

        if tables is None:
            r = 8
            self._table = np.full((self.num_moe_layers, e, r), -1, np.int32)
            self._q = np.zeros((self.num_moe_layers, e, r), np.float32)
        else:
            self._table = np.asarray(tables.table)
            self._q = np.asarray(tables.q)
        if self.cache.buddy_table is None and tables is not None:
            # buddy-aware eviction: prefer victims whose misses buddies absorb
            self.cache.buddy_table = self._table

        self._step_fn = self._jit_step(transformer.decode_step)
        self._chunk_fn = self._jit_step(transformer.prefill_chunk)
        self._launch = ("step", (1, 1))    # last launch: mode, token shape

    def _jit_step(self, fn):
        """``fn`` jitted with this engine's static arguments; the program
        keeps ``fn``'s name (``jit_decode_step``, ``jit_prefill_chunk``)."""
        step = functools.partial(fn, cfg=self.cfg, policy=self.policy,
                                 record=True, window=self.window)
        return jax.jit(functools.update_wrapper(step, fn))

    # ------------------------------------------------------------------
    def _build_peer_links(self) -> dict:
        """One ICI TransferScheduler per peer device (empty at D=1). Every
        link shares the engine's event clock, commits borrowed experts into
        device 0's cache on completion (same listener protocol as the host
        PCIe link), and books its bytes/stalls into the shared ledger."""
        if self.n_devices <= 1:
            return {}
        links = make_ici_links(self.n_devices, self.hw, ici_bw=self._ici_bw)
        for link in links.values():
            link.add_listener(self._on_peer_link_event)
            self.ledger.attach(link)
        return links

    def _on_peer_link_event(self, kind: str, t) -> None:
        """ICI-link cache listener: a borrow lands in DEVICE 0's cache, but
        a placement controller's 'replicate' push lands in the TARGET
        PEER's HBM (peer_insert flips that mask at submit time), so it must
        not touch device 0's residency or in-flight state."""
        if t.cause == "replicate":
            return
        self.cache.on_transfer_event(kind, t)

    def advance_clock(self, to_time: float) -> None:
        """Advance EVERY link of the mesh (host PCIe + all ICI links) to the
        same simulated instant — the single event clock the serving
        schedulers use for idle time between steps. At D=1 this is exactly
        ``scheduler.advance``."""
        self.scheduler.advance(to_time)
        for link in self.peer_links.values():
            link.advance(to_time)

    def _links_busy_s(self) -> float:
        return (self.scheduler.busy_s
                + sum(l.busy_s for l in self.peer_links.values()))

    # ------------------------------------------------------------------
    def _wire_telemetry(self) -> None:
        """Attach the (optional) telemetry bundle to the CURRENT scheduler —
        called from __init__ and again by reset_runtime (which rebuilds the
        scheduler, so the trace hook and prefetch-meter listener must be
        re-registered). A replacement bundle can be installed between runs
        with ``eng.telemetry = Telemetry(...); eng.reset_runtime()``.
        No-op when telemetry is None: the off path stays bit-identical."""
        tele = self.telemetry
        if tele is None:
            return
        self.scheduler.trace = tele.trace
        self.scheduler.add_listener(tele.prefetch.on_transfer_event)
        for link in self.peer_links.values():
            # per-link trace lanes ("transfers:ici<d>"); peer borrows are
            # demand-class, so the prefetch meter does not listen here
            link.trace = tele.trace
        if self.tier is not None:
            self.tier.telemetry = tele
        if tele.expert_stats is None:
            tele.expert_stats = ExpertStats(self.num_moe_layers,
                                            self.cfg.moe.num_experts)

    def _miss_eta(self) -> np.ndarray:
        """[L, E] expected stall of fetching each expert on a miss THIS step:
        a cold miss pays the full modeled transfer; an in-flight prefetch
        only its optimistic remaining tail (TransferScheduler.eta_s)."""
        return self.costs.fetch_eta(self.scheduler)

    def _tier_fidelity(self) -> Optional[np.ndarray]:
        """[L, E] calibrated replica error with uncovered experts at inf,
        or None when no tier is attached."""
        return None if self.tier is None else self.tier.effective_fidelity()

    def _buddy_state(self) -> BuddyState:
        res = self.cache.residency_mask()
        hop = np.stack([self.cache.hop_vector(l)
                        for l in range(self.num_moe_layers)])
        quant_ok = fid_cost = fetch_cost = peer_ok = peer_cost = None
        peer_on = self.peer_borrow and bool(self.peer_links)
        if self._cost_mode:
            # unified cost mode: the in-graph argmin consumes per-expert
            # stall-equivalent costs instead of the precedence quant_ok mask
            eta = self._miss_eta()
            fid_cost = jnp.asarray(self.costs.degraded_cost(
                self._tier_fidelity(), shape=eta.shape), jnp.float32)
            fetch_cost = jnp.asarray(eta, jnp.float32)
            if peer_on:
                # peer-borrow priced from the owning links' live queues
                peer_cost = jnp.asarray(self.costs.peer_eta(
                    self.peer_links, self.cache.peer_resident), jnp.float32)
        else:
            if self.tier is not None:
                quant_ok = jnp.asarray(
                    self.tier.degraded_ok(res, self._miss_eta()))
            if peer_on:
                # precedence mode: any expert a peer holds is borrowable
                # (chain: buddy -> degraded -> peer -> fetch/drop)
                peer_ok = jnp.asarray(self.cache.peer_resident.any(axis=0))
        return BuddyState(resident=jnp.asarray(res),
                          table=jnp.asarray(self._table),
                          q=jnp.asarray(self._q),
                          hop=jnp.asarray(hop),
                          quant_ok=quant_ok,
                          fid_cost=fid_cost,
                          fetch_cost=fetch_cost,
                          peer_ok=peer_ok,
                          peer_cost=peer_cost)

    def init_caches(self, batch: int, seq_len: int):
        if self._paged:
            bs = self._kv_block
            cap = seq_len + self.cfg.num_cond_tokens
            max_blocks = -(-cap // bs)
            n_blocks = (int(self._kv_blocks) if self._kv_blocks
                        else batch * max_blocks)
            self.kv_pool = PagedKVPool(n_blocks, bs, batch, max_blocks)
            self._prefix_hits = 0
            self._prefix_hit_tokens = 0
            self._prefix_novel_tokens = 0
            if self._prefix_on:
                self.prefix_tree = PrefixTree(self.kv_pool)
            return transformer.init_paged_caches(self.cfg, n_blocks, bs)
        return transformer.init_caches(
            self.cfg, batch, seq_len,
            window=0 if self.window < 0 else self.window)

    def _apply_kv_copies(self, caches):
        """Batched device copy of the pool's pending CoW pairs — must land
        before the next scatter so a shared block's content survives the
        remap. Leaves are [repeat, P, bs, KV, hd]: block axis 1."""
        pairs = self.kv_pool.drain_copies()
        if not pairs:
            return caches
        src = jnp.asarray([p[0] for p in pairs], jnp.int32)
        dst = jnp.asarray([p[1] for p in pairs], jnp.int32)
        return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), caches)

    # ------------------------------------------------------------------
    def step(self, token, caches, pos, active: Optional[np.ndarray] = None):
        """One decode step for the whole batch. token [B] int32 device array;
        pos scalar (lockstep batch) or [B] per-row positions (continuous
        batching). ``active`` is a bool [B] slot mask: inactive rows still
        flow through the fixed-shape jitted graph, but are excluded from all
        expert-usage, transfer, and throughput accounting.
        Returns (logits [B, V], new_caches)."""
        b = int(token.shape[0])
        with host_span("serve.step", kind="decode") as span:
            active = (np.ones(b, bool) if active is None
                      else np.asarray(active, bool))
            if span.on:
                n = int(active.sum())
                span.set(rows=n, tokens=n)
            with host_span("serve.buddy_state"):
                buddies = self._buddy_state()
            with host_span("serve.launch"):
                self._key, sub = jax.random.split(self._key)
                kw = {}
                if self._paged:
                    # paged decode is always per-row: broadcast a lockstep
                    # scalar
                    pos = np.broadcast_to(np.asarray(pos, np.int32), (b,))
                    for i in np.flatnonzero(active):
                        p = int(pos[i])
                        self.kv_pool.ensure_range(i, p, p + 1)
                    caches = self._apply_kv_copies(caches)
                    kw["block_tables"] = jnp.asarray(self.kv_pool.tables)
                logits, caches, aux = self._step_fn(
                    params=self.params, token=token, caches=caches,
                    pos=jnp.asarray(pos, jnp.int32), buddies=buddies,
                    rng=sub, tok_valid=jnp.asarray(active), **kw)
            self._launch = ("step", (b, 1))
            self._account(aux, active=active)
        return logits, caches

    def prefill_rows(self, tokens, rows, caches, base_pos, tok_valid=None):
        """Fused chunked-prefill step: ingest up to C tokens per row in ONE
        jitted launch and ONE timeline replay (vs C decode steps).

        tokens [B, C] int32; ``rows`` bool [B] marks live slots (others ride
        the fixed-shape graph masked out of all accounting); base_pos [B] is
        each row's absolute position of chunk token 0; tok_valid [B, C] is a
        PREFIX validity mask (default: every token of a live row). A decode
        row joins as a 1-valid-token chunk, so decode rows keep stepping
        while a neighbour slot prefills.

        The chunk is compute-dense on the simulated clock — all its tokens
        share one weight-streaming pass in ``hw.decode_compute_time`` — so
        per-layer compute slices are ~C× longer and hide proportionally more
        PCIe transfer time. The chunk's dense expert activations feed the
        predictor/cache (``_observe_layer``) as a high-confidence warm-up
        for the request's first decode steps.

        Returns (logits [B, C, V], new_caches); row i's next-token logits
        sit at index ``tok_valid[i].sum() - 1``."""
        with host_span("serve.step", kind="chunk") as span:
            tokens = jnp.asarray(tokens, jnp.int32)
            b, c = tokens.shape
            rows = np.asarray(rows, bool)
            if tok_valid is None:
                tok_valid = np.repeat(rows[:, None], c, axis=1)
            tok_valid = np.asarray(tok_valid, bool) & rows[:, None]
            base = np.asarray(base_pos, np.int32)
            counts = tok_valid.sum(axis=1)
            if span.on:
                span.set(rows=int((counts > 0).sum()),
                         tokens=int(counts.sum()))
            with host_span("serve.buddy_state"):
                buddies = self._buddy_state()
            with host_span("serve.launch"):
                kw = {}
                if self._paged:
                    # no ring to wrap: a block's slot index IS its content
                    # position. Map/CoW the write range of every live row,
                    # land pending block copies, and ship the block table
                    # with the launch.
                    for i in np.flatnonzero(counts > 0):
                        self.kv_pool.ensure_range(int(i), int(base[i]),
                                                  int(base[i] + counts[i]))
                    caches = self._apply_kv_copies(caches)
                    kw["block_tables"] = jnp.asarray(self.kv_pool.tables)
                else:
                    # ring-wrap guard: a multi-token chunk is scattered into
                    # the KV cache before its queries attend, so it must not
                    # wrap the ring buffer (attn_prefill_chunk); single-token
                    # rows are plain decode writes
                    cap = jax.tree.leaves(caches)[0].shape[2]
                    multi = counts > 1
                    assert not multi.any() or \
                        int((base[multi] + counts[multi]).max()) <= cap, \
                        "chunked prefill would wrap the KV ring buffer: " \
                        "size caches to the full prompt (prompt end %d > " \
                        "capacity %d)" % (
                            int((base[multi] + counts[multi]).max()), cap)
                self._key, sub = jax.random.split(self._key)
                logits, caches, aux = self._chunk_fn(
                    params=self.params, tokens=tokens, caches=caches,
                    base_pos=jnp.asarray(base, jnp.int32),
                    tok_valid=jnp.asarray(tok_valid), buddies=buddies,
                    rng=sub, **kw)
            self._launch = ("chunk", (b, c))
            self._account(aux, active=tok_valid.reshape(-1))
        return logits, caches

    # -- per-layer step timeline ---------------------------------------
    def _account(self, aux, active: np.ndarray) -> None:
        """Read the step's routing record back in one transfer (the wait on
        the device; ``aux["recorded"]`` then holds host copies), and replay
        the step on the transfer timeline. While a profiler trace runs, the
        ``serve.account`` span carries the step's counters."""
        with host_span("serve.readback"):
            aux["recorded"] = jax.device_get(aux.get("recorded", []))
        with host_span("serve.account") as span:
            n_hit = self.stats.n_hit
            self._replay(aux["recorded"], active)
            if span.on:
                span.set(hits=self.stats.n_hit - n_hit,
                         **self._step_counters(aux["recorded"], active))

    def _step_counters(self, recorded, active: np.ndarray) -> dict:
        """The step's counts over its valid tokens: distinct (layer, expert)
        pairs routed, miss outcomes in slots summed over layers, and the
        slots routed against the slots the dispatch computed."""
        out = dict(routed=0, substituted=0, fetched=0, dropped=0,
                   degraded=0)
        fetch = self.policy.fallback == "fetch"
        for rec in recorded:
            idx = rec["indices"][:, active]                   # [L, T, K]
            out["routed"] += sum(len(np.unique(li)) for li in idx)
            out["substituted"] += int(rec["substituted"][:, active].sum())
            n_miss = int(rec["missed"][:, active].sum())
            out["fetched" if fetch else "dropped"] += n_miss
            for key in ("dropped", "degraded"):
                if key in rec:
                    out[key] += int(rec[key][:, active].sum())
        mode, shape = self._launch
        out["slots_routed"] = (int(active.sum()) * self.cfg.moe.top_k
                               * self.num_moe_layers)
        out["slots_computed"] = transformer.moe_slots_computed(
            self.cfg, self.policy, mode, shape, n_live=int(active.sum()))
        return out

    def _replay(self, recorded, active: np.ndarray) -> None:
        """Replay the step on the transfer timeline, layer by layer.
        ``active`` is a flat [T] TOKEN mask (T = B for decode steps, B*C
        row-major for chunk steps) — pad rows (StaticBatcher), empty decode
        slots, and invalid chunk tokens must not generate expert traffic or
        count as served tokens. Per-step compute is
        ``hw.decode_compute_time(active_params, n_valid_tokens)``: every
        valid token pays the FLOPs term but the weight-streaming memory term
        is paid once per step — which is exactly why a fused prefill chunk
        beats C single-token steps on the clock."""
        n_active = int(active.sum())
        if n_active == 0:
            return
        self._step_worthwhile = None    # fresh per-step aggregate
        tele = self.telemetry
        trace = tele.trace if tele is not None else None
        sched = self.scheduler
        step_t0 = sched.now
        busy0 = self._links_busy_s()
        compute_total = self.hw.decode_compute_time(
            self._active_params, n_active)
        per_layer = compute_total / max(1, self.num_moe_layers)
        cursor = step_t0
        step_stall = 0.0

        layer_off = 0
        e_n = self.cfg.moe.num_experts
        for rec in recorded:
            idx = np.asarray(rec["indices"])                  # [L, T, K]
            sub_sl = np.asarray(rec["substituted"])           # [L, T, K]
            miss_sl = np.asarray(rec["missed"])               # [L, T, K]
            deg_sl = (np.asarray(rec["degraded"])             # [L, T, K]
                      if "degraded" in rec else None)
            drop_sl = (np.asarray(rec["dropped"])             # [L, T, K]
                       if "dropped" in rec else None)
            peer_sl = (np.asarray(rec["peered"])              # [L, T, K]
                       if "peered" in rec else None)
            for li in range(idx.shape[0]):
                layer = layer_off + li
                # transfers in flight overlap all earlier layers' compute
                self.advance_clock(cursor)
                rows = idx[li][active]                        # [T_act, K]
                used = rows.reshape(-1)
                self._observe_layer(layer, used)
                res_used = np.unique(used[self.cache.resident[layer, used]])
                self.cache.pin(layer, res_used)
                self.stats.n_hit += int(len(res_used))

                n_sub = int(sub_sl[li][active].sum())
                self.stats.n_sub += n_sub
                self.ledger.buddy_hit(n_sub)
                n_deg = n_dr = 0
                if deg_sl is not None:
                    # misses served by the resident quant tier: no transfer,
                    # no stall — only the degraded-token accounting
                    n_deg = int(deg_sl[li][active].sum())
                    if n_deg:
                        self.ledger.degraded(n_deg)
                        if self.tier is not None:
                            self.tier.note_degraded(n_deg)
                        if self.upgrade_degraded:
                            self._upgrade_degraded(
                                layer, rows[deg_sl[li][active]])
                if drop_sl is not None:
                    # misses the cost argmin dropped: renormalized in-graph,
                    # no transfer, no stall — event accounting only
                    n_dr = int(drop_sl[li][active].sum())
                    if n_dr:
                        self.ledger.drop(n_dr)
                        self.stats.n_miss_drop += n_dr
                miss_row = np.bincount(rows[miss_sl[li][active]],
                                       minlength=e_n)
                peer_row = None
                n_peer = 0
                if peer_sl is not None and peer_sl[li][active].any():
                    # slots the argmin resolved by peer-HBM borrow: a
                    # demand-class ICI transfer from the owning device
                    peer_row = np.bincount(rows[peer_sl[li][active]],
                                           minlength=e_n)
                    n_peer = int(peer_row.sum())
                if tele is not None:
                    self._record_layer_telemetry(
                        layer, rows, used, res_used, miss_row, cursor,
                        n_sub=n_sub, n_deg=n_deg, n_dr=n_dr, n_peer=n_peer,
                        sub_slots=sub_sl[li][active],
                        deg_slots=(deg_sl[li][active]
                                   if deg_sl is not None else None))
                if self.placement is not None:
                    # the controller owns its own ExpertStats so live
                    # placement works with or without a telemetry bundle
                    self.placement.observe_layer(
                        layer, np.unique(used), res_used,
                        np.flatnonzero(miss_row > 0),
                        (np.unique(rows[deg_sl[li][active]])
                         if n_deg else None))
                stall_t0 = cursor
                stall = 0.0
                if peer_row is not None:
                    cursor, pstall = self._resolve_peer(layer, peer_row,
                                                        cursor)
                    stall += pstall
                cursor, fstall = self._resolve_misses(layer, miss_row,
                                                      cursor)
                stall += fstall
                step_stall += stall
                if trace is not None:
                    if stall > 0.0:
                        trace.span("layers", layer, "stall", "stall",
                                   stall_t0, cursor, stall_s=stall,
                                   n_fetch=int((miss_row > 0).sum()))
                    trace.span("layers", layer, "compute", "compute",
                               cursor, cursor + per_layer)
                cursor += per_layer          # this layer's compute slice
                self._issue_prefetches(layer, used)
                self.cache.unpin(layer)
            layer_off += idx.shape[0]

        self.advance_clock(cursor)          # drain overlap to end of step
        step_time = cursor - step_t0
        overlapped = max(0.0, (self._links_busy_s() - busy0) - step_stall)
        self.ledger.overlapped(overlapped)

        self.stats.steps += 1
        self.stats.tokens += n_active
        self.stats.compute_s += compute_total
        self.stats.stall_s += step_stall
        self.stats.sim_time_s += step_time

        if tele is not None:
            m = tele.metrics
            m.ema("step_time_s", alpha=0.05).update(step_time)
            m.histogram("step_stall_s").observe(step_stall)
            m.gauge("inflight_transfers").set(sched.n_in_flight)
            if trace is not None:
                trace.span("engine", 0, "step",
                           f"step{self.stats.steps - 1}", step_t0, cursor,
                           tokens=n_active, stall_s=step_stall,
                           overlapped_s=overlapped)
        if self.placement is not None:
            # placement ticks ride the step loop on the SIMULATED clock
            # (interval-gated, so the continuous scheduler's feedback hook
            # ticking it as well never double-fires a window)
            self.placement.maybe_tick(self)

    def _observe_layer(self, layer: int, used: np.ndarray) -> None:
        self.cache.touch(layer, used)
        if self.predictor is not None:
            if hasattr(self.predictor, "observe_transition") and layer > 0:
                self.predictor.observe_transition(
                    layer, self._last_used.get(layer - 1, []), used)
            self.predictor.observe(layer, used)
        self._last_used[layer] = used

    def _record_layer_telemetry(self, layer: int, rows, used, res_used,
                                miss_row, t_layer: float, *, n_sub: int,
                                n_deg: int, n_dr: int, sub_slots,
                                deg_slots, n_peer: int = 0) -> None:
        """Per-(layer, step) telemetry: the miss-outcome breakdown (trace
        instant + counters), per-expert EMA updates, the prefetch meter's
        used-in-time credit, and the zero-stall calibration rows for the
        transfer-free outcomes (buddy/degraded/drop) with their cost-model
        quality price — the ``stall_per_quality`` calibration signal. The
        fetch-outcome calibration rows are recorded in _resolve_misses,
        where predicted ETA and realized stall are both in hand. Pure
        observer: touches no engine/cache/scheduler state."""
        tele = self.telemetry
        missing = np.flatnonzero(miss_row > 0)
        uniq_used = np.unique(used)
        deg_e = (np.unique(rows[deg_slots]) if n_deg else None)
        if tele.expert_stats is not None:
            tele.expert_stats.update(layer, uniq_used, res_used, missing,
                                     deg_e)
        tele.prefetch.note_used(layer, uniq_used)
        m = tele.metrics
        m.counter("slots", outcome="hit").inc(len(res_used))
        for outcome, n in (("buddy", n_sub), ("degraded", n_deg),
                           ("peer", n_peer),
                           ("fetch", int(miss_row.sum())), ("drop", n_dr)):
            if n:
                m.counter("slots", outcome=outcome).inc(n)
        cal = tele.calibration
        if n_sub:
            # buddy: zero stall by construction; the quality price is the
            # cost model's host-side estimate at the substituted experts
            # (the in-graph argmin recomputes Psi per token)
            bq = best_resident_q(self._table[layer], self._q[layer],
                                 self.cache.resident[layer])
            bc = self.costs.buddy_cost(bq)[np.asarray(rows[sub_slots])]
            bc = bc[np.isfinite(bc)]
            cal.record("buddy", 0.0, 0.0, n=n_sub,
                       quality_cost=float(bc.mean()) if bc.size else 0.0)
        if n_deg:
            dc = self.costs.degraded_cost(self._tier_fidelity())[layer][deg_e]
            dc = dc[np.isfinite(dc)]
            cal.record("degraded", 0.0, 0.0, n=n_deg,
                       quality_cost=float(dc.mean()) if dc.size else 0.0)
        if n_dr:
            cal.record("drop", 0.0, 0.0, n=n_dr,
                       quality_cost=self.costs.drop_cost())
        if tele.trace is not None:
            extra = {"peer": n_peer} if self.n_devices > 1 else {}
            tele.trace.instant(
                "layers", layer, "outcomes", f"L{layer}", t_layer,
                hit=len(res_used), buddy=n_sub, degraded=n_deg,
                fetch=int(miss_row.sum()), drop=n_dr, **extra)

    def _resolve_misses(self, layer: int, miss_row: np.ndarray,
                        cursor: float):
        """Residual misses (post-substitution) block THIS layer only. An
        in-flight prefetch is escalated and waited for its tail (late
        prefetch); otherwise a demand fetch pays the full transfer."""
        missing = np.flatnonzero(miss_row > 0)
        tele = self.telemetry
        if self.policy.fallback != "fetch":
            n_dropped = int(miss_row.sum())
            self.ledger.drop(n_dropped)
            if tele is not None and n_dropped:
                tele.calibration.record("drop", 0.0, 0.0, n=n_dropped,
                                        quality_cost=self.costs.drop_cost())
            return cursor, 0.0
        sched = self.scheduler
        stall = 0.0
        for e in missing:
            e = int(e)
            if self.cache.resident[layer, e]:
                # arrived after this step's mask snapshot — already on device
                continue
            t = sched.in_flight(layer, e)
            # calibration: the cost model's predicted stall for the fetch
            # outcome AT DECISION TIME — the in-flight optimistic tail, or
            # the modeled cold transfer (same quantities fetch_eta feeds the
            # argmin) — recorded against the realized stall below
            predicted = None
            if tele is not None:
                predicted = (sched.eta_s(t) if t is not None else
                             self.hw.transfer_time(self._expert_bytes))
                if t is None:
                    tele.prefetch.note_uncovered_miss(layer, e)
            if t is not None:
                sched.escalate(t)
                if t.cause in ("upgrade", "replicate"):
                    # an upgrade (or a placement replica copy) is not a
                    # prediction: waiting on one is a demand-class stall
                    # (the cost model priced it at the COLD transfer; the
                    # in-flight bytes are just reused) — booking it as
                    # late-prefetch would feed a false lateness signal to
                    # the adaptive budget controller
                    kind = "demand"
                else:
                    kind = "late_prefetch"
                    self.stats.n_late_prefetch += 1
            else:
                t = sched.submit(layer, e, self._expert_bytes, "demand")
                kind = "demand"
            done = sched.run_until_done(t)
            s = max(0.0, done - cursor)
            self.ledger.stall(kind, s)      # ledger owns the breakdown
            if tele is not None:
                tele.calibration.record("fetch", predicted, s)
                tele.metrics.histogram("stall_s", kind=kind).observe(s)
            stall += s
            cursor = max(cursor, done)
            self.stats.n_miss_fetch += 1
        return cursor, stall

    def _resolve_peer(self, layer: int, peer_row: np.ndarray,
                      cursor: float):
        """Peer-HBM borrows block THIS layer until the ICI transfer lands.
        Each borrowed expert is fetched from the cheapest live holder's
        link (priced exactly as costs.peer_eta: in-flight tail, else queue
        backlog + hop-priced transfer) and inserted into device 0's cache
        on completion via the link's cache listener — a hot borrowed expert
        converges to a plain hit. Experts no reachable peer holds fall back
        to a host demand fetch so the slot is never silently lost."""
        tele = self.telemetry
        stall = 0.0
        for e in np.flatnonzero(peer_row > 0):
            e = int(e)
            if self.cache.resident[layer, e]:
                # landed after this step's mask snapshot — already on device
                continue
            best_d = best_eta = t = None
            for d in self.cache.peer_holders(layer, e):
                link = self.peer_links.get(int(d))
                if link is None:
                    continue
                tf = link.in_flight(layer, e)
                eta = (link.eta_s(tf) if tf is not None else
                       link.backlog_s()
                       + link.transfer_time(self._expert_bytes))
                if best_eta is None or eta < best_eta:
                    best_d, best_eta, t = int(d), eta, tf
            if best_d is None:
                # raced out of every peer (eviction churn): demand-fetch
                t = self.scheduler.submit(layer, e, self._expert_bytes,
                                          "demand")
                done = self.scheduler.run_until_done(t)
                s = max(0.0, done - cursor)
                self.ledger.stall("demand", s)
                stall += s
                cursor = max(cursor, done)
                self.stats.n_miss_fetch += 1
                continue
            link = self.peer_links[best_d]
            if t is None:
                t = link.submit(layer, e, self._expert_bytes, "peer")
            done = link.run_until_done(t)
            s = max(0.0, done - cursor)
            self.ledger.stall("peer", s)
            if tele is not None:
                tele.calibration.record("peer", best_eta, s)
                tele.metrics.histogram("stall_s", kind="peer").observe(s)
            stall += s
            cursor = max(cursor, done)
            self._n_peer_borrow += 1
        return cursor, stall

    def _upgrade_degraded(self, layer: int, experts: np.ndarray) -> None:
        """Degraded-then-upgrade: background-fetch the TRUE experts that the
        quant tier just served, so later steps compute them at full
        precision. 'upgrade' cause: prefetch priority (never preempts a
        stalled layer), exempt from stale-prediction cancellation, bytes
        ledgered separately. The residency snapshot for THIS step was taken
        before the upgrade lands, so already-computed tokens keep their
        degraded outputs and accounting — an upgrade only changes future
        steps. Duplicate submissions return the in-flight transfer, so an
        expert degraded on many tokens/steps pays its bytes once."""
        for e_up in np.unique(np.asarray(experts, np.int64)):
            e_up = int(e_up)
            if self.cache.resident[layer, e_up] or \
                    self.scheduler.in_flight(layer, e_up) is not None:
                continue
            self.scheduler.submit(layer, e_up, self._expert_bytes, "upgrade")
            self.stats.n_upgrade_issued += 1

    def _rank_prefetch(self, tgt: int, used: np.ndarray):
        """Expected-stall-saved prefetch ranking (runtime/costs.py):
        score[e] = P(use e at the target layer) x the unified miss cost the
        runtime would pay without it (lateness risk on the current
        timeline).

        Returns (want, worthwhile). ``want`` is the keep/submit list (best
        first, positive-saving only, capped at prefetch_k): it INCLUDES
        still-attractive in-flight experts, because the caller also feeds
        it to cancel_stale_prefetches — dropping them would cancel our own
        unfinished prefetches every step and ping-pong issue/cancel (the
        submit loop skips resident/in-flight entries anyway). ``worthwhile``
        counts candidates whose saving justifies NEW bytes (in-flight ones
        are already paid for) — the adaptive budget controller's cap."""
        p_use = np.asarray(self.predictor.predict_proba(
            tgt, lookahead=self.lookahead, context=used), np.float64)
        # rank at the COLD fetch cost: the ranking asks "is this expert
        # worth having in flight at all", so an already-running transfer
        # must not discount its own score (the in-flight ETA would sink it
        # below fresh candidates and rotate it out of the keep-set — the
        # same ping-pong as zeroing it). The in-flight discount belongs to
        # the wait-vs-degrade argmin (_buddy_state), not here.
        eta = np.full(self.cfg.moe.num_experts,
                      self.hw.transfer_time(self._expert_bytes))
        fid_row = (None if self.tier is None
                   else self.tier.effective_fidelity(tgt))
        # mode 'none' never reroutes: the in-graph argmin prices buddies at
        # inf there, and the ranking must agree or it understates the stall
        # a miss will actually pay
        best_q = (None if self.policy.mode == "none" else
                  best_resident_q(self._table[tgt], self._q[tgt],
                                  self.cache.resident[tgt]))
        # a mesh miss a peer can absorb over ICI is cheap — its prefetch
        # saving shrinks to the peer ETA, freeing PCIe bytes for experts
        # only the host can supply
        peer_row = (self.costs.peer_eta(self.peer_links,
                                        self.cache.peer_resident)[tgt]
                    if self.peer_borrow and self.peer_links else None)
        risk = self.costs.miss_cost(eta, fid_row, best_q, peer_eta=peer_row)
        score = self.costs.prefetch_scores(p_use, risk,
                                           self.cache.resident[tgt])
        new_score = np.where(self.cache.inflight[tgt], 0.0, score)
        worthwhile = int((new_score > self.prefetch_min_saving).sum())
        order = np.argsort(-score, kind="stable")
        want = [int(e) for e in order[:self.prefetch_k]
                if score[e] > self.prefetch_min_saving]
        # stash for the prefetch meter: _issue_prefetches credits the score
        # (expected stall saved) of each NEW submission to the telemetry
        # bundle — plain attribute, no behavioral effect when telemetry off
        self._last_rank_scores = score
        return want, worthwhile

    def _issue_prefetches(self, layer: int, used: np.ndarray) -> None:
        """While ``layer`` computes, line up transfers for layer
        ``layer + lookahead`` (wrapping into the next step). Predictions
        that changed since the last issue are cancelled if still unserved.
        Under the unified cost policy (and a predict_proba predictor) the
        candidates are ranked by expected stall saved instead of the
        predictor's raw top-k."""
        if self.predictor is None or self.prefetch_k <= 0:
            return
        tgt = (layer + self.lookahead) % self.num_moe_layers
        scores = None
        if self._cost_mode and hasattr(self.predictor, "predict_proba"):
            want, w = self._rank_prefetch(tgt, used)
            scores = self._last_rank_scores
            # the controller clamps the GLOBAL budget from this signal, so
            # report the step's MAX across target layers — a point sample
            # from one fully-resident layer would starve every other layer
            # for a whole controller window
            self._step_worthwhile = (w if self._step_worthwhile is None
                                     else max(self._step_worthwhile, w))
            self.last_prefetch_worthwhile = self._step_worthwhile
        else:
            want = self.predictor.predict_ahead(
                tgt, self.prefetch_k, lookahead=self.lookahead, context=used)
            want = [int(e) for e in np.atleast_1d(want)]
        self.stats.n_prefetch_cancelled += \
            self.scheduler.cancel_stale_prefetches(tgt, want)
        for e in want:
            if self.cache.resident[tgt, e] or self.cache.inflight[tgt, e]:
                continue
            self.scheduler.submit(tgt, e, self._expert_bytes, "prefetch")
            self.stats.n_prefetch_issued += 1
            if self.telemetry is not None and scores is not None:
                self.telemetry.prefetch.add_expected_saving(scores[e])

    # ------------------------------------------------------------------
    def reset_runtime(self, cache: Optional[ExpertCache] = None,
                      predictor=None) -> None:
        """Fresh serving state (clock, ledger, cache, predictor, stats) on
        the same compiled model — e.g. after a measurement probe, or to
        reuse one engine across benchmark runs without re-jitting."""
        e = self.cfg.moe.num_experts
        if cache is None:
            old = self.cache
            cache = ExpertCache(self.num_moe_layers, e, old.capacity / e,
                                policy=old.policy,
                                num_partitions=old.num_partitions,
                                buddy_table=old.buddy_table,
                                buddy_candidates=old.buddy_candidates)
        self.cache = cache
        if self.tier is not None:
            # the tier's replicas are static; repoint its cache at the fresh
            # one (same displaced capacity) and re-pay the one-time upload
            self.tier.cache = cache
            self.tier.reset_counters()
        if predictor is None and self.predictor is not None:
            # carry the predictor's configuration (accuracy/seed/decay/...)
            # into the fresh instance — a bare type(...)(L, E) silently reset
            # every knob to its default between benchmark runs
            if hasattr(self.predictor, "clone_fresh"):
                predictor = self.predictor.clone_fresh()
            else:
                predictor = type(self.predictor)(self.num_moe_layers, e)
        self.predictor = predictor
        self.ledger = TransferLedger(self.hw)
        self.scheduler = TransferScheduler(self.hw)
        self.scheduler.add_listener(self.cache.on_transfer_event)
        self.ledger.attach(self.scheduler)
        if self.cache.n_devices != self.n_devices:
            # a caller-supplied cache arrives un-sharded: seed the home
            # shard and peer pools exactly as __init__ did
            self.cache.enable_mesh(self.n_devices)
        self.peer_links = self._build_peer_links()
        self._n_peer_borrow = 0
        if self.tier is not None:
            self.ledger.tier_upload(self.tier.quant_bytes)
        self.stats = EngineStats()
        self._last_used = {}
        self.last_prefetch_worthwhile = None
        self._step_worthwhile = None
        # an attached telemetry bundle keeps accumulating across resets
        # (swap it first to start a fresh one); the scheduler was just
        # rebuilt, so its trace hook + meter listener must be re-registered
        self._wire_telemetry()
        if self.placement is not None:
            # fresh per-run placement state (streaks, replica sets,
            # counters) on the controller's UNCHANGED configuration
            self.placement.attach(self)

    def reset_rows(self, caches, rows):
        """Free the decode caches of ``rows`` (batch indices) so a freed slot
        can be re-used by a newly admitted request. Ring mode zeroes the
        rows' cache slices; paged mode releases the rows' block-table
        entries back to the pool (shared prefix blocks survive via their
        radix-tree refcounts) and leaves device storage untouched. Only
        attention-stack caches keep batch on axis 1 of every ring leaf
        ([repeat, B, ...]); super groups (hybrid/vlm) nest another layer
        axis first, so guard rather than silently zero the wrong axis."""
        assert all(k in ("attn_dense", "attn_moe") for k, _ in
                   self.cfg.stack()), \
            "reset_rows assumes [repeat, B, ...] cache leaves (attention " \
            f"stacks only), got {self.cfg.stack()}"
        if self._paged:
            for r in np.atleast_1d(rows):
                self.kv_pool.free_row(int(r))
            return caches
        rows = jnp.asarray(np.atleast_1d(rows), jnp.int32)
        return jax.tree.map(lambda a: a.at[:, rows].set(0), caches)

    def release_kv_row(self, row: int) -> None:
        """Return a row's KV pages to the pool without touching the caches —
        the preemption hook (ContinuousScheduler.preempt). No-op on the
        ring path, where the row's slots are zeroed on re-admission."""
        if self._paged:
            self.kv_pool.free_row(int(row))

    # -- prefix cache ---------------------------------------------------
    def adopt_prefix(self, row: int, prompt) -> int:
        """Match ``prompt`` against the radix tree and map the longest
        cached prefix into ``row``'s block table (refcount bump; CoW of the
        shared tail happens lazily in ensure_range before the first write).
        Returns the number of adopted tokens m — the scheduler then feeds
        prompt[m] first and chunk-prefills only the novel suffix."""
        tree = self.prefix_tree
        assert tree is not None, "adopt_prefix needs prefix_cache=True"
        toks = [int(t) for t in prompt]
        m, blocks = tree.match(toks, cap=len(toks) - 1)
        if m > 0:
            self.kv_pool.adopt(row, blocks)
            self._prefix_hits += 1
        self._prefix_hit_tokens += m
        self._prefix_novel_tokens += len(toks) - m
        tele = self.telemetry
        if tele is not None:
            tele.metrics.counter("prefix_tokens", kind="hit").inc(m)
            tele.metrics.counter("prefix_tokens",
                                 kind="novel").inc(len(toks) - m)
            self._prefix_gauges(tele)
            if m > 0 and tele.trace is not None:
                tele.trace.instant("engine", 0, "prefix_hit", f"row{row}",
                                   self.scheduler.now, row=int(row),
                                   hit_tokens=int(m),
                                   novel_tokens=int(len(toks) - m))
        return m

    def insert_prefix(self, row: int, prompt) -> None:
        """Donate a fully-prefilled row's prompt KV to the radix tree,
        trimmed to full blocks — the final partial block stays private so
        the donor keeps decoding into it without a CoW."""
        tree = self.prefix_tree
        if tree is None:
            return
        bs = self.kv_pool.block_size
        covered = (len(prompt) // bs) * bs
        if covered == 0:
            return
        toks = [int(t) for t in prompt[:covered]]
        tree.insert(toks, self.kv_pool.row_blocks(row, covered))
        if self.telemetry is not None:
            self._prefix_gauges(self.telemetry)

    def _prefix_gauges(self, tele) -> None:
        occ = self.kv_pool.occupancy()
        tele.metrics.gauge("kv_pool_used_blocks").set(occ["used_blocks"])
        tele.metrics.gauge("kv_pool_free_blocks").set(occ["free_blocks"])
        if self.prefix_tree is not None:
            tele.metrics.gauge("prefix_tree_nodes").set(
                self.prefix_tree.n_nodes)

    def sample_tokens(self, logits, greedy: bool, temperature: float = 1.0):
        """Next-token choice from [B, V] logits: argmax, or seeded temperature
        sampling from the engine's PRNG stream (greedy=False)."""
        with host_span("serve.sample"):
            if greedy:
                return np.asarray(jnp.argmax(logits, axis=-1))
            assert temperature > 0.0, "temperature must be > 0 for sampling"
            self._key, sub = jax.random.split(self._key)
            scaled = logits.astype(jnp.float32) / temperature
            return np.asarray(jax.random.categorical(sub, scaled, axis=-1))

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 greedy: bool = True, temperature: float = 1.0,
                 row_mask: Optional[np.ndarray] = None) -> np.ndarray:
        """Teacher-free batched generation. prompts [B, P] int32.
        ``row_mask`` [B] marks real rows — StaticBatcher pad rows (rid=-1
        copies) are stepped for shape but excluded from throughput/transfer
        accounting. greedy=False samples with ``temperature`` from the
        engine's seeded PRNG."""
        b, p_len = prompts.shape
        total = p_len + max_new_tokens
        caches = self.init_caches(b, total)
        out = np.zeros((b, total), np.int64)
        out[:, :p_len] = prompts
        tok = jnp.asarray(prompts[:, 0], jnp.int32)
        logits = None
        for pos in range(total - 1):
            logits, caches = self.step(tok, caches, pos, active=row_mask)
            if pos + 1 < p_len:
                tok = jnp.asarray(prompts[:, pos + 1], jnp.int32)
            else:
                nxt = self.sample_tokens(logits, greedy, temperature)
                out[:, pos + 1] = nxt
                tok = jnp.asarray(nxt, jnp.int32)
        return out

    def teacher_forced_nll(self, tokens: np.ndarray,
                           row_mask: Optional[np.ndarray] = None) -> float:
        """Mean next-token NLL under the engine's policy (accuracy metric).
        ``row_mask`` [B] excludes pad rows from the mean."""
        b, s = tokens.shape
        mask = (np.ones(b, bool) if row_mask is None
                else np.asarray(row_mask, bool))
        caches = self.init_caches(b, s)
        nll, n = 0.0, 0
        for pos in range(s - 1):
            tok = jnp.asarray(tokens[:, pos], jnp.int32)
            logits, caches = self.step(tok, caches, pos, active=mask)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            tgt = tokens[:, pos + 1]
            row_nll = -np.take_along_axis(np.asarray(logp), tgt[:, None], 1)[:, 0]
            nll += float(row_nll[mask].sum())
            n += int(mask.sum())
        return nll / n

    def stall_breakdown(self) -> dict:
        """Single source of truth: the ledger's event-timeline attribution.
        The peer key appears only on a mesh (D>1) so single-device
        summaries stay bit-identical to the pre-mesh engine."""
        d = {
            "demand_stall_s": self.ledger.demand_stall_s,
            "late_prefetch_stall_s": self.ledger.late_prefetch_stall_s,
            "overlapped_s": self.ledger.overlapped_s,
        }
        if self.n_devices > 1:
            d["peer_stall_s"] = self.ledger.peer_stall_s
        return d

    def summary(self) -> dict:
        s = {
            "policy": dataclasses.asdict(self.policy),
            "cache_rate": self.cache.capacity / self.cfg.moe.num_experts,
            "stats": dataclasses.asdict(self.stats),
            "tokens_per_s": self.stats.tokens_per_s,
            "stall_breakdown": self.stall_breakdown(),
            "ledger": self.ledger.summary(),
        }
        if self.tier is not None:
            # only present with a tier attached: with quant_tier off the
            # summary stays bit-identical to the pre-tier engine
            s["tier"] = self.tier.summary()
        if self._cost_mode:
            # only present under the unified cost policy: precedence-mode
            # summaries stay bit-identical to the pre-cost engine
            s["cost_policy"] = {
                "stall_per_quality": self.policy.stall_per_quality,
                "drop_loss": self.policy.drop_loss,
                "n_miss_drop": self.stats.n_miss_drop,
                "n_upgrade_issued": self.stats.n_upgrade_issued,
                "upgrade_degraded": self.upgrade_degraded,
                "prefetch_worthwhile_last": self.last_prefetch_worthwhile,
            }
        if self.n_devices > 1:
            # only present on a mesh: n_devices=1 summaries stay
            # bit-identical to the pre-mesh engine
            total = self.stats.n_hit + self.stats.n_sub + \
                self.stats.n_miss_fetch + self._n_peer_borrow
            s["mesh"] = {
                "n_devices": self.n_devices,
                "ici_bw": self._ici_bw,
                "peer_borrow": self.peer_borrow,
                "n_peer_borrow": self._n_peer_borrow,
                "peer_share": (self._n_peer_borrow / total if total else 0.0),
                "peer_stall_s": self.ledger.peer_stall_s,
                "links": [self.peer_links[d].utilization()
                          for d in sorted(self.peer_links)],
            }
        if self._paged:
            # only present in paged mode: paged_kv=off summaries stay
            # bit-identical to the pre-paged engine
            s["prefix"] = {
                "paged_kv": True,
                "kv_block": self._kv_block,
                "prefix_cache": self._prefix_on,
                "pool": (self.kv_pool.occupancy()
                         if self.kv_pool is not None else None),
                "hits": self._prefix_hits,
                "hit_tokens": self._prefix_hit_tokens,
                "novel_tokens": self._prefix_novel_tokens,
            }
            if self.prefix_tree is not None:
                s["prefix"]["tree"] = self.prefix_tree.stats()
        if self.placement is not None:
            # only present with a placement controller attached:
            # placement=None summaries stay bit-identical to the
            # pre-placement engine
            s["placement"] = self.placement.summary()
        if self.telemetry is not None:
            # only present with a telemetry bundle attached: telemetry=off
            # summaries stay bit-identical to the pre-telemetry engine
            s["telemetry"] = self.telemetry.summary()
        return s
