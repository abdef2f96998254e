"""Continuous-batching serving engine over a fixed pool of KV-cache slots.

The paper's serving contribution (Sec. III-A) is iteration-based
scheduling: ONE model iteration serves every active user, so each layer's
weights are streamed once and reused batch-wide (weight temporal locality
— in the LLC on the paper's machine, in VMEM on TPU).  This engine makes
that iteration the scheduling quantum, Orca/vLLM-style:

  * ``init_cache`` allocates a fixed ``[max_batch, cache_len]`` KV pool
    once; requests are prefilled *into* free slots
    (``lm.prefill_into_slot``) and retired per-slot, so the batch never
    reshapes and the decode step compiles exactly once;
  * every ``step()`` admits waiting requests into free slots (FIFO, with
    a Sarathi-style cap on new prefill tokens per iteration), appends
    each active request's pending token, retires slots on EOS/max-tokens,
    and runs one masked decode iteration for all remaining slots;
  * a request arriving mid-decode joins the very next iteration instead
    of waiting for the cohort to drain — the weight-reuse window the
    paper optimizes is never wasted on a partially idle batch.

Runs the SAIL path: weights SAIL-quantized (QTensor), KV cache optionally
int8.  Precision comes from a ``repro.planning.PlanSpec``
(``EngineConfig.plan`` / ``slo``; the engine always reports one —
``stats()["plan_hash"]``); with ``tap_capacity > 0`` an ``ActivationTap``
captures per-layer decode inputs and ``Engine.replan()`` recalibrates
measured PRT discounts from live traffic, hot-swapping the requantized
weights under the running KV pool.

The loop closes itself: ``EngineConfig.controller`` attaches a
``repro.serving.control.SloController`` that runs inside ``step()`` —
admissions are shed (deferred) and the decode batch shrunk to the
largest occupancy at which the plan's modeled iteration time still
meets the SLO, and ``replan()`` fires automatically when measured-vs-
modeled tokens/s drift leaves the deadband (with hysteresis, escalating
to a full re-solve only when the tapped PRT hit rate moved).  Every
engine — controller or not — reports ``measured_tps`` / ``planned_tps``
/ ``drift`` in ``stats()`` so a stale calibration is visible.  The
engine is synchronous and deterministic; streaming consumers hook
``submit(..., on_token=...)``.

``EngineConfig.kv_block_size`` swaps the slot pool for a *paged* block
pool (``lm.init_paged_cache`` + ``repro.serving.block_pool``): requests
hold per-lane block tables into a shared pool instead of a worst-case
``cache_len`` row, identical prompt prefixes share blocks copy-on-write,
and admission is gated on free blocks with recompute-style preemption
under pressure.  Paged-mode invariants:

- the pool reserves one extra physical *trash* block; every masked or
  retired lane's table entries point at it, so dead scatter writes never
  corrupt a live block;
- a paged lane never wraps: ``submit`` rejects requests whose
  prompt+max_new exceed the table capacity, which is what lets the paged
  and ring attention paths share one validity formula;
- shared prefix blocks are never rewritten — prefill scatters of a
  sharing request are trash-redirected over the shared span, and the
  first divergent write triggers copy-on-write — so sharers always
  attend to bit-identical KV;
- preemption is recompute-style and lossless: the victim's blocks are
  freed, its committed tokens become the resume prompt (re-prefilled on
  re-admission, front of the FIFO), and under greedy sampling the
  resumed request produces exactly the tokens it would have unpreempted.

KV precision is plan-driven: a ``PlanSpec.kv_bits`` of 8/32 (or
``"auto"``, resolved by the Planner's per-layer KV probe) overrides
``EngineConfig.quant_kv``; the pool's dtype is fixed at construction and
``apply_plan`` warns rather than reallocating mid-serve.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.scheduler import DECODE, IterationScheduler, Request
from repro.kernels.decode_attn.kernel import live_blocks
from repro.models import lm
from repro.models.common import ModelConfig
from repro.models.layers import layer_windows
from repro.models.sail_linear import QuantPolicy, quantize_params
from repro.serving.trace import SpanTable


@functools.partial(jax.jit, static_argnames=("seed", "temperature"))
def _sample_rows_jit(logits, uids, indices, seed: int, temperature: float):
    """One categorical draw per row under a per-row key folded from
    (engine seed, request uid, per-request sample index) — sampling that
    depends only on WHICH token of WHICH request is being drawn, never on
    global iteration count or batch composition."""
    base = jax.random.PRNGKey(seed)

    def draw(uid, idx, row):
        key = jax.random.fold_in(jax.random.fold_in(base, uid), idx)
        return jax.random.categorical(key, row / temperature)

    return jax.vmap(draw)(uids, indices, logits)


@dataclasses.dataclass
class EngineConfig:
    batch_size: int = 8            # KV-pool slots (paper: 8 balances the pipe)
    cache_len: int = 4096
    quantize: bool = True
    ql: int = 4
    group_size: int = 128
    quant_kv: bool = True
    min_size: int = 1024           # quantize tensors >= this many elements
    # Precision plan: a repro.planning.PlanSpec (possibly solved / loaded
    # from plan.json), a grammar string ("uniform:<b>[a<ab>]",
    # "rules:<regex>=<b>[a<ab>],...", "auto:q<b>[a<ab>][,prt=...]
    # [,maxseg=<n>][,slo=<tps>]", "auto:<f>bpw"), or a PlanSpec JSON
    # dict.  Unsolved auto plans run the Planner at engine construction.
    plan: Any = None
    # target decode tokens/s at ``batch_size`` — makes an auto ``plan``
    # an SLO solve (cycle AND DRAM-byte budgets derived from the target);
    # set without ``plan`` it implies "auto:q<ql>a8,prt=measured".
    slo: Optional[float] = None
    # >0 attaches a repro.planning.ActivationTap of that row capacity:
    # every ``tap_every``-th decode iteration's per-layer block inputs
    # are captured for online PRT recalibration (Engine.replan).
    tap_capacity: int = 0
    tap_every: int = 1
    # keep the raw f32 weights resident so apply_plan/replan can
    # requantize mid-serve.  None (default) retains them exactly when a
    # tap is attached; set True for tap-less hot-swapping, False to
    # reclaim the memory even with a tap (replan then raises).
    retain_raw: Optional[bool] = None
    # autonomous SLO control loop: True (defaults), a knob dict, or a
    # repro.serving.control.ControllerConfig.  The controller sheds /
    # shrinks occupancy against the SLO and gates replans on measured-
    # vs-modeled drift.
    controller: Any = None
    eos_token: int = -1            # -1: never stop early
    temperature: float = 0.0       # 0 = greedy
    # PRNG root for temperature>0 sampling.  Tokens are drawn with a key
    # folded from (seed, request uid, per-request sample index), so a
    # request's sampled sequence is invariant to batch composition,
    # sheds, preemption/resume, and slot-vs-paged pool layout.
    seed: int = 0
    prefill_budget: Optional[int] = None  # new prefill tokens per iteration
    prompt_bucket: int = 16        # prompts padded to a multiple (compile reuse)
    # Paged KV pool (attention families).  Setting a block size
    # replaces the fixed [batch, cache_len] slot pool with a
    # shared pool of fixed-size blocks managed by a
    # repro.serving.block_pool.BlockSpaceManager: per-request block
    # tables, copy-on-write prefix sharing, block-gated admission, and
    # recompute-style preemption under pressure.
    kv_block_size: Optional[int] = None   # tokens per block; None = slot pool
    # pool sizing (first match wins): explicit block count, a byte budget
    # priced via planning.kv_pool_blocks, else batch_size slot-equivalents
    kv_pool_blocks: Optional[int] = None
    kv_budget_bytes: Optional[int] = None
    share_prefix: bool = True      # COW-share identical prompt prefixes
    preempt: bool = True           # evict newest request when the pool runs dry
    # Tensor-parallel serving (repro.serving.distributed): shard the
    # quantized weight tree over ``tp`` model-parallel shards and run
    # decode/prefill under shard_map.  A plan carrying a concrete
    # ``tp=``/``wire=`` dimension overrides these knobs.  tp > 1
    # requires no tap, no draft, and tp visible devices (CPU:
    # XLA_FLAGS=--xla_force_host_platform_device_count=N before
    # importing jax).
    tp: int = 1
    wire: int = 32                 # all-reduce bits: 32 exact, 8 compressed


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]
    latency_s: float
    ttft_s: float = 0.0            # submit -> first token available


class Engine:
    def __init__(self, params, cfg: ModelConfig, ecfg: EngineConfig):
        from repro import planning
        self.cfg = cfg
        self.ecfg = ecfg
        self.quant_policy: Optional[QuantPolicy] = None
        self.plan: Optional[planning.PlanSpec] = None
        self.plan_report = None
        self.replan_count = 0
        self.prt_hit_rate: Optional[float] = None
        self.tap: Optional[planning.ActivationTap] = None
        self.controller = None
        self.slo: Optional[planning.Slo] = None
        self._raw_params = None
        # plan pricing state (units + fixed DRAM bytes captured while the
        # raw tree is in hand; iteration-seconds memoized per occupancy)
        self._plan_units = None
        self._plan_fixed_bytes = 0
        self._iter_cache: Dict[Any, float] = {}
        # host spans of step() (repro.serving.trace); decode_seconds is
        # the engine.decode span's total: the measured decode throughput
        # behind stats()["measured_tps"] / drift
        self.spans = SpanTable()
        self.decode_seconds = 0.0
        self._decode_tokens = 0
        # submit -> first prefill, summed over first admissions
        self.queue_wait_seconds = 0.0
        self.queue_admissions = 0
        # modeled seconds of the SAME iterations at their true occupancy
        # — the occupancy-matched reference side of stats()["drift"]
        self.modeled_seconds = 0.0
        if ecfg.plan is not None and not ecfg.quantize:
            raise ValueError("a precision plan requires quantize=True")
        if ecfg.tap_capacity > 0:
            self.tap = planning.ActivationTap(ecfg.tap_capacity,
                                              ecfg.tap_every)
        if ecfg.quantize:
            base = self._base_policy()
            plan_in = ecfg.plan
            if plan_in is None and ecfg.slo is not None:
                # bare --slo: joint SLO solve anchored at the engine's ql
                plan_in = planning.PlanSpec(
                    mode="auto", weight_bits=ecfg.ql, act_bits=8,
                    prt="measured", quant_kv=ecfg.quant_kv)
            if plan_in is not None:
                plan_obj = planning.as_plan(plan_in)
                # SLOs are quoted at this engine's decode batch, whether
                # they arrive via EngineConfig.slo or the plan itself
                target = (ecfg.slo if ecfg.slo is not None
                          else plan_obj.target_tps)
                slo = (planning.Slo(target, batch=ecfg.batch_size)
                       if target is not None else None)
                self.slo = slo
                result = planning.resolve_plan(
                    plan_obj, params, cfg, base=base, slo=slo,
                    compute_cost=plan_obj.solved and slo is not None)
                if (slo is not None and result.cost is not None
                        and result.cost.tokens_per_second
                        < slo.target_tps * (1 - 1e-9)):
                    # an SLO the served plan cannot meet — whether it
                    # arrived pre-solved or the solver just found the
                    # budgets infeasible — must never pass silently
                    feas = getattr(result.report, "feasible", True)
                    warnings.warn(
                        f"plan {result.spec.spec_hash} models "
                        f"{result.cost.tokens_per_second:.1f} tok/s at "
                        f"batch {slo.batch}, below the requested SLO of "
                        f"{slo.target_tps:.1f}"
                        + ("" if feas else " (solver budgets infeasible "
                           "even at minimum precision)")
                        + "; lower the target, raise the batch, or "
                        "re-solve (Engine.replan(resolve=True))",
                        UserWarning, stacklevel=2)
                policy = result.policy
                self.plan = result.spec
                self.plan_report = result.report
            else:
                policy = base
                self.plan = planning.PlanSpec.from_policy(
                    policy, quant_kv=ecfg.quant_kv)
            self.quant_policy = policy
            # price the plan while the raw tree is still in hand — the
            # cost-model units and fixed DRAM bytes behind planned_tps()
            # and the controller's occupancy cap
            self._plan_units = planning.policy_units(params, policy)
            self._plan_fixed_bytes = planning.unquantized_bytes(params,
                                                               policy)
            retain = (ecfg.retain_raw if ecfg.retain_raw is not None
                      else self.tap is not None)
            if retain:
                # a 7B-class model keeps ~28 GB of f32 resident here —
                # only pay it when hot-swap requantization is wanted
                self._raw_params = params
            self.params, b0, b1 = quantize_params(params, policy)
            self.compression = b0 / max(b1, 1)
        else:
            self.params, self.compression = params, 1.0
        # KV precision: the plan's kv_bits dimension (when concrete — the
        # Planner resolves "auto" before the spec reaches here) overrides
        # the quant_kv flag; the pool dtype is fixed from here on.
        kvb = (self.plan.kv_bits
               if self.plan is not None
               and isinstance(self.plan.kv_bits, int) else None)
        self.kv_bits = kvb if kvb is not None else (8 if ecfg.quant_kv
                                                    else 32)
        self._quant_kv = self.kv_bits == 8
        # Tensor-parallel serving: a concrete plan tp=/wire= wins over
        # the EngineConfig knobs (the plan is the precision contract —
        # the Planner may have spent shards instead of bits to meet the
        # SLO).  The mesh is fixed for the engine's lifetime.
        plan_tp = (self.plan.tp if self.plan is not None
                   and isinstance(self.plan.tp, int) else None)
        plan_wire = (self.plan.wire if self.plan is not None
                     and self.plan.wire is not None else None)
        self.tp = plan_tp if plan_tp is not None else int(ecfg.tp)
        self.wire_bits = (plan_wire if plan_wire is not None
                          else int(ecfg.wire))
        self.tp_serving = None
        if self.tp > 1:
            from repro.serving.distributed import TPServing
            if self.tap is not None:
                raise ValueError(
                    "tensor-parallel serving and an ActivationTap cannot "
                    "coexist — the shard_map decode body has no "
                    "capture path")
            self.tp_serving = TPServing(cfg, self.tp, self.wire_bits)
            self.params = self.tp_serving.shard_params(self.params)
        self.sched = IterationScheduler(max_batch=ecfg.batch_size,
                                        prefill_budget=ecfg.prefill_budget)
        self._uid = 0
        self.completions: Dict[int, Completion] = {}
        self._gen: Dict[int, List[int]] = {}
        self._t0: Dict[int, float] = {}
        self._ttft: Dict[int, float] = {}
        self._on_token: Dict[int, Callable[[int, int], None]] = {}
        self.events: Dict[int, Dict[str, int]] = {}   # per-uid iteration marks
        self.iterations = 0            # total model iterations (prefill+decode)
        self.prefill_iterations = 0
        # (batch, padded length) of every prefill run: each first one
        # traces and compiles (or loads) its program
        self._prefill_shapes: set = set()
        self.decode_iterations = 0
        # decode steps that took the KV pool's buffers over (donation);
        # equal to the single-device decode iterations unless the
        # backend declined it
        self.decode_pool_donated = 0
        self.prefill_tokens = 0
        # routed MoE decode steps, summed over (layer, step) pairs: the
        # (token, expert) pairs of active lanes, the experts that got at
        # least one, and the largest expert's tokens over the mean per
        # touched expert (stats()["moe"])
        self.moe_counts: Optional[Dict[str, float]] = (
            dict.fromkeys(("layer_steps", "routed_pairs", "experts_touched",
                           "imbalance_sum"), 0)
            if cfg.family == "moe" else None)
        # decode attention over the paged pool (stats()["decode_attn"]):
        # steps that ran the Pallas kernel, the live blocks the steps
        # attended (what the kernel reads), summed over lanes and layers,
        # and the table blocks a gather of every lane's table reads
        self.attn_counts: Optional[Dict[str, int]] = None
        # a lane keeps its window where every layer is windowed; a model
        # with full-attention layers keeps the whole context
        clen = ecfg.cache_len if cfg.lane_window is None \
            else min(ecfg.cache_len, cfg.lane_window)
        self._clen = clen
        self._orig_plen: Dict[int, int] = {}
        self.peak_active = 0
        self.paged = ecfg.kv_block_size is not None
        self.block_mgr = None
        if self.paged:
            if cfg.family in ("ssm", "hybrid"):
                raise ValueError(
                    f"paged KV is attention-only; family={cfg.family!r} "
                    "keeps recurrent state, not a block pool")
        elif cfg.layer_types is not None:
            raise ValueError(
                "a model whose layers differ in kind (layer_types) serves "
                "from the paged pool (kv_block_size): a ring lane would "
                "wrap over positions its full-attention layers still read")
        self._cur = np.zeros((ecfg.batch_size,), np.int32)
        if self.paged:
            from repro.serving.block_pool import BlockSpaceManager
            bs = int(ecfg.kv_block_size)
            self._mbs = -(-clen // bs)     # table columns per lane
            nblocks = self._paged_pool_blocks(bs)
            self.block_mgr = BlockSpaceManager(
                nblocks, bs, share_prefix=ecfg.share_prefix)
            # one extra physical block: the trash block every dead
            # table entry / masked lane points at
            self._trash = nblocks
            self.cache = lm.init_paged_cache(
                self.params, cfg, ecfg.batch_size, nblocks + 1, bs,
                self._quant_kv)
            self._tables_np = np.full(
                (ecfg.batch_size, self._mbs), self._trash, np.int32)
            self._len_np = np.zeros((ecfg.batch_size,), np.int64)
            self.attn_counts = dict.fromkeys(
                ("kernel_steps", "blocks_read", "blocks_in_tables"), 0)
            self._windows = layer_windows(cfg, self._mbs * bs)
        else:
            self.cache = lm.init_cache(self.params, cfg,
                                       ecfg.batch_size, clen,
                                       self._quant_kv)
        if self.tp_serving is not None:
            self.cache = self.tp_serving.shard_cache(self.cache)
        # ``blocks._paged_int8_attend``'s dispatch: the kernel serves
        # an int8 pool on a TPU outside tensor parallelism
        pool = jax.tree_util.tree_leaves(self.cache["layers"])[0]
        self._attn_kernel = (self.paged and self._quant_kv
                             and self.tp_serving is None
                             and next(iter(pool.devices())).platform
                             == "tpu")
        # self-speculative decoding: the plan's draft= sub-spec requants
        # the SAME raw tree aggressively; the draft tree stays resident
        # alongside the conservative one for the engine's lifetime
        self.spec_decoder = None
        draft = self.plan.draft if self.plan is not None else None
        if draft is not None:
            from repro import planning
            from repro.serving.speculative import SpeculativeDecoder
            if not isinstance(draft, planning.DraftSpec):
                raise ValueError(
                    "plan.draft is unresolved ('auto') — solve the plan "
                    "(Planner / resolve_plan with an SLO) before serving")
            if self.tp_serving is not None:
                raise ValueError(
                    "speculative decoding is not supported under "
                    "tensor-parallel serving — the draft/verify round "
                    "runs outside the shard_map entry points")
            if cfg.family in ("ssm", "hybrid"):
                raise ValueError(
                    "speculative decoding needs a pure-attention family "
                    "— recurrent state cannot roll back to the accepted "
                    f"frontier (family={cfg.family!r})")
            if cfg.layer_types is not None:
                raise ValueError(
                    "speculative decoding is not supported for a model "
                    "whose layers differ in kind (layer_types): its "
                    "draft/verify round is untested there")
            if cfg.pos == "sinusoidal":
                raise ValueError(
                    "speculative decoding does not support sinusoidal "
                    "positions (multi-token verify embeds at "
                    "pos_offset=0, like decode_step)")
            if self.tap is not None:
                raise ValueError(
                    "speculative decoding and an ActivationTap cannot "
                    "coexist — the round bypasses the tapped decode step")
            self.spec_decoder = SpeculativeDecoder(
                params, cfg, draft, self.quant_policy)
        if ecfg.controller:
            from repro.serving.control import ControllerConfig, SloController
            self.controller = SloController(
                ControllerConfig.coerce(ecfg.controller),
                slo=self.slo,
                iter_seconds=(self._modeled_iter_seconds
                              if self._plan_units is not None else None),
                planned_tps=self.planned_tps(),
                plan_hit_rate=self.prt_hit_rate,
                tokens_per_iter=(self.spec_decoder.expected_tokens()
                                 if self.spec_decoder is not None
                                 else 1.0))

    # --- client API -------------------------------------------------------
    def submit(self, prompt: List[int], max_new_tokens: int,
               on_token: Optional[Callable[[int, int], None]] = None) -> int:
        """Queue a request; returns its uid.

        ``on_token(uid, token)`` (optional) is invoked as each generated
        token is committed — the streaming hook.
        """
        # speculative rounds write k extra candidate positions past the
        # committed frontier; lanes must never wrap over them
        spec_extra = (self.spec_decoder.k + 1
                      if self.spec_decoder is not None else 0)
        if self.paged:
            need = len(prompt) + max_new_tokens + spec_extra
            room = self._mbs * int(self.ecfg.kv_block_size)
            if need > room:
                raise ValueError(
                    f"request needs {need} KV positions but a paged lane "
                    f"holds {room} ({self._mbs} blocks x "
                    f"{self.ecfg.kv_block_size}) — paged lanes never "
                    "wrap; raise cache_len or shorten the request")
        elif spec_extra:
            need = len(prompt) + max_new_tokens + spec_extra
            if need > self._clen:
                raise ValueError(
                    f"request needs {need} KV positions (prompt + "
                    f"max_new + draft lookahead) but the ring holds "
                    f"{self._clen} — speculative rollback forbids ring "
                    "wrap; raise cache_len or shorten the request")
        self._uid += 1
        now = time.perf_counter()
        self.sched.submit(Request(uid=self._uid, prompt_len=len(prompt),
                                  max_new_tokens=max_new_tokens,
                                  arrived_at=now))
        self._orig_plen[self._uid] = len(prompt)
        self._gen[self._uid] = list(prompt)
        self._t0[self._uid] = now
        if on_token is not None:
            self._on_token[self._uid] = on_token
        return self._uid

    def step(self) -> bool:
        """One engine iteration: admit+prefill into free slots, commit each
        active slot's pending token (retiring on EOS/max-tokens), then run
        one masked decode for every remaining slot.  Returns True while
        work remains."""
        with self.spans.span("engine.step"):
            return self._step()

    def _step(self) -> bool:
        ctl = self.controller
        with self.spans.span("engine.admit"):
            cap = None
            if ctl is not None and ctl.cfg.shed:
                free_cap = self._block_free_cap() if self.paged else None
                cap = ctl.batch_cap(self.ecfg.batch_size,
                                    free_cap=free_cap)
            admitted = self.sched.schedule(
                max_active=cap,
                can_admit=self._try_allocate if self.paged else None)
            if (cap is not None and self.sched.waiting
                    and self.sched.free_slots
                    and self.sched.active >= cap):
                # free slots exist but the SLO cap is binding: these
                # admissions are shed (deferred in FIFO), not dropped
                ctl.record_shed()
        if admitted:
            # group same-padded-length admissions into ONE prefill pass:
            # a K-request burst streams each layer's weights once, not K
            # times (the paper's weight temporal locality, applied to
            # prefill as well as decode)
            groups: Dict[int, List[Request]] = {}
            for req in admitted:
                groups.setdefault(self._padded_len(req), []).append(req)
            for padded, reqs in groups.items():
                with self.spans.span(
                        "engine.prefill",
                        uids=" ".join(str(r.uid) for r in reqs),
                        padded=padded,
                        tokens=sum(r.prompt_len for r in reqs)):
                    self._prefill_slots(reqs, padded)
        # commit pending tokens, retire finished slots
        with self.spans.span("engine.commit"):
            for req in list(self.sched.running):
                finished = req.generated >= req.max_new_tokens  # max_new == 0
                if not finished:
                    tok = int(self._cur[req.slot])
                    self._gen[req.uid].append(tok)
                    req.generated += 1
                    cb = self._on_token.get(req.uid)
                    if cb is not None:
                        cb(req.uid, tok)
                    finished = (tok == self.ecfg.eos_token or
                                req.generated >= req.max_new_tokens)
                if finished:
                    self._finish(req)
        # one masked decode iteration serves every still-active slot
        active = list(self.sched.running)
        spec = self.spec_decoder
        if self.paged and active:
            # every active lane appends one KV position this iteration
            # (k+1 for a speculative round: k drafts re-written by
            # verify, plus the bonus slot): grant block slots first (COW
            # off shared blocks, preempt the newest when the pool runs
            # dry)
            with self.spans.span("engine.kv_grant"):
                active = self._ensure_append_blocks(
                    active, n=(spec.k + 1) if spec is not None else 1)
        self.peak_active = max(self.peak_active, len(active))
        if spec is not None and active:
            self._speculative_round(active, ctl)
            return not self.sched.idle()
        if active:
            mask = np.zeros((self.ecfg.batch_size,), bool)
            for req in active:
                mask[req.slot] = True
            capture = (self.tap is not None
                       and self.tap.should_capture(self.decode_iterations))
            with self.spans.span("engine.decode", lanes=len(active)) as sp:
                if self.tp_serving is not None:
                    out = self.tp_serving.decode_step(
                        self.params, jnp.asarray(self._cur[:, None]),
                        self.cache, quant_kv=self._quant_kv,
                        active_mask=jnp.asarray(mask),
                        block_tables=(jnp.asarray(self._tables_np)
                                      if self.paged else None))
                else:
                    old_pool = jax.tree_util.tree_leaves(
                        self.cache["layers"])[0]
                    out = lm.donating(lm.decode_step)(
                        self.params, jnp.asarray(self._cur[:, None]),
                        self.cache, cfg=self.cfg, quant_kv=self._quant_kv,
                        active_mask=jnp.asarray(mask),
                        capture_layer_inputs=capture,
                        block_tables=(jnp.asarray(self._tables_np)
                                      if self.paged else None),
                        count_experts=self.moe_counts is not None)
                    self.decode_pool_donated += int(old_pool.is_deleted())
                logits, self.cache, *rest = out
                if capture:
                    self.tap.observe(rest.pop(0), mask)
                routed = rest[0] if rest else None
                self.iterations += 1
                self.decode_iterations += 1
                uids = np.zeros((self.ecfg.batch_size,), np.uint32)
                sidx = np.zeros((self.ecfg.batch_size,), np.uint32)
                for req in active:
                    uids[req.slot] = req.uid
                    sidx[req.slot] = self._sample_index(req.uid)
                if routed is None:
                    nxt = self._sample(logits, uids, sidx)
                else:
                    nxt, routed = self._sample(logits, uids, sidx,
                                               also=routed)
                    self._count_experts(routed)
            # _sample's np.asarray blocks on the device, so the span
            # covers the whole iteration (incl. any tap-capture sync)
            dt = sp.seconds
            self.decode_seconds += dt
            self._decode_tokens += len(active)
            exp = self._modeled_iter_seconds(len(active))
            if exp is not None:
                self.modeled_seconds += exp
            if self.paged:
                self._count_attention(mask)
                self._len_np[mask] += 1
            for req in active:
                self._cur[req.slot] = nxt[req.slot]
                self.events[req.uid].setdefault("first_decode_iteration",
                                                self.iterations)
            if ctl is not None and ctl.observe(len(active), dt,
                                              self.decode_iterations):
                self._controller_step()
        return not self.sched.idle()

    def run(self) -> List[Completion]:
        """Serve until all submitted requests finish (the drain loop)."""
        while self.step():
            pass
        return list(self.completions.values())

    # --- paged-pool internals ---------------------------------------------
    def _paged_pool_blocks(self, bs: int) -> int:
        """Pool size in blocks (excluding the trash block): explicit
        count, else a byte budget priced by planning.kv_pool_blocks, else
        batch_size worst-case slot-equivalents.  Clamped so one maximal
        request always fits."""
        from repro import planning
        ecfg = self.ecfg
        if ecfg.kv_pool_blocks is not None:
            n = int(ecfg.kv_pool_blocks)
        elif ecfg.kv_budget_bytes is not None:
            n = planning.kv_pool_blocks(
                ecfg.kv_budget_bytes, bs, lm.n_scan_blocks(self.cfg),
                self.cfg.n_kv, self.cfg.head_dim, self.kv_bits)
        else:
            n = ecfg.batch_size * self._mbs
        return max(n, self._mbs)

    def _block_free_cap(self) -> int:
        """How many requests the block pool could hold right now: the
        active set plus a non-mutating greedy estimate of admissible
        waiters (prefix sharing included) — the memory bound fed to
        SloController.batch_cap."""
        prompts = [tuple(self._gen[r.uid][:r.prompt_len])
                   for r in self.sched.waiting]
        return self.sched.active + self.block_mgr.admission_cap(prompts)

    def _try_allocate(self, req: Request) -> bool:
        """Scheduler admission gate: allocate the request's prefill
        blocks (sharing any registered prefix).  Called only when
        admission is otherwise certain, so allocating here is safe; a
        False return stops this iteration's admissions (FIFO holds)."""
        prompt = tuple(self._gen[req.uid][:req.prompt_len])
        if not self.block_mgr.can_allocate(prompt):
            return False
        self.block_mgr.allocate(req.uid, prompt)
        return True

    def _ensure_append_blocks(self, active: List[Request],
                              n: int = 1) -> List[Request]:
        """Grant every active lane physical slots for this iteration's
        KV writes: in-place into its frontier block, a fresh block at a
        block boundary, or a copy-on-write split off a shared block.
        ``n`` > 1 (speculative rounds) grants a RANGE of consecutive
        positions up front — the draft writes k of them and verify all
        n.  When the pool runs dry the newest arrival is preempted
        (recompute-style) and the grant retried.  Returns the requests
        that still decode this iteration; COW copies are applied to the
        device pool in one batched scatter."""
        bs = int(self.ecfg.kv_block_size)
        cows: List[tuple] = []
        preempted: set = set()
        granted: List[Request] = []
        for req in active:
            if req.uid in preempted:
                continue
            for j in range(n):
                if req.uid in preempted:
                    break
                pos = int(self._len_np[req.slot]) + j
                while True:
                    res = self.block_mgr.append_slot(req.uid, pos)
                    if res is not None:
                        kind, src, dst = res
                        if kind in ("alloc", "cow"):
                            self._tables_np[req.slot, pos // bs] = dst
                        if kind == "cow":
                            cows.append((src, dst))
                        break
                    victim = self._pick_victim()
                    if victim is None:
                        raise MemoryError(
                            "KV block pool exhausted and preemption is "
                            "disabled (EngineConfig.preempt=False) — grow "
                            "kv_pool_blocks/kv_budget_bytes")
                    self._preempt(victim)
                    preempted.add(victim.uid)
                    if victim is req:
                        break
            if req.uid not in preempted:
                granted.append(req)
        if cows:
            src = jnp.asarray(np.asarray([s for s, _ in cows], np.int32))
            dst = jnp.asarray(np.asarray([d for _, d in cows], np.int32))
            self.cache["layers"] = lm._copy_blocks_jit(
                self.cache["layers"], src, dst)
        return [r for r in granted if r.uid not in preempted]

    def _pick_victim(self) -> Optional[Request]:
        """Preemption victim: the newest running request (FIFO priority —
        the oldest work keeps its blocks)."""
        if not self.ecfg.preempt:
            return None
        for cand in reversed(self.sched.running):
            if self.block_mgr.has_table(cand.uid):
                return cand
        return None

    def _preempt(self, victim: Request) -> None:
        """Recompute-style eviction: free the victim's blocks, trash its
        table row, and requeue it at the FRONT of the waiting queue with
        its committed tokens as the resume prompt.  Under greedy
        sampling the resumed request regenerates the exact suffix it
        would have produced unpreempted."""
        uid, slot = victim.uid, victim.slot
        self.block_mgr.preempt(uid)
        self._tables_np[slot, :] = self._trash
        self._len_np[slot] = 0
        self.sched.preempt(uid)
        # resume prompt = original prompt + every committed token; the
        # re-prefill recomputes their KV and re-samples the pending token
        victim.prompt_len = len(self._gen[uid])
        ev = self.events.setdefault(uid, {})
        ev["preemptions"] = ev.get("preemptions", 0) + 1
        ev["preempted_iteration"] = self.iterations

    # --- step internals ---------------------------------------------------
    def _speculative_round(self, active: List[Request], ctl) -> None:
        """One self-speculative round: fused k-token draft under the
        aggressive tree, one batched (k+1)-token verify under the
        conservative tree, then commit-accepted / rollback-rejected (see
        ``repro.serving.speculative``).

        The accepted prefix is committed token by token with the same
        EOS/max-new checks as the top-of-step commit; the round's
        correction (first rejection) or bonus (all accepted) token
        becomes the new pending ``_cur``.  Rollback is one device write
        of per-lane lengths back to the accepted frontier — verify
        already overwrote every draft KV slot at conservative precision,
        and slots past the frontier are unreadable (held > position)
        until rewritten in order — plus a paged block-table truncation.
        """
        spec = self.spec_decoder
        k = spec.k
        bsz = self.ecfg.batch_size
        mask = np.zeros((bsz,), bool)
        uids = np.zeros((bsz,), np.uint32)
        sidx = np.zeros((bsz,), np.uint32)
        for req in active:
            mask[req.slot] = True
            uids[req.slot] = req.uid
            sidx[req.slot] = self._sample_index(req.uid)
        prev_len = np.asarray(self.cache["length"]).copy()
        amask = jnp.asarray(mask)
        tables = jnp.asarray(self._tables_np) if self.paged else None
        temp = self.ecfg.temperature
        with self.spans.span("engine.decode", lanes=len(active)) as sp:
            d_toks, d_logits, self.cache = lm.draft_tokens(
                spec.draft_params, jnp.asarray(self._cur[:, None]),
                self.cache, self.cfg, k, quant_kv=self._quant_kv,
                active_mask=amask, block_tables=tables, temperature=temp,
                seed=self.ecfg.seed, uids=jnp.asarray(uids),
                indices=jnp.asarray(sidx))
            draft_np = np.asarray(d_toks)
            # rewind: verify re-feeds the round from its first position
            self.cache["length"] = jnp.asarray(prev_len)
            vt = np.concatenate([self._cur[:, None], draft_np], axis=1)
            v_logits, self.cache = lm.verify_step(
                self.params, jnp.asarray(vt), self.cache, self.cfg,
                quant_kv=self._quant_kv, active_mask=amask,
                block_tables=tables)
            n_acc, nxt = spec.accept(
                draft_np, np.asarray(v_logits),
                np.asarray(d_logits) if temp > 0 else None,
                temperature=temp, seed=self.ecfg.seed, uids=uids,
                indices=sidx)
        # np.asarray above blocked on the device: dt is the whole round
        dt = sp.seconds
        self.iterations += 1
        self.decode_iterations += 1
        produced = 0
        # rule-level acceptance (draft quality): lanes that hit max_new or
        # EOS mid-prefix truncate the COMMIT, not the acceptance stat —
        # conflating them would bias assumed_acceptance() low and misprice
        # expected tokens/round for the controller
        accepted_total = int(n_acc[mask].sum())
        new_len = prev_len.astype(np.int64).copy()
        for req in active:
            s, uid = req.slot, req.uid
            self.events[uid].setdefault("first_decode_iteration",
                                        self.iterations)
            finished = False
            for j in range(int(n_acc[s])):
                tok = int(draft_np[s, j])
                self._gen[uid].append(tok)
                req.generated += 1
                produced += 1
                cb = self._on_token.get(uid)
                if cb is not None:
                    cb(uid, tok)
                if (tok == self.ecfg.eos_token
                        or req.generated >= req.max_new_tokens):
                    finished = True
                    break
            new_len[s] = len(self._gen[uid])
            if finished:
                self._finish(req)
                continue
            # correction (first rejection) or bonus (all accepted)
            self._cur[s] = int(nxt[s])
            produced += 1
            if self.paged:
                dropped = self.block_mgr.truncate(uid, int(new_len[s]))
                if dropped:
                    keep = len(self.block_mgr.table(uid))
                    self._tables_np[s, keep:keep + dropped] = self._trash
                self._len_np[s] = int(new_len[s])
        # one device write rolls every lane back to its accepted frontier
        self.cache["length"] = jnp.asarray(new_len.astype(np.int32))
        self.decode_seconds += dt
        self._decode_tokens += produced
        exp = self._modeled_iter_seconds(len(active))
        if exp is not None:
            self.modeled_seconds += exp
        spec.note_round(len(active), accepted_total)
        if ctl is not None and ctl.observe(len(active), dt,
                                           self.decode_iterations):
            self._controller_step()

    def _padded_len(self, req: Request) -> int:
        # recurrent families (ssm/hybrid) fold every input token into the
        # state, so right-padding would pollute it — prefill exact-length;
        # attention families bucket-pad for compile-cache reuse (causal
        # masking + the ring-cache validity window ignore the padding).
        bucket = 1 if self.cfg.family in ("ssm", "hybrid") \
            else max(1, self.ecfg.prompt_bucket)
        plen = req.prompt_len
        return max(min(-(-plen // bucket) * bucket,
                       max(self._clen, plen)), plen)

    def _prefill_slots(self, reqs: List[Request], padded: int) -> None:
        """One prefill pass admits a same-length group into its slots.

        Paged mode scatters the freshly computed KV through each
        request's block table instead of into a contiguous slot row;
        padding rows and shared-prefix rows are redirected to the trash
        block (shared blocks are append-only for sharers — the KV they
        attend to is the registrant's, bit-identical by construction)."""
        now = time.perf_counter()
        for req in reqs:
            if "admitted_iteration" not in self.events.get(req.uid, {}):
                # a first admission; a resume after preemption is none
                self.queue_wait_seconds += now - req.arrived_at
                self.queue_admissions += 1
        b = len(reqs)
        self._prefill_shapes.add((b, padded))
        toks = np.zeros((b, padded), np.int32)
        lengths = np.zeros((b,), np.int32)
        for i, req in enumerate(reqs):
            toks[i, :req.prompt_len] = self._gen[req.uid][:req.prompt_len]
            lengths[i] = req.prompt_len
        slots = np.asarray([req.slot for req in reqs], np.int32)
        if self.paged:
            bs = int(self.ecfg.kv_block_size)
            phys = np.full((b, padded), self._trash, np.int32)
            offs = np.tile(
                (np.arange(padded) % bs).astype(np.int32), (b, 1))
            for i, req in enumerate(reqs):
                table = self.block_mgr.table(req.uid)
                nsh = self.block_mgr.shared_prefix_blocks(req.uid)
                row = np.full((self._mbs,), self._trash, np.int32)
                row[:len(table)] = table
                self._tables_np[req.slot] = row
                for t in range(req.prompt_len):
                    j = t // bs
                    if j >= nsh:   # shared blocks keep the registrant's KV
                        phys[i, t] = table[j]
            if self.tp_serving is not None:
                logits, self.cache = self.tp_serving.prefill_into_blocks(
                    self.params, jnp.asarray(toks), self.cache, slots,
                    phys.ravel(), offs.ravel(),
                    quant_kv=self._quant_kv,
                    lengths=jnp.asarray(lengths))
            else:
                logits, self.cache = lm.prefill_into_blocks(
                    self.params, jnp.asarray(toks), self.cache, slots,
                    phys.ravel(), offs.ravel(), self.cfg,
                    quant_kv=self._quant_kv, lengths=jnp.asarray(lengths))
            for req in reqs:
                self._len_np[req.slot] = req.prompt_len
        elif self.tp_serving is not None:
            logits, self.cache = self.tp_serving.prefill_into_slot(
                self.params, jnp.asarray(toks), self.cache, slots,
                quant_kv=self._quant_kv, lengths=jnp.asarray(lengths))
        else:
            logits, self.cache = lm.prefill_into_slot(
                self.params, jnp.asarray(toks), self.cache, slots,
                self.cfg, quant_kv=self._quant_kv,
                lengths=jnp.asarray(lengths))
        self.iterations += 1
        self.prefill_iterations += 1
        self.prefill_tokens += int(lengths.sum())
        first = self._sample(
            logits, [req.uid for req in reqs],
            [self._sample_index(req.uid) for req in reqs])
        now = time.perf_counter()
        for i, req in enumerate(reqs):
            self._cur[req.slot] = int(first[i])
            # preserved across preemption: TTFT is submit -> FIRST token
            self._ttft.setdefault(req.uid, now - self._t0[req.uid])
            req.state = DECODE
            ev = self.events.setdefault(req.uid, {})
            if "admitted_iteration" in ev:
                ev["resumed_iteration"] = self.iterations
            else:
                ev["admitted_iteration"] = self.iterations

    def _finish(self, req: Request) -> None:
        slot = req.slot
        self.sched.release(req.uid)
        if self.paged and self.block_mgr.has_table(req.uid):
            self.block_mgr.free(req.uid)
            self._tables_np[slot, :] = self._trash
            self._len_np[slot] = 0
        # slice at the ORIGINAL prompt length: after a preemption
        # req.prompt_len includes committed tokens (the resume prompt)
        gen = self._gen[req.uid][self._orig_plen.get(req.uid,
                                                     req.prompt_len):]
        self.completions[req.uid] = Completion(
            uid=req.uid, tokens=gen,
            latency_s=time.perf_counter() - self._t0[req.uid],
            ttft_s=self._ttft.get(req.uid, 0.0))
        self.events[req.uid]["finished_iteration"] = self.iterations

    # --- planning ---------------------------------------------------------
    def _base_policy(self) -> QuantPolicy:
        return QuantPolicy(bits=self.ecfg.ql,
                           group_size=self.ecfg.group_size,
                           min_size=self.ecfg.min_size)

    # --- plan pricing / control loop --------------------------------------
    def _plan_cost_model(self, batch: int):
        """DecodeCostModel matching the served plan's knobs (fitted
        machine when the plan carries calibration provenance)."""
        from repro import planning
        kw: Dict[str, Any] = {"batch": int(batch)}
        if self.plan is not None:
            kw["prt"] = self.plan.prt
            kw["nbw"] = self.plan.nbw
            if self.plan.calibration is not None:
                kw["machine"] = planning.machine_from_json(
                    self.plan.calibration)
                disp = planning.dispatch_from_json(self.plan.calibration)
                if disp is not None:
                    kw["dispatch_cycles"] = disp
        if self.tp_serving is not None:
            kw["tp"] = self.tp_serving.tp
            kw["wire_bits"] = self.tp_serving.wire_bits
            kw["allreduce_elems"] = planning.tp_allreduce_elems(self.cfg)
        return planning.DecodeCostModel(**kw)

    def _modeled_iter_seconds(self, occupancy: int) -> Optional[float]:
        """Modeled seconds of one scheduling quantum at the given
        occupancy (memoized per plan; lookup cycles scale with batch, so
        this is nondecreasing — the controller's feasibility curve).

        Plain decode: one masked iteration.  Speculative: one whole
        round, ``k * t_draft + t_verify`` — t_draft under the aggressive
        tree's units, t_verify at batch x (k+1) token positions under
        the conservative units (``planning.speculative_round_seconds``).
        The plan hash in the memo key covers the draft sub-spec."""
        if self._plan_units is None:
            return None
        key = (self.plan.spec_hash if self.plan is not None else None,
               int(occupancy))
        got = self._iter_cache.get(key)
        if got is None:
            from repro import planning
            cost = self._plan_cost_model(occupancy)
            if self.spec_decoder is not None:
                got = planning.speculative_round_seconds(
                    cost, self._plan_units, self.spec_decoder.draft_units,
                    self.quant_policy.group_size, self._plan_fixed_bytes,
                    self.spec_decoder.k)
            else:
                cycles = cost.cycles(self._plan_units)
                total = (cost.qbytes(self._plan_units,
                                     self.quant_policy.group_size)
                         + self._plan_fixed_bytes)
                got = cost.iteration_seconds(cycles, total)
            self._iter_cache[key] = got
        return got

    def planned_tps(self, batch: Optional[int] = None) -> Optional[float]:
        """Modeled decode tokens/s of the served plan at ``batch``
        occupancy (default: the full pool) — the reference side of
        ``stats()["drift"]``.  None when serving unquantized.  Under
        speculative decoding one quantum commits E[accepted + 1] tokens
        per lane, so throughput scales by the acceptance curve."""
        b = self.ecfg.batch_size if batch is None else int(batch)
        secs = self._modeled_iter_seconds(b)
        if secs is None:
            return None
        tpi = (self.spec_decoder.expected_tokens()
               if self.spec_decoder is not None else 1.0)
        return b * tpi / max(secs, 1e-30)

    def measured_tps(self) -> Optional[float]:
        """Measured decode-phase tokens/s over the whole run (tokens
        produced per wall second of masked decode iterations)."""
        if self.decode_seconds <= 0 or self._decode_tokens == 0:
            return None
        return self._decode_tokens / self.decode_seconds

    def modeled_run_tps(self) -> Optional[float]:
        """Modeled tokens/s of the iterations actually run, each priced
        at its true occupancy — the occupancy-matched counterpart of
        :meth:`measured_tps` (``planned_tps`` prices the full pool)."""
        if self.modeled_seconds <= 0 or self._decode_tokens == 0:
            return None
        return self._decode_tokens / self.modeled_seconds

    def _tapped_hit_rate(self) -> Optional[float]:
        """PRT hit rate of the tapped traffic at the served plan's
        operating point (the escalation signal: compare against the rate
        the plan was priced with)."""
        if self.tap is None:
            return None
        calib = self.tap.calib()
        if calib is None:
            return None
        from repro.core import cost_model as cm
        from repro.core import pattern
        merged = calib.get(None) if isinstance(calib, dict) else calib
        wbits = (self.plan.weight_bits if self.plan is not None
                 and self.plan.weight_bits is not None else self.ecfg.ql)
        abits = (self.plan.act_bits if self.plan is not None
                 and self.plan.act_bits is not None else 8)
        nbw = self.plan.nbw if self.plan is not None else "auto"
        if not isinstance(nbw, int):
            k = int(merged.shape[-1])
            nbw = cm.best_nbw_for_unit(k, k, wbits, abits,
                                       batch=self.ecfg.batch_size)
        return pattern.prt_hit_rate(nbw, abits, merged)

    def _controller_step(self) -> None:
        """Apply the drift loop's requested action: replan (re-price on
        tapped traffic) or, when the tapped PRT hit rate moved enough to
        change the allocation, escalate to a full re-solve.  Without a
        tap (or raw weights) the action is recorded as skipped — the
        drift stays visible in stats() but nothing can act on it."""
        ctl = self.controller
        it = self.decode_iterations
        can = (self.tap is not None and self._raw_params is not None
               and self.tap.rows_seen > 0)
        if not can:
            ctl.acted("skipped", it)
            return
        action = ctl.decide(self._tapped_hit_rate(), self.prt_hit_rate)
        self.replan(resolve=(action == "resolve"))
        ctl.acted(action, it)

    def apply_plan(self, plan, force_requantize: bool = False) -> None:
        """Hot-swap the engine onto a new (solved) plan mid-serve.

        Requantizes the retained raw weights under the plan's policy and
        swaps the parameter tree; the KV pool, scheduler, and every
        in-flight request are untouched (the cache layout is independent
        of the plan's scan segmentation), so decoding continues without
        dropping a token.  Accepts a PlanSpec, grammar string/JSON, or a
        ``Planner`` ``PlanResult``.

        When the new plan resolves to the policy already being served
        (e.g. a discount-only replan), the requantization pass is
        skipped — it would produce byte-identical weights; pass
        ``force_requantize=True`` to run it anyway.
        """
        from repro import planning
        if self._raw_params is None:
            raise ValueError("apply_plan needs the raw weights resident "
                             "— construct the engine with quantize=True "
                             "and retain_raw=True (or a tap attached)")
        hit = None
        report = None
        if isinstance(plan, planning.PlanResult):
            hit = plan.measured_prt_hit_rate
            spec, policy, report = plan.spec, plan.policy, plan.report
        else:
            spec = planning.as_plan(plan)
            policy = spec.to_policy(self._base_policy())
        if isinstance(spec.kv_bits, int) and spec.kv_bits != self.kv_bits:
            warnings.warn(
                f"plan requests kv_bits={spec.kv_bits} but the KV pool "
                f"was allocated {self.kv_bits}-bit at construction — KV "
                "precision cannot hot-swap under in-flight requests; "
                "rebuild the engine to change it", UserWarning,
                stacklevel=2)
        want_tp = spec.tp if isinstance(spec.tp, int) else None
        if want_tp is not None and want_tp != self.tp:
            warnings.warn(
                f"plan requests tp={want_tp} but the engine serves "
                f"tp={self.tp} — the mesh is fixed at construction; "
                "rebuild the engine to change shard count", UserWarning,
                stacklevel=2)
        if spec.wire is not None and spec.wire != self.wire_bits:
            warnings.warn(
                f"plan requests wire={spec.wire} but the engine serves "
                f"wire={self.wire_bits} — all-reduce precision is fixed "
                "at construction; rebuild the engine to change it",
                UserWarning, stacklevel=2)
        if force_requantize or policy != self.quant_policy:
            self.params, b0, b1 = quantize_params(self._raw_params,
                                                  policy)
            self.compression = b0 / max(b1, 1)
            if self.tp_serving is not None:
                # the fresh tree replaces the sharded one: re-place it on
                # the mesh so decode keeps running sharded without a
                # resharding transfer on first use
                self.params = self.tp_serving.shard_params(self.params)
        self.quant_policy = policy
        self.plan = spec
        # the report must track the plan actually served — a stale one
        # would describe a different allocation in stats/replans
        self.plan_report = report
        self.replan_count += 1
        if hit is not None:
            self.prt_hit_rate = hit
        # re-price: the swapped plan has its own units / feasibility
        # curve, and the controller must re-anchor drift against it
        from repro import planning
        self._plan_units = planning.policy_units(self._raw_params, policy)
        self._plan_fixed_bytes = planning.unquantized_bytes(
            self._raw_params, policy)
        self._iter_cache.clear()
        if spec.target_tps is not None:
            self.slo = planning.Slo(spec.target_tps,
                                    batch=spec.slo_batch
                                    or self.ecfg.batch_size)
        # draft sub-spec hot-swap: requantize the draft tree when the new
        # plan drafts differently, or drop it when the plan stopped
        # speculating (the pending _cur token carries over either way)
        draft = spec.draft if isinstance(spec.draft, planning.DraftSpec) \
            else None
        if draft is None:
            self.spec_decoder = None
        elif (self.spec_decoder is None
              or self.spec_decoder.spec != draft):
            from repro.serving.speculative import SpeculativeDecoder
            self.spec_decoder = SpeculativeDecoder(
                self._raw_params, self.cfg, draft, policy)
        if self.controller is not None:
            self.controller.slo = self.slo
            self.controller.plan_changed(
                iter_seconds=self._modeled_iter_seconds,
                planned_tps=self.planned_tps(),
                plan_hit_rate=self.prt_hit_rate,
                tokens_per_iter=(self.spec_decoder.expected_tokens()
                                 if self.spec_decoder is not None
                                 else 1.0))

    def replan(self, planner=None, resolve: bool = False):
        """Online recalibration from live traffic (ROADMAP: "PRT hit
        rates from live traffic").

        Feeds the ActivationTap's captured per-layer batches to a
        ``Planner.replan`` — measured PRT discounts refresh from real
        activations, and ``resolve=True`` additionally re-solves the
        allocation — then hot-swaps the result via :meth:`apply_plan`.
        Pass an existing ``planner`` to reuse its cached sensitivity
        probes across replans; otherwise a fresh one wraps the engine's
        current plan.  Returns the ``PlanResult``.
        """
        from repro import planning
        if self.tap is None:
            raise ValueError("no ActivationTap attached — set "
                             "EngineConfig.tap_capacity > 0")
        if self._raw_params is None:
            raise ValueError("replan needs the raw weights resident — "
                             "construct the engine with quantize=True "
                             "and retain_raw=True (or rely on the tap "
                             "default)")
        if planner is None:
            planner = planning.Planner(self._raw_params, self.cfg,
                                       self.plan,
                                       base=self._base_policy())
            planner.last = planning.PlanResult(
                spec=self.plan, policy=self.quant_policy,
                report=self.plan_report)
        result = planner.replan(self.tap, resolve=resolve)
        self.apply_plan(result)
        return result

    # --- shared -----------------------------------------------------------
    def _sample_index(self, uid: int) -> int:
        """Per-request sample counter: how many tokens this request has
        had sampled AND committed so far (0 at the prefill sample).
        Derived from committed state only, so it is invariant to batch
        composition, iteration count, preemption/resume (the resumed
        re-prefill re-samples the pending token under its original
        index), and slot-vs-paged pool layout."""
        return len(self._gen[uid]) - self._orig_plen.get(uid, 0)

    def _sample(self, logits, uids=None, indices=None, also=None):
        """Sample one token per logits row.

        ``uids``/``indices`` carry each row's (request uid, per-request
        sample index); rows without a live request (masked slots) pass
        uid 0 and their draws are discarded by the caller.  Greedy
        ignores them entirely.  The host waits here for the device (the
        ``engine.sample`` span).  ``also``: a device array fetched in the
        same transfer; then (tokens, its host copy) is returned."""
        with self.spans.span("engine.sample"):
            if self.ecfg.temperature <= 0:
                toks = jnp.argmax(logits, axis=-1)
            else:
                if uids is None:
                    uids = np.zeros((logits.shape[0],), np.uint32)
                    indices = np.zeros((logits.shape[0],), np.uint32)
                toks = _sample_rows_jit(
                    logits, jnp.asarray(np.asarray(uids, np.uint32)),
                    jnp.asarray(np.asarray(indices, np.uint32)),
                    self.ecfg.seed, self.ecfg.temperature)
            if also is None:
                return np.asarray(toks)
            return tuple(np.asarray(a) for a in jax.device_get((toks, also)))

    def _count_attention(self, mask: np.ndarray) -> None:
        """Add one paged decode step: the live blocks its ``mask`` lanes
        attend in every layer, and the table blocks a gather reads."""
        c = self.attn_counts
        _, live = live_blocks(self._len_np[mask][:, None],
                              self._windows[None, :],
                              self.ecfg.kv_block_size, xp=np)
        c["kernel_steps"] += int(self._attn_kernel)
        c["blocks_read"] += int(live.sum())
        c["blocks_in_tables"] += mask.size * self._mbs * self._windows.size

    def _count_experts(self, tokens: np.ndarray) -> None:
        """Add one decode step's tokens per expert ``[layers, E]``."""
        t = tokens.astype(np.int64)
        pairs, top = t.sum(1), t.max(1)
        touched = (t > 0).sum(1)
        live = pairs > 0
        m = self.moe_counts
        m["layer_steps"] += int(live.sum())
        m["routed_pairs"] += int(pairs.sum())
        m["experts_touched"] += int(touched.sum())
        m["imbalance_sum"] += float(
            (top[live] * touched[live] / pairs[live]).sum())

    def _tp_stats(self) -> Dict[str, Any]:
        """Observability for tensor-parallel serving: shard count, wire
        precision, modeled wire seconds and their share of the full-pool
        iteration, and the per-shard all-reduce bytes one decode
        iteration moves."""
        b = self.ecfg.batch_size
        tw = (self._plan_cost_model(b).t_wire(b)
              if self._plan_units is not None else None)
        secs = self._modeled_iter_seconds(b)
        return {"shards": self.tp_serving.tp,
                "wire_bits": self.tp_serving.wire_bits,
                "allreduce_bytes_per_iter":
                    self.tp_serving.allreduce_bytes_per_iter(b),
                "modeled_t_wire_s": tw,
                "modeled_wire_share": (tw / secs if tw is not None
                                       and secs else None)}

    def stats(self) -> Dict[str, Any]:
        lats = [c.latency_s for c in self.completions.values()]
        ttfts = [c.ttft_s for c in self.completions.values()]
        toks = sum(len(c.tokens) for c in self.completions.values())
        measured = self.measured_tps()
        planned = self.planned_tps()
        modeled = self.modeled_run_tps()
        # measured-vs-modeled decode tokens/s drift: the "is the
        # calibration stale?" signal, reported with or without a
        # controller.  Occupancy-matched (each iteration priced at its
        # true occupancy) raw ratio — absolute value is only meaningful
        # when the plan carries host calibration (plan_calibrated);
        # the controller's internal drift is anchor-normalized.
        ref = modeled if modeled is not None else planned
        drift = (measured / ref - 1.0
                 if measured is not None and ref else None)
        return {"requests": len(self.completions),
                "measured_tps": measured,
                "planned_tps": planned,
                "modeled_run_tps": modeled,
                "drift": drift,
                "controller": (self.controller.stats()
                               if self.controller is not None else None),
                "generated_tokens": toks,
                # paged-pool observability: peak concurrent decode lanes
                # (the gate metric), served KV precision, pool stats
                "peak_active": self.peak_active,
                "kv_bits": self.kv_bits,
                # tensor-parallel serving: shard count, wire precision,
                # modeled wire share (None when serving single-device)
                "tp": (self._tp_stats() if self.tp_serving is not None
                       else None),
                "block_pool": (self.block_mgr.stats()
                               if self.paged else None),
                # decode attention over the paged pool (None without
                # one): kernel_steps, blocks_read, blocks_in_tables
                "decode_attn": (dict(self.attn_counts)
                                if self.attn_counts is not None else None),
                # routed experts over the decode steps (None without
                # experts): layer_steps, routed_pairs, experts_touched,
                # imbalance_sum
                "moe": (dict(self.moe_counts)
                        if self.moe_counts is not None else None),
                # self-speculative decoding: draft plan, rounds,
                # acceptance rate (None when not speculating)
                "speculative": (self.spec_decoder.stats()
                                if self.spec_decoder is not None
                                else None),
                "iterations": self.iterations,
                "prefill_iterations": self.prefill_iterations,
                # prefill calls, and those at a (batch, padded length)
                # this engine had not run: its compiled-program misses
                "prefill": {"calls": self.prefill_iterations,
                            "new_shapes": len(self._prefill_shapes)},
                "decode_iterations": self.decode_iterations,
                "decode_pool_donated": self.decode_pool_donated,
                "prefill_tokens": self.prefill_tokens,
                "weight_compression": round(self.compression, 2),
                "mixed_precision": bool(self.quant_policy is not None
                                        and self.quant_policy.is_mixed()),
                # plan provenance: serve_bench artifacts track churn by
                # hash; replan_count/prt_hit_rate expose online recalib
                "plan_hash": (self.plan.spec_hash
                              if self.plan is not None else None),
                "plan_mode": (self.plan.mode
                              if self.plan is not None else None),
                # whether the served plan was priced against fitted
                # (measured-hardware) cost-model constants
                "plan_calibrated": bool(self.plan is not None
                                        and self.plan.calibration
                                        is not None),
                "replan_count": self.replan_count,
                "prt_hit_rate": self.prt_hit_rate,
                "tapped_rows": (self.tap.rows_seen
                                if self.tap is not None else 0),
                "mean_latency_s": float(np.mean(lats)) if lats else 0.0,
                "p99_latency_s": float(np.percentile(lats, 99))
                if lats else 0.0,
                "mean_ttft_s": float(np.mean(ttfts)) if ttfts else 0.0}
