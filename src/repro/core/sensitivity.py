"""Sensitivity-calibrated mixed-precision bit allocation.

SAIL's first stated challenge is that "optimal bit precision varies across
models and layers" (Sec. I); its LUT-GEMV supports arbitrary ``ql`` per
matmul at minimal overhead.  This module turns that capability into a
serving feature:

  * ``output_sensitivity`` — from a small calibration batch, score each
    weight matrix (per layer, per matrix: attn qkv/o vs mlp up/down vs
    lm_head) by the quantization-induced end-to-end output error: quantize
    ONE matrix (or one layer slice of a scan stack) at each candidate
    precision, run the model, and measure the mean squared logit deviation
    against the f32 reference.
  * ``weight_sensitivity`` — the calibration-free proxy (squared weight
    reconstruction error), for when no forward passes are affordable.
  * ``allocate_bits`` — greedy solver for "minimize total predicted error
    subject to a byte budget" over ``SUPPORTED_BITS``, using the exact
    QTensor byte accounting (packed words + group scales + codebook).
  * ``activation_sensitivity`` — the activation-precision twin: probe
    ONE unit's matmul inputs at each candidate ``abits`` (gate-masked
    ``ActQuantWeight`` wrapper, one compiled forward per path) against
    the same exact center.
  * ``kv_sensitivity`` — the KV-cache twin: prefill an f32 cache, then
    per layer quantize->dequantize that layer's cached K/V (the exact
    int8 transform ``quant_kv`` serving applies) and measure one decode
    step's logit MSE vs the f32-cache reference.  ``Planner`` resolves
    ``PlanSpec.kv_bits="auto"`` against the normalized total.
  * ``calibrate_policy`` — end-to-end: score, solve, and return a
    ``QuantPolicy`` whose ``allocation`` carries per-path (and per-layer)
    bits; ``quantize_params`` then emits a mixed tree.  With
    ``abits_candidates`` it allocates ``(wbits, abits)`` JOINTLY under a
    projected-cycles budget (``allocate_bits_joint``), accepts held-out
    ``calib_batches``, and caps scan segmentation via ``max_segments``.

Invariants the probes guarantee: every score is measured against an
exact center (f32 reference logits from the SAME jitted forward), probes
are deterministic for a given (params, tokens) — calibration batches are
seeded — and probing never mutates ``params`` (tree surgery happens on
copies of the flattened leaf list).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.quant import SUPPORTED_ABITS, SUPPORTED_BITS

# A unit key: (keystr path, layer index or None for non-stacked leaves).
UnitKey = Tuple[str, Optional[int]]


@dataclasses.dataclass(frozen=True)
class Unit:
    """One independently allocatable weight: a 2-D leaf or one layer slice
    of a scan-stacked leaf.  ``copies`` folds extra leading dims (MoE
    experts) into the byte accounting.  ``aerrors`` (activation-precision
    -> predicted output error, from ``activation_sensitivity``) is only
    present for joint (wbits, abits) allocation."""
    path: str
    layer: Optional[int]
    k: int
    n: int
    copies: int
    errors: Mapping[int, float]    # wbits -> predicted output error
    aerrors: Optional[Mapping[Optional[int], float]] = None

    @property
    def key(self) -> UnitKey:
        return (self.path, self.layer)


@dataclasses.dataclass(frozen=True)
class AllocationReport:
    """Solver diagnostics (the bench's Pareto bookkeeping)."""
    bits_by_unit: Dict[UnitKey, int]
    bytes_total: int
    budget_bytes: int
    predicted_error: float
    feasible: bool                 # min-bits config fit inside the budget


@dataclasses.dataclass(frozen=True)
class JointAllocationReport:
    """Joint (wbits, abits) solver diagnostics."""
    bits_by_unit: Dict[UnitKey, Tuple[int, int]]   # key -> (wbits, abits)
    bytes_total: int
    cycles_total: float
    byte_budget: Optional[int]
    cycle_budget: float
    predicted_error: float
    feasible: bool


def unit_bytes(k: int, n: int, bits: int, group_size: int,
               copies: int = 1) -> int:
    """QTensor storage bytes for one [K, N] weight (x ``copies``): packed
    words + group scales.  The 2^bits-entry codebook is shared per tensor
    (and tiny), so it is excluded — allocator accounting must price a
    per-layer unit and a whole-leaf unit consistently."""
    from repro.core.cost_model import qtensor_bytes
    return qtensor_bytes(k, n, bits, group_size, copies)


def fake_quant(w: jax.Array, bits: int, group_size: int,
               codebook: Optional[jax.Array] = None) -> jax.Array:
    """Quantize->dequantize roundtrip of ``w[..., K, N]`` (vmapped over
    leading dims) — the error a SAIL-served matmul would see."""
    if w.ndim == 2:
        return quant.dequantize(quant.quantize(w, bits, group_size,
                                               codebook))
    lead = w.shape[:-2]
    flat = w.reshape((-1,) + w.shape[-2:])
    out = jax.vmap(lambda a: quant.dequantize(
        quant.quantize(a, bits, group_size, codebook)))(flat)
    return out.reshape(lead + out.shape[-2:])


def calibration_tokens(vocab: int, batch: int = 4, seq: int = 32,
                       seed: int = 0) -> jax.Array:
    """Deterministic synthetic calibration batch (matches the synthetic
    data pipeline used everywhere else in this repro)."""
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                              vocab)


def quantizable_units(params, policy) -> List[Tuple[str, Any, bool]]:
    """(path, leaf, stacked?) for every leaf ``policy`` would quantize."""
    from repro.models.sail_linear import (_should_quantize,
                                          _should_quantize_stacked)
    out = []
    for path, w in jax.tree_util.tree_flatten_with_path(params)[0]:
        pstr = jax.tree_util.keystr(path)
        if _should_quantize(pstr, w, policy):
            out.append((pstr, w, False))
        elif _should_quantize_stacked(pstr, w, policy):
            out.append((pstr, w, True))
    return out


def uniform_bytes(params, policy, bits: int) -> int:
    """Total QTensor bytes of quantizing every eligible leaf at ``bits``
    (the byte budget 'uniform b-bit' occupies)."""
    total = 0
    for _, w, stacked in quantizable_units(params, policy):
        k, n = w.shape[-2:]
        copies = 1
        for d in w.shape[:-2]:
            copies *= d
        total += unit_bytes(k, n, bits, policy.group_size, copies)
    return total


# ---------------------------------------------------------------------------
# sensitivity scoring
# ---------------------------------------------------------------------------

def weight_sensitivity(params, policy,
                       bits_candidates: Sequence[int] = SUPPORTED_BITS,
                       per_layer: bool = True) -> Dict[UnitKey, Dict[int, float]]:
    """Calibration-free proxy: sum of squared weight reconstruction error
    per unit and candidate precision."""
    scores: Dict[UnitKey, Dict[int, float]] = {}
    for pstr, w, stacked in quantizable_units(params, policy):
        if stacked and per_layer:
            slices = [(layer, w[layer]) for layer in range(w.shape[0])]
        else:
            slices = [(None if not stacked else -1, w)]
        for layer, ws in slices:
            key = (pstr, None) if layer in (None, -1) else (pstr, layer)
            errs = {}
            for b in bits_candidates:
                dq = fake_quant(ws, b, policy.group_size,
                                policy.codebook_for(b))
                errs[b] = float(jnp.sum((dq - ws) ** 2))
            scores[key] = errs
    return scores


def output_sensitivity(params, cfg, tokens, policy,
                       bits_candidates: Sequence[int] = SUPPORTED_BITS,
                       per_layer: bool = True) -> Dict[UnitKey, Dict[int, float]]:
    """Calibrated scores, centered at the uniform-``policy.bits`` model.

    Independent per-matrix probes against the f32 model mispredict the
    fully quantized operating point (quantization errors interact), so
    each score is instead the TRUE end-to-end logit MSE (vs the f32
    reference) of the model with every eligible weight at the uniform
    baseline precision and ONLY the probed unit moved to the candidate
    precision.  An allocation differing from uniform in few units is then
    predicted to second order in the number of moved units.

    The forward is jitted once (probe trees share the structure), so the
    cost is |units| x (|bits_candidates| - 1) reruns of one compiled step.
    """
    from repro.models import lm
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    fwd = jax.jit(lambda p: lm.forward(p, tokens, cfg)[0])
    ref = fwd(params)

    eligible = {pstr: stacked
                for pstr, _, stacked in quantizable_units(params, policy)}
    base_bits = policy.bits
    base_cb = policy.codebook_for(base_bits)
    base_leaves = []
    for path, w in flat:
        pstr = jax.tree_util.keystr(path)
        base_leaves.append(fake_quant(w, base_bits, policy.group_size,
                                      base_cb)
                           if pstr in eligible else w)

    def probe(idx: int, new_leaf) -> float:
        swapped = list(base_leaves)
        swapped[idx] = new_leaf
        logits = fwd(jax.tree_util.tree_unflatten(treedef, swapped))
        return float(jnp.mean((logits - ref) ** 2))

    err_base = float(jnp.mean(
        (fwd(jax.tree_util.tree_unflatten(treedef, base_leaves)) - ref)
        ** 2))

    scores: Dict[UnitKey, Dict[int, float]] = {}
    for idx, (path, w) in enumerate(flat):
        pstr = jax.tree_util.keystr(path)
        if pstr not in eligible:
            continue
        stacked = eligible[pstr]
        if stacked and per_layer:
            for layer in range(w.shape[0]):
                errs = {}
                for b in bits_candidates:
                    if b == base_bits:
                        errs[b] = err_base
                        continue
                    dq = fake_quant(w[layer], b, policy.group_size,
                                    policy.codebook_for(b))
                    errs[b] = probe(idx, base_leaves[idx].at[layer].set(dq))
                scores[(pstr, layer)] = errs
        else:
            errs = {}
            for b in bits_candidates:
                if b == base_bits:
                    errs[b] = err_base
                    continue
                dq = fake_quant(w, b, policy.group_size,
                                policy.codebook_for(b))
                errs[b] = probe(idx, dq)
            scores[(pstr, None)] = errs
    return scores


def kv_sensitivity(params, cfg, tokens, bits: int = 8) -> Dict[str, Any]:
    """Per-layer decode-logit error from quantizing ONE layer's KV cache.

    The probe mirrors the weight probes' exact-centering: prefill the
    calibration batch with an f32 cache, take one reference decode step,
    then for each layer quantize->dequantize that layer's cached K and V
    (int8 per-head-dim absmax — the exact transform ``quant_kv`` serving
    applies) and re-run the same decode step.  Scores are logit MSE vs
    the reference; ``relative`` normalizes the summed error by the
    reference logit power — the number ``Planner`` compares against
    ``kv_tolerance`` when resolving ``kv_bits="auto"``.

    Attention families only (recurrent state has no KV to quantize).
    """
    from repro.core.quant import dequantize_kv, quantize_kv
    from repro.models import lm
    if bits != 8:
        raise ValueError(f"only int8 KV is served; got bits={bits}")
    if cfg.family == "ssm":
        raise ValueError("kv_sensitivity needs an attention family "
                         f"(family={cfg.family!r} has no KV cache)")
    b, t = tokens.shape
    logits, cache = lm.prefill(params, tokens, cfg, cache_len=t + 1,
                               quant_kv=False)
    tok = jnp.argmax(logits, axis=-1)[:, None]
    ref, _ = lm.decode_step(params, tok, cache, cfg)
    ref = ref.astype(jnp.float32)
    denom = float(jnp.mean(ref ** 2))
    layers = cache["layers"]
    n_layers = int(layers["k"].shape[0])
    per_layer = []
    for i in range(n_layers):
        kd = dequantize_kv(*quantize_kv(layers["k"][i]))
        vd = dequantize_kv(*quantize_kv(layers["v"][i]))
        probed = dict(layers)
        probed["k"] = layers["k"].at[i].set(kd)
        probed["v"] = layers["v"].at[i].set(vd)
        lg, _ = lm.decode_step(params, tok,
                               {"length": cache["length"],
                                "layers": probed}, cfg)
        per_layer.append(float(jnp.mean((lg.astype(jnp.float32) - ref) ** 2)))
    total = float(sum(per_layer))
    return {"bits": int(bits), "per_layer": per_layer, "total": total,
            "relative": total / max(denom, 1e-30)}


def activation_sensitivity(params, cfg, tokens, policy,
                           abits_candidates: Sequence[int] = SUPPORTED_ABITS,
                           per_layer: bool = True
                           ) -> Dict[UnitKey, Dict[Optional[int], float]]:
    """Activation-precision scores, exact-centered like the weight probes.

    Each score is the TRUE end-to-end logit MSE (vs the f32 reference) of
    the model with every eligible weight at the uniform baseline precision
    and ONLY the probed unit's *matmul inputs* quantized to the candidate
    ``abits`` (via the ``ActQuantWeight`` wrapper, whose per-layer gate
    lets one compiled forward probe every layer of a scan stack).  The
    ``None`` entry (f32 activations — the center) is the baseline error
    itself, so a joint allocation moving few units stays second-order
    accurate exactly like the weight side.
    """
    from repro.models import lm
    from repro.models.sail_linear import ActQuantWeight
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    treedef = jax.tree_util.tree_structure(params)
    fwd = jax.jit(lambda p: lm.forward(p, tokens, cfg)[0])
    ref = fwd(params)

    eligible = {pstr: stacked
                for pstr, _, stacked in quantizable_units(params, policy)}
    base_bits = policy.bits
    base_cb = policy.codebook_for(base_bits)
    base_leaves = []
    for path, w in flat:
        pstr = jax.tree_util.keystr(path)
        base_leaves.append(fake_quant(w, base_bits, policy.group_size,
                                      base_cb)
                           if pstr in eligible else w)

    err_base = float(jnp.mean(
        (fwd(jax.tree_util.tree_unflatten(treedef, base_leaves)) - ref)
        ** 2))

    def probe(idx: int, gate, abits: int) -> float:
        swapped = list(base_leaves)
        swapped[idx] = ActQuantWeight(w=base_leaves[idx],
                                      gate=jnp.asarray(gate, jnp.float32),
                                      abits=int(abits))
        logits = fwd(jax.tree_util.tree_unflatten(treedef, swapped))
        return float(jnp.mean((logits - ref) ** 2))

    scores: Dict[UnitKey, Dict[Optional[int], float]] = {}
    for idx, (path, w) in enumerate(flat):
        pstr = jax.tree_util.keystr(path)
        if pstr not in eligible:
            continue
        stacked = eligible[pstr]
        if stacked and per_layer:
            n_layers = w.shape[0]
            for layer in range(n_layers):
                errs: Dict[Optional[int], float] = {None: err_base}
                gate = np.zeros((n_layers,), np.float32)
                gate[layer] = 1.0
                for ab in abits_candidates:
                    errs[int(ab)] = probe(idx, gate, ab)
                scores[(pstr, layer)] = errs
        else:
            errs = {None: err_base}
            gate = (np.ones((w.shape[0],), np.float32)
                    if stacked else np.float32(1.0))
            for ab in abits_candidates:
                errs[int(ab)] = probe(idx, gate, ab)
            scores[(pstr, None)] = errs
    return scores


# ---------------------------------------------------------------------------
# greedy budgeted allocation
# ---------------------------------------------------------------------------

def allocate_bits(units: Sequence[Unit], budget_bytes: int,
                  group_size: int,
                  bits_candidates: Sequence[int] = SUPPORTED_BITS,
                  pinned: Optional[Mapping[UnitKey, int]] = None
                  ) -> AllocationReport:
    """Greedy knapsack: start every free unit at the narrowest candidate,
    then repeatedly apply the upgrade with the best error-reduction per
    extra byte that still fits the budget.  Upgrades may jump several
    precisions at once, so locally non-monotone error ladders (a 3-bit
    grid occasionally reconstructs worse than 2-bit) cannot wedge the
    solver."""
    cand = sorted(set(int(b) for b in bits_candidates))
    pinned = dict(pinned or {})
    free = [u for u in units if u.key not in pinned]

    def bytes_at(u: Unit, b: int) -> int:
        return unit_bytes(u.k, u.n, b, group_size, u.copies)

    def climb(start_bits: int):
        """Greedy upgrades from every free unit at ``start_bits``.
        Returns (bits_by_unit, total_bytes, predicted_error) or None if
        the start itself exceeds the budget."""
        current: Dict[UnitKey, int] = {}
        total = 0
        for u in units:
            b = pinned.get(u.key, start_bits)
            current[u.key] = b
            total += bytes_at(u, b)
        if total > budget_bytes:
            return None
        while True:
            best = None  # (ratio, delta_err, key_tiebreak, new_bits)
            for u in free:
                cur = current[u.key]
                err_cur = u.errors[cur]
                for b in cand:
                    if b <= cur:
                        continue
                    db = bytes_at(u, b) - bytes_at(u, cur)
                    if db <= 0 or total + db > budget_bytes:
                        continue
                    de = err_cur - u.errors[b]
                    if de <= 0:
                        continue
                    pick = (de / db, de, u.key, b)
                    if best is None or pick > best:
                        best = pick
            if best is None:
                break
            _, _, key, b = best
            u = next(x for x in free if x.key == key)
            total += bytes_at(u, b) - bytes_at(u, current[key])
            current[key] = b
        total = swap_refine(current, total)
        predicted = sum(u.errors[current[u.key]] for u in units)
        return current, total, predicted

    def swap_refine(current: Dict[UnitKey, int], total: int) -> int:
        """Pairwise trades: downgrade one unit to fund upgrading another.
        A monotone climb cannot cross a tight budget (e.g. start =
        uniform-4 at the uniform-4 budget leaves zero headroom); profitable
        down+up swaps are how mixed precision beats uniform there."""
        while True:
            best = None  # (net_err_delta, key_down, bits_down, key_up, bits_up)
            for ud in free:
                cur_d = current[ud.key]
                for bd in cand:
                    if bd >= cur_d:
                        continue
                    saved = bytes_at(ud, cur_d) - bytes_at(ud, bd)
                    loss = ud.errors[bd] - ud.errors[cur_d]
                    for uu in free:
                        if uu.key == ud.key:
                            continue
                        cur_u = current[uu.key]
                        for bu in cand:
                            if bu <= cur_u:
                                continue
                            cost = bytes_at(uu, bu) - bytes_at(uu, cur_u)
                            if total - saved + cost > budget_bytes:
                                continue
                            net = loss + uu.errors[bu] - uu.errors[cur_u]
                            pick = (net, ud.key, bd, uu.key, bu)
                            if net < 0 and (best is None or pick < best):
                                best = pick
            if best is None:
                return total
            _, kd, bd, ku, bu = best
            ud = next(x for x in free if x.key == kd)
            uu = next(x for x in free if x.key == ku)
            total += (bytes_at(ud, bd) - bytes_at(ud, current[kd])
                      + bytes_at(uu, bu) - bytes_at(uu, current[ku]))
            current[kd] = bd
            current[ku] = bu

    # Multi-start: all-narrowest plus every feasible uniform level — the
    # result is never predicted-worse than the best uniform config the
    # budget admits (greedy alone can wedge when a cheap early upgrade
    # starves a crucial later one).
    solutions = [s for s in (climb(b) for b in cand) if s is not None]
    if not solutions:
        # infeasible even at min bits: report the min-bits config
        current = {u.key: pinned.get(u.key, cand[0]) for u in units}
        total = sum(bytes_at(u, current[u.key]) for u in units)
        predicted = sum(u.errors[current[u.key]] for u in units)
        return AllocationReport(bits_by_unit=current, bytes_total=total,
                                budget_bytes=int(budget_bytes),
                                predicted_error=predicted, feasible=False)
    current, total, predicted = min(solutions, key=lambda s: (s[2], s[1]))
    return AllocationReport(bits_by_unit=current, bytes_total=total,
                            budget_bytes=int(budget_bytes),
                            predicted_error=predicted, feasible=True)


def pareto_state_filter(states, err_of, cyc_of, byte_of=None):
    """Drop states strictly dominated in (error, cycles[, bytes]).

    A state another state beats-or-ties on every objective (and beats on
    at least one) can never be part of a better allocation, so the joint
    solver's climb and swap-refinement loops — O(|units|^2 x |states|^2)
    per accepted swap — need not consider it.  Real probe ladders
    saturate (several precisions reach the same error at different
    cost), so the surviving frontier is typically a fraction of the
    product grid; see the scaling regression in tests/test_planning.py.
    """
    scored = [
        (s, err_of(s), cyc_of(s), byte_of(s) if byte_of is not None else 0)
        for s in states
    ]
    kept = []
    for s, e, c, b in scored:
        dominated = False
        for t, e2, c2, b2 in scored:
            if t == s:
                continue
            if e2 <= e and c2 <= c and b2 <= b and (e2 < e or c2 < c or b2 < b):
                dominated = True
                break
        if not dominated:
            kept.append(s)
    return kept


def allocate_bits_joint(units: Sequence[Unit], cycle_budget: float,
                        group_size: int,
                        byte_budget: Optional[int] = None,
                        bits_candidates: Sequence[int] = SUPPORTED_BITS,
                        abits_candidates: Sequence[int] = SUPPORTED_ABITS,
                        pinned: Optional[Mapping[UnitKey, int]] = None,
                        pinned_act: Optional[Mapping[UnitKey, int]] = None,
                        batch: int = 8, threads: int = 16,
                        machine=None, prt="paper", calib=None,
                        prune_states: bool = True
                        ) -> JointAllocationReport:
    """Joint (wbits, abits) allocation under a projected-cycles budget.

    SAIL's lutmm takes BOTH precisions per call, so the allocator searches
    the product grid: minimize total predicted error (weight probe +
    activation probe, both exact-centered) subject to
    ``mixed_decode_cycles <= cycle_budget`` and optionally
    ``bytes <= byte_budget``.  Every unit is priced at its own
    cycle-optimal NBW (``best_nbw_for_unit``) and, under
    ``prt="measured"``, its own simulated PRT hit rate — this is what
    lets the solver trade activation width (pure cycles) against weight
    width (cycles + bytes) where each actually pays.

    Same solver shape as :func:`allocate_bits`: multi-start greedy climbs
    (best error reduction per normalized budget use) followed by pairwise
    down/up swap refinement, so tight budgets where a monotone climb
    cannot move still reach mixed assignments.  ``prune_states`` (on by
    default) restricts every per-unit move list to its (error, cycles[,
    bytes]) Pareto frontier — dominated states cannot improve any
    allocation, and dropping them bounds the swap-refinement candidate
    count at calibration scale (the ROADMAP's joint-solver scaling item).
    ``calib`` may be a per-layer mapping (``ActivationTap.calib()``):
    each unit is then priced with its own layer's measured PRT hit rate.
    """
    from repro.core import cost_model as cm
    from repro.core import pattern as _pattern
    m = machine or cm.SailMachine()
    calib = _pattern.canonical_calib(calib)
    wcand = sorted(set(int(b) for b in bits_candidates))
    acand = sorted(set(int(b) for b in abits_candidates))
    states = [(wb, ab) for wb in wcand for ab in acand]
    pinned = dict(pinned or {})
    pinned_act = dict(pinned_act or {})

    for u in units:
        if u.aerrors is None:
            raise ValueError(f"unit {u.key} has no activation scores "
                             "(aerrors) — run activation_sensitivity")

    bytes_tab: Dict[Tuple[UnitKey, int], int] = {}
    cyc_tab: Dict[Tuple[UnitKey, Tuple[int, int]], float] = {}
    for u in units:
        ucalib = _pattern.calib_for_layer(calib, u.layer)
        for wb in wcand:
            bytes_tab[(u.key, wb)] = unit_bytes(u.k, u.n, wb, group_size,
                                                u.copies)
        for s in states:
            wb, ab = s
            _, cyc = cm._best_nbw_and_cycles(u.k, u.n, wb, ab, batch,
                                             threads, m, prt, ucalib)
            cyc_tab[(u.key, s)] = u.copies * cyc

    def err(u: Unit, s: Tuple[int, int]) -> float:
        return u.errors[s[0]] + u.aerrors[s[1]]

    _states_cache: Dict[UnitKey, list] = {}

    def unit_states(u: Unit):
        got = _states_cache.get(u.key)
        if got is not None:
            return got
        wfix = pinned.get(u.key)
        afix = pinned_act.get(u.key)
        opts = [(wb, ab) for wb, ab in states
                if (wfix is None or wb == wfix)
                and (afix is None or ab == afix)]
        if prune_states and len(opts) > 2:
            opts = pareto_state_filter(
                opts, lambda s: err(u, s), lambda s: cyc_tab[(u.key, s)],
                (lambda s: bytes_tab[(u.key, s[0])])
                if byte_budget is not None else None)
        _states_cache[u.key] = opts
        return opts

    free = [u for u in units
            if len(unit_states(u)) > 1]

    def totals(current):
        by = sum(bytes_tab[(k, s[0])] for k, s in current.items())
        cy = sum(cyc_tab[(k, s)] for k, s in current.items())
        return by, cy

    def fits(by, cy):
        return (cy <= cycle_budget
                and (byte_budget is None or by <= byte_budget))

    def norm_cost(key, s) -> float:
        c = cyc_tab[(key, s)] / max(cycle_budget, 1e-9)
        if byte_budget is not None:
            c += bytes_tab[(key, s[0])] / max(byte_budget, 1)
        return c

    def min_state(u: Unit):
        return min(unit_states(u), key=lambda s: (norm_cost(u.key, s),
                                                  err(u, s)))

    def climb(start: Tuple[int, int]):
        current: Dict[UnitKey, Tuple[int, int]] = {}
        for u in units:
            opts = unit_states(u)
            current[u.key] = start if start in opts else min_state(u)
        by, cy = totals(current)
        if not fits(by, cy):
            return None
        while True:
            best = None  # (ratio, de, key, state)
            for u in free:
                cur = current[u.key]
                e_cur = err(u, cur)
                c_cur = norm_cost(u.key, cur)
                for s in unit_states(u):
                    if s == cur:
                        continue
                    de = e_cur - err(u, s)
                    if de <= 0:
                        continue
                    nby = by + bytes_tab[(u.key, s[0])] - \
                        bytes_tab[(u.key, cur[0])]
                    ncy = cy + cyc_tab[(u.key, s)] - cyc_tab[(u.key, cur)]
                    if not fits(nby, ncy):
                        continue
                    dc = norm_cost(u.key, s) - c_cur
                    ratio = de / dc if dc > 1e-12 else float("inf")
                    pick = (ratio, de, u.key, s)
                    if best is None or pick > best:
                        best = pick
            if best is None:
                break
            _, _, key, s = best
            by += bytes_tab[(key, s[0])] - bytes_tab[(key, current[key][0])]
            cy += cyc_tab[(key, s)] - cyc_tab[(key, current[key])]
            current[key] = s
        by, cy = swap_refine(current, by, cy)
        predicted = sum(err(u, current[u.key]) for u in units)
        return current, by, cy, predicted

    def swap_refine(current, by, cy):
        """Pairwise trades: move one unit to a cheaper state to fund a
        more accurate state elsewhere (e.g. drop one layer's abits to
        afford another layer's extra weight bit at a tight cycle
        budget)."""
        while True:
            best = None  # (net_err_delta, key_d, s_d, key_u, s_u)
            for ud in free:
                cur_d = current[ud.key]
                for sd in unit_states(ud):
                    d_by = bytes_tab[(ud.key, sd[0])] - \
                        bytes_tab[(ud.key, cur_d[0])]
                    d_cy = cyc_tab[(ud.key, sd)] - cyc_tab[(ud.key, cur_d)]
                    if d_cy >= 0 and d_by >= 0:
                        continue   # not a funding move
                    loss = err(ud, sd) - err(ud, cur_d)
                    for uu in free:
                        if uu.key == ud.key:
                            continue
                        cur_u = current[uu.key]
                        for su in unit_states(uu):
                            gain = err(uu, cur_u) - err(uu, su)
                            if gain <= 0:
                                continue
                            nby = by + d_by + \
                                bytes_tab[(uu.key, su[0])] - \
                                bytes_tab[(uu.key, cur_u[0])]
                            ncy = cy + d_cy + \
                                cyc_tab[(uu.key, su)] - \
                                cyc_tab[(uu.key, cur_u)]
                            if not fits(nby, ncy):
                                continue
                            net = loss - gain
                            pick = (net, ud.key, sd, uu.key, su)
                            if net < -1e-15 and (best is None
                                                 or pick < best):
                                best = pick
            if best is None:
                return by, cy
            _, kd, sd, ku, su = best
            by += (bytes_tab[(kd, sd[0])] - bytes_tab[(kd, current[kd][0])]
                   + bytes_tab[(ku, su[0])]
                   - bytes_tab[(ku, current[ku][0])])
            cy += (cyc_tab[(kd, sd)] - cyc_tab[(kd, current[kd])]
                   + cyc_tab[(ku, su)] - cyc_tab[(ku, current[ku])])
            current[kd] = sd
            current[ku] = su

    solutions = [s for s in (climb(st) for st in states) if s is not None]
    if not solutions:
        current = {u.key: min_state(u) for u in units}
        by, cy = totals(current)
        predicted = sum(err(u, current[u.key]) for u in units)
        return JointAllocationReport(
            bits_by_unit=current, bytes_total=by, cycles_total=cy,
            byte_budget=byte_budget, cycle_budget=float(cycle_budget),
            predicted_error=predicted, feasible=False)
    current, by, cy, predicted = min(solutions,
                                     key=lambda s: (s[3], s[2], s[1]))
    return JointAllocationReport(
        bits_by_unit=current, bytes_total=by, cycles_total=cy,
        byte_budget=byte_budget, cycle_budget=float(cycle_budget),
        predicted_error=predicted, feasible=True)


def _spec_map_from_units(assign: Mapping[UnitKey, int]) -> Dict[str, Any]:
    """{(path, layer): bits} -> {path: bits | per-layer tuple}."""
    per_path: Dict[str, Any] = {}
    layered: Dict[str, Dict[int, int]] = {}
    for (path, layer), b in assign.items():
        if layer is None:
            per_path[path] = int(b)
        else:
            layered.setdefault(path, {})[layer] = int(b)
    for path, by_layer in layered.items():
        n_layers = max(by_layer) + 1
        if set(by_layer) != set(range(n_layers)):
            raise ValueError(f"allocation for {path} misses layers: "
                             f"{sorted(by_layer)}")
        per_path[path] = tuple(by_layer[i] for i in range(n_layers))
    return per_path


def _allocation_from_units(bits_by_unit: Mapping[UnitKey, Any]):
    """Unit assignment -> BitAllocation.

    Values are scalar wbits (weight-only solve) or (wbits, abits) pairs
    (joint solve, which also fills ``act_per_path``)."""
    from repro.models.sail_linear import BitAllocation
    joint = any(isinstance(b, (tuple, list))
                for b in bits_by_unit.values())
    if not joint:
        return BitAllocation(per_path=_spec_map_from_units(bits_by_unit))
    return BitAllocation(
        per_path=_spec_map_from_units(
            {k: s[0] for k, s in bits_by_unit.items()}),
        act_per_path=_spec_map_from_units(
            {k: s[1] for k, s in bits_by_unit.items()}))


def _segment_cuts(assign: Mapping[UnitKey, Any], paths, n_layers
                  ) -> List[int]:
    """Layer cut points of an assignment: a cut wherever ANY stacked
    path's state differs between adjacent layers (the same rule
    ``sail_linear._segment_bounds`` applies to the emitted policy, so
    the allocator's cap and the actual scan segmentation agree).
    Equal-adjacent layers never produce a cut — the lossless merge."""
    cuts = [0]
    for layer in range(1, n_layers):
        if any(assign.get((p, layer)) != assign.get((p, layer - 1))
               for p in paths):
            cuts.append(layer)
    cuts.append(n_layers)
    return cuts


def segment_count(assign: Mapping[UnitKey, Any]) -> int:
    """Number of uniform-precision scan segments an assignment implies.

    Adjacent layers whose joint assignment matches across every stacked
    path share a segment; non-stacked units don't segment anything."""
    layers = sorted({k[1] for k in assign if k[1] is not None})
    if not layers:
        return 1
    paths = sorted({k[0] for k in assign if k[1] is not None})
    return len(_segment_cuts(assign, paths, max(layers) + 1)) - 1


def enforce_max_segments(units: Sequence[Unit],
                         assign: Dict[UnitKey, Any],
                         max_segments: int,
                         err_of=None,
                         bytes_of=None) -> Dict[UnitKey, Any]:
    """Cap the number of scan segments by merging adjacent segments.

    Each uniform-bits segment compiles its own scan body, so an
    unconstrained per-layer allocation can multiply trace/compile cost.
    While over the cap, the adjacent segment pair whose merge costs the
    least predicted error is coalesced: per stacked path the merged range
    adopts whichever side's assignment raises the summed unit error
    least.  Adjacent segments that already agree merge for free (the
    lossless case); equal-adjacent layers never count as separate
    segments in the first place (see :func:`segment_count`).

    With a ``bytes_of(unit, state)`` hook, a direction that grows the
    byte footprint is taken only when no byte-neutral direction exists:
    merging must spend error, not the byte budget the assignment was
    solved under (one side of every disagreeing pair adopts the
    narrower state, so a non-growing direction always exists for
    weight bits).  Joint (wbits, abits) merges can still leave the
    *cycle* budget — ``calibrate_policy`` re-derives the report's
    ``feasible`` flag after capping for exactly this reason.
    """
    if max_segments < 1:
        raise ValueError(f"max_segments must be >= 1, got {max_segments}")
    if err_of is None:
        def err_of(u, s):
            if isinstance(s, (tuple, list)):
                return u.errors[s[0]] + u.aerrors[s[1]]
            return u.errors[s]
    assign = dict(assign)
    by_key = {u.key: u for u in units}
    paths = sorted({k[0] for k in assign if k[1] is not None})
    layers = sorted({k[1] for k in assign if k[1] is not None})
    if not layers:
        return assign
    n_layers = max(layers) + 1

    while True:
        cuts = _segment_cuts(assign, paths, n_layers)
        if len(cuts) - 1 <= max_segments:
            return assign
        best = None   # (err_delta, cut_index, {(path, layer): state})
        for i in range(1, len(cuts) - 1):
            a, b, c = cuts[i - 1], cuts[i], cuts[i + 1]
            delta = 0.0
            moves: Dict[UnitKey, Any] = {}
            for p in paths:
                lv, rv = assign[(p, a)], assign[(p, b)]
                if lv == rv:
                    continue
                # adopt the left value over [b, c) or the right over [a, b)
                d_left = sum(err_of(by_key[(p, layer)], lv)
                             - err_of(by_key[(p, layer)],
                                      assign[(p, layer)])
                             for layer in range(b, c))
                d_right = sum(err_of(by_key[(p, layer)], rv)
                              - err_of(by_key[(p, layer)],
                                       assign[(p, layer)])
                              for layer in range(a, b))
                take_left = d_left <= d_right
                if bytes_of is not None:
                    b_left = sum(bytes_of(by_key[(p, layer)], lv)
                                 - bytes_of(by_key[(p, layer)],
                                            assign[(p, layer)])
                                 for layer in range(b, c))
                    b_right = sum(bytes_of(by_key[(p, layer)], rv)
                                  - bytes_of(by_key[(p, layer)],
                                             assign[(p, layer)])
                                  for layer in range(a, b))
                    if b_left > 0 and b_right <= 0:
                        take_left = False
                    elif b_right > 0 and b_left <= 0:
                        take_left = True
                if take_left:
                    delta += d_left
                    for layer in range(b, c):
                        moves[(p, layer)] = lv
                else:
                    delta += d_right
                    for layer in range(a, b):
                        moves[(p, layer)] = rv
            if best is None or (delta, i) < best[:2]:
                best = (delta, i, moves)
        assign.update(best[2])


def _tokens_from_calib_batches(calib_batches) -> jax.Array:
    """Held-out token batches -> one [B, T] calibration array.

    Accepts a single [B, T] array or a sequence of [b_i, T] arrays (e.g.
    batches drawn from an eval data pipeline), concatenated along batch.
    """
    if isinstance(calib_batches, (list, tuple)):
        arrs = [np.asarray(b) for b in calib_batches]
        widths = {a.shape[-1] for a in arrs}
        if len(widths) != 1:
            raise ValueError(
                f"calib_batches have mixed sequence lengths {widths}")
        arr = np.concatenate([a.reshape(-1, a.shape[-1]) for a in arrs], 0)
    else:
        arr = np.asarray(calib_batches)
        if arr.ndim == 1:
            arr = arr[None]
    return jnp.asarray(arr, jnp.int32)


def calibrate_policy(params, cfg, policy=None, budget_bytes=None,
                     match_uniform: Optional[int] = None,
                     budget_bpw: Optional[float] = None,
                     tokens=None, mode: str = "output",
                     bits_candidates: Sequence[int] = SUPPORTED_BITS,
                     per_layer: bool = True, calib_batch: int = 4,
                     calib_seq: int = 32, scores=None,
                     calib_batches=None,
                     abits_candidates: Optional[Sequence[int]] = None,
                     act_scores=None, cycle_budget: Optional[float] = None,
                     match_uniform_abits: int = 8,
                     prt="paper", prt_calib=None, cost_batch: int = 8,
                     cost_threads: int = 16, machine=None,
                     max_segments: Optional[int] = None):
    """Score sensitivities and solve the budgeted allocation.

    Weight-only (default): minimize total predicted error subject to
    ``bytes <= budget``, where the budget is one of ``budget_bytes``
    (absolute), ``match_uniform=b`` (bytes of uniform b-bit),
    ``budget_bpw`` (bits per quantizable weight).

    Joint mode (``abits_candidates`` given): additionally allocate the
    activation precision per unit under a projected-cycles budget —
    ``cycle_budget`` (absolute C-SRAM cycles per decode iteration), or by
    default the projected cycles of the uniform reference
    ``(match_uniform or policy.bits, match_uniform_abits)`` — the joint
    answer then Pareto-improves the weight-only one at equal projected
    speed.  ``prt`` selects the pattern-discount model ("paper" flat
    13.8% or "measured" per-precision hit rates); the byte budget is only
    enforced in joint mode when ``budget_bytes`` is explicit (cycles are
    what bound decode speed; weight bytes only bound DRAM residency).

    Calibration data: ``tokens`` (explicit array), or ``calib_batches``
    (held-out token batches from a real eval pipeline — single [B, T]
    array or list of same-T arrays), else the synthetic default.  Under
    ``prt="measured"`` the PRT hit rates are simulated on ``prt_calib``
    (f32 [B, K] activations) — when omitted, the calibration tokens'
    embedding vectors stand in for real hidden activations (capped at
    ``cost_batch`` rows), falling back to the synthetic normal batch.

    Paths matched by ``policy.rules`` / ``policy.act_rules`` are pinned
    to their rule bits and charged against the budgets.  ``scores`` /
    ``act_scores`` short-circuit the probing — budget sweeps score once,
    solve many.  ``max_segments`` caps the scan-segment count of the
    resulting per-layer allocation (merging adjacent segments at least
    predicted-error cost; see :func:`enforce_max_segments`).

    Returns ``(policy_with_allocation, AllocationReport |
    JointAllocationReport)``.
    """
    from repro.models.sail_linear import QuantPolicy
    policy = policy or QuantPolicy()
    joint = abits_candidates is not None
    if not joint and prt not in ("paper", True):
        raise ValueError(
            f"prt={prt!r} only affects the joint (wbits, abits) cycle "
            "budget — a weight-only allocation is priced in bytes, so "
            "the option would be silently ignored; add a<ab> to the "
            "spec (abits_candidates=) to enable joint mode")
    if calib_batches is not None and tokens is None:
        tokens = _tokens_from_calib_batches(calib_batches)
    if scores is not None:
        pass
    elif mode == "output":
        if tokens is None:
            tokens = calibration_tokens(cfg.vocab, calib_batch, calib_seq)
        scores = output_sensitivity(params, cfg, tokens, policy,
                                    bits_candidates, per_layer)
    elif mode == "weight":
        if joint:
            raise ValueError(
                "joint (wbits, abits) allocation requires mode='output': "
                "weight_sensitivity scores are weight-space SSE while "
                "activation probes are logit MSE — summing them would let "
                "the larger scale silently dominate the trade-off")
        scores = weight_sensitivity(params, policy, bits_candidates,
                                    per_layer)
    else:
        raise ValueError(f"mode must be 'output' or 'weight', got {mode}")
    if joint and act_scores is None:
        if tokens is None:
            tokens = calibration_tokens(cfg.vocab, calib_batch, calib_seq)
        act_scores = activation_sensitivity(params, cfg, tokens, policy,
                                            abits_candidates, per_layer)

    units: List[Unit] = []
    pinned: Dict[UnitKey, int] = {}
    pinned_act: Dict[UnitKey, int] = {}
    total_weights = 0
    for pstr, w, stacked in quantizable_units(params, policy):
        k, n = w.shape[-2:]
        per_slice_copies = 1
        for d in w.shape[1:-2]:
            per_slice_copies *= d
        total_weights += w.size
        keys = ([(pstr, layer) for layer in range(w.shape[0])]
                if stacked and per_layer else [(pstr, None)])
        copies = (per_slice_copies if stacked and per_layer
                  else per_slice_copies * (w.shape[0] if stacked else 1))
        rule_bits = None
        for pat, b in policy.rules:
            if re.search(pat, pstr):
                rule_bits = int(b)
                if rule_bits not in bits_candidates:
                    raise ValueError(
                        f"rule ({pat!r}, {b}) pins {pstr} outside the "
                        f"scored candidates {tuple(bits_candidates)}")
                break
        act_rule_bits = None
        if joint:
            for pat, b in policy.act_rules:
                if re.search(pat, pstr):
                    act_rule_bits = int(b)
                    if act_rule_bits not in abits_candidates:
                        raise ValueError(
                            f"act rule ({pat!r}, {b}) pins {pstr} outside "
                            f"the scored candidates "
                            f"{tuple(abits_candidates)}")
                    break
        for key in keys:
            units.append(Unit(path=pstr, layer=key[1], k=k, n=n,
                              copies=copies, errors=scores[key],
                              aerrors=(act_scores[key] if joint
                                       else None)))
            if rule_bits is not None:
                pinned[key] = rule_bits
            if act_rule_bits is not None:
                pinned_act[key] = act_rule_bits

    # a bpw request is an explicit byte budget too — joint mode must not
    # silently drop it just because it arrives in different units
    explicit_bytes = budget_bytes is not None or budget_bpw is not None
    if budget_bytes is None:
        if match_uniform is not None:
            budget_bytes = uniform_bytes(params, policy, match_uniform)
        elif budget_bpw is not None:
            budget_bytes = int(budget_bpw * total_weights / 8)
        else:
            budget_bytes = uniform_bytes(params, policy, policy.bits)

    if joint:
        from repro.core import cost_model as cm
        if prt == "measured" and prt_calib is None and tokens is not None \
                and isinstance(params, dict) and "embed" in params:
            # real-data stand-in for hidden activations: the calibration
            # tokens' embedding vectors (one PRT compute-batch worth)
            emb = np.asarray(jnp.take(params["embed"],
                                      jnp.asarray(tokens), axis=0),
                             np.float32)
            prt_calib = emb.reshape(-1, emb.shape[-1])[:cost_batch]
        if cycle_budget is None:
            ref_wb = match_uniform if match_uniform is not None \
                else policy.bits
            cycle_budget = cm.mixed_decode_cycles(
                [(u.k, u.n, ref_wb, match_uniform_abits, u.copies)
                 for u in units],
                machine=machine or cm.SailMachine(), batch=cost_batch,
                nbw="auto", threads=cost_threads, prt=prt,
                calib=prt_calib)
        report = allocate_bits_joint(
            units, cycle_budget, policy.group_size,
            byte_budget=budget_bytes if explicit_bytes else None,
            bits_candidates=bits_candidates,
            abits_candidates=abits_candidates,
            pinned=pinned, pinned_act=pinned_act, batch=cost_batch,
            threads=cost_threads, machine=machine, prt=prt,
            calib=prt_calib)
    else:
        report = allocate_bits(units, budget_bytes, policy.group_size,
                               bits_candidates, pinned)
    assign = dict(report.bits_by_unit)
    if max_segments is not None:
        def seg_bytes(u, s):
            return unit_bytes(u.k, u.n, s[0] if joint else s,
                              policy.group_size, u.copies)

        capped = enforce_max_segments(units, assign, max_segments,
                                      bytes_of=seg_bytes)
        if capped != assign:
            assign = capped
            nbytes = sum(unit_bytes(
                u.k, u.n,
                assign[u.key][0] if joint else assign[u.key],
                policy.group_size, u.copies) for u in units)
            err = sum(
                (u.errors[assign[u.key][0]] + u.aerrors[assign[u.key][1]])
                if joint else u.errors[assign[u.key]]
                for u in units)
            # merging adopts a neighbor's (wider or narrower) state, so
            # the capped assignment can leave the budgets — re-derive
            # feasible so callers are never told a violating allocation
            # fits
            if joint:
                cycles = cm.mixed_decode_cycles(
                    [(u.k, u.n, assign[u.key][0], assign[u.key][1],
                      u.copies) for u in units],
                    machine=machine or cm.SailMachine(), batch=cost_batch,
                    nbw="auto", threads=cost_threads, prt=prt,
                    calib=prt_calib)
                ok = (cycles <= report.cycle_budget * (1 + 1e-9)
                      and (report.byte_budget is None
                           or nbytes <= report.byte_budget))
                report = dataclasses.replace(
                    report, bits_by_unit=assign, bytes_total=nbytes,
                    cycles_total=cycles, predicted_error=err,
                    feasible=report.feasible and ok)
            else:
                report = dataclasses.replace(
                    report, bits_by_unit=assign, bytes_total=nbytes,
                    predicted_error=err,
                    feasible=(report.feasible
                              and nbytes <= report.budget_bytes))
    allocation = _allocation_from_units(assign)
    return dataclasses.replace(policy, allocation=allocation), report


# public alias: the planner emits solved PlanSpecs from solver reports
spec_map_from_units = _spec_map_from_units
