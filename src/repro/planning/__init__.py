"""Precision planning: the single front door for mixed-precision serving.

``PlanSpec`` (the typed plan), ``DecodeCostModel`` (DRAM-aware pricing),
``Planner`` (offline solve + SLO budgets + online replan), and
``ActivationTap`` (live-traffic capture).  See ``repro/planning/spec.py``
for the object model and README "Planning API" for the migration story.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional

from repro.planning.calibrate_cost import (
    CalibrationResult,
    dispatch_from_json,
    machine_from_json,
    run_calibration,
)
from repro.planning.cost import (
    DEFAULT_LINK_BW,
    Budgets,
    DecodeCostModel,
    PlanCost,
    Slo,
    calib_for_layer,
    expected_tokens_per_round,
    kv_block_bytes,
    kv_pool_blocks,
    kv_token_bytes,
    policy_units,
    speculative_round_seconds,
    tp_allreduce_elems,
    unquantized_bytes,
)
from repro.planning.planner import Planner, PlanResult
from repro.planning.spec import DraftSpec, PlanRule, PlanSpec
from repro.planning.tap import ActivationTap

__all__ = [
    "ActivationTap",
    "Budgets",
    "CalibrationResult",
    "DEFAULT_LINK_BW",
    "DecodeCostModel",
    "DraftSpec",
    "PlanCost",
    "PlanRule",
    "PlanResult",
    "PlanSpec",
    "Planner",
    "Slo",
    "as_plan",
    "calib_for_layer",
    "dispatch_from_json",
    "expected_tokens_per_round",
    "kv_block_bytes",
    "kv_pool_blocks",
    "kv_token_bytes",
    "machine_from_json",
    "plan_from_arg",
    "policy_units",
    "resolve_plan",
    "speculative_round_seconds",
    "run_calibration",
    "tp_allreduce_elems",
    "unquantized_bytes",
]


def plan_from_arg(value: Any) -> PlanSpec:
    """CLI plan argument -> PlanSpec: an existing PlanSpec passes
    through; a string is loaded as a plan file when it exists on disk or
    ends in .json, else parsed as grammar.  The one sniffing rule every
    launcher shares."""
    import os

    if isinstance(value, PlanSpec):
        return value
    if isinstance(value, str) and (os.path.exists(value) or value.endswith(".json")):
        return PlanSpec.load(value)
    return as_plan(value)


def as_plan(obj: Any) -> PlanSpec:
    """Coerce any accepted plan form to a PlanSpec: an existing PlanSpec,
    a grammar string, or a plan JSON dict."""
    if isinstance(obj, PlanSpec):
        return obj
    if isinstance(obj, str):
        return PlanSpec.parse(obj)
    if isinstance(obj, Mapping):
        return PlanSpec.from_json(obj)
    raise TypeError(f"cannot interpret {type(obj).__name__!r} as a PlanSpec")


def resolve_plan(
    plan: Any,
    params,
    cfg,
    base=None,
    slo: Optional[Slo] = None,
    cost: Optional[DecodeCostModel] = None,
    tokens=None,
    compute_cost: bool = False,
) -> PlanResult:
    """Plan -> servable PlanResult.

    Uniform/rules plans and *solved* auto plans (e.g. loaded from a
    ``plan.json``) resolve directly — no calibration runs.  Unsolved auto
    plans run a :class:`Planner` (sensitivity probes + joint solve,
    honoring ``slo``/``plan.target_tps``).  ``compute_cost`` prices the
    result under the DRAM-aware model (skipped by default: the engine
    hot path doesn't need it).
    """
    plan = as_plan(plan)
    if plan.solved:
        planner = Planner(params, cfg, plan, base=base, cost=cost, tokens=tokens)
        policy = plan.to_policy(planner.base)
        return PlanResult(
            spec=plan,
            policy=policy,
            cost=planner._price(policy, plan, None, slo) if compute_cost else None,
        )
    planner = Planner(params, cfg, plan, base=base, cost=cost, tokens=tokens)
    return planner.solve(slo=slo)
