"""Serving runtime: prefill, decode, KV-cache management, batching and
compressed-activation serving plans."""
from .batching import ContinuousBatcher, Request
from .decode import decode_step, prefill, prefill_replay
from .generate import Generation, generate
from .kvcache import cache_shardings, cache_specs, init_cache
from .plans import (
    ServingPlans,
    SitePlan,
    activation_sites,
    build_serving_plans,
    verify_backend_equivalence,
)
from .sharded import (
    PlacementPolicy,
    ShardedServe,
    place_tables,
    plan_placement_report,
    serve_cache_shardings,
    serve_param_shardings,
)
from .stacked import StackedPlanArrays, tables_nbytes

__all__ = ["prefill", "decode_step", "prefill_replay", "generate",
           "Generation", "cache_specs",
           "init_cache", "cache_shardings", "ContinuousBatcher", "Request",
           "ServingPlans", "SitePlan", "StackedPlanArrays",
           "activation_sites", "build_serving_plans", "tables_nbytes",
           "verify_backend_equivalence", "ShardedServe", "PlacementPolicy",
           "place_tables", "plan_placement_report", "serve_param_shardings",
           "serve_cache_shardings"]
