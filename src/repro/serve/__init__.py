"""Long-lived survey serving: plan-cached, multi-tenant, epoch-pipelined.

The one-shot pipeline (``plan_engine`` → ``shard_dodgr`` →
``jax.jit(make_survey_fn)`` → traverse) pays planning, sharding, and
compilation on every request. This package amortizes all three:

* :mod:`repro.serve.plan_cache` — content-keyed LRU over (plan, shards,
  jitted closure) triplets with byte-budget eviction, persistable across
  process restarts (:func:`save_plan_cache` / :func:`load_plan_cache`);
* :mod:`repro.serve.coalesce` — many tenants' questions against the same
  graph epoch merged into one :class:`~repro.core.surveys.SurveyBundle`
  traversal, with per-tenant extraction afterwards;
* :mod:`repro.serve.ingest` — background epoch pipeline: ``append_edges``
  batches are sharded and delta-surveyed off the query path;
* :mod:`repro.serve.service` — :class:`SurveyService`, the long-lived
  front door tying them together.

Everything served is bitwise-identical to the one-shot ``survey_*`` path
(docs/serve.md, docs/determinism.md: warm == cold == solo).
"""
from repro.serve.coalesce import TenantRequest, coalesce, extract
from repro.serve.plan_cache import (CacheEntry, PlanCache, entry_nbytes,
                                    load_plan_cache, save_plan_cache)
from repro.serve.service import SurveyService

__all__ = ["CacheEntry", "PlanCache", "SurveyService", "TenantRequest",
           "coalesce", "entry_nbytes", "load_plan_cache", "save_plan_cache"]
