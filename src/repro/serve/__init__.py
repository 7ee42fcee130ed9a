from repro.serve.decode import (decode_step, init_caches, init_paged_caches,
                                paged_cache_kinds, serving_params)
from repro.serve.engine import ServeEngine, generate, schedule_plan
from repro.serve.loadgen import TrafficConfig, poisson_trace, run_load
from repro.serve.pool import KVBlockPool, PoolCapacityError, PoolError
from repro.serve.scheduler import FairScheduler, Request, Tenant

__all__ = ["decode_step", "init_caches", "init_paged_caches",
           "paged_cache_kinds", "serving_params",
           "generate",
           "ServeEngine", "schedule_plan", "KVBlockPool",
           "PoolCapacityError", "PoolError", "FairScheduler", "Request",
           "Tenant", "TrafficConfig", "poisson_trace", "run_load"]
