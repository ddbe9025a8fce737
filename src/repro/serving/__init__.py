"""The continuous-batching geo serving engine (see docs/serving.md):
family-polymorphic per-server state pools (StateSpec-dispatched), pooled
decode + bucketed prefill steps with a pluggable compute backend
(``GeoServingSystem(backend="xla" | "pallas")`` — oracle jnp paths vs the
``repro.kernels`` Pallas kernels with per-call XLA fallback), slab and
paged cache layouts (``cache_layout="paged"``: PagePool free-list
allocation, page-granular eq. (5)/(20) accounting, preemption/resume),
per-session sampling policies, the event-loop scheduler, and the
session/request record types."""
from repro.launch.sharding import DeviceGroup, as_device_group
from repro.serving.engine import (BlockServer, EngineSession,
                                  GeoServingSystem, generate)
from repro.serving.faults import (FailureDetector, FaultEvent, FaultPlan,
                                  NoCapacityError, recovery_replay_cost)
from repro.serving.kv_cache import (SUPPORTED_KINDS, CachePool, PagePool,
                                    StateSpec, bucket_for,
                                    default_prefill_buckets, kind_runs,
                                    make_paged_decode_step,
                                    make_paged_prefill_step,
                                    make_paged_round_step,
                                    make_paged_row_prefill_step,
                                    make_pool_decode_step,
                                    make_pool_prefill_step,
                                    make_pool_round_step,
                                    make_pool_row_prefill_step,
                                    new_block_cache,
                                    new_cache_pool_tree, new_paged_pool_tree,
                                    new_state_pool_tree, pages_for,
                                    state_spec_for, state_specs,
                                    write_prefill_kv)
from repro.serving.sampling import SamplingSpec, make_round_tail, make_sampler
from repro.serving.scheduler import (AdmissionScheduler,
                                     ContinuousBatchingScheduler,
                                     ServedRequest)

__all__ = ["AdmissionScheduler", "BlockServer", "CachePool",
           "ContinuousBatchingScheduler", "DeviceGroup", "EngineSession",
           "FailureDetector", "FaultEvent", "FaultPlan",
           "GeoServingSystem", "NoCapacityError", "PagePool",
           "SUPPORTED_KINDS", "SamplingSpec",
           "ServedRequest", "StateSpec", "as_device_group", "bucket_for",
           "default_prefill_buckets", "generate", "recovery_replay_cost",
           "kind_runs", "make_paged_decode_step", "make_paged_prefill_step",
           "make_paged_round_step", "make_paged_row_prefill_step",
           "make_pool_decode_step", "make_pool_prefill_step",
           "make_pool_round_step", "make_pool_row_prefill_step",
           "make_round_tail", "make_sampler", "new_block_cache",
           "new_cache_pool_tree", "new_paged_pool_tree",
           "new_state_pool_tree", "pages_for", "state_spec_for",
           "state_specs", "write_prefill_kv"]
