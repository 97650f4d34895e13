"""repro.serve — continuous-batching inference engine.

Serving on top of the model zoo's ``prefill_chunk`` / ``decode_step``: a
fixed-shape decode batch of ``n_slots`` sequences, priority-class
admission (interactive/batch, FCFS within a class, preemption by page
eviction under pressure), per-request sampling/stop, and caches that
shard through ``repro.dist`` logical-axis rules. ``server.GenerateServer``
puts an HTTP/SSE streaming frontend in front of the engine. The engine
keeps K/V in a paged cache (``PagedCache`` — global KV page pool, block
tables, ref-counted prefix reuse, chunked prefill, paged-attention
decode). Speculative decoding (``Engine(..., spec_draft=(model, params))``)
adds a draft that proposes k tokens against its own page pool; the target
verifies the window in one dispatch, and draft+target share one prefix
trie.

``resilience`` adds the fault-tolerance layer: a deterministic seeded
``FaultInjector`` (chaos testing), a per-slot watchdog that quarantines
non-finite logits without perturbing co-batched requests, a reversible
``DegradationLadder`` (spec off -> prefix flush -> load shed), and
deadline/retry policy — all bundled into ``Resilience`` and passed as
``Engine(..., resilience=...)``.
"""

from .cache import (PagedCache, PagePool, PrefixTrie, publish_prefix_shared,
                    share_trie)
from .engine import Engine, Handoff
from .metrics import (RequestMetrics, RouterMetrics, ServeMetrics,
                      merge_request_metrics, render_prometheus)
from .router import Router, prefix_affinity_key
from .resilience import (STAGE_NAMES, DegradationLadder, FaultInjector,
                         FaultSpec, InjectedFault, Resilience, parse_schedule,
                         storm_schedule)
from .sampling import SamplingParams, sample, spec_accept
from .scheduler import PRIORITIES, Request, RequestState, Scheduler
from .server import GenerateServer

__all__ = [
    "Engine", "PagedCache", "PagePool", "PrefixTrie",
    "share_trie", "publish_prefix_shared",
    "ServeMetrics", "RequestMetrics", "RouterMetrics", "GenerateServer",
    "Router", "Handoff", "prefix_affinity_key", "render_prometheus",
    "merge_request_metrics",
    "SamplingParams", "sample", "spec_accept", "Request", "RequestState",
    "Scheduler", "PRIORITIES",
    "FaultInjector", "FaultSpec", "InjectedFault", "DegradationLadder",
    "Resilience", "parse_schedule", "storm_schedule", "STAGE_NAMES",
]
