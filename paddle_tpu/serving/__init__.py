"""paddle_tpu.serving — continuous-batching inference with a paged KV
cache (docs/SERVING.md).

The ROADMAP's serving-side subsystem: the single-request ZeroCopy
`Predictor` (paddle_tpu.inference) answers one client; this package
serves MANY — queued requests are continuously batched into a
fixed-shape decode step over a paged KV cache (Ragged Paged Attention,
PAPERS.md), with capacity-based admission, deadlines, preemption,
backpressure and /stats counters.

Quickstart (in-process):

    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import Engine, GPTDecodeModel

    model = GPTDecodeModel(GPTConfig.tiny())
    with Engine(model, num_slots=8, num_pages=64, page_size=16) as eng:
        tokens = eng.generate([1, 2, 3], max_new_tokens=16)

Network mode (PS wire format, see serving/frontend.py):

    from paddle_tpu.serving import ServingServer, ServingClient
    srv = ServingServer(engine).start()          # engine-owned thread
    out = ServingClient(srv.endpoint).generate([1, 2, 3], 16)

Models whose layers keep other state than K/V (a convolution's last
inputs, per slot) are served through the same Engine by
`HybridDecodeModel` (models/lfm2.py), and a looped decoder, whose layers
run several times a token with K/V of every pass, by `LoopedDecodeModel`
(models/ouro.py), and one that caches a latent row a token in place of
keys and values by `LatentDecodeModel` (models/deepseek_v3.py), and one
whose layers keep a recurrence's state per slot beside a few attention
layers by `RecurrentDecodeModel` (models/jamba.py);
`DecodeModel` (serving/model.py) is what the engine asks
of each.

Replicated fleet (serving/router.py, docs/SERVING.md): a Router fronts
N replicas with least-loaded dispatch, session affinity, streaming
token frames, exactly-once failover, draining, and elastic respawn
from engine checkpoints — the same ServingClient talks to it.
"""
from .kv_cache import PagePool, PageTable, defrag_plan, pages_needed
from .prefix_cache import PrefixCache, PrefixMatch
from .sampling import SamplingParams, derive_seed
from .scheduler import (QueueFull, QuotaExceeded, Request, Scheduler,
                        TokenBucket)
from .model import (DecodeModel, GPTDecodeModel, HybridDecodeModel,
                    LatentDecodeModel, LoopedDecodeModel,
                    RecurrentDecodeModel, WindowedDecodeModel)
from .engine import Engine
from .frontend import ServingClient, ServingServer
from .loadgen import (Arrival, LoadGenerator, LoadResult, TrafficConfig,
                      slo_report)
from .router import InProcessReplica, Replica, ReplicaSpec, Router

__all__ = [
    "PagePool", "PageTable", "pages_needed", "defrag_plan",
    "PrefixCache", "PrefixMatch", "SamplingParams", "derive_seed",
    "Request", "Scheduler", "QueueFull", "QuotaExceeded", "TokenBucket",
    "DecodeModel", "GPTDecodeModel", "HybridDecodeModel",
    "LoopedDecodeModel", "LatentDecodeModel", "WindowedDecodeModel",
    "RecurrentDecodeModel", "Engine",
    "ServingServer", "ServingClient",
    "Arrival", "LoadGenerator", "LoadResult", "TrafficConfig",
    "slo_report",
    "Router", "ReplicaSpec", "Replica", "InProcessReplica",
]
