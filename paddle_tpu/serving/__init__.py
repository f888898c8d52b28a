"""paddle_tpu.serving — continuous-batching LLM inference.

One serving engine, ``LLMEngine``: Orca-style iteration-level scheduling
over a device-resident paged K/V pool (vLLM-style block tables, a prefix
cache and chunked prefill, specialised to TPU static shapes), plus the
sampling helpers it shares with ``GPT.generate``, and the elastic
multi-replica layer on top: ``ServingFleet`` runs N engines behind an
SLO-aware ``Router`` with heartbeat health-checking and fault-driven
drain/respawn.  A fleet can run disaggregated — prefill replicas hand
finished prompts to decode replicas by block-granular KV migration, with
``FleetAutoscaler`` rebalancing the split from health-plane burn alerts.  Engines can run tensor-parallel over a JAX mesh
(``LLMEngine(mesh=...)``): the ``StateArena`` spec layer shards the KV
block pools' head axis and the weight matrices across chips while the
compiled programs stay single (GSPMD inserts in-graph collectives).  See
``serving.paged`` (the engine: cache and programs), ``serving.engine``
(the request lifecycle under it), ``serving.block_decode`` (the engine of
a model that generates by diffusion over blocks), ``serving.fleet`` and ``serving.arena``
for the design notes and README "Serving" / "Elastic serving" / "Disaggregated
serving" / "Sharded serving" for the API tour.
"""

from .arena import (DEFAULT_SHARD_RULES, KV_POOL_SPEC,  # noqa: F401
                    StateArena)
from .autoscale import FleetAutoscaler  # noqa: F401
from .block_decode import BlockDecodeLLMEngine  # noqa: F401
from .engine import (BlockDecodeUnsupported,  # noqa: F401
                     EngineBackpressure, EngineClosed,
                     LatentCacheUnsupported, RecurrentStateUnsupported,
                     Request, WindowCacheUnsupported, bucket_length)
from .fleet import FleetRequest, Replica, ServingFleet  # noqa: F401
from .kvcache import (BlockPool, BlockPoolExhausted,  # noqa: F401
                      PrefixCache, blocks_for_tokens)
from .paged import LLMEngine  # noqa: F401
from .router import RetryAfter, Router  # noqa: F401
from .sampling import filter_logits, residual_sample, sample_tokens  # noqa: F401
from .speculative import SpeculativeLLMEngine  # noqa: F401

__all__ = ["LLMEngine", "SpeculativeLLMEngine", "BlockDecodeLLMEngine",
           "Request", "EngineBackpressure", "EngineClosed",
           "RecurrentStateUnsupported", "LatentCacheUnsupported",
           "WindowCacheUnsupported", "BlockDecodeUnsupported",
           "bucket_length",
           "filter_logits", "sample_tokens", "residual_sample",
           "ServingFleet", "FleetRequest", "Replica", "FleetAutoscaler",
           "Router", "RetryAfter", "BlockPool", "BlockPoolExhausted",
           "PrefixCache", "blocks_for_tokens", "StateArena",
           "DEFAULT_SHARD_RULES", "KV_POOL_SPEC"]
