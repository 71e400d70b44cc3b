"""`paddle_tpu.serving` — continuous-batching generation serving runtime
(docs/SERVING.md).

The "millions of users" leg of the north star: a multi-model generation
service that batches concurrent requests at decode-*step* granularity
(Orca-style iteration-level scheduling over one fixed-shape XLA step, so
joins/retires never retrace) with a blocked KV-cache pool (vLLM-style
block tables) for memory feasibility. Prompts are prefilled in chunks
(Sarathi-style mixed prompt-window/decode steps, ``prefill_chunk=`` /
``$PTPU_SERVE_PREFILL_CHUNK`` sets the chunk's size). Opt-in on top:
radix prefix caching (content-addressed refcounted KV block sharing
across requests, ``prefix_cache=`` / ``$PTPU_SERVE_PREFIX_CACHE``) and
speculative decoding (draft-k tokens — n-gram prompt lookup by default,
or a pluggable draft model — verified in one batched target step,
``spec_k=`` / ``$PTPU_SERVE_SPEC_K``). A second decoder block,
latent attention over a paged latent cache with sigmoid-routed experts
(``GenerationConfig(block=LatentMoEBlock(...))``, ``latent_moe.py``),
runs through the same engine, scheduler and pool accounting; so does a
third, grouped-query attention over window and global layers that keep
their cache in two kinds of page (``AfmoeBlock``, ``afmoe.py``: found
by ``block.kind`` and imported when first named, so a server of another
block never loads it).
``native_serve`` remains the
Python-free deployment backend for the same exported artifact
directory.

    from paddle_tpu import serving
    engine = serving.ServingEngine(serving.GenerationModel.random(cfg))
    req = engine.submit([1, 2, 3], max_new_tokens=16)
    tokens = engine.result(req)
"""

from .engine import ServingEngine  # noqa: F401
from .kv_cache import (CacheEntry, KVBlockPool, PageKind,  # noqa: F401
                       RowState, blocks_needed, prefix_chain_keys)
from .latent_moe import LatentMoEBlock  # noqa: F401
from .loadgen import PoissonLoadGenerator  # noqa: F401
from .model import (GenerationArtifactError,  # noqa: F401
                    GenerationConfig, GenerationModel,
                    ModelDrafter, NGramDrafter,
                    extract_decoder_weights, load_generation_artifact,
                    parse_tree_shape, random_weights, reference_decode,
                    save_generation_artifact, tree_topology,
                    verify_generation_artifact)
from .online import CanaryGate, OnlineUpdater  # noqa: F401
from .router import RouterRequest, ServingRouter  # noqa: F401
from .scheduler import (AdmissionError,  # noqa: F401
                        DeadlineExceededError, GenerationRequest,
                        RequestQueue, StepScheduler,
                        spec_tree_acceptance)



def __getattr__(name):
    # the third, fourth and fifth blocks, lazily: `serving.AfmoeBlock`
    # loads its module
    if name == "AfmoeBlock":
        from .afmoe import AfmoeBlock

        return AfmoeBlock
    if name == "ZayaBlock":
        from .zaya import ZayaBlock

        return ZayaBlock
    if name == "LingBlock":
        from .ling import LingBlock

        return LingBlock
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, name))


__all__ = ["ServingEngine", "ServingRouter", "RouterRequest",
           "KVBlockPool", "CacheEntry", "PageKind", "RowState",
           "LatentMoEBlock", "AfmoeBlock", "ZayaBlock", "LingBlock",
           "blocks_needed",
           "prefix_chain_keys",
           "PoissonLoadGenerator", "GenerationConfig", "GenerationModel",
           "GenerationArtifactError", "ModelDrafter", "NGramDrafter",
           "extract_decoder_weights", "load_generation_artifact",
           "parse_tree_shape", "random_weights", "reference_decode",
           "save_generation_artifact", "tree_topology",
           "verify_generation_artifact",
           "OnlineUpdater", "CanaryGate",
           "spec_tree_acceptance", "AdmissionError",
           "DeadlineExceededError", "GenerationRequest", "RequestQueue",
           "StepScheduler"]
