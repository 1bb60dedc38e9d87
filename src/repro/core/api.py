"""Public compression API: the Codec session is the single entry point.

A ``Codec`` binds a frozen ``CodecConfig`` (error bound + bound mode on the
quantizer side; sync method, decode strategy, backend, and tuner ``t_high``
on the decoder side) to a backend handle and a digest-keyed ``PlanCache``:

    from repro.core.api import Codec, CodecConfig

    codec = Codec(CodecConfig(eb=1e-4, strategy="tuned"))
    c = codec.compress(x)
    xhat = codec.decompress(c)                 # phase 1-3 plan cached
    tree = codec.compress_tree(params)         # pytree of Compressed leaves
    back = codec.decompress_tree(tree)         # ONE dispatch per CR class

Every framework consumer rides on a Codec (see README.md for the
architecture of the plan/execute decode stack):
  * repro/store            -- ``Archive`` / ``KVPager`` take ``codec=``;
                              chunk digests key the codec's plan cache, so
                              a warm open rebuilds zero plans
  * checkpoint/manager.py  -- ``CheckpointManager(dir, codec=...)``; the
                              codec's eb/mode compresses the shards and its
                              plan cache makes re-restores phase-4 only
  * models/kvcache.py      -- ``compress_cache(cache, codec=...)`` /
                              ``decompress_cache`` over ``compress_tree``
  * launch/serve.py        -- one ``--kv-eb``/``--kv-backend``-built Codec
                              drives both KV offload paging and in-memory
                              cache compression

The module-level ``compress`` / ``decompress`` / ``decompress_batch``
functions are thin shims over a default Codec (kept for one-off library
use); the legacy ``use_tiles`` / ``use_kernels`` / ``tuned`` flags raise
``TypeError`` pointing at ``CodecConfig`` (migration table in docs/api.md).

Decoding is served by ``repro.core.huffman.pipeline``: ``build_plan`` runs
the sync/count/prefix-sum phases and CR classification, ``decode`` executes
the plan on a registered backend ("ref" jnp or "pallas" kernels), and
``decode_batch`` merges the per-CR-class decode dispatch across tensors.
"""

from __future__ import annotations

from repro.core.cache import (  # noqa: F401  (public re-exports)
    DEFAULT_PLAN_CACHE,
    PlanCache,
    compressed_digest,
)
from repro.core.codec import (  # noqa: F401  (public re-exports)
    Codec,
    CodecConfig,
    compress,
    decompress,
    decompress_batch,
    default_codec,
)
from repro.core.huffman.pipeline import (  # noqa: F401  (public re-exports)
    DecodeBackend,
    DecoderPlan,
    available_backends,
    build_plan,
    decode,
    decode_batch,
    get_backend,
    register_backend,
)
from repro.core.sz.compressor import Compressed  # noqa: F401
from repro.core.sz import lorenzo  # noqa: F401


def roundtrip_error(x, c: "Compressed", xhat) -> float:
    """Max abs error of a round trip (must be <= c.eb)."""
    import numpy as np

    return float(np.max(np.abs(np.asarray(x) - np.asarray(xhat))))
