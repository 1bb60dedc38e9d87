"""Codec sessions: one configured object for compress / decompress.

The paper's decoder choices -- gap-array vs. self-sync sync discovery,
tile/padded/tuned decode-write, the online per-CR-class tuner -- are
*policy*, not per-call detail.  ``CodecConfig`` freezes that policy (plus
the quantizer settings: error bound, bound mode, radius) into one hashable
value, and ``Codec`` binds it to the two stateful resources every decode
needs:

* the **backend handle** (``pipeline.get_backend``) with its dispatch /
  plan-build counters, and
* a digest-keyed **PlanCache** so phase 1-3 sync/count/prefix-sum plans are
  built once per distinct payload, no matter which consumer decodes it
  (archive reads, checkpoint restore, KV page-ins, direct library calls all
  share the same ``(chunk digest, method, t_high)`` key space).

Consumers (``repro.store``, ``checkpoint.CheckpointManager``,
``models.kvcache``, ``launch/serve``, the benchmarks) accept a Codec
instead of growing kwarg soup.  The module-level ``compress`` /
``decompress`` / ``decompress_batch`` functions remain as thin shims over a
default Codec; the legacy ``use_tiles`` / ``use_kernels`` / ``tuned`` flag
triple is gone from every signature and raises a ``TypeError`` pointing at
``CodecConfig``.

    codec = Codec(CodecConfig(eb=1e-4, strategy="tuned"))
    c = codec.compress(x)
    xhat = codec.decompress(c)                  # plan cached by digest
    shards = codec.compress_tree({"w": w, "b": b})
    restored = codec.decompress_tree(shards)    # one dispatch per CR class
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import numpy as np

from repro.core.cache import DEFAULT_PLAN_CACHE, PlanCache, compressed_digest
from repro.core.huffman import codebook as cb
from repro.core.huffman import encode as he
from repro.core.huffman import pipeline as hp
from repro.core.sz import compressor, lorenzo
from repro.core.sz.compressor import Compressed
from repro.runtime import fault_tolerance as ft
from repro.runtime import trace

VALID_MODES = ("rel", "abs")
VALID_METHODS = ("gap", "selfsync", "naive_ref")
VALID_STRATEGIES = hp.VALID_STRATEGIES

#: The one home of the default error bound / bound mode (the scattered
#: per-consumer ``eb=1e-3`` / ``mode="rel"`` literals collapse onto this).
DEFAULT_EB = compressor.DEFAULT_EB


@dataclasses.dataclass(frozen=True)
class CodecConfig:
    """Frozen compression + decode policy; hashable, validates on build.

    Quantizer / encoder side:
      eb               error bound (relative to the value range for
                       ``mode="rel"``, absolute for ``mode="abs"``)
      mode             "rel" | "abs"
      radius           Lorenzo quantization radius (2*radius bins)
      max_len          codeword length cap (decode-LUT size is 2**max_len)
      subseqs_per_seq  encoder framing (128-bit subsequences per sequence)
      encode_backend   a ``pipeline.available_encode_backends()`` name:
                       "ref" is the host write path (f64 prequantization +
                       numpy histogram + searchsorted bit-pack); "jnp" /
                       "pallas" run quantize ->
                       outlier gather -> histogram -> bit-pack emit on
                       device, transferring only the 2*radius-entry
                       histogram to host for codebook construction.  The
                       emitted ``Compressed`` payload is layout-identical
                       across backends (decode never knows who wrote it);
                       inputs a device backend cannot serve (non-float32)
                       fall back to "ref" and count
                       ``stats["encode_fallbacks"]``.

    Decoder side (paper policy knobs):
      method           "gap" (gap-array sync) | "selfsync" | "naive_ref"
      backend          a ``pipeline.available_backends()`` name
      strategy         "tuned" (per-CR-class tiles, Alg. 2) | "tile"
                       (fixed tiles, Alg. 1) | "padded" (baseline layout)
      t_high           highest non-overflow CR class of the tuner
      tile_syms        ``None`` (default): the "tile" strategy sizes its
                       tiles from each plan's counts so a tile's windows
                       fill one vector register of decoder lanes
                       (``pipeline.tile_geometry``); an int pins the tile
                       size (the paper's fixed-buffer Alg. 1)
      fused            decode→dequantize→reconstruct in ONE dispatch: phase
                       4 emits reconstructed values directly, never writing
                       the uint16 quant-code array to HBM.  Bit-exact with
                       the two-pass path.  Serves tensors of any rank
                       (unit axes squeezed) in float32 / bfloat16 / float16
                       (``compressor.FUSED_DTYPES``; low-precision outputs
                       compute in f32 with one final cast).  Decodes it
                       cannot serve (other dtypes, rows over
                       ``compressor.FUSED_MAX_COLS``, the "tuned"
                       strategy, "naive_ref", or a backend without fused
                       ops) automatically fall back to two-pass and count
                       ``stats["fused_fallbacks"]`` once per tensor.

    Session side:
      plan_cache_size  LRU bound of the Codec's digest-keyed plan cache

    Recovery side (what consumers do when a read fails; see
    ``runtime/fault_tolerance.py:RecoveryPolicy`` and docs/robustness.md):
      recovery         "raise" (default) | "skip" | "zero_fill" -- applied
                       by ``Archive.iter_decode``, ``CheckpointManager.
                       restore`` (salvage mode) and ``KVPager.page_in`` to
                       persistent corruption; per-call ``policy=`` overrides
                       win over this default.
      io_retries       transient-IO retry count for store reads (``OSError``
                       only; corruption is never retried)
      io_backoff       initial backoff seconds between retries (doubles)
    """

    eb: float = DEFAULT_EB
    mode: str = "rel"
    radius: int = lorenzo.DEFAULT_RADIUS
    max_len: int = cb.DEFAULT_MAX_LEN
    subseqs_per_seq: int = he.DEFAULT_SUBSEQS_PER_SEQ
    encode_backend: str = "ref"
    method: str = "gap"
    backend: str = "ref"
    strategy: str = "tile"
    t_high: int = hp.T_HIGH_DEFAULT
    tile_syms: "int | None" = None
    fused: bool = False
    plan_cache_size: int = 4096
    recovery: str = "raise"
    io_retries: int = 2
    io_backoff: float = 0.05

    def __post_init__(self):
        if not (self.eb > 0):
            raise ValueError(f"eb must be positive, got {self.eb!r}")
        if self.mode not in VALID_MODES:
            raise ValueError(
                f"unknown mode {self.mode!r}; valid modes: {VALID_MODES}")
        if self.method not in VALID_METHODS:
            raise ValueError(f"unknown method {self.method!r}; valid "
                             f"methods: {VALID_METHODS}")
        if self.strategy not in VALID_STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}; valid "
                             f"strategies: {VALID_STRATEGIES}")
        if self.backend not in hp.available_backends():
            raise ValueError(f"unknown backend {self.backend!r}; available: "
                             f"{hp.available_backends()}")
        if self.encode_backend not in hp.available_encode_backends():
            raise ValueError(
                f"unknown encode_backend {self.encode_backend!r}; "
                f"available: {hp.available_encode_backends()}")
        if self.t_high < 1:
            raise ValueError(f"t_high must be >= 1, got {self.t_high}")
        if self.radius < 2:
            raise ValueError(f"radius must be >= 2, got {self.radius}")
        if not (1 <= self.max_len <= 24):
            raise ValueError(f"max_len must be in [1, 24], got {self.max_len}")
        if self.tile_syms is not None and self.tile_syms < 1:
            raise ValueError(f"tile_syms must be >= 1, got {self.tile_syms}")
        if self.subseqs_per_seq < 1:
            raise ValueError("subseqs_per_seq must be >= 1, got "
                             f"{self.subseqs_per_seq}")
        if not isinstance(self.fused, bool):
            raise ValueError(f"fused must be a bool, got {self.fused!r}")
        if self.plan_cache_size < 0:
            raise ValueError("plan_cache_size must be >= 0, got "
                             f"{self.plan_cache_size}")
        if self.recovery not in ft.VALID_RECOVERY:
            raise ValueError(f"unknown recovery {self.recovery!r}; valid "
                             f"policies: {ft.VALID_RECOVERY}")
        if self.io_retries < 0:
            raise ValueError(f"io_retries must be >= 0, got "
                             f"{self.io_retries}")
        if self.io_backoff < 0:
            raise ValueError(f"io_backoff must be >= 0, got "
                             f"{self.io_backoff}")

    def replace(self, **changes) -> "CodecConfig":
        return dataclasses.replace(self, **changes)


class Codec:
    """A configured compression/decompression session.

    Holds a ``CodecConfig``, the resolved backend handle (whose ``stats``
    count decode-write dispatches and plan builds), and a digest-keyed
    ``PlanCache``.  All the framework surfaces (store archives, checkpoint
    manager, KV pager, serving) accept one of these, so plan reuse and
    policy travel together instead of being re-decided at every call site.
    """

    def __init__(self, config: "CodecConfig | None" = None, *,
                 plan_cache: "PlanCache | None" = None):
        self.config = config if config is not None else CodecConfig()
        self.backend = hp.get_backend(self.config.backend)
        self.encode_backend = hp.get_encode_backend(
            self.config.encode_backend)
        self.plan_cache = (plan_cache if plan_cache is not None
                           else PlanCache(self.config.plan_cache_size))

    def __repr__(self):
        c = self.config
        return (f"Codec(eb={c.eb:g}, mode={c.mode!r}, method={c.method!r}, "
                f"backend={c.backend!r}, strategy={c.strategy!r})")

    @property
    def stats(self) -> dict:
        """Merged backend dispatch counters, plan-cache hit counters and
        the process-wide transfer and compile counters.

        Backend handles are process-wide singletons per name, so the
        dispatch/plan-build counters are shared by every codec on the same
        backend (and ``reset_stats`` zeroes them for all of them); the
        plan-cache counters are per-codec unless a cache was injected.
        The encode backend's write-path counters (``encode_dispatches``,
        ``encode_fallbacks``, ``encoder_plan_builds``) merge in under their
        own keys -- disjoint from the decode counters by construction.
        ``h2d_bytes``, ``d2h_bytes``, ``compiles`` and ``compile_ms`` come
        from ``runtime/trace.py`` (docs/api.md, "Tracing").
        """
        return {**self.backend.stats, **self.encode_backend.stats,
                **self.plan_cache.stats, **trace.counters()}

    def reset_stats(self):
        self.backend.reset_stats()
        self.encode_backend.reset_stats()
        self.plan_cache.reset_stats()
        trace.reset_counters()

    def recovery_policy(self, policy=None) -> ft.RecoveryPolicy:
        """This codec's ``RecoveryPolicy``; ``policy`` (a string or a
        ``RecoveryPolicy``) overrides the config's ``recovery`` default."""
        return ft.RecoveryPolicy.resolve(policy, self.config)

    # -- single tensors ------------------------------------------------------

    def compress(self, x, *, value_range: "float | None" = None
                 ) -> Compressed:
        """Compress one tensor.  ``value_range``: the range of the whole
        tensor ``x`` is a part of, for a relative bound shared by all its
        parts (``compressor.compress``)."""
        c = self.config
        with trace.span("codec.compress"):
            return compressor.compress(x, eb=c.eb, mode=c.mode,
                                       radius=c.radius, max_len=c.max_len,
                                       subseqs_per_seq=c.subseqs_per_seq,
                                       encode_backend=self.encode_backend,
                                       value_range=value_range)

    def value_range(self, x) -> "float | None":
        """The range ``max(x) - min(x)`` this codec's relative bound takes
        over ``x``, for ``compress(part, value_range=)`` of each part of
        ``x``; None under an absolute bound, which reads no range."""
        if self.config.mode != "rel":
            return None
        with trace.span("codec.value_range"):
            return compressor.value_range(x)

    def build_plan(self, stream, codebook) -> hp.DecoderPlan:
        """Phase 1-3 plan under this codec's (method, backend, t_high)."""
        c = self.config
        return hp.build_plan(stream, codebook, method=c.method,
                             backend=self.backend, t_high=c.t_high)

    def plan_for(self, compressed: Compressed) -> hp.DecoderPlan:
        """Cached ``DecoderPlan`` for one tensor, keyed by content digest.

        The key space is shared with the archive reader: a plan built while
        streaming a ``.szt`` chunk is a hit here and vice versa.  Plan
        resolution is single-flight (``PlanCache.get_or_build_plan``): N
        threads missing on the same payload concurrently build it once.
        """
        c = self.config
        key = (compressed_digest(compressed), c.method, c.t_high)
        return self.plan_cache.get_or_build_plan(
            key, lambda: self.build_plan(compressed.stream,
                                         compressed.codebook))

    def decompress(self, compressed: Compressed, *, plan=None):
        """Decompress one tensor under the codec's policy.

        The phase 1-3 plan is fetched from / inserted into the plan cache
        by content digest; with ``config.fused`` the decode runs the fused
        decode→dequantize→reconstruct dispatch (falling back to two-pass,
        counted in ``stats["fused_fallbacks"]``, when it cannot serve the
        tensor).
        """
        c = self.config
        if plan is None and c.method != "naive_ref":
            plan = self.plan_for(compressed)
        return compressor.decompress(compressed, method=c.method,
                                     tile_syms=c.tile_syms,
                                     backend=self.backend,
                                     strategy=c.strategy, t_high=c.t_high,
                                     plan=plan, fused=c.fused)

    def decompress_batch(self, cs, *, plans=None) -> list:
        """Decompress many tensors: one decode-write dispatch per CR class
        across ALL of them, phase 1-3 plans served from the cache.  With
        ``config.fused``, eligible tensors instead decode through the fused
        per-tensor dispatch (see ``compressor.decompress_batch``)."""
        cs = list(cs)
        if not cs:
            return []
        c = self.config
        if c.method == "naive_ref":
            return [self.decompress(x) for x in cs]
        if plans is None:
            plans = [self.plan_for(x) for x in cs]
        return compressor.decompress_batch(cs, method=c.method,
                                           backend=self.backend,
                                           strategy=c.strategy,
                                           t_high=c.t_high, plans=plans,
                                           fused=c.fused,
                                           tile_syms=c.tile_syms)

    def decode(self, stream, codebook, n_out: int, *, plan=None,
               early_exit: bool = True):
        """Decode a raw encoded stream to quant codes (no dequantization).

        The benchmark harness rides on this: every paper decoder variant is
        one ``CodecConfig`` (method x strategy x backend) driving the same
        entry point.
        """
        c = self.config
        return hp.decode(stream, codebook, n_out, plan=plan, method=c.method,
                         backend=self.backend, strategy=c.strategy,
                         tile_syms=c.tile_syms, t_high=c.t_high,
                         early_exit=early_exit)

    # -- pytrees -------------------------------------------------------------

    def compress_tree(self, tree, *, min_size: int = 1, predicate=None):
        """Compress every compressible leaf of a pytree, in place of it.

        A leaf is compressed when ``predicate(leaf)`` is true (default:
        float32 / bfloat16 / float16 -- the dtypes checkpoints and KV
        caches actually hold, ``compressor.FUSED_DTYPES`` -- with at least
        ``min_size`` elements); everything else passes through untouched,
        so checkpoint shards and KV blocks can hand whole trees over
        instead of hand-rolling dict loops.
        """
        if predicate is None:
            def predicate(leaf):
                arr = np.asarray(leaf)
                return (arr.dtype.name in compressor.FUSED_DTYPES
                        and arr.size >= min_size)
        return jax.tree.map(
            lambda leaf: self.compress(leaf) if predicate(leaf) else leaf,
            tree)

    def decompress_tree(self, tree, *, shardings=None):
        """Inverse of ``compress_tree``: every ``Compressed`` leaf decodes
        through ONE class-batched ``decompress_batch`` call; other leaves
        pass through untouched.

        ``shardings`` (optional) is a pytree matching ``tree`` whose leaves
        are ``jax.sharding.Sharding`` or ``None``: decoded (and
        pass-through) leaves with a sharding are placed into it with
        ``jax.device_put``, so a restored tree lands directly in its target
        layout instead of on the default device.
        """
        leaves, treedef = jax.tree_util.tree_flatten(
            tree, is_leaf=lambda x: isinstance(x, Compressed))
        shard_leaves = None
        if shardings is not None:
            shard_leaves, sdef = jax.tree_util.tree_flatten(
                shardings, is_leaf=lambda x: x is None or
                isinstance(x, jax.sharding.Sharding))
            if len(shard_leaves) != len(leaves):
                raise ValueError(
                    f"shardings tree has {len(shard_leaves)} leaves but the "
                    f"compressed tree has {len(leaves)}")
        idx = [i for i, leaf in enumerate(leaves)
               if isinstance(leaf, Compressed)]
        outs = self.decompress_batch([leaves[i] for i in idx])
        for i, out in zip(idx, outs):
            leaves[i] = out
        if shard_leaves is not None:
            leaves = [jax.device_put(leaf, s) if s is not None else leaf
                      for leaf, s in zip(leaves, shard_leaves)]
        return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Default codec + module-level shims
# ---------------------------------------------------------------------------

_DEFAULT_CODEC: "Codec | None" = None
_SHIM_CODECS: dict = {}
_SHIM_LOCK = threading.Lock()


def default_codec() -> Codec:
    """The process-wide default ``Codec`` (default config, shared
    ``DEFAULT_PLAN_CACHE``) used by the module-level shims and by consumers
    constructed without an explicit codec."""
    global _DEFAULT_CODEC
    if _DEFAULT_CODEC is None:
        _DEFAULT_CODEC = Codec(CodecConfig(), plan_cache=DEFAULT_PLAN_CACHE)
    return _DEFAULT_CODEC


def _codec_for(config: CodecConfig) -> Codec:
    """Memoized per-config codecs for the shims; all share the default plan
    cache so kwarg-style callers still get digest-keyed plan reuse."""
    if config == default_codec().config:
        return default_codec()
    with _SHIM_LOCK:
        codec = _SHIM_CODECS.get(config)
        if codec is None:
            codec = Codec(config, plan_cache=DEFAULT_PLAN_CACHE)
            if len(_SHIM_CODECS) >= 64:   # kwarg soup bound, not a cache
                _SHIM_CODECS.clear()
            _SHIM_CODECS[config] = codec
        return codec


_REMOVED_FLAGS = ("use_tiles", "use_kernels", "tuned")


def _reject_removed(fn_name: str, kwargs: dict):
    bad = sorted(set(kwargs) & set(_REMOVED_FLAGS))
    if bad:
        raise TypeError(
            f"{fn_name}() no longer accepts {', '.join(bad)}; configure a "
            f"repro.core.Codec instead -- CodecConfig(backend='pallas'|'ref')"
            f" replaces use_kernels, CodecConfig(strategy='tuned'|'tile'|"
            f"'padded') replaces tuned/use_tiles (see docs/api.md)")
    if kwargs:
        raise TypeError(f"{fn_name}() got unexpected keyword arguments "
                        f"{sorted(kwargs)}")


def _replace_some(config: CodecConfig, **overrides) -> CodecConfig:
    changes = {k: v for k, v in overrides.items() if v is not None}
    return config.replace(**changes) if changes else config


def compress(x, eb: "float | None" = None, mode: "str | None" = None,
             radius: "int | None" = None, max_len: "int | None" = None,
             subseqs_per_seq: "int | None" = None,
             encode_backend: "str | None" = None, **removed) -> Compressed:
    """Compress a float tensor (shim over a default ``Codec``).

    mode="rel": bound is ``eb * (max(x) - min(x))`` (the paper's setting,
    "relative error bound 1e-3"); mode="abs": bound is ``eb`` directly.
    Prefer holding a ``Codec`` when compressing more than once.
    """
    _reject_removed("compress", removed)
    cfg = _replace_some(default_codec().config, eb=eb, mode=mode,
                        radius=radius, max_len=max_len,
                        subseqs_per_seq=subseqs_per_seq,
                        encode_backend=encode_backend)
    return _codec_for(cfg).compress(x)


def decompress(c: Compressed, method: "str | None" = None,
               tile_syms: "int | None" = None, *,
               backend: "str | None" = None, strategy: "str | None" = None,
               t_high: "int | None" = None, fused: "bool | None" = None,
               plan=None, **removed):
    """Decompress one tensor (shim over a default ``Codec``).

    The legacy ``use_tiles`` / ``use_kernels`` / ``tuned`` flags are gone;
    they raise ``TypeError`` pointing at ``CodecConfig``.
    """
    _reject_removed("decompress", removed)
    cfg = _replace_some(default_codec().config, method=method,
                        tile_syms=tile_syms, backend=backend,
                        strategy=strategy, t_high=t_high, fused=fused)
    return _codec_for(cfg).decompress(c, plan=plan)


def decompress_batch(cs, method: "str | None" = None, *,
                     backend: "str | None" = None,
                     t_high: "int | None" = None, fused: "bool | None" = None,
                     plans=None, **removed) -> list:
    """Decompress many tensors with class-batched decode dispatch (shim
    over a default ``Codec``); see ``Codec.decompress_batch``."""
    _reject_removed("decompress_batch", removed)
    cfg = _replace_some(default_codec().config, method=method,
                        backend=backend, t_high=t_high, fused=fused)
    return _codec_for(cfg).decompress_batch(cs, plans=plans)
