"""Compressed KV-cache blocks: the paper's in-memory-compression use case.

Decode caches are large, cold beyond the active window, and tolerant of
bounded error -- exactly the profile of the paper's RTM / GAMESS in-memory
workloads.  ``compress_cache`` SZ-compresses (Lorenzo+Huffman) each cache
tensor; ``decompress_cache`` restores it with the optimized parallel decoder
(gap-array by default -- the encoder is ours, so coupling is free; see paper
§V-C for the self-sync trade-off).

Along the sequence axis a KV cache is smooth per channel (adjacent tokens'
keys correlate), so the 1-D Lorenzo predictor applied along S gets ratios
well above the raw-entropy floor.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.codec import Codec, default_codec
from repro.runtime import trace
from repro.store.paging import KVPager, itemsize


@dataclasses.dataclass
class CompressedCache:
    blobs: dict           # name -> core.sz.Compressed
    orig_dtypes: dict
    orig_shapes: dict

    @property
    def compressed_bytes(self) -> int:
        return sum(c.compressed_bytes for c in self.blobs.values())

    @property
    def original_bytes(self) -> int:
        return sum(int(np.prod(s)) * itemsize(self.orig_dtypes[n])
                   for n, s in self.orig_shapes.items())

    @property
    def ratio(self) -> float:
        return self.original_bytes / max(self.compressed_bytes, 1)


def compress_cache(cache: dict, codec: "Codec | None" = None,
                   skip: tuple = ()) -> CompressedCache:
    """Compress every tensor of a decode cache through one ``Codec``.

    The cache layout (L, B, S, H, D) is flattened with S innermost-adjacent
    to channels so the Lorenzo predictor sees token-to-token continuity.
    ``codec`` defaults to ``repro.core.default_codec()`` (the paper's
    relative 1e-3 bound).
    """
    codec = codec if codec is not None else default_codec()
    picked = {n: np.asarray(a, np.float32) for n, a in cache.items()
              if n not in skip}
    blobs = codec.compress_tree(picked)
    return CompressedCache(
        blobs,
        {n: str(cache[n].dtype) for n in picked},
        {n: cache[n].shape for n in picked})


def decompress_cache(cc: CompressedCache,
                     codec: "Codec | None" = None) -> dict:
    """Restore every cache tensor via the codec's class-batched decoder.

    All blocks decode in one ``decompress_tree`` call -- one decode-write
    dispatch per CR class across the whole cache, not per tensor -- with
    phase 1-3 plans served from the codec's cache on repeats.
    """
    codec = codec if codec is not None else default_codec()
    xs = codec.decompress_tree(cc.blobs)
    # Cast on device: decode_batch already produced device arrays, so the
    # dtype cast must not bounce them through host memory.
    return {n: jnp.asarray(x, jnp.dtype(cc.orig_dtypes[n]))
            for n, x in xs.items()}


# ---------------------------------------------------------------------------
# Block paging through the compressed tensor store (serve --kv-offload)
# ---------------------------------------------------------------------------


def offload_prefix(cache: dict, pager: KVPager, n_tokens: int,
                   block_tokens: int = 64, keys=None):
    """Evict tokens [0, n_tokens) of the cache in fixed-size blocks.

    Each block becomes one store archive (one chunk per cache tensor,
    codebooks deduped); the evicted region of ``cache`` is zeroed.  Each
    tensor's relative bound is taken once, over the whole tensor, before
    the first block.  Returns ``(cache, block_ids)`` in eviction order.
    """
    ranges = pager.value_ranges(cache, pager.pageable(cache, 1, keys))
    ids = []
    for lo in range(0, n_tokens, block_tokens):
        cache, bid = pager.offload(cache, lo, min(lo + block_tokens,
                                                  n_tokens), keys=keys,
                                   value_ranges=ranges)
        ids.append(bid)
    return cache, ids


def page_in_blocks(cache: dict, pager: KVPager, block_ids,
                   on_lost=None) -> dict:
    """Restore offloaded blocks into the cache (demand paging: call with
    whatever blocks the next attention window needs).

    ``on_lost(block_id, exc)`` turns a lost block (``PageLostError``:
    missing/corrupt archive -- already evicted and counted in
    ``pager.stats["pages_lost"]``) into degraded service: the callback is
    invoked, the block's span stays zeroed, and paging continues with the
    remaining blocks.  Without the callback the named error propagates.
    """
    from repro.store import PageLostError

    for bid in block_ids:
        try:
            cache = pager.page_in(cache, bid)
        except PageLostError as e:
            if on_lost is None:
                raise
            on_lost(bid, e)
    return cache


def page_in_blocks_batched(cache: dict, pager: KVPager, block_ids,
                           on_lost=None) -> dict:
    """Batched ``page_in_blocks``: stage every block, decode them all in ONE
    class-merged dispatch set (``KVPager.fetch_many``), then install each
    block at its original token span.

    Same loss semantics as ``page_in_blocks`` -- a lost block either raises
    ``PageLostError`` or is absorbed by ``on_lost(block_id, exc)`` with its
    span left zeroed -- but the decode cost is one ``decompress_batch`` over
    every tensor of every block instead of one dispatch chain per block.
    """
    decoded = pager.fetch_many(block_ids, on_lost=on_lost)
    for bid, tensors in decoded.items():
        cache = pager.install(cache, bid, tensors)
    return cache


# ---------------------------------------------------------------------------
# Preemption and resume of whole sequences
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Preempted:
    """What a preemption left in the store, enough to resume it.

    ``pages``: block ids of the token pages, in token order;
    ``snapshot``: block id of the state snapshot (None for a cache without
    state tensors); ``specs``: ``{name: (shape, dtype)}`` of the cache;
    ``blocks``: ``{block_id: meta}`` so that another pager over the same
    directory (a fresh ``Codec``, a restarted process) can adopt them.
    """

    pages: list
    snapshot: "int | None"
    specs: dict
    blocks: dict

    @property
    def block_ids(self) -> list:
        return self.pages + ([] if self.snapshot is None
                             else [self.snapshot])


def preempt(cache: dict, pager: KVPager, *,
            page_tokens: int = 1024) -> Preempted:
    """Move a preempted sequence batch's cache into the store.

    The whole token axis of every tensor with one goes out in
    ``page_tokens`` pages (``KVPager.offload``), each tensor's relative
    bound taken once over the whole tensor; the tensors without a token
    axis go out whole in one snapshot archive (``KVPager.snapshot``).
    ``cache`` is not changed.  Returns the handle :func:`resume` takes.
    """
    cache = dict(cache)
    specs = {k: (tuple(a.shape), str(a.dtype)) for k, a in cache.items()}
    with trace.operation("pager.preempt"):
        paged = pager.pageable(cache, 1)
        pages = []
        if paged:
            n_tokens = cache[paged[0]].shape[pager.token_axis(paged[0])]
            cache, pages = offload_prefix(cache, pager, n_tokens,
                                          block_tokens=page_tokens,
                                          keys=paged)
        state = pager.state_keys(cache)
        snap = pager.snapshot(cache, state) if state else None
    ids = pages + ([] if snap is None else [snap])
    return Preempted(pages=pages, snapshot=snap, specs=specs,
                     blocks={b: pager.block_meta(b) for b in ids})


def resume(pager: KVPager, handle: Preempted) -> dict:
    """Bring a preempted cache back: stage and decode every page and the
    snapshot in one class-merged ``KVPager.fetch_many``, then install them
    into a zeroed cache of the handle's shapes.

    Blocks the pager does not hold (another pager wrote them) are adopted
    from the handle first.  A lost block raises ``PageLostError``.
    """
    with trace.operation("pager.resume"):
        held = set(pager.resident_blocks)
        for bid in handle.block_ids:
            if bid not in held:
                pager.adopt_block(bid, handle.blocks[bid])
        cache = {k: jnp.zeros(shape, jnp.dtype(dt))
                 for k, (shape, dt) in handle.specs.items()}
        return page_in_blocks_batched(cache, pager, handle.block_ids)
