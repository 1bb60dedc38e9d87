"""KV-cache block paging through the compressed tensor store.

The serving cache (``models/decode``: k/v of shape (L, B, S, H, D), a
latent (L, B, S, W), recurrent states) is large, cold outside the active
attention window, and tolerant of bounded error -- the paper's
in-memory-compression profile.  ``KVPager`` evicts a token range of every
pageable cache tensor into one ``.szt`` archive (one chunk per tensor,
codebooks deduped across K/V) and pages it back on demand with the batched
decoder.  Repeated page-ins of the same block hit the plan cache, so the
steady-state page-in cost is pure phase-4 decode.

The cache's layout (``models/decode.cache_layout``: ``{name: token_axis}``)
says which axis of each tensor holds tokens.  Only tensors with a token
axis are paged by token ranges; a tensor without one (a recurrent state, a
convolution window) is never sliced: ``snapshot`` compresses it whole into
an archive of its own, and a page-in of that block restores it whole.  A
cache that declares no layout pages every tensor on ``seq_axis``.

Under a relative bound, ``offload`` bounds each page by its own value
range unless it is given ``value_ranges``.  A caller that pages a whole
tensor out (``models/kvcache.offload_prefix``, ``preempt``) reads them once
(:meth:`KVPager.value_ranges`), so every page of a tensor is compressed
against the range of the whole tensor and the pages share one quantization
lattice: the bound is the cache's, not a page's.

The paged region is zeroed after eviction: attention over masked-out
positions never reads it, and the zeros compress to nothing if the block
is re-offloaded.

Concurrency: one pager may be shared by many serving sessions (the
``repro.serving`` scheduler does exactly that), so all block-table and
counter mutations happen under an internal lock.  The decode work itself
is *not* serialized here -- ``stage`` (host read + CRC + plan) and
``decode_staged`` (one class-merged ``decompress_batch`` across blocks)
split the page-in into the two pipeline stages the scheduler overlaps.
"""

from __future__ import annotations

import dataclasses
import os
import threading

import jax.numpy as jnp
import numpy as np

from repro.core.cache import PlanCache
from repro.core.codec import Codec, default_codec
from repro.core.huffman import pipeline as hp
from repro.runtime import trace
from repro.store import format as F
from repro.store.reader import Archive
from repro.store.writer import ArchiveWriter


class PageLostError(F.StoreError):
    """An offloaded KV block could not be read back (missing, truncated,
    corrupt, or mangled archive).

    The pager *evicts* the block and counts ``stats["pages_lost"]`` before
    raising, so the serving loop degrades (the paged span stays zeroed --
    attention re-reads masked positions as zeros) instead of crashing on a
    raw ``FileNotFoundError`` / decode error.  ``block_id`` names the block.
    """

    def __init__(self, msg: str, block_id: "int | None" = None):
        super().__init__(msg)
        self.block_id = block_id


def itemsize(dtype) -> int:
    """Bytes per element of ``dtype``, bfloat16 included."""
    return jnp.dtype(str(dtype)).itemsize


def _is_float(arr) -> bool:
    return jnp.issubdtype(jnp.dtype(str(arr.dtype)), jnp.floating)


def _pageable(arr, token_axis, hi: int) -> bool:
    return (token_axis is not None and _is_float(arr)
            and getattr(arr, "ndim", 0) > token_axis
            and arr.shape[token_axis] >= hi)


@dataclasses.dataclass
class StagedBlock:
    """One block's host-side half of a page-in: chunks read + CRC-checked,
    phase 1-3 plans resolved (cache hits for repeats), no decode yet.

    ``key`` is the block's *content* identity -- the sorted (tensor name,
    chunk digest) pairs -- so two blocks holding identical bytes (e.g. the
    same shared prompt prefix offloaded twice) compare equal and can share
    one decode (``repro.serving.prefix_cache`` keys on it).
    """

    block_id: int
    key: tuple
    names: list
    cs: list
    plans: list
    meta: dict

    @property
    def decoded_bytes(self) -> int:
        """Size of the decoded tensors this block expands to."""
        return sum(int(np.prod(c.shape)) * itemsize(c.dtype)
                   for c in self.cs)


class KVPager:
    """Evict / restore token ranges of a decode cache via store archives.

    One ``Codec`` drives both directions: its eb/mode compresses evicted
    blocks, its method/backend/t_high decode them back, and its plan cache
    makes repeat page-ins phase-4 only.  ``layout`` (``{name:
    token_axis}``, ``models/decode.cache_layout``) says where each tensor
    holds its tokens; None marks a tensor that only :meth:`snapshot` takes,
    whole.  Safe to share across threads: the block table (``_blocks``), id
    counter, and ``stats`` are guarded by one reentrant lock.
    """

    def __init__(self, directory: str, *, codec: "Codec | None" = None,
                 seq_axis: int = 2, layout: "dict | None" = None,
                 plan_cache: "PlanCache | None" = None):
        self.dir = directory
        self.codec = codec if codec is not None else default_codec()
        self.seq_axis = seq_axis
        self.layout = dict(layout or {})
        self.cache = (self.codec.plan_cache if plan_cache is None
                      else plan_cache)
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.RLock()
        self._blocks: dict = {}
        self._next_id = 0
        self.stats = {"pages_out": 0, "pages_in": 0,
                      "bytes_raw": 0, "bytes_compressed": 0,
                      "pages_lost": 0,
                      "snapshots_out": 0, "snapshots_in": 0,
                      "snapshot_bytes_raw": 0,
                      "snapshot_bytes_compressed": 0}

    def _bump(self, key: str, n: int = 1):
        with self._lock:
            self.stats[key] += n

    def token_axis(self, name: str) -> "int | None":
        """The layout's token axis of tensor ``name`` (``seq_axis`` for a
        name the layout does not list; None: never sliced)."""
        return self.layout.get(name, self.seq_axis)

    def span(self, name: str, lo: int, hi: int) -> tuple:
        """Index of tokens [lo, hi) of tensor ``name``."""
        return (slice(None),) * self.token_axis(name) + (slice(lo, hi),)

    def pageable(self, cache: dict, hi: int, keys=None) -> list:
        """The float tensors of ``cache`` (or of ``keys``) whose token axis
        reaches ``hi``."""
        return [k for k in (cache if keys is None else keys)
                if _pageable(cache[k], self.token_axis(k), hi)]

    def state_keys(self, cache: dict) -> list:
        """The float tensors of ``cache`` without a token axis."""
        return [k for k in cache
                if self.token_axis(k) is None and _is_float(cache[k])]

    def value_ranges(self, cache: dict, keys) -> dict:
        """``{name: range}`` over each whole tensor (``Codec.value_range``:
        None under an absolute bound), for ``offload(value_ranges=)`` of
        every page of those tensors, so that they share one lattice."""
        return {k: self.codec.value_range(cache[k]) for k in keys}

    def block_path(self, block_id: int) -> str:
        return os.path.join(self.dir, f"block_{block_id:06d}.szt")

    @property
    def resident_blocks(self) -> list:
        with self._lock:
            return sorted(self._blocks)

    def block_meta(self, block_id: int) -> dict:
        """{"path", "lo", "hi", "names"} of one offloaded block (``lo`` and
        ``hi`` None, and ``"snapshot": True``, for a snapshot)."""
        with self._lock:
            return dict(self._blocks[block_id])

    def _meta(self, block_id: int) -> dict:
        """Resident-block lookup for the paging paths: a non-resident id
        (never offloaded, dropped, or already evicted by a prior
        ``PageLostError``) raises the named error, so a serving loop that
        re-requests a lost block degrades instead of crashing on
        ``KeyError``."""
        with self._lock:
            meta = self._blocks.get(block_id)
        if meta is None:
            raise PageLostError(
                f"kv block {block_id} is not resident (unknown, dropped, "
                f"or already evicted after a page loss)", block_id=block_id)
        return meta

    def _lose(self, block_id: int, path: str, exc) -> PageLostError:
        """Evict + count a lost block; returns the named error to raise."""
        with self._lock:
            self._blocks.pop(block_id, None)
            self.stats["pages_lost"] += 1
        return PageLostError(
            f"kv block {block_id} ({path}) lost: "
            f"{type(exc).__name__}: {exc}", block_id=block_id)

    # -- eviction -----------------------------------------------------------

    def offload(self, cache: dict, lo: int, hi: int, keys=None,
                value_ranges: "dict | None" = None):
        """Compress tokens [lo, hi) of each pageable tensor to one archive.

        Returns ``(cache, block_id)`` where ``cache`` has the paged region
        zeroed for every tensor that was written.  ``keys`` defaults to all
        float tensors with a token axis covering the range; a tensor
        without a token axis is never paged.  ``value_ranges``
        (``{name: range}``, from :meth:`value_ranges` before the first
        page) fixes each tensor's relative bound; without it each page is
        bounded by its own range.
        """
        if hi <= lo:
            raise ValueError(f"empty page range [{lo}, {hi})")
        keys = self.pageable(cache, hi, keys)
        if not keys:
            raise ValueError("no pageable cache tensors for range "
                             f"[{lo}, {hi})")
        with trace.span("pager.offload", lo=lo, hi=hi):
            block_id = self._write(
                cache, keys, lambda k: cache[k][self.span(k, lo, hi)],
                value_ranges or {}, {"lo": lo, "hi": hi},
                ("pages_out", "bytes_raw", "bytes_compressed"))
            for k in keys:
                cache[k] = cache[k].at[self.span(k, lo, hi)].set(0)
        return cache, block_id

    def snapshot(self, cache: dict, keys=None) -> int:
        """Compress whole tensors without a token axis into one archive.

        ``keys`` defaults to every float tensor the layout gives no token
        axis (:meth:`state_keys`).  The cache is left as it is.  Returns
        the block id; a page-in of it restores each tensor whole.
        """
        keys = self.state_keys(cache) if keys is None else list(keys)
        if not keys:
            raise ValueError("no state tensors to snapshot")
        with trace.span("pager.snapshot"):
            return self._write(
                cache, keys, lambda k: cache[k], {},
                {"lo": None, "hi": None, "snapshot": True},
                ("snapshots_out", "snapshot_bytes_raw",
                 "snapshot_bytes_compressed"))

    def _write(self, cache, keys, part, value_ranges, meta, counters) -> int:
        """Compress ``part(k)`` of each tensor ``k`` (copied to the host)
        into a new block's archive, each against ``value_ranges[k]`` where
        given; register the block with ``meta`` and count it in the three
        ``counters`` (blocks, raw bytes, stored bytes)."""
        with self._lock:
            block_id = self._next_id
            self._next_id += 1
        path = self.block_path(block_id)
        raw_bytes = 0
        with ArchiveWriter(path) as w:
            for k in keys:
                block = trace.to_host(part(k), np.float32)
                raw_bytes += block.size * itemsize(cache[k].dtype)
                w.add(k, self.codec.compress(
                    block, value_range=value_ranges.get(k)),
                    orig_dtype=str(cache[k].dtype))
        n_out, n_raw, n_stored = counters
        with self._lock:
            self._blocks[block_id] = {"path": path, "names": keys, **meta}
            self.stats[n_out] += 1
            self.stats[n_raw] += raw_bytes
            self.stats[n_stored] += os.path.getsize(path)
        return block_id

    # -- page-in ------------------------------------------------------------

    def block_key(self, block_id: int) -> tuple:
        """Content identity of a block: sorted (name, chunk digest) pairs.

        Index-only read (no chunk payload, CRC, or decode), memoized in the
        block table -- the serving scheduler's prefix cache calls this per
        request to detect blocks whose decode can be shared.  A missing /
        corrupt archive evicts the block and raises ``PageLostError``.
        """
        meta = self._meta(block_id)
        key = meta.get("key")
        if key is not None:
            return key
        try:
            with Archive(meta["path"], codec=self.codec,
                         plan_cache=self.cache) as ar:
                key = tuple(sorted(
                    (n, ar.chunk(n).digest) for n in meta["names"]))
        except (F.StoreError, OSError, KeyError) as e:
            raise self._lose(block_id, meta["path"], e) from e
        with self._lock:
            live = self._blocks.get(block_id)
            if live is not None:
                live["key"] = key
        return key

    def stage(self, block_id: int) -> StagedBlock:
        """Host half of a page-in: read + CRC-check every chunk of the
        block and resolve its phase 1-3 plans (plan-cache hits on repeats).

        No decode dispatch happens here, so this is safe to run on an I/O
        thread while the device decodes another block's batch
        (``decode_staged``).  Failures evict + count the block and raise
        ``PageLostError``.
        """
        meta = self._meta(block_id)
        try:
            with trace.span("pager.stage", block=block_id):
                with Archive(meta["path"], codec=self.codec,
                             plan_cache=self.cache) as ar:
                    missing = [k for k in meta["names"] if k not in ar]
                    if missing:
                        raise F.StoreCorruptError(
                            f"{meta['path']}: block is missing tensors "
                            f"{missing}")
                    cs = [ar.read_chunk(n) for n in meta["names"]]
                    key = tuple(sorted(
                        (n, ar.chunk(n).digest) for n in meta["names"]))
                plans = [self.codec.plan_for(c) for c in cs]
        except (F.StoreError, hp.DecodeGuardError, OSError) as e:
            raise self._lose(block_id, meta["path"], e) from e
        with self._lock:
            live = self._blocks.get(block_id)
            if live is not None:
                live["key"] = key
        return StagedBlock(block_id=block_id, key=key,
                           names=list(meta["names"]), cs=cs, plans=plans,
                           meta=meta)

    def decode_staged(self, staged, *, on_lost=None) -> dict:
        """Decode staged blocks: ONE class-merged ``decompress_batch`` over
        every tensor of every block.  Returns {block_id: {name: array}}.

        A block whose decode trips a guard (malformed stream) is salvaged
        out of the batch: it is evicted + counted, and either ``on_lost
        (block_id, exc)`` absorbs it or the named ``PageLostError`` raises.
        """
        staged = list(staged)
        if not staged:
            return {}
        all_cs = [c for s in staged for c in s.cs]
        all_plans = [p for s in staged for p in s.plans]
        out: dict = {}
        try:
            decoded = self.codec.decompress_batch(all_cs, plans=all_plans)
            i = 0
            for s in staged:
                out[s.block_id] = dict(zip(s.names,
                                           decoded[i:i + len(s.names)]))
                i += len(s.names)
        except hp.DecodeGuardError:
            # Per-block salvage: one malformed stream must not take down
            # its batch-mates.
            for s in staged:
                try:
                    decoded = self.codec.decompress_batch(s.cs,
                                                          plans=s.plans)
                    out[s.block_id] = dict(zip(s.names, decoded))
                except hp.DecodeGuardError as e:
                    err = self._lose(s.block_id, s.meta["path"], e)
                    if on_lost is None:
                        raise err from e
                    on_lost(s.block_id, err)
        snaps = sum(1 for s in staged
                    if s.block_id in out and s.meta.get("snapshot"))
        self._bump("snapshots_in", snaps)
        self._bump("pages_in", len(out) - snaps)
        return out

    def fetch(self, block_id: int) -> dict:
        """Decode a block's tensors (device arrays), without touching any
        cache.  Plan-cache hits make repeat fetches phase-4 only.

        Any store-level failure -- missing/truncated block file, checksum
        mismatch, decode-guard trip, persistent IO error -- evicts the
        block, increments ``stats["pages_lost"]``, and raises the named
        ``PageLostError`` (with the original error chained) so callers
        catch one exception family.
        """
        return self.decode_staged([self.stage(block_id)])[block_id]

    def fetch_many(self, block_ids, *, on_lost=None) -> dict:
        """Batched ``fetch``: stage every block, then decode them ALL in one
        class-merged dispatch set.  Returns {block_id: {name: array}}.

        With ``on_lost(block_id, exc)`` a lost block (missing / corrupt /
        guard-tripped archive -- evicted + counted as usual) is reported and
        skipped; without it the first ``PageLostError`` propagates.
        """
        staged = []
        for bid in block_ids:
            try:
                staged.append(self.stage(bid))
            except PageLostError as e:
                if on_lost is None:
                    raise
                on_lost(bid, e)
        return self.decode_staged(staged, on_lost=on_lost)

    def page_in(self, cache: dict, block_id: int) -> dict:
        """Restore a block into ``cache`` at its original token range.

        On a lost block (see ``fetch``) the named ``PageLostError``
        propagates; the cache is untouched and the paged span stays zeroed,
        so a caller that catches the error keeps serving degraded.
        """
        self._meta(block_id)
        return self.install(cache, block_id, self.fetch(block_id))

    def install(self, cache: dict, block_id: int, tensors: dict) -> dict:
        """Put a fetched block's ``tensors`` into ``cache``: a page at its
        token range, a snapshot's tensors whole."""
        meta = self.block_meta(block_id)
        with trace.span("pager.install", block=block_id):
            for k, block in tensors.items():
                block = jnp.asarray(block, cache[k].dtype)
                cache[k] = (block if meta.get("snapshot") else cache[k].at[
                    self.span(k, meta["lo"], meta["hi"])].set(block))
        return cache

    def adopt_block(self, block_id: int, meta: dict):
        """(Re-)register an offloaded block from its metadata.

        Recovery / restart path: a serving process that inherits block
        archives on disk (or re-tries a block evicted by ``PageLostError``
        after the storage heals) re-registers it here.  ``meta`` needs
        ``path`` / ``lo`` / ``hi`` / ``names`` as returned by
        ``block_meta``.
        """
        missing = {"path", "lo", "hi", "names"} - set(meta)
        if missing:
            raise ValueError(f"block meta missing keys {sorted(missing)}")
        with self._lock:
            self._blocks[block_id] = dict(meta)
            self._next_id = max(self._next_id, block_id + 1)

    def drop(self, block_id: int):
        """Forget a block and delete its archive.

        Dropping a non-resident id raises the named ``PageLostError``
        (matching the paging paths), not a bare ``KeyError``.
        """
        meta = self._meta(block_id)
        with self._lock:
            self._blocks.pop(block_id, None)
        if os.path.exists(meta["path"]):
            os.unlink(meta["path"])

    @property
    def ratio(self) -> float:
        """Achieved compression ratio of pages and snapshots together;
        ``0.0`` until something has been offloaded."""
        with self._lock:
            st = self.stats
            stored = st["bytes_compressed"] + st["snapshot_bytes_compressed"]
            if stored == 0:
                return 0.0
            return (st["bytes_raw"] + st["snapshot_bytes_raw"]) / stored
