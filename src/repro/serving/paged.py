"""Block-paged quantized KV storage: shared code/scale pools, a
device-resident free-list allocator, and the prefill -> pages scatter.

Page size is the codec's ``page_tokens`` (= ``cfg.kv_chunk``), so a kv2
scale group never straddles a page — one page is exactly one flash-decode
tile and one scale row.  Every layer's pools are dimensioned by the same
``n_pages``; a single *logical* page allocation (one page id) addresses
that page in every layer at once, which is why one page table per request
serves the whole stack.

Page 0 is reserved as the trash page: inactive engine slots route their
(fixed-shape, unmasked) appends there, and unused page-table entries point
at it.  Tiles past a request's position are fully masked in the paged
kernels — exact no-ops of the streaming-softmax update, and left off
the GQA kernel's work list — so trash/stale table entries never perturb
results.  No page is shared between requests, which bounds that work
list by the pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=("n",))
def _alloc(free, top, n: int):
    """Pop ``n`` page ids off the free stack.  ``free``: (capacity,) i32,
    ``top``: () i32 live count.  Host checks ``top >= n`` *before* calling
    (device-side slicing cannot raise)."""
    ids = jax.lax.dynamic_slice_in_dim(free, top - n, n)
    return top - jnp.int32(n), ids


@jax.jit
def _release(free, top, ids):
    """Push page ids back onto the free stack (LIFO — freshly retired
    pages are reused first, which is what the stale-page-reuse test
    leans on)."""
    free = jax.lax.dynamic_update_slice(free, ids.astype(jnp.int32), (top,))
    return free, top + jnp.int32(ids.shape[0])


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_prefill(pools, cache, ids):
    """Scatter one request's (batch-1) prefill cache into the pools.

    ``cache`` leaves are ``(n_groups, 1, S, ...)`` (group scan) or
    ``(1, S, ...)`` (prefix blocks) with S an exact multiple of the page
    row count of that leaf (codes: ``page`` rows; scales: ``page//chunk``
    rows — prefill already rounds to a page multiple); ``ids``: (n_pp,)
    i32 physical pages.  The codes move codes->codes: nothing is
    dequantized here."""
    n_pp = ids.shape[0]

    def scat_group(pool, c):
        per = c.shape[2] // n_pp
        chunked = c.reshape((c.shape[0], n_pp, per) + c.shape[3:])
        return pool.at[:, ids].set(chunked.astype(pool.dtype))

    def scat_prefix(pool, c):
        per = c.shape[1] // n_pp
        chunked = c.reshape((n_pp, per) + c.shape[2:])
        return pool.at[ids].set(chunked.astype(pool.dtype))

    new = {"groups": jax.tree.map(scat_group, pools["groups"],
                                  cache["groups"])}
    if "prefix" in pools:
        new["prefix"] = jax.tree.map(scat_prefix, pools["prefix"],
                                     cache["prefix"])
    return new


class PagedPools:
    """Shared paged KV pools + free-list allocator for one model.

    ``n_pages`` counts *allocatable* pages; one extra trash page (id 0)
    is always added on top.  ``alloc``/``release`` run on device against
    the free stack; only the exhaustion check reads the stack top back."""

    def __init__(self, model, n_pages: int):
        codec = model.codec
        if not codec.quantized:
            raise ValueError(
                "paged serving stores quantized codes — build the model "
                "with kv_bits=8 or kv_bits=2 (kv_bits=0 has no code/scale "
                "layout to page; use launch.serve.generate instead)")
        self.model = model
        self.codec = codec
        self.page = codec.page_tokens
        self.n_pages = n_pages
        cache = jax.eval_shape(lambda: model.init_cache(1, self.page))
        total = n_pages + 1  # + trash page 0
        self.pools = {"groups": jax.tree.map(
            lambda s: jnp.zeros((s.shape[0], total) + s.shape[2:], s.dtype),
            cache["groups"])}
        if "prefix" in cache:
            self.pools["prefix"] = jax.tree.map(
                lambda s: jnp.zeros((total,) + s.shape[1:], s.dtype),
                cache["prefix"])
        self.free = jnp.arange(1, total, dtype=jnp.int32)
        self.top = jnp.int32(n_pages)
        # host-side mirror of the allocated set: preemption makes
        # page-accounting bugs (double release, leaked reservations) easy
        # to write, so every alloc/release is cross-checked here and a
        # violation raises PageAccountingError instead of silently
        # corrupting the device free stack
        self._live: set[int] = set()

    def free_pages(self) -> int:
        return int(self.top)

    def occupancy(self) -> float:
        """Live fraction of the pool (0.0 empty .. 1.0 full) — the
        watermark signal the engine's admission control reads."""
        return 1.0 - self.free_pages() / self.n_pages

    def resident_bytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree.leaves(self.pools))

    def sizing(self, prompt_len: int, max_new: int) -> str:
        """One sentence of request sizing math — the single source of the
        text shared by ``Engine.submit``'s fail-fast / page-table errors
        and the allocator's exhaustion error (they used to duplicate
        it)."""
        need = -(-(prompt_len + max_new) // self.page)
        return (f"{prompt_len} prompt + {max_new} new tokens at "
                f"{self.page}/page = {need} pages")

    def exhausted(self, n: int, *, context: str = "",
                  have: int | None = None,
                  retry_after_s: float | None = None
                  ) -> "PageAllocatorExhausted":
        """Build the actionable sizing error for an allocation of ``n``
        pages that cannot be satisfied — shared by ``alloc`` (runtime
        exhaustion) and ``Engine.submit`` (fail-fast on requests that can
        never fit, where ``have`` is the pool capacity).  The message
        always carries the live occupancy; the engine passes a
        ``retry_after_s`` hint when retirements will free pages."""
        have = self.free_pages() if have is None else have
        occ = 1.0 - have / self.n_pages
        hint = (f"  Retry after ~{retry_after_s:.2f}s."
                if retry_after_s is not None else "")
        err = PageAllocatorExhausted(
            f"page allocator exhausted{context}: need {n} pages, "
            f"{have} of {self.n_pages} free (occupancy "
            f"{occ:.0%}, page = {self.page} "
            f"tokens).  Retire requests, raise n_pages (one page is "
            f"~{self.page_bytes() / 1e3:.1f}KB across all layers), or "
            f"lower max_new_tokens/prompt lengths.{hint}")
        err.need, err.have, err.occupancy = n, have, occ
        err.retry_after_s = retry_after_s
        return err

    def alloc(self, n: int, *, context: str = "") -> jax.Array:
        """Reserve ``n`` pages; raises with the actionable sizing math on
        exhaustion (the caller retires requests to make progress)."""
        if n > self.free_pages():
            raise self.exhausted(n, context=context)
        self.top, ids = _alloc(self.free, self.top, n)
        for i in np.asarray(ids).tolist():
            if i in self._live or i == 0:  # pragma: no cover - drift guard
                raise PageAccountingError(
                    f"allocator handed out page {i} which is "
                    f"{'the trash page' if i == 0 else 'already live'} — "
                    "free-stack accounting has drifted")
            self._live.add(i)
        return ids

    def release(self, ids) -> None:
        if len(ids) == 0:
            return
        ids_host = np.asarray(ids).tolist()
        for i in ids_host:
            if i == 0:
                raise PageAccountingError(
                    "attempt to release the reserved trash page (id 0)")
            if i not in self._live:
                raise PageAccountingError(
                    f"double free: page {i} is not live "
                    f"({self.free_pages()} of {self.n_pages} already free) "
                    "— releasing a free page would alias it across "
                    "requests on the next alloc")
        if len(set(ids_host)) != len(ids_host):
            raise PageAccountingError(
                f"duplicate page ids in one release: {sorted(ids_host)}")
        self._live.difference_update(ids_host)
        self.free, self.top = _release(self.free, self.top,
                                       jnp.asarray(ids, jnp.int32))

    def assert_quiescent(self) -> None:
        """Every allocated page is back on the free stack — ``Engine.
        drain()`` calls this after the last retirement so any page leak
        (or double count) fails loudly at the end of every drain, not as
        mysterious exhaustion three traces later."""
        if self._live or self.free_pages() != self.n_pages:
            live = sorted(self._live)
            tail = "..." if len(live) > 16 else ""
            raise PageAccountingError(
                f"page leak after drain: {self.free_pages()} of "
                f"{self.n_pages} pages free, {len(live)} still marked "
                f"live: {live[:16]}{tail}")

    def page_bytes(self) -> int:
        return self.resident_bytes() // (self.n_pages + 1)

    def write_prefill(self, cache, ids) -> None:
        """Scatter a batch-1 prefill cache into pages ``ids`` (only the
        first ``ceil(S/page)`` of a request's reservation; growth pages
        stay zero until decode appends into them)."""
        self.pools = _scatter_prefill(self.pools, cache, ids)


class PageAllocatorExhausted(RuntimeError):
    """Pool cannot satisfy an allocation; carries ``need`` / ``have`` /
    ``occupancy`` / ``retry_after_s`` fields for programmatic callers."""


class PageAccountingError(RuntimeError):
    """Double free, trash-page release, or a post-drain page leak — the
    free stack no longer matches the set of pages handed out, which would
    alias pages across live requests on a later alloc."""
