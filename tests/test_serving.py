"""Continuous-batching serve engine on block-paged quantized KV (PR 8).

The engine's contract is *bit-identity*: a request's tokens are bitwise
the tokens ``launch.serve.generate`` produces for that prompt alone at
batch 1 with the same ``SamplingParams``, regardless of what shares the
batch — pinned here across GQA/MLA × kv8/kv2, heterogeneous budgets and
temperatures, page reuse after retirement (stale page contents must not
perturb later requests), and the paged kernels' tile-indirect loop
against the flat kernels at partial-tile positions.  The paged path must
also never materialize an fp copy of the cache (codes+scales end to
end), and the page allocator must fail actionably, not opaquely.

PR 9 (chunked prefill) extends the contract: admission with
``prefill_chunk=N`` streams prompts in page-aligned chunks interleaved
with decode bursts, and the default exact mode must keep every stream
bitwise identical to the solo batch-1 ``generate`` — including prompts
spanning several pages with a partial final chunk.  The paged-extend
kernels are pinned bitwise against their refs (GQA + MLA × kv8/kv2,
empty/partial-chunk edges), ``submit`` fails fast with the sizing math
when a request can never fit, and ``generate_batch`` now warns that the
engine supersedes it.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.synthetic import SyntheticCorpus
from repro.launch.serve import generate, generate_batch
from repro.models import attention as att
from repro.models import build_model
from repro.runtime.tracing import TRACER, ingest_s_per_ktok
from repro.serving import (Engine, PagedPools, RequestOutput, SamplingParams,
                           ServeRequest, poisson_trace, run_trace)
from repro.serving.paged import PageAllocatorExhausted

PAIRS = [("qwen1.5-4b", 8), ("qwen1.5-4b", 2),
         ("deepseek-v2-236b", 8), ("deepseek-v2-236b", 2)]


@functools.lru_cache(maxsize=None)
def _model_params(name, kv_bits):
    # capacity_factor=100: MoE capacity dropping couples tokens across a
    # batch (true of any batched serving) — lift it so deepseek's streams
    # are batch-composition-independent and bit-identity is testable.
    cfg = dataclasses.replace(get_config(name).reduced(), dtype="float32",
                              capacity_factor=100.0, kv_bits=kv_bits)
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(0))
    return model, params


def _prompts(model, n, t):
    corpus = SyntheticCorpus(vocab_size=model.cfg.vocab_size, seed=0)
    return corpus.sample(jax.random.key(2), n, t)


def _baseline(model, params, prompt, n_gen, sp):
    """The single-request batch-1 reference stream for one request."""
    key = jax.random.key(sp.seed) if sp.temperature > 0 else None
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   n_gen, temperature=sp.temperature, key=key)
    return out[0].tolist()


@pytest.mark.parametrize("name,kv_bits", PAIRS)
def test_engine_bit_identical_to_single_request(name, kv_bits):
    """Heterogeneous budgets + sampling params over fewer slots than
    requests (forces queueing + admission mid-flight): every request's
    tokens must match its solo batch-1 ``generate`` stream bitwise, and
    every page must come back after the drain.  Prompt 60 + budgets up
    to 12 push every request past the 64-token page boundary, so the
    paged kernel walks a 2-entry page table mid-stream — the identity
    must survive the second-page indirection, not just page 0."""
    model, params = _model_params(name, kv_bits)
    prompts = _prompts(model, 3, 60)
    sps = [SamplingParams(), SamplingParams(),
           SamplingParams(temperature=1.3, seed=7)]
    budgets = [12, 9, 7]
    expected = [_baseline(model, params, prompts[i].tolist(), budgets[i],
                          sps[i])
                for i in range(3)]

    engine = Engine(model, params, max_slots=2, n_pages=16,
                    max_pages_per_request=2, burst_steps=4)
    rids = [engine.submit(ServeRequest(tokens=prompts[i].tolist(),
                                       max_new_tokens=budgets[i],
                                       sampling=sps[i]))
            for i in range(3)]
    outs = {o.request_id: o for o in engine.drain()}
    assert sorted(outs) == sorted(rids)
    for i, rid in enumerate(rids):
        assert outs[rid].tokens == expected[i], \
            f"request {i}: {outs[rid].tokens} != {expected[i]}"
        assert outs[rid].prompt_len == 60
    assert engine.pools.free_pages() == 16, "pages leaked after drain"


def test_engine_eos_early_stop():
    """A request stops at its eos token (inclusive) and retires early,
    releasing pages while other requests keep decoding."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 2, 12)
    full = _baseline(model, params, prompts[0].tolist(), 10,
                     SamplingParams())
    eos = full[4]
    cut = full.index(eos) + 1  # first occurrence (may precede slot 4)
    engine = Engine(model, params, max_slots=2, n_pages=8,
                    max_pages_per_request=1, burst_steps=3)
    r0 = engine.submit(ServeRequest(
        tokens=prompts[0].tolist(), max_new_tokens=10,
        sampling=SamplingParams(eos_token=eos)))
    r1 = engine.submit(ServeRequest(tokens=prompts[1].tolist(),
                                    max_new_tokens=10))
    outs = {o.request_id: o for o in engine.drain()}
    assert outs[r0].tokens == full[:cut]
    assert outs[r1].tokens == _baseline(model, params, prompts[1].tolist(),
                                        10, SamplingParams())


def test_page_reuse_after_retirement():
    """The allocator is LIFO (freshly retired pages are reused first) and
    stale page contents from a drained batch must not perturb the next
    one — pages are reused without any zeroing."""
    model, params = _model_params("qwen1.5-4b", 8)
    pools = PagedPools(model, 8)
    a = pools.alloc(3)
    assert pools.free_pages() == 5
    pools.release(a)
    assert pools.free_pages() == 8
    b = pools.alloc(3)
    assert b.tolist() == a.tolist(), "retired pages are not reused first"
    pools.release(b)

    # engine-level: second wave decodes on pages the first wave dirtied
    prompts = _prompts(model, 4, 12)
    engine = Engine(model, params, max_slots=2, n_pages=2,
                    max_pages_per_request=1, burst_steps=4)
    for i in range(2):
        engine.submit(ServeRequest(tokens=prompts[i].tolist(),
                                   max_new_tokens=6))
    engine.drain()
    assert engine.pools.free_pages() == 2
    rids = [engine.submit(ServeRequest(tokens=prompts[i].tolist(),
                                       max_new_tokens=6))
            for i in (2, 3)]
    outs = {o.request_id: o for o in engine.drain()}
    for i, rid in zip((2, 3), rids):
        assert outs[rid].tokens == _baseline(
            model, params, prompts[i].tolist(), 6, SamplingParams()), \
            "stale page contents leaked into a reused page's stream"


def test_retired_slot_off_the_work_list_keeps_tokens(monkeypatch):
    """Two slots retire mid-burst; the next request takes the pages freed
    last while the retired slot's stale position and table stay in its
    rows.  The paged decode kernel (interpret mode), which walks only the
    live (slot, page) pairs, emits the tokens of the dense scan reference
    — the old grid's bitwise twin — and both are the solo streams."""
    from repro.serving import engine as engine_mod

    model, params = _model_params("qwen1.5-4b", 8)
    prompts = [p.tolist() for p in _prompts(model, 4, 60)]
    budgets = [6, 9, 20, 7]  # 4-step bursts: 6 and 9 end mid-burst

    def serve(kernel):
        monkeypatch.setenv("REPRO_FD_KERNEL", kernel)
        engine_mod._burst_fn.cache_clear()  # programs are traced per path
        # 4-entry tables, 6 pages: a work list of 10 items, not 12
        engine = Engine(model, params, max_slots=3, n_pages=6,
                        max_pages_per_request=4, burst_steps=4)
        rids = [engine.submit(ServeRequest(tokens=prompts[i],
                                           max_new_tokens=budgets[i]))
                for i in range(3)]
        outs = {}
        while rids[1] not in outs:
            outs.update((o.request_id, o.tokens) for o in engine.step())
        assert engine._slot_rid[:2] == [None, None] and engine.pos[1] > 0
        rids.append(engine.submit(ServeRequest(tokens=prompts[3],
                                               max_new_tokens=budgets[3])))
        engine.step()
        # slot 0 holds the new request on slot 1's freed pages, and slot 1
        # still names them with its stale position
        assert engine._slot_rid[0] == rids[3] and not engine.act[1]
        assert set(engine.tbl[1, :2]) == set(engine.tbl[0, :2])
        outs.update((o.request_id, o.tokens) for o in engine.drain())
        return [outs[r] for r in rids]

    try:
        dense, work = serve("0"), serve("1")
    finally:
        engine_mod._burst_fn.cache_clear()
    assert work == dense
    assert dense == [_baseline(model, params, prompts[i], budgets[i],
                               SamplingParams()) for i in range(4)]


def test_allocator_exhaustion_is_actionable():
    model, params = _model_params("qwen1.5-4b", 8)
    pools = PagedPools(model, 4)
    with pytest.raises(PageAllocatorExhausted, match="need 5 pages"):
        pools.alloc(5)
    with pytest.raises(PageAllocatorExhausted, match="Retire requests"):
        pools.alloc(5)

    engine = Engine(model, params, max_slots=2, n_pages=4,
                    max_pages_per_request=2, burst_steps=2)
    page = engine.page
    big = ServeRequest(tokens=list(range(2 * page)), max_new_tokens=page)
    with pytest.raises(ValueError, match="max_pages_per_request"):
        engine.submit(big)
    # submit fails fast with the sizing math when prompt + budget can
    # never fit the pool, even one with every page free (PR 9): the
    # request must be rejected at submission, not after it has queued
    # behind requests that will never unblock it
    wide = Engine(model, params, max_slots=2, n_pages=2,
                  max_pages_per_request=8, burst_steps=2)
    with pytest.raises(PageAllocatorExhausted) as ei:
        wide.submit(big)
    msg = str(ei.value)
    assert "can never fit" in msg and "need 3 pages" in msg
    assert "raise n_pages" in msg  # the actionable sizing advice
    assert f"{2 * page} prompt" in msg and f"{page} new tokens" in msg

    # kv_bits=0 has no code/scale layout to page
    fp_model, _ = _model_params("qwen1.5-4b", 0)
    with pytest.raises(ValueError, match="kv_bits=8 or kv_bits=2"):
        PagedPools(fp_model, 4)


@pytest.mark.parametrize("kv_bits", [8, 2])
def test_paged_path_never_materializes_fp_cache(kv_bits, monkeypatch):
    """Codes+scales are the paged cache's only representation: a full
    engine run (prefill scatter + burst decode + retire) must never call
    the fp cache decoders."""
    model, params = _model_params("qwen1.5-4b", kv_bits)
    prompts = _prompts(model, 2, 12)

    def boom(*a, **k):
        raise AssertionError("paged serving materialized an fp KV cache")

    monkeypatch.setattr(att, "kv_dequantize", boom)
    monkeypatch.setattr(att, "kv_log_decode", boom)
    engine = Engine(model, params, max_slots=2, n_pages=4,
                    max_pages_per_request=1, burst_steps=4)
    for i in range(2):
        engine.submit(ServeRequest(tokens=prompts[i].tolist(),
                                   max_new_tokens=6))
    outs = engine.drain()
    assert len(outs) == 2 and all(len(o.tokens) == 6 for o in outs)


def test_engine_rejects_unpageable_models():
    """SSM state is per-slot, not per-page: jamba must be rejected with a
    pointer at the flat path, not fail deep in the paged kernels."""
    cfg = dataclasses.replace(get_config("jamba-v0.1-52b").reduced(),
                              dtype="float32", kv_bits=8)
    model = build_model(cfg)
    with pytest.raises(ValueError, match="launch.serve.generate"):
        Engine(model, jax.jit(model.init)(jax.random.key(0)), n_pages=4)


def test_poisson_trace_driver():
    """Arrivals land at their scheduled rounds and the driver reports the
    sustained-throughput/latency summary the bench leg records."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 4, 12)
    reqs = [ServeRequest(tokens=prompts[i].tolist(), max_new_tokens=4)
            for i in range(4)]
    trace = poisson_trace(reqs, rate=2.0, seed=3)
    assert [e.step for e in trace] == sorted(e.step for e in trace)
    engine = Engine(model, params, max_slots=2, n_pages=8,
                    max_pages_per_request=1, burst_steps=2)
    stats = run_trace(engine, trace)
    assert stats["n_requests"] == 4
    assert stats["n_tokens"] == 16
    assert stats["sustained_tok_s"] > 0
    assert stats["p99_latency_s"] >= stats["p50_latency_s"] >= 0
    assert engine.pools.free_pages() == 8


def test_generate_batch_wraps_generate():
    """The request-typed wrapper returns per-request truncations of the
    fixed-batch stream and rejects what only the engine can serve."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 2, 12)
    sp = SamplingParams(temperature=0.9, seed=3)
    reqs = [ServeRequest(tokens=prompts[i].tolist(), max_new_tokens=n,
                         sampling=sp) for i, n in enumerate((4, 6))]
    out = generate_batch(model, params, reqs)
    ref = generate(model, params, prompts, 6, temperature=0.9,
                   key=jax.random.key(3))
    assert out[0] == ref[0, :4].tolist()
    assert out[1] == ref[1].tolist()
    assert generate_batch(model, params, []) == []

    mixed_len = [reqs[0], dataclasses.replace(reqs[1],
                                              tokens=prompts[1][:8])]
    with pytest.raises(ValueError, match="one prompt length"):
        generate_batch(model, params, mixed_len)
    mixed_sp = [reqs[0], dataclasses.replace(
        reqs[1], sampling=SamplingParams(temperature=0.5))]
    with pytest.raises(ValueError, match="identical SamplingParams"):
        generate_batch(model, params, mixed_sp)
    eos = [dataclasses.replace(r, sampling=SamplingParams(eos_token=3))
           for r in reqs]
    with pytest.raises(ValueError, match="serving.Engine"):
        generate_batch(model, params, eos)


# ------------------------------------------------- paged kernels vs flat


# (page table, positions, pool pages) of each paged GQA case: pos -1 is a
# dead slot; page 0 is the trash page
_GQA_CASES = {
    # both tiles partial for slot 1, whose second entry is the trash page
    "base": ([[3, 1], [4, 0]], [70, 35], 5),
    # slot 2 retired: its stale table still points at slot 0's live pages
    "dead_stale": ([[3, 1], [4, 0], [3, 1]], [70, 35, -1], 5),
    "pos0": ([[2, 0], [4, 3]], [0, 70], 5),
    "full_width": ([[1, 2, 3], [4, 0, 0]], [191, 10], 7),
    # live tiles + dead slots = n_pages - 1 + B: one padding item (the
    # pool shares pages here; the kernel only reads them)
    "bound": ([[1, 2, 3, 4], [2, 3, 0, 0], [1, 0, 0, 0]], [250, 100, -1],
              5),
    # every item of the L = n_pages + B list is real: no padding at all
    "full_list": ([[1, 2, 3, 4], [2, 3, 4, 0], [1, 0, 0, 0]], [250, 150, -1],
                  5),
}


def _gqa_pool_case(kv_bits, case):
    """Random code pools + each slot's flat (B, S, KV, ·) cache gathered
    through its page table.  Flat rows past a slot's position hold other
    codes than its pages: masked rows must not matter."""
    tbl, pos, n_pages = _GQA_CASES[case]
    tbl, pos = np.asarray(tbl, np.int32), np.asarray(pos, np.int32)
    page, kv, g, dh = 64, 2, 2, 16
    b, n_tiles = tbl.shape
    s = n_tiles * page
    codec = att.kv_codec(kv_bits, page)
    keys = jax.random.split(jax.random.key(5), 5)
    q = jax.random.normal(keys[0], (b, kv, g, dh), jnp.float32)
    rows = np.arange(s)
    past = rows[None, :] > pos[:, None]                     # (B, S)
    spast = past[:, ::codec.chunk]                          # scale rows
    flat, pools = [], []
    for kp, kj in ((keys[1], keys[2]), (keys[3], keys[4])):
        cp, sp = codec.encode(
            jax.random.normal(kp, (n_pages, page, kv, dh), jnp.float32))
        jc, js = codec.encode(
            jax.random.normal(kj, (b, s, kv, dh), jnp.float32))
        fc = cp[tbl].reshape((b, s) + cp.shape[2:])
        fs = sp[tbl].reshape((b, -1) + sp.shape[2:])
        flat += [jnp.where(past[:, :, None, None], jc, fc),
                 jnp.where(spast[:, :, None], js, fs)]
        pools += [cp, sp]
    return codec, q, flat, pools, jnp.asarray(tbl), pos, dh


@pytest.mark.parametrize("case,use_kernel,kv_bits", [
    pytest.param(c, k, bits,
                 id=f"{k}-{bits}" if c == "base" else f"{c}-{k}-{bits}")
    for c in _GQA_CASES for bits in (8, 2) for k in (False, True)])
def test_paged_gqa_matches_flat_bitwise(case, use_kernel, kv_bits):
    """The page-table-indirect tile loop is the flat kernel's loop with an
    indirection: same codes at tile = page -> bitwise-identical output per
    request, with partial trailing tiles and a trash-page table entry in
    play.  The kernel walks only the live (slot, page) pairs: cases put a
    dead slot over another slot's live pages, a slot at pos 0, one at the
    full table width, and the work list at and one short of its length
    bound; a dead slot reads 0.  The flat call is pinned to
    ``s_blk = page`` (its dispatch may pick a larger tile, which reorders
    the streaming-softmax rescales by an ulp) and run at the full batch
    shape per request pos (the flat kernels share one pos across the
    batch)."""
    from repro.kernels.flash_decode import (flash_decode_pallas,
                                            flash_decode_ref,
                                            paged_flash_decode)

    codec, q, flat, pools, tbl, pos, dh = _gqa_pool_case(kv_bits, case)
    paged = paged_flash_decode(tbl, pos, q, *pools, kv_bits=kv_bits,
                               chunk=codec.chunk, dv=dh, page=64,
                               use_kernel=use_kernel)
    for bb in range(len(pos)):
        px = jnp.full((1, 1), pos[bb], jnp.int32)
        if use_kernel:
            acc, _, l = flash_decode_pallas(
                q, *flat, px, kv_bits=kv_bits, chunk=codec.chunk, dh=dh,
                dv=dh, s_blk=64, interpret=True)
        else:
            acc, _, l = flash_decode_ref(
                q, *flat, px, kv_bits=kv_bits, chunk=codec.chunk, dh=dh,
                dv=dh, s_blk=64)
        ref = acc / jnp.maximum(l, 1e-30)
        assert jnp.array_equal(paged[bb], ref[bb]), \
            f"request {bb} not bitwise equal (kernel={use_kernel})"
        if pos[bb] < 0:
            assert not jnp.any(paged[bb]), f"dead slot {bb} is not 0"


def test_paged_work_list_order_dead_slots_and_padding():
    """Items run slot by slot, tiles in order; a dead slot gives exactly
    one item that opens its partials and computes nothing; padding
    repeats the last real item with no flag; the length is the static
    bound ``min(B * n_tiles, n_pages + B)``."""
    from repro.kernels.flash_decode import kernel as fdk

    first, live = fdk._FIRST, fdk._LIVE
    assert fdk.paged_work_len(24, 72, 420) == 444  # the chat cell
    assert fdk.paged_work_len(2, 3, 100) == 6      # the dense grid
    tbl = np.arange(1, 13, dtype=np.int32).reshape(3, 4)
    pos = np.array([130, -1, 0], np.int32)   # 3 tiles, dead, 1 tile
    items = fdk.paged_work_list(jnp.asarray(tbl), jnp.asarray(pos), page=64,
                                n_pages=7)
    slot, kk, pg, flag = (np.asarray(a).tolist() for a in items)
    n = fdk.paged_work_len(3, 4, 7)
    assert n == 10 and len(slot) == len(kk) == len(pg) == len(flag) == n
    assert slot == [0, 0, 0, 1, 2] + [2] * 5
    assert kk == [0, 1, 2, 0, 0] + [0] * 5
    assert pg == [1, 2, 3, 5, 9] + [9] * 5
    assert flag == ([first | live, live, live, first, first | live]
                    + [0] * 5)


@pytest.mark.parametrize("kv_bits", [8, 2])
@pytest.mark.parametrize("use_kernel", [False, True])
def test_paged_mla_matches_flat_bitwise(kv_bits, use_kernel):
    from repro.kernels.flash_decode import (mla_flash_decode_pallas,
                                            mla_flash_decode_ref,
                                            paged_mla_flash_decode)

    page, b, h, dl, dr = 64, 2, 2, 32, 16
    s = 2 * page
    codec = att.kv_codec(kv_bits, page)
    key = jax.random.key(9)
    cx, rx, qlx, qrx = (jax.random.normal(k, shp, jnp.float32)
                        for k, shp in zip(jax.random.split(key, 4),
                                          [(b, s, dl), (b, s, dr),
                                           (b, h, dl), (b, h, dr)]))
    cq, cs = codec.encode(cx)
    rq, rs = codec.encode(rx)
    tblh = np.array([[3, 1], [4, 2]], np.int32)
    n_pages = 5

    def pool(codes, scales):
        cp = jnp.zeros((n_pages, page) + codes.shape[2:], codes.dtype)
        sp = jnp.zeros((n_pages, page // codec.chunk), scales.dtype)
        for bb in range(b):
            for t in range(2):
                pid = int(tblh[bb, t])
                cp = cp.at[pid].set(codes[bb, t * page:(t + 1) * page])
                sr = page // codec.chunk
                sp = sp.at[pid].set(scales[bb, t * sr:(t + 1) * sr])
        return cp, sp

    cqp, csp = pool(cq, cs)
    rqp, rsp = pool(rq, rs)
    pos = np.array([70, 35], np.int32)
    tbl = jnp.asarray(tblh).at[1, 1].set(0)
    paged = paged_mla_flash_decode(tbl, pos, qlx, qrx, cqp, csp, rqp, rsp,
                                   kv_bits=kv_bits, chunk=codec.chunk,
                                   dl=dl, dr=dr, page=page,
                                   use_kernel=use_kernel)
    for bb in range(b):
        px = jnp.full((1, 1), pos[bb], jnp.int32)
        if use_kernel:
            acc, _, l = mla_flash_decode_pallas(
                qlx, qrx, cq, cs, rq, rs, px, kv_bits=kv_bits,
                chunk=codec.chunk, dl=dl, dr=dr, s_blk=page,
                interpret=True)
        else:
            acc, _, l = mla_flash_decode_ref(
                qlx, qrx, cq, cs, rq, rs, px, kv_bits=kv_bits,
                chunk=codec.chunk, dl=dl, dr=dr, s_blk=page)
        ref = acc / jnp.maximum(l, 1e-30)
        assert jnp.array_equal(paged[bb], ref[bb]), \
            f"request {bb} not bitwise equal (kernel={use_kernel})"


# -------------------------------------------- chunked prefill (PR 9)


def _extend_pools(codec, x, pages, n_pages, page):
    """Encode a flat past and scatter it into shuffled pages; returns the
    (n_pages, page, ...) code pool and its scale pool.  ``x`` is the full
    fp past (batch 1); pages beyond ``pages`` stay zero (trash-shaped)."""
    codes, scales = codec.encode(x)
    cp = jnp.zeros((n_pages, page) + codes.shape[2:], codes.dtype)
    sr = page // codec.chunk
    sp = jnp.zeros((n_pages, sr) + scales.shape[2:], scales.dtype)
    for t, pid in enumerate(pages):
        cp = cp.at[pid].set(codes[0, t * page:(t + 1) * page])
        sp = sp.at[pid].set(scales[0, t * sr:(t + 1) * sr])
    return cp, sp


@pytest.mark.parametrize("kv_bits", [8, 2])
@pytest.mark.parametrize("n_past,L", [(0, 17), (2, 30), (2, 64)])
def test_paged_gqa_extend_kernel_matches_ref_bitwise(kv_bits, n_past, L):
    """The extend kernel's tile loop (quantized past pages + causal fp
    within-chunk tile) must match its ref bitwise at every edge: no past
    pages, a partial final chunk, and a full page-multiple chunk."""
    from repro.kernels.flash_decode import (paged_flash_extend_pallas,
                                            paged_flash_extend_ref)

    page, kv, g, dh = 64, 2, 2, 16
    codec = att.kv_codec(kv_bits, page)
    keys = jax.random.split(jax.random.key(11), 5)
    s_past = max(n_past, 1) * page  # >= 1 page so pool shapes exist
    kx, vx = (jax.random.normal(k, (1, s_past, kv, dh), jnp.float32)
              for k in keys[:2])
    pages = [3, 1, 5][:n_past]
    kqp, ksp = _extend_pools(codec, kx, pages, 6, page)
    vqp, vsp = _extend_pools(codec, vx, pages, 6, page)
    q = jax.random.normal(keys[2], (1, L, kv * g, dh), jnp.float32)
    k_new, v_new = (jax.random.normal(k, (1, L, kv, dh), jnp.float32)
                    for k in keys[3:])
    tbl = jnp.asarray(pages, jnp.int32)
    start = jnp.int32(n_past * page)
    kw = dict(kv_bits=kv_bits, chunk=codec.chunk, dh=dh, dv=dh, page=page)
    ref = paged_flash_extend_ref(tbl, q, k_new, v_new, kqp, ksp, vqp, vsp,
                                 start, **kw)
    ker = paged_flash_extend_pallas(tbl, q, k_new, v_new, kqp, ksp, vqp,
                                    vsp, start, interpret=True, **kw)
    assert ker.shape == (1, L, kv * g, dh)
    assert jnp.array_equal(ker, ref), \
        f"extend kernel != ref (kv_bits={kv_bits}, n_past={n_past}, L={L})"


@pytest.mark.parametrize("kv_bits", [8, 2])
@pytest.mark.parametrize("n_past,L", [(0, 17), (2, 30), (2, 64)])
def test_paged_mla_extend_kernel_matches_ref_bitwise(kv_bits, n_past, L):
    from repro.kernels.flash_decode import (paged_mla_flash_extend_pallas,
                                            paged_mla_flash_extend_ref)

    page, h, dl, dr = 64, 2, 32, 16
    codec = att.kv_codec(kv_bits, page)
    keys = jax.random.split(jax.random.key(13), 6)
    s_past = max(n_past, 1) * page
    cx = jax.random.normal(keys[0], (1, s_past, dl), jnp.float32)
    rx = jax.random.normal(keys[1], (1, s_past, dr), jnp.float32)
    pages = [3, 1, 5][:n_past]
    cqp, csp = _extend_pools(codec, cx, pages, 6, page)
    rqp, rsp = _extend_pools(codec, rx, pages, 6, page)
    ql = jax.random.normal(keys[2], (L, h, dl), jnp.float32)
    qr = jax.random.normal(keys[3], (L, h, dr), jnp.float32)
    c_new = jax.random.normal(keys[4], (L, dl), jnp.float32)
    r_new = jax.random.normal(keys[5], (L, dr), jnp.float32)
    tbl = jnp.asarray(pages, jnp.int32)
    start = jnp.int32(n_past * page)
    kw = dict(kv_bits=kv_bits, chunk=codec.chunk, dl=dl, dr=dr, page=page)
    ref = paged_mla_flash_extend_ref(tbl, ql, qr, c_new, r_new, cqp, csp,
                                     rqp, rsp, start, **kw)
    ker = paged_mla_flash_extend_pallas(tbl, ql, qr, c_new, r_new, cqp,
                                        csp, rqp, rsp, start,
                                        interpret=True, **kw)
    assert ker.shape == (L, h, dl)
    assert jnp.array_equal(ker, ref), \
        f"MLA extend kernel != ref (kv_bits={kv_bits}, " \
        f"n_past={n_past}, L={L})"


@pytest.mark.parametrize("name,kv_bits", PAIRS)
def test_chunked_prefill_bit_identical_to_single_request(name, kv_bits):
    """Chunked admission (prefill_chunk=64, 150-token prompts spanning
    three pages with a partial final chunk) must keep every stream
    bitwise identical to the solo batch-1 ``generate`` — the exact-mode
    fp prefix buffers replay the flat prefill's tiles, so streaming the
    prompt through the running decode batch changes scheduling, never
    tokens.  TTFT accounting (the tracer's prompt ingest time) and page
    hygiene ride along."""
    model, params = _model_params(name, kv_bits)
    prompts = _prompts(model, 3, 150)
    sps = [SamplingParams(), SamplingParams(temperature=1.3, seed=7),
           SamplingParams()]
    budgets = [12, 9, 7]
    expected = [_baseline(model, params, prompts[i].tolist(), budgets[i],
                          sps[i])
                for i in range(3)]
    engine = Engine(model, params, max_slots=2, n_pages=16,
                    max_pages_per_request=3, burst_steps=4,
                    prefill_chunk=64)
    with TRACER.recording():
        rids = [engine.submit(ServeRequest(tokens=prompts[i].tolist(),
                                           max_new_tokens=budgets[i],
                                           sampling=sps[i]))
                for i in range(3)]
        outs = {o.request_id: o for o in engine.drain()}
    assert sorted(outs) == sorted(rids)
    for i, rid in enumerate(rids):
        assert outs[rid].tokens == expected[i], \
            f"request {i}: {outs[rid].tokens} != {expected[i]}"
        assert outs[rid].ttft > 0
        assert outs[rid].latency >= outs[rid].ttft
    assert engine.pools.free_pages() == 16, "pages leaked after drain"
    assert ingest_s_per_ktok(TRACER.record(), rids) > 0


def test_chunked_prefill_paged_attention_mode_drains():
    """The opt-in ``prefill_attn="paged"`` mode re-reads earlier chunks
    from their quantized pages (documented lossy vs the flat prefill, so
    no bit-identity claim): it must admit, decode and retire cleanly
    with page hygiene intact."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 2, 150)
    engine = Engine(model, params, max_slots=2, n_pages=8,
                    max_pages_per_request=3, burst_steps=4,
                    prefill_chunk=64, prefill_attn="paged")
    for i in range(2):
        engine.submit(ServeRequest(tokens=prompts[i].tolist(),
                                   max_new_tokens=6))
    outs = engine.drain()
    assert len(outs) == 2 and all(len(o.tokens) == 6 for o in outs)
    assert engine.pools.free_pages() == 8

    with pytest.raises(ValueError, match="prefill_attn"):
        Engine(model, params, max_slots=2, n_pages=8,
               max_pages_per_request=3, prefill_chunk=64,
               prefill_attn="bogus")


def test_generate_batch_is_deprecated():
    """``generate_batch`` survives as a thin compatibility wrapper but
    must warn that the engine supersedes it."""
    model, params = _model_params("qwen1.5-4b", 8)
    prompts = _prompts(model, 1, 12)
    req = ServeRequest(tokens=prompts[0].tolist(), max_new_tokens=2)
    with pytest.warns(DeprecationWarning, match="serving.Engine"):
        generate_batch(model, params, [req])


# ------------------------------------------------------- trace driver


def test_poisson_trace_deterministic_under_seed():
    """Fixed seed -> bitwise-identical arrival schedule (the bench's
    whole/chunked admission comparison depends on both engine runs
    seeing the same trace); different seeds -> different schedules."""
    reqs = [ServeRequest(tokens=[1], max_new_tokens=1)] * 16
    a = poisson_trace(reqs, rate=0.7, seed=11)
    b = poisson_trace(reqs, rate=0.7, seed=11)
    assert [e.step for e in a] == [e.step for e in b]
    assert [e.step for e in a] == sorted(e.step for e in a)
    c = poisson_trace(reqs, rate=0.7, seed=12)
    assert [e.step for e in c] != [e.step for e in a]


def test_run_trace_percentiles_on_hand_built_outputs():
    """The summary's p50/p99 latency, ttft percentiles and TTFT-split
    fields are plain ``np.percentile`` over per-request wall times and
    the tracer's request phases — pinned on a stub engine emitting
    hand-built outputs and phases with known timestamps."""
    outs = [RequestOutput(request_id=i, tokens=list(range(i + 1)),
                          prompt_len=4, submit_time=0.0,
                          finish_time=float(i + 1),
                          first_token_time=0.25 * (i + 1))
            for i in range(5)]

    class Stub:
        def __init__(self, pending):
            self._pending = list(pending)
            self._rid = 0

        def submit(self, req):
            # request i queues 0.125 * i s, then ingests its 1000 prompt
            # tokens in 0.25 s
            i, self._rid = self._rid, self._rid + 1
            TRACER.phase(i, "queued", 0.0, prompt=1000)
            TRACER.phase(i, "ingest", 0.125 * i)
            TRACER.phase(i, "decode", 0.125 * i + 0.25)
            TRACER.finish(i, float(i + 1), "ok")
            return i

        @property
        def busy(self):
            return bool(self._pending)

        def step(self):
            return [self._pending.pop(0)] if self._pending else []

    reqs = [ServeRequest(tokens=[1], max_new_tokens=1)] * 5
    stats = run_trace(Stub(outs), poisson_trace(reqs, rate=2.0, seed=0))
    lats = [1.0, 2.0, 3.0, 4.0, 5.0]
    ttfts = [0.25 * (i + 1) for i in range(5)]
    assert stats["n_requests"] == 5
    assert stats["n_tokens"] == 15
    assert stats["p50_latency_s"] == pytest.approx(np.percentile(lats, 50))
    assert stats["p99_latency_s"] == pytest.approx(np.percentile(lats, 99))
    assert stats["ttft_p50_s"] == pytest.approx(np.percentile(ttfts, 50))
    assert stats["ttft_p99_s"] == pytest.approx(np.percentile(ttfts, 99))
    waits = [0.125 * i for i in range(5)]
    assert stats["queue_wait_p50_s"] == pytest.approx(
        np.percentile(waits, 50))
    assert stats["queue_wait_p90_s"] == pytest.approx(
        np.percentile(waits, 90))
    assert stats["ingest_s_per_ktok"] == pytest.approx(0.25)
    assert stats["p99_latency_s"] > stats["p50_latency_s"]
