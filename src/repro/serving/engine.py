"""Batched serving engine: slot-based continuous batching over the decode step.

A fixed pool of ``max_batch`` slots shares one KV cache; requests are admitted
into free slots (prefill writes that slot's cache rows), and one fused
``decode_step`` advances every active slot per tick. Finished slots are
recycled without disturbing the others — the standard continuous-batching
pattern (vLLM-style, static-shape TPU variant with per-slot position masks).

Positions are tracked per slot; the decode attention mask uses each slot's
own length (ragged batches decode correctly because cache rows beyond a
slot's length are masked by its position).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import decode_step, init_cache, prefill


# ---------------------------------------------------------------------------
# KGE candidate ranking service
# ---------------------------------------------------------------------------
class KGECandidateRanker:
    """Serving-side link-prediction: filtered ranks and streaming top-k
    candidates over a trained KGE model.

    Ranking goes through the streaming fused-rank engine
    (``kernels.triple_score``), candidate retrieval through a blockwise
    ``lax.scan`` top-k merge — in both cases the (B, E) score matrix never
    materializes, so a ranker over a 10⁶-entity table serves from
    O(B·block_e) working memory per step. Both read the entity table its
    ``TableVersion`` staged once, where the params live, as the serving
    tier's replicas do. The ranker therefore holds its entity table twice
    in device memory, the params as given and the staged copy: 0.25 GB
    more for Dbpedia's TransE table, 2.01 GB for its RotatE one
    (``TableVersion.staged_bytes``). ``swap`` replaces both.

    Per-request host work is O(B): the known-true filter is packed once at
    construction into a padded CSR ``FilterPack`` (pow-2 width, so the jits
    see one filter shape) and sliced per batch, and non-finite-row
    validation is a bitmask lookup against the active ``TableVersion``
    (computed once at publish) instead of pulling embedding rows per call.
    ``swap()`` hot-swaps to a newly published version between requests —
    the filter pack carries over (known triples outlive table versions).
    """

    def __init__(self, params, model, known_triples=None, *, block_e: int = 2048,
                 impl: Optional[str] = None, filters=None):
        from repro.serving.tables import FilterPack, TableVersion

        self.model = model
        self.block_e = block_e
        self.impl = impl
        self.filters = (
            filters if filters is not None
            else FilterPack(known_triples, model.num_entities)
        )
        self._hr_t, self._rt_h = self.filters.hr_t, self.filters.rt_h
        self._tv = TableVersion(params, model, self.filters, version=0,
                                block_e=block_e)

    @property
    def params(self):
        return self._tv.params

    @property
    def version(self) -> int:
        return self._tv.version

    def swap(self, params, *, version: Optional[int] = None):
        """Atomically switch to a new table version (a fresh published
        params snapshot). Requests issued after this serve the new tables;
        the filter pack and compiled programs are reused as-is."""
        from repro.serving.tables import TableVersion

        v = self._tv.version + 1 if version is None else int(version)
        self._tv = TableVersion(
            params, self.model, self.filters, version=v, owner=self._tv.owner,
            block_e=self.block_e,
        )
        return self._tv

    # ---- request validation ----------------------------------------------
    def _check_ids(self, name: str, ids: np.ndarray, limit: int) -> np.ndarray:
        from repro.serving.tables import check_id_range

        return check_id_range(name, ids, limit)

    def _check_query(self, h: np.ndarray, r: np.ndarray) -> None:
        """A NaN/Inf row in the tables poisons every rank it touches (it
        compares incomparably against the whole entity table), so a query
        that would serve from one is refused up front with the id named.
        O(B) per request: the per-row verdict was precomputed at publish."""
        self._tv.check_finite("entity", self._tv.ent_bad, h)
        self._tv.check_finite("relation", self._tv.rel_bad, r)

    # ---- filtered ranking ------------------------------------------------
    def rank_filter(self, h: np.ndarray, r: np.ndarray, t: np.ndarray) -> np.ndarray:
        """(B, width+1) int32 filter for rank queries: the gold tail in
        column 0 (duplicates in the known row are harmless — the in-kernel
        exclusion is a membership test) plus the precomputed CSR row slice."""
        return np.concatenate(
            [np.asarray(t, np.int32)[:, None], self.filters.rows_for(h, r)],
            axis=1,
        )

    def rank_tails(self, h, r, t) -> np.ndarray:
        """Filtered rank of each gold tail t among all entities — (B,) int."""
        h = self._check_ids("head entity", h, self.model.num_entities)
        t = self._check_ids("tail entity", t, self.model.num_entities)
        r = self._check_ids("relation", r, self.model.num_relations)
        self._check_query(h, r)
        counts = side_counts_dispatch(
            self._tv.on(None), self.model, jnp.asarray(h), jnp.asarray(r),
            jnp.asarray(t), jnp.asarray(self.rank_filter(h, r, t)),
            entities=self._tv.entities, block_e=self.block_e, impl=self.impl,
        )
        return np.asarray(counts) + 1

    # ---- streaming top-k candidates --------------------------------------
    def topk_tails(self, h, r, k: int = 10, *, exclude_known: bool = True):
        """Top-k candidate tails for (h, r, ·) queries → (ids, scores), each
        (B, k). Streams the entity table blockwise with a carried top-k."""
        h_np = self._check_ids("head entity", h, self.model.num_entities)
        r_np = self._check_ids("relation", r, self.model.num_relations)
        self._check_query(h_np, r_np)
        if exclude_known:
            filt = self.filters.rows_for(h_np, r_np)
        else:
            filt = np.full((h_np.shape[0], 1), -1, np.int32)
        vals, ids = _streaming_topk_jit(
            self._tv.on(None), self.model, jnp.asarray(h_np),
            jnp.asarray(r_np), jnp.asarray(filt), k=k,
            entities=self._tv.entities, block_e=self.block_e,
        )
        return np.asarray(ids), np.asarray(vals)


def _topk_scan(score_block, blocks, b, e, filt, *, k, block_e):
    """Shared blockwise top-k merge: carry (vals, ids), fold in one entity
    block per step. ``score_block(block, ids_block) → (B, Be)`` scores;
    ``blocks`` (n_blocks, Be, …) are scanned with the ids (None: ids only,
    in blocks of ``block_e``)."""
    be = min(block_e, e) if blocks is None else blocks.shape[1]
    n_blocks = -(-e // be)
    cols = jnp.arange(n_blocks * be, dtype=jnp.int32).reshape(n_blocks, be)

    def step(carry, inp):
        vals, ids = carry  # (B, k)
        eb, cb = inp
        s = score_block(eb, cb)  # (B, Be)
        excl = jnp.any(filt[:, :, None] == cb[None, None, :], axis=1)
        s = jnp.where(excl | (cb >= e)[None, :], -jnp.inf, s)
        allv = jnp.concatenate([vals, s], axis=1)
        alli = jnp.concatenate([ids, jnp.tile(cb[None], (vals.shape[0], 1))], 1)
        nv, sel = jax.lax.top_k(allv, vals.shape[1])
        ni = jnp.take_along_axis(alli, sel, axis=1)
        return (nv, ni), None

    init = (
        jnp.full((b, min(k, e)), -jnp.inf, jnp.float32),
        jnp.full((b, min(k, e)), -1, jnp.int32),
    )
    (vals, ids), _ = jax.lax.scan(step, init, (blocks, cols))
    return vals, ids


def streaming_topk_graph(tables, model, h, r, filt, *, k, entities, block_e):
    """Filtered streaming top-k tails as a pure graph, against the tables a
    ``TableVersion`` serves (``TableVersion.on``): the query projection is
    traced into the same program as the blockwise scan, so a top-k
    dispatch is one jitted call with no eager gathers ahead of it — the
    top-k counterpart of ``served_counts_graph``. Decomposable
    families take the query rows and each block of the scan from the staged
    entity table (``entities`` real rows) as it is, by slice; TransH/R/D
    score every (h, r, candidate) triple of a block from their tables."""
    from repro.kernels.triple_score.ops import staged_blocks, unstage_rows
    from repro.kernels.triple_score.triple_score import _tile_scores
    from repro.kge.models import lp_layout, lp_tail_query, score_triples

    b = h.shape[0]
    layout = lp_layout(model)
    if layout is not None:
        mode, d = layout
        staged = tables["staged"]
        q = lp_tail_query(tables, model, unstage_rows(staged[h], mode, d), r)
        q = q.astype(jnp.float32)
        e = entities
        _, sb = staged_blocks(e, d, mode, block_e)
        blocks = staged.reshape(-1, sb, staged.shape[1])

        def score_block(eb, cb):
            eb = unstage_rows(eb, mode, d).astype(jnp.float32)
            return _tile_scores(q, eb, mode)
    else:
        e = model.num_entities
        blocks = None

        def score_block(_, cb):
            ids = jnp.clip(cb, 0, e - 1)
            be = ids.shape[0]
            hh = jnp.repeat(h[:, None], be, axis=1).reshape(-1)
            rr = jnp.repeat(r[:, None], be, axis=1).reshape(-1)
            tt = jnp.tile(ids[None], (b, 1)).reshape(-1)
            return score_triples(tables, model, hh, rr, tt).reshape(b, be)

    return _topk_scan(score_block, blocks, b, e, filt, k=k, block_e=block_e)


_streaming_topk_jit = jax.jit(
    streaming_topk_graph, static_argnames=("model", "k", "entities", "block_e")
)


def served_counts_graph(tables, model, h, r, t, filt, *, entities, block_e,
                        impl=None):
    """Tail rank counts against the tables a ``TableVersion`` serves
    (``TableVersion.on``) — the serving tier's and the ranker's rank
    program. Decomposable families gather the query and gold rows from the
    staged entity table (``entities`` real rows) and rank the table as it
    is; TransH/R/D score triples from their published tables, as
    ``kge.eval.side_counts_graph`` does."""
    from repro.kernels.triple_score.ops import staged_ranks_graph, unstage_rows
    from repro.kge.eval import generic_counts_graph
    from repro.kge.models import (
        lp_gold_rows, lp_layout, lp_tail_query, score_triples,
    )

    layout = lp_layout(model)
    if layout is None:
        gold = score_triples(tables, model, h, r, t)
        return generic_counts_graph(tables, model, h, r, gold, filt,
                                    side="tail", block_e=block_e)
    mode, d = layout
    staged = tables["staged"]
    q = lp_tail_query(tables, model, unstage_rows(staged[h], mode, d), r)
    gold = lp_gold_rows(q, unstage_rows(staged[t], mode, d), mode)
    return staged_ranks_graph(q, staged, gold, filt, mode=mode,
                              num_entities=entities, block_e=block_e,
                              impl=impl)


_served_counts_jit = jax.jit(
    served_counts_graph,
    static_argnames=("model", "entities", "block_e", "impl"),
)


def side_counts_dispatch(tables, model, h, r, t, filt, *, entities, block_e,
                         impl: Optional[str] = None):
    """One ASYNC jitted dispatch of ``served_counts_graph``: device arrays
    in, device array out, no host sync — the serving tier's batch call and
    the ranker's. The implementation is resolved here (host-side) so the
    env overrides keep taking effect per call; the caller materializes the
    result when it chooses (``jax.Array.is_ready`` polling lets batches
    complete out of band while new ones dispatch)."""
    from repro.kernels.dispatch import resolve_rank_impl

    return _served_counts_jit(
        tables, model, h, r, t, filt,
        entities=entities, block_e=block_e, impl=resolve_rank_impl(impl),
    )


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    generated: List[int] = field(default_factory=list)
    done: bool = False
    # perf_counter: latency math (finished_at - submitted_at) must be
    # monotonic; time.time() jumps with NTP/clock adjustments
    submitted_at: float = field(default_factory=time.perf_counter)
    finished_at: Optional[float] = None


class ServingEngine:
    def __init__(self, params, cfg, *, max_batch: int = 4, max_len: int = 512,
                 seed: int = 0):
        if cfg.encoder_layers:
            raise NotImplementedError(
                "continuous batching engine supports decoder-only archs; "
                "use launch/serve.py for enc-dec (whisper)"
            )
        self.params = params
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len + cfg.num_patches
        self.cache = init_cache(cfg, max_batch, self.max_len)
        self.lengths = np.zeros(max_batch, np.int32)   # tokens in each slot
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self.last_token = np.zeros((max_batch, 1), np.int32)
        self.queue: List[Request] = []
        self.finished: List[Request] = []
        self._key = jax.random.PRNGKey(seed)
        self._next_rid = 0

        # one-slot prefill: pad batch dim by running a single-row cache merge
        def _prefill_one(params, tokens, cache_slice):
            return prefill(params, cfg, tokens, cache_slice)

        self._prefill = jax.jit(_prefill_one)
        self._decode = jax.jit(
            lambda p, tok, c, positions: self._decode_masked(p, tok, c, positions)
        )

    # --- decode with PER-SLOT positions -----------------------------------
    def _decode_masked(self, params, tok, cache, positions):
        # positions: (B,) current length per slot. decode_step uses one scalar
        # cache_pos; we call it with the max and rely on per-slot rope via the
        # scalar — for exactness with ragged slots we decode each slot at its
        # own position using vmap over single-slot views.
        def one(p, t, c, pos):
            t = t[None]  # (1, 1)
            c = jax.tree.map(lambda x: x[:, None], c)  # restore the batch dim
            logits, new_c = decode_step(p, self.cfg, t, c, pos)
            return logits[0], jax.tree.map(lambda x: x[:, 0], new_c)

        # vmap over the slot axis (dim 1 of the layer-stacked caches)
        cache_axes = jax.tree.map(lambda _: 1, cache)
        return jax.vmap(one, in_axes=(None, 0, cache_axes, 0), out_axes=(0, cache_axes))(
            params, tok, cache, positions
        )

    # --- public API ---------------------------------------------------------
    def submit(self, prompt: np.ndarray, *, max_new_tokens: int = 16,
               temperature: float = 0.0) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(Request(rid, np.asarray(prompt, np.int32),
                                  max_new_tokens, temperature))
        return rid

    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue.pop(0)
            p = len(req.prompt)
            tokens = jnp.asarray(req.prompt[None, :])
            # prefill into this slot: run a batch-1 prefill on a slot view
            slot_cache = jax.tree.map(lambda x: x[:, slot : slot + 1], self.cache)
            logits, new_slot = self._prefill(self.params, tokens, slot_cache)
            self.cache = jax.tree.map(
                lambda full, piece: jax.lax.dynamic_update_slice_in_dim(
                    full, piece, slot, axis=1
                ),
                self.cache,
                new_slot,
            )
            first = int(jnp.argmax(logits[0, -1]))
            req.generated.append(first)
            self.slot_req[slot] = req
            self.lengths[slot] = p + self.cfg.num_patches
            self.last_token[slot, 0] = first

    def _retire(self) -> None:
        for slot, req in enumerate(self.slot_req):
            if req is not None and len(req.generated) >= req.max_new_tokens:
                req.done = True
                req.finished_at = time.perf_counter()
                self.finished.append(req)
                self.slot_req[slot] = None
                self.lengths[slot] = 0

    def step(self) -> int:
        """One engine tick: admit, decode all active slots, retire. Returns
        the number of active slots decoded."""
        self._admit()
        active = [s for s, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        logits, new_cache = self._decode(
            self.params,
            jnp.asarray(self.last_token),
            self.cache,
            jnp.asarray(self.lengths),
        )
        self.cache = new_cache
        nxt = np.asarray(jnp.argmax(logits[:, 0], axis=-1), np.int32)
        for slot in active:
            req = self.slot_req[slot]
            tok = int(nxt[slot])
            req.generated.append(tok)
            self.lengths[slot] += 1
            self.last_token[slot, 0] = tok
        self._retire()
        return len(active)

    def run_until_drained(self, *, max_ticks: int = 1000) -> List[Request]:
        for _ in range(max_ticks):
            if not self.queue and all(r is None for r in self.slot_req):
                break
            self.step()
        return self.finished
