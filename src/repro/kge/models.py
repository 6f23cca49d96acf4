"""Translation-family KGE models: TransE, TransH, TransR, TransD.

Exactly the four base models the paper plugs into FKGE (§4.1.3), plus
DistMult/ComplEx/RotatE as beyond-paper extras. A model is a (params, score)
pair; FKGE only ever touches ``params["ent"]`` / ``params["rel"]`` — that is
what makes it a meta-algorithm.

Score convention: **higher is better** (we negate distances).
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

MODEL_FAMILIES = ("transe", "transh", "transr", "transd", "distmult", "complex", "rotate")


@dataclass(frozen=True)
class KGEModel:
    family: str
    num_entities: int
    num_relations: int
    dim: int
    margin: float = 4.0
    norm_ord: int = 1  # L1 per OpenKE default for TransE-family


def _uniform(key, shape, dim):
    bound = 6.0 / math.sqrt(dim)
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def init_kge(key, m: KGEModel) -> Dict[str, jnp.ndarray]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    e, r, d = m.num_entities, m.num_relations, m.dim
    p = {"ent": _uniform(k1, (e, d), d), "rel": _uniform(k2, (r, d), d)}
    if m.family == "transh":
        w = _uniform(k3, (r, d), d)
        p["norm_vec"] = w / (jnp.linalg.norm(w, axis=-1, keepdims=True) + 1e-9)
    elif m.family == "transr":
        eye = jnp.eye(d, dtype=jnp.float32)
        p["proj"] = jnp.tile(eye[None], (r, 1, 1)) + 0.01 * _uniform(k3, (r, d, d), d)
    elif m.family == "transd":
        p["ent_p"] = _uniform(k3, (e, d), d)
        p["rel_p"] = _uniform(k4, (r, d), d)
    elif m.family == "complex":
        p["ent_im"] = _uniform(k3, (e, d), d)
        p["rel_im"] = _uniform(k4, (r, d), d)
    elif m.family == "rotate":
        p["rel"] = jax.random.uniform(k2, (r, d // 2), jnp.float32, -math.pi, math.pi)
    return p


def _norm(x, ord_):  # noqa: A002
    if ord_ == 1:
        return jnp.sum(jnp.abs(x), axis=-1)
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=-1) + 1e-12)


def score_triples(
    params: Dict[str, jnp.ndarray],
    m: KGEModel,
    h: jnp.ndarray,
    r: jnp.ndarray,
    t: jnp.ndarray,
    *,
    h_emb=None,
    t_emb=None,
) -> jnp.ndarray:
    """Score a batch of (h, r, t) index triples; higher = more plausible.

    ``h_emb``/``t_emb`` optionally override the gathered entity embeddings —
    used by the PPAT pipeline to score with refined/translated embeddings.
    """
    ent, rel = params["ent"], params["rel"]
    he = ent[h] if h_emb is None else h_emb
    te = ent[t] if t_emb is None else t_emb

    if m.family == "transe":
        re = rel[r]
        return -_norm(he + re - te, m.norm_ord)
    if m.family == "transh":
        re, w = rel[r], params["norm_vec"][r]
        w = w / (jnp.linalg.norm(w, axis=-1, keepdims=True) + 1e-9)
        hp = he - jnp.sum(w * he, -1, keepdims=True) * w
        tp = te - jnp.sum(w * te, -1, keepdims=True) * w
        return -_norm(hp + re - tp, m.norm_ord)
    if m.family == "transr":
        re, mat = rel[r], params["proj"][r]  # (B,d), (B,d,d)
        hp = jnp.einsum("bd,bde->be", he, mat)
        tp = jnp.einsum("bd,bde->be", te, mat)
        return -_norm(hp + re - tp, m.norm_ord)
    if m.family == "transd":
        re = rel[r]
        hpv, tpv = params["ent_p"][h], params["ent_p"][t]
        rpv = params["rel_p"][r]
        hp = he + jnp.sum(hpv * he, -1, keepdims=True) * rpv
        tp = te + jnp.sum(tpv * te, -1, keepdims=True) * rpv
        return -_norm(hp + re - tp, m.norm_ord)
    if m.family == "distmult":
        return jnp.sum(he * rel[r] * te, axis=-1)
    if m.family == "complex":
        hre, him = he, params["ent_im"][h]
        tre, tim = te, params["ent_im"][t]
        rre, rim = rel[r], params["rel_im"][r]
        return jnp.sum(
            hre * rre * tre + him * rre * tim + hre * rim * tim - him * rim * tre,
            axis=-1,
        )
    if m.family == "rotate":
        d2 = he.shape[-1] // 2
        hr, hi = he[..., :d2], he[..., d2:]
        tr, ti = te[..., :d2], te[..., d2:]
        ph = params["rel"][r]
        cr, ci = jnp.cos(ph), jnp.sin(ph)
        rr = hr * cr - hi * ci
        ri = hr * ci + hi * cr
        return -jnp.sum(
            jnp.sqrt(jnp.square(rr - tr) + jnp.square(ri - ti) + 1e-12), axis=-1
        )
    raise ValueError(f"unknown family {m.family!r}")


def margin_loss(pos_scores: jnp.ndarray, neg_scores: jnp.ndarray, margin: float):
    """Margin ranking loss (paper's base objective via OpenKE defaults)."""
    return jnp.mean(jax.nn.relu(margin - pos_scores + neg_scores))


def virtual_pad_rows(
    params: Dict[str, jnp.ndarray], dim: int, n_ent: int, n_rel: int
) -> Dict[str, jnp.ndarray]:
    """Inert rows appended to the family-specific tables when ``n_ent``
    virtual entities / ``n_rel`` virtual relations extend ``ent``/``rel``:
    zero projections for TransD, unit normals for TransH, identity maps for
    TransR. The ONE definition of these rules — shared by
    ``KGETrainer.extend_tables`` and the tick engine's in-graph extension,
    so the two cannot drift apart per family."""
    pads: Dict[str, jnp.ndarray] = {}
    if "ent_p" in params:
        pads["ent_p"] = jnp.zeros((n_ent, dim), jnp.float32)
        pads["rel_p"] = jnp.zeros((n_rel, dim), jnp.float32)
    if "norm_vec" in params:
        padr = jnp.ones((n_rel, dim), jnp.float32)
        pads["norm_vec"] = padr / jnp.sqrt(jnp.float32(dim))
    if "proj" in params:
        pads["proj"] = jnp.tile(jnp.eye(dim)[None], (n_rel, 1, 1))
    return pads


def normalize_entities(params: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Project entity embeddings onto the unit ball (TransE constraint)."""
    out = dict(params)
    n = jnp.linalg.norm(params["ent"], axis=-1, keepdims=True)
    out["ent"] = params["ent"] / jnp.maximum(n, 1.0)
    return out


# ---------------------------------------------------------------------------
# link-prediction query decomposition — the streaming-rank-engine surface
# ---------------------------------------------------------------------------
# A family is "decomposable" when score(q, e) factors into a per-query vector
# against a query-independent entity table: score = −‖q − ent[e]‖ (l1/l2),
# q · ent[e] (dot), or the per-component complex modulus distance (cl1, the
# RotatE metric over [re | im] halves). That is exactly the contract of the
# Pallas triple_score kernels; TransH/R/D project the *entity* table per
# relation, so a mixed-relation batch has no shared table and falls back to
# index expansion.
#
# ComplEx factors through the real (E, 2d) table [ent | ent_im]:
#   tail: s = Σ tre·(hre·rre − him·rim) + tim·(him·rre + hre·rim)
#   head: s = Σ hre·(rre·tre + rim·tim) + him·(rre·tim − rim·tre)
# RotatE rotates the query side (rotations are per-component isometries, so
# ranking heads uses the inverse rotation t∘r̄):
#   tail: s = −Σ_k |h_k·r_k − t_k|      → q = h∘r,  mode cl1
#   head: s = −Σ_k |h_k·r_k − t_k|
#          = −Σ_k |h_k − t_k·r̄_k|       → q = t∘r̄,  mode cl1


def _complex_table(params) -> jnp.ndarray:
    return jnp.concatenate([params["ent"], params["ent_im"]], axis=1)


def lp_layout(m: KGEModel):
    """(mode, row width) of the entity table a decomposable family scores
    tail queries against (``lp_table``), or None for TransH/R/D."""
    if m.family == "transe":
        return ("l2" if m.norm_ord == 2 else "l1"), m.dim
    if m.family == "distmult":
        return "dot", m.dim
    if m.family == "complex":
        return "dot", 2 * m.dim
    if m.family == "rotate":
        return "cl1", m.dim
    return None


def lp_table(params, m: KGEModel) -> jnp.ndarray:
    """The (E, width) entity table of ``lp_layout``."""
    return _complex_table(params) if m.family == "complex" else params["ent"]


def _complex_tail(hre, him, rre, rim) -> jnp.ndarray:
    return jnp.concatenate([hre * rre - him * rim, him * rre + hre * rim], 1)


def lp_tail_query(params, m: KGEModel, rows: jnp.ndarray, r: jnp.ndarray):
    """(B, width) tail query of (h, r) from ``rows``, the head entities'
    rows of ``lp_table`` — which the serving programs gather from the table
    they keep staged. Reads only the relation tables of ``params``."""
    if m.family == "transe":
        return rows + params["rel"][r]
    if m.family == "distmult":
        return rows * params["rel"][r]
    if m.family == "complex":
        return _complex_tail(rows[:, : m.dim], rows[:, m.dim:],
                             params["rel"][r], params["rel_im"][r])
    d2 = rows.shape[-1] // 2  # rotate
    hr, hi = rows[..., :d2], rows[..., d2:]
    ph = params["rel"][r]
    cr, ci = jnp.cos(ph), jnp.sin(ph)
    return jnp.concatenate([hr * cr - hi * ci, hr * ci + hi * cr], 1)


def lp_query_tails(params, m: KGEModel, h: jnp.ndarray, r: jnp.ndarray):
    """(query (B,d), entity table (E,d), mode) for tail ranking, or None."""
    layout = lp_layout(m)
    if layout is None:
        return None
    if m.family == "complex":
        q = _complex_tail(params["ent"][h], params["ent_im"][h],
                          params["rel"][r], params["rel_im"][r])
    else:
        q = lp_tail_query(params, m, params["ent"][h], r)
    return q, lp_table(params, m), layout[0]


def lp_query_heads(params, m: KGEModel, r: jnp.ndarray, t: jnp.ndarray):
    """(query (B,d), entity table (E,d), mode) for head ranking, or None."""
    if m.family == "transe":
        q = params["ent"][t] - params["rel"][r]
        return q, params["ent"], ("l2" if m.norm_ord == 2 else "l1")
    if m.family == "distmult":
        return params["rel"][r] * params["ent"][t], params["ent"], "dot"
    if m.family == "complex":
        tre, tim = params["ent"][t], params["ent_im"][t]
        rre, rim = params["rel"][r], params["rel_im"][r]
        q = jnp.concatenate([rre * tre + rim * tim, rre * tim - rim * tre], 1)
        return q, _complex_table(params), "dot"
    if m.family == "rotate":
        te = params["ent"][t]
        d2 = te.shape[-1] // 2
        tr, ti = te[..., :d2], te[..., d2:]
        ph = params["rel"][r]
        cr, ci = jnp.cos(ph), jnp.sin(ph)  # conj rotation: t ∘ r̄
        q = jnp.concatenate([tr * cr + ti * ci, ti * cr - tr * ci], 1)
        return q, params["ent"], "cl1"
    return None


def lp_gold_scores(q: jnp.ndarray, ent: jnp.ndarray, idx: jnp.ndarray, mode: str):
    """Gather gold scores with the SAME expansion the tile kernel uses, so the
    gold entity's in-tile score differs from its gathered score only by fp
    noise (and the engine excludes gold via the filter row anyway)."""
    return lp_gold_rows(q, ent[idx], mode)


def lp_gold_rows(q: jnp.ndarray, rows: jnp.ndarray, mode: str):
    """``lp_gold_scores`` from the gold entities' (B, d) table rows."""
    e = rows.astype(jnp.float32)
    q = q.astype(jnp.float32)
    if mode == "dot":
        return jnp.sum(q * e, axis=-1)
    if mode == "l2":
        d2 = jnp.sum(q * q, -1) - 2.0 * jnp.sum(q * e, -1) + jnp.sum(e * e, -1)
        return -jnp.sqrt(jnp.maximum(d2, 0.0) + 1e-12)
    if mode == "cl1":
        half = q.shape[-1] // 2
        dr, di = q[:, :half] - e[:, :half], q[:, half:] - e[:, half:]
        return -jnp.sum(jnp.sqrt(dr * dr + di * di + 1e-12), axis=-1)
    return -jnp.sum(jnp.abs(q - e), axis=-1)


def _use_score_kernel(via_kernel: bool | None, mode: str) -> bool:
    if via_kernel is not None:
        return via_kernel
    from repro.kernels.dispatch import COMPILED_BACKENDS, PAIRWISE_KERNEL_MODES

    # compiled Pallas backends route through the tiled kernel (write-once
    # tiles — safe on TPU and GPU) for the modes it lowers; CPU CI keeps the
    # numerically-identical jnp broadcast (interpret mode would be slower)
    return (
        jax.default_backend() in COMPILED_BACKENDS
        and mode in PAIRWISE_KERNEL_MODES
    )


def _decomposed_scores(q, table, mode: str, m: KGEModel, via_kernel):
    """(B, d) query × (E, d) table → (B, E) through the tile kernel on
    compiled backends, or the numerically-identical jnp broadcast on CPU."""
    if _use_score_kernel(via_kernel, mode):
        from repro.kernels.triple_score import pairwise_scores

        return pairwise_scores(q, table, mode=mode)
    if mode == "dot":
        return q @ table.T
    if mode == "cl1":
        half = q.shape[-1] // 2
        dr = q[:, None, :half] - table[None, :, :half]
        di = q[:, None, half:] - table[None, :, half:]
        return -jnp.sum(jnp.sqrt(dr * dr + di * di + 1e-12), axis=-1)
    return -_norm(q[:, None, :] - table[None], m.norm_ord)


def score_all_tails(
    params, m: KGEModel, h: jnp.ndarray, r: jnp.ndarray,
    *, via_kernel: bool | None = None,
) -> jnp.ndarray:
    """Score (h, r, ·) against every entity → (B, E). Used by link prediction."""
    e = m.num_entities
    ent = params["ent"]

    qd = lp_query_tails(params, m, h, r)
    if qd is not None:
        q, table, mode = qd
        return _decomposed_scores(q, table, mode, m, via_kernel)
    # generic fallback: score against every entity by index expansion
    b = h.shape[0]
    t_all = jnp.arange(e)
    hh = jnp.repeat(h[:, None], e, axis=1).reshape(-1)
    rr = jnp.repeat(r[:, None], e, axis=1).reshape(-1)
    tt = jnp.tile(t_all[None], (b, 1)).reshape(-1)
    return score_triples(params, m, hh, rr, tt).reshape(b, e)


def score_all_heads(
    params, m: KGEModel, r: jnp.ndarray, t: jnp.ndarray,
    *, via_kernel: bool | None = None,
) -> jnp.ndarray:
    qd = lp_query_heads(params, m, r, t)
    if qd is not None:
        q, table, mode = qd
        return _decomposed_scores(q, table, mode, m, via_kernel)
    b = t.shape[0]
    e = m.num_entities
    h_all = jnp.arange(e)
    hh = jnp.tile(h_all[None], (b, 1)).reshape(-1)
    rr = jnp.repeat(r[:, None], e, axis=1).reshape(-1)
    tt = jnp.repeat(t[:, None], e, axis=1).reshape(-1)
    return score_triples(params, m, hh, rr, tt).reshape(b, e)
