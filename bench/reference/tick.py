"""Plain reference for federation ticks of TransE owners (Alg. 1 and 2).

Independent of the program: it imports nothing of ``repro``. It starts from
the seed, as the program's scheduler does: each owner's tables are drawn
uniform in +-6/sqrt(d) from ``PRNGKey(seed + i)`` (split in four, the first
two for entities and relations), each owner's sampling stream starts at
``PRNGKey(seed + i + 7919)`` and the handshake stream at
``PRNGKey(seed + 101)``, and every key is split in the order the protocol
consumes it. Every tick, every owner hosts one handshake with its partner,
reading the partner's tables as they stood when the tick began:

1. PPAT (Alg. 2): teachers and a student discriminator (d -> hidden -> 1,
   leaky ReLU 0.2, sigmoid) against a linear generator W (from I); per
   round a batch of aligned client rows X_b and host rows, teacher SGD
   with momentum, PATE noisy-argmax labels (Laplace noise of scale 1/lambda
   on the clean vote counts), student SGD, the generator's non-saturating
   loss back through the student, and W's momentum step followed by
   W <- (1 + beta) W - beta (W W^T) W;
2. the synthesized aligned rows S = X W, refined by the orthogonal
   procrustes R = U V^T of S^T Y, averaged into the host's aligned rows;
3. virtual extension: the first 2000 client triples that join an aligned
   entity to an unaligned one (aligned tail first, then aligned head, in
   training order) add their neighbours and relations, translated by
   (. W) R, as extra host rows, and those triples, remapped, to the host's
   training store;
4. one epoch of margin-SGD (margin, learning rate, batch as configured)
   over the training store cycled up to a power-of-two number of batches,
   permuted, with 1:1 head-or-tail corruption against the true extended
   entity count; every entity row is then projected into the unit ball and
   the virtual rows dropped;
5. backtrack scoring (``score_metric``): filtered Hit@10 over the first
   ``score_queries`` validation triples, tail and head; or triple
   classification accuracy over every validation triple against one fixed
   head-or-tail corruption of each (``default_rng(0)``), at the best of at
   most 256 thresholds taken evenly from the sorted distinct scores;
6. the moments accountant (Eqs. 8-10) over the round's clean vote counts.

``dtype`` is float32 for the reference (matrix products at the highest
precision) and bfloat16 for the control.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------- structure
def aligned(uid_a: np.ndarray, uid_b: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Local ids in a and in b of the universe entities both hold."""
    _, ia, ib = np.intersect1d(uid_a, uid_b, return_indices=True)
    return ia.astype(np.int64), ib.astype(np.int64)


@dataclass
class Pair:
    """What one handshake (client -> host) needs, all from the graphs."""

    idx_c: np.ndarray
    idx_h: np.ndarray
    neigh: np.ndarray       # client entity ids of the virtual rows
    rels: np.ndarray        # client relation ids of the virtual rows
    store: np.ndarray       # host training store + remapped extra triples,
    #                         cycled up to a power-of-two number of batches
    real: int               # triples in the store before cycling


def virtual(client_train, idx_c, idx_h, e0, r0, limit: int = 2000):
    ali = np.zeros(int(max(client_train[:, [0, 2]].max(), idx_c.max())) + 1, bool)
    ali[idx_c] = True
    h_al, t_al = ali[client_train[:, 0]], ali[client_train[:, 2]]
    tail_side = client_train[t_al & ~h_al]
    head_side = client_train[h_al & ~t_al]
    rows = np.concatenate([
        np.stack([tail_side[:, 0], tail_side[:, 1], tail_side[:, 2],
                  np.zeros(len(tail_side), np.int64)], 1),
        np.stack([head_side[:, 2], head_side[:, 1], head_side[:, 0],
                  np.ones(len(head_side), np.int64)], 1),
    ]).astype(np.int64)[:limit]
    neigh = np.unique(rows[:, 0])
    rels = np.unique(rows[:, 1])
    to_host = np.full(len(ali), -1, np.int64)
    to_host[idx_c] = idx_h
    vn = e0 + np.searchsorted(neigh, rows[:, 0])
    vr = r0 + np.searchsorted(rels, rows[:, 1])
    ha = to_host[rows[:, 2]]
    fwd = rows[:, 3] == 0
    extra = np.where(fwd[:, None], np.stack([vn, vr, ha], 1), np.stack([ha, vr, vn], 1))
    return neigh, rels, extra


def pair(client: Dict, host: Dict, batch: int) -> Pair:
    idx_c, idx_h = aligned(client["universe_ids"], host["universe_ids"])
    neigh, rels, extra = virtual(
        client["train"].astype(np.int64), idx_c, idx_h,
        int(host["num_entities"]), int(host["num_relations"]),
    )
    store = np.concatenate([host["train"].astype(np.int64), extra])
    real = len(store)
    nb = -(-real // batch)
    n_pad = (1 << (nb - 1).bit_length()) * batch
    store = np.concatenate([store, store[np.arange(n_pad - real) % real]])
    return Pair(idx_c, idx_h, neigh, rels, store.astype(np.int32), real)


# ----------------------------------------------------------------- tables
def init_tables(seed: int, entities: int, relations: int, dim: int, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    b = 6.0 / math.sqrt(dim)
    ent = jax.random.uniform(k[0], (entities, dim), jnp.float32, -b, b)
    rel = jax.random.uniform(k[1], (relations, dim), jnp.float32, -b, b)
    return {"ent": ent.astype(dtype), "rel": rel.astype(dtype)}


# ------------------------------------------------------------------- PPAT
def _disc(p, x):
    h = x @ p["w1"] + p["b1"]
    h = jnp.where(h >= 0, h, 0.2 * h)
    return jax.nn.sigmoid((h @ p["w2"] + p["b2"])[..., 0])


def _disc_init(key, d, hidden, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "w1": (jax.random.normal(k1, (d, hidden), jnp.float32) / np.sqrt(d)).astype(dtype),
        "b1": jnp.zeros((hidden,), dtype),
        "w2": (jax.random.normal(k2, (hidden, 1), jnp.float32) / np.sqrt(hidden)).astype(dtype),
        "b2": jnp.zeros((1,), dtype),
    }


def _momentum(p, v, g, lr, mom):
    v = jax.tree.map(lambda a, b: mom * a + b, v, g)
    return jax.tree.map(lambda a, b: a - lr * b, p, v), v


def ppat(x, y, n_x, n_y, key, cfg: Dict, dtype):
    """-> (W, clean vote counts n0 (steps, B), n1 (steps, B))."""
    return _ppat_jit(x, y, n_x, n_y, key, tuple(sorted(cfg.items())), dtype)


def _ppat(x, y, n_x, n_y, key, cfg_items, dtype):
    cfg = dict(cfg_items)
    d = x.shape[1]
    T, B, H = cfg["teachers"], cfg["batch"], cfg["hidden"]
    lr, mom, beta, lam = cfg["lr"], cfg["momentum"], cfg["ortho_beta"], cfg["lam"]
    kh, sub = jax.random.split(key)
    kt, ks = jax.random.split(kh)
    teachers = [_disc_init(k, d, H, dtype) for k in jax.random.split(kt, T)]
    student = _disc_init(ks, d, H, dtype)
    state = {
        "t": teachers, "tv": jax.tree.map(jnp.zeros_like, teachers),
        "s": student, "sv": jax.tree.map(jnp.zeros_like, student),
        "w": jnp.eye(d, dtype=dtype), "wv": jnp.zeros((d, d), dtype),
    }
    per = B // T

    def t_loss(p, fake, real):
        return -(jnp.mean(jnp.log(1 - _disc(p, fake) + 1e-8))
                 + jnp.mean(jnp.log(_disc(p, real) + 1e-8)))

    def round_(st, k):
        kx, ky, kn = jax.random.split(k, 3)
        xb = x[jax.random.randint(kx, (B,), 0, n_x)]
        adv = xb @ st["w"]
        real = y[jax.random.randint(ky, (B,), 0, n_y)]
        new_t, new_tv = [], []
        for i in range(T):
            g = jax.grad(t_loss)(st["t"][i], adv[i * per:(i + 1) * per],
                                 real[i * per:(i + 1) * per])
            p, v = _momentum(st["t"][i], st["tv"][i], g, lr, mom)
            new_t.append(p)
            new_tv.append(v)
        votes = jnp.stack([(_disc(p, adv) >= 0.5).astype(jnp.int32) for p in new_t])
        n1 = votes.sum(0)
        n0 = T - n1
        noise = jax.random.laplace(kn, (2, B)) * (1.0 / lam)
        labels = (n1.astype(jnp.float32) + noise[1]
                  > n0.astype(jnp.float32) + noise[0]).astype(dtype)

        def s_loss(p):
            ps = _disc(p, adv)
            return -jnp.mean(labels * jnp.log(ps + 1e-8)
                             + (1 - labels) * jnp.log(1 - ps + 1e-8))

        s, sv = _momentum(st["s"], st["sv"], jax.grad(s_loss)(st["s"]), lr, mom)
        g_adv = jax.grad(lambda a: -jnp.mean(jnp.log(_disc(s, a) + 1e-8)))(adv)
        wv = mom * st["wv"] + xb.T @ g_adv
        w = st["w"] - lr * wv
        w = (1 + beta) * w - beta * (w @ w.T) @ w
        return {"t": new_t, "tv": new_tv, "s": s, "sv": sv, "w": w, "wv": wv}, (n0, n1)

    state, (n0s, n1s) = jax.lax.scan(round_, state, jax.random.split(sub, cfg["steps"]))
    return state["w"], n0s, n1s


def epsilon(n0, n1, lam: float, delta: float, moments: int = 32) -> float:
    """Moments accountant (Eqs. 8-10) over clean vote counts, float64: per
    query the data-dependent bound where PATE's condition holds, else the
    data-independent 2 lambda^2 l (l + 1)."""
    ls = np.arange(1, moments + 1, dtype=np.float64)
    gap = np.abs(np.asarray(n0, np.float64) - np.asarray(n1, np.float64)).ravel()
    q = (2.0 + lam * gap) / (4.0 * np.exp(lam * gap))
    indep = 2.0 * lam ** 2 * ls * (ls + 1.0)
    denom = 1.0 - np.exp(2.0 * lam) * q
    ok = (q < 1.0 / (1.0 + np.exp(2.0 * lam))) & (denom > 0)
    ratio = (1.0 - q[ok]) / denom[ok]
    dep = np.log((1.0 - q[ok])[:, None] * ratio[:, None] ** ls
                 + q[ok][:, None] * np.exp(2.0 * lam * ls))
    alpha = (np.minimum(indep, np.maximum(dep, 0.0)).sum(0)
             + indep * int((~ok).sum()))
    return float(np.min((alpha + np.log(1.0 / delta)) / ls))


# ---------------------------------------------------------------- retrain
def epoch(ent, rel, store, key, n_ent, batch: int, lr: float, margin: float):
    kp, kc, ks = jax.random.split(jax.random.split(key, 1)[0], 3)
    n = store.shape[0]
    nb = n // batch
    pos = store[jax.random.permutation(kp, n)].reshape(nb, batch, 3)
    head = jax.random.bernoulli(kc, 0.5, (nb, batch))
    rnd = jax.random.randint(ks, (nb, batch), 0, n_ent, dtype=jnp.int32)
    neg = jnp.stack([jnp.where(head, rnd, pos[..., 0]), pos[..., 1],
                     jnp.where(head, pos[..., 2], rnd)], -1)

    def loss(hp, rp, tp, hn, rn, tn):
        sp = -jnp.sum(jnp.abs(hp + rp - tp), -1)
        sn = -jnp.sum(jnp.abs(hn + rn - tn), -1)
        return jnp.mean(jnp.maximum(margin - sp + sn, 0.0))

    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))

    def step(tabs, b):
        e, r = tabs
        p, q = b
        g = grad(e[p[:, 0]], r[p[:, 1]], e[p[:, 2]], e[q[:, 0]], r[q[:, 1]], e[q[:, 2]])
        e = e.at[jnp.concatenate([p[:, 0], p[:, 2], q[:, 0], q[:, 2]])].add(
            -lr * jnp.concatenate([g[0], g[2], g[3], g[5]]))
        r = r.at[jnp.concatenate([p[:, 1], q[:, 1]])].add(
            -lr * jnp.concatenate([g[1], g[4]]))
        return (e, r), None

    (ent, rel), _ = jax.lax.scan(step, (ent, rel), (pos, neg))
    norm = jnp.sqrt(jnp.sum(jnp.square(ent.astype(jnp.float32)), -1, keepdims=True))
    return (ent / jnp.maximum(norm, 1.0).astype(ent.dtype)), rel


# ----------------------------------------------------------------- scoring
def hit10(ent, rel, queries: np.ndarray, known: np.ndarray, block: int = 32) -> float:
    """Filtered Hit@10 over tail and head ranking of ``queries``: per query
    the entities scoring above the gold one, less the known-true ones among
    them (counted on the device, corrected for the few filtered ids)."""
    ranks = []
    for side in ("tail", "head"):
        fix, cand_col, gold_col = ((0, 1), 2, 2) if side == "tail" else ((1, 2), 0, 0)
        key_k = known[:, fix[0]].astype(np.int64) * (1 << 32) + known[:, fix[1]]
        key_q = queries[:, fix[0]].astype(np.int64) * (1 << 32) + queries[:, fix[1]]
        order = np.argsort(key_k, kind="stable")
        sk, sc = key_k[order], known[order, cand_col]
        lo, hi = np.searchsorted(sk, key_q, "left"), np.searchsorted(sk, key_q, "right")
        gold = queries[:, gold_col]
        for i in range(0, len(queries), block):
            j = min(i + block, len(queries))
            if side == "tail":
                q = ent[queries[i:j, 0]] + rel[queries[i:j, 1]]
            else:
                q = ent[queries[i:j, 2]] - rel[queries[i:j, 1]]
            s_gold, above = _above_gold(q, ent, jnp.asarray(gold[i:j]))
            for r, (g, a0, a1) in enumerate(zip(gold[i:j], lo[i:j], hi[i:j])):
                f = np.unique(sc[a0:a1])
                f = f[f != g]
                s_f = np.asarray(_l1_rows(q[r], ent[jnp.asarray(f)]).astype(jnp.float32)) \
                    if len(f) else np.zeros(0, np.float32)
                ranks.append(int(above[r]) - int((s_f > s_gold[r]).sum()) + 1)
    return float(np.mean(np.asarray(ranks) <= 10))


@jax.jit
def _above_gold(q, ent, gold_ids):
    s = _l1_scores(q, ent)
    g = jnp.take_along_axis(s, gold_ids[:, None], axis=1)
    return g[:, 0].astype(jnp.float32), jnp.sum(s > g, axis=1)


@jax.jit
def _l1_rows(q, rows):
    return -jnp.sum(jnp.abs(q[None] - rows), -1, dtype=q.dtype)


def accuracy(ent, rel, valid: np.ndarray, num_entities: int) -> float:
    """Triple-classification accuracy at the best threshold."""
    rng = np.random.default_rng(0)
    neg = valid.copy()
    head = rng.random(len(neg)) < 0.5
    rnd = rng.integers(0, num_entities, len(neg))
    neg[head, 0] = rnd[head]
    neg[~head, 2] = rnd[~head]

    def score(t):
        t = jnp.asarray(t)
        return np.asarray(-jnp.sum(jnp.abs(ent[t[:, 0]] + rel[t[:, 1]] - ent[t[:, 2]]),
                                   -1).astype(jnp.float32))

    sp, sn = score(valid), score(neg)
    cand = np.unique(np.concatenate([sp, sn]))
    if len(cand) > 256:
        cand = cand[:: len(cand) // 256]
    acc = ((sp[None, :] >= cand[:, None]).mean(1) + (sn[None, :] < cand[:, None]).mean(1)) / 2
    return float(acc.max())


@jax.jit
def _l1_scores(q, ent):
    def one(eb):
        return -jnp.sum(jnp.abs(q[:, None, :] - eb[None]), -1, dtype=q.dtype)

    e = ent.shape[0]
    blk = 4096
    nb = -(-e // blk)
    pad = jnp.pad(ent, ((0, nb * blk - e), (0, 0)))
    s = jax.lax.map(one, pad.reshape(nb, blk, -1))
    return jnp.transpose(s, (1, 0, 2)).reshape(q.shape[0], -1)[:, :e]


# ------------------------------------------------------------------- ticks
@dataclass
class TickOut:
    epsilon: Dict[str, float]
    score: Dict[str, float]


class Federation:
    """The reference federation of ``owners`` (name -> generated graph, in
    the scheduler's order), every owner partnered with every other that
    shares aligned entities. ``rotate``: each owner handshakes with one
    partner per tick, the next in name order each tick (else with its only
    partner)."""

    def __init__(self, owners: Dict[str, Dict], cfg: Dict, seed: int, dtype=jnp.float32):
        self.names = list(owners)
        self.owners = owners
        self.cfg = cfg
        self.dtype = dtype
        self.d = cfg["dim"]
        self.tables = {
            n: init_tables(seed + i, int(g["num_entities"]), int(g["num_relations"]),
                           self.d, dtype)
            for i, (n, g) in enumerate(owners.items())
        }
        self.sample_key = {n: jax.random.PRNGKey(seed + i + 7919)
                           for i, n in enumerate(self.names)}
        self.ppat_key = jax.random.PRNGKey(seed + 101)
        self.pairs: Dict[Tuple[str, str], Pair] = {}
        self.known = {n: np.concatenate([g["train"], g["valid"], g["test"]]).astype(np.int64)
                      for n, g in owners.items()}
        self.partners = {
            n: [m for m in self.names if m != n
                and len(aligned(owners[n]["universe_ids"], owners[m]["universe_ids"])[0])]
            for n in self.names
        }

    def _pair(self, client: str, host: str) -> Pair:
        k = (client, host)
        if k not in self.pairs:
            self.pairs[k] = pair(self.owners[client], self.owners[host], self.cfg["batch"])
        return self.pairs[k]

    def entry(self, host: str, client: str, view: Dict, key_train, key_ppat):
        cfg, dt = self.cfg, self.dtype
        g = self.owners[host]
        e_log, r_log = int(g["num_entities"]), int(g["num_relations"])
        pr = self._pair(client, host)
        tab = self.tables[host]
        x = view["ent"][jnp.asarray(pr.idx_c)]
        y = tab["ent"][jnp.asarray(pr.idx_h)]
        n = x.shape[0]
        w, n0, n1 = ppat(x, y, n, n, key_ppat, cfg["ppat"], dt)
        synth = x @ w
        m = synth.T @ y
        u, _, vt = jnp.linalg.svd(m.astype(jnp.float32), full_matrices=False)
        refine = (u @ vt).astype(dt)
        synth = synth @ refine
        ent = tab["ent"].at[jnp.asarray(pr.idx_h)].set(0.5 * (y + synth))
        v_ent = (view["ent"][jnp.asarray(pr.neigh)] @ w) @ refine
        v_rel = (view["rel"][jnp.asarray(pr.rels)] @ w) @ refine
        ent = jnp.concatenate([ent, v_ent])
        rel = jnp.concatenate([tab["rel"], v_rel])
        ent, rel = _epoch_jit(ent, rel, jnp.asarray(pr.store), key_train,
                              jnp.int32(e_log + len(pr.neigh)), cfg["batch"],
                              cfg["lr"], cfg["margin"])
        ent, rel = ent[:e_log], rel[:r_log]
        if cfg.get("score_metric", "hit10") == "hit10":
            q = g["valid"][: cfg["score_queries"]].astype(np.int64)
            score = hit10(ent, rel, q, self.known[host])
        else:
            score = accuracy(ent, rel, g["valid"].astype(np.int64), e_log)
        eps = epsilon(np.asarray(n0), np.asarray(n1), cfg["ppat"]["lam"], cfg["ppat"]["delta"])
        return {"ent": ent, "rel": rel}, eps, score

    def tick(self, t: int) -> TickOut:
        """One tick: every owner hosts a handshake with its partner for
        this tick (the one in position t mod partners), against the
        tables as they stood at the start of the tick."""
        start = dict(self.tables)
        out, eps, score = {}, {}, {}
        for host in self.names:
            parts = self.partners[host]
            client = parts[t % len(parts)]
            self.sample_key[host], kt = jax.random.split(self.sample_key[host])
            self.ppat_key, kp = jax.random.split(self.ppat_key)
            out[host], eps[host], score[host] = self.entry(
                host, client, start[client], kt, kp)
        self.tables.update(out)
        return TickOut(eps, score)


_epoch_jit = jax.jit(epoch, static_argnames=("batch", "lr", "margin"))
_ppat_jit = jax.jit(_ppat, static_argnums=(2, 3, 5, 6))
