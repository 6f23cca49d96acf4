"""The benchmark's own knowledge-graph generators.

Copies of ``synthesize_universe`` (exact mode) and ``equal_shape_universe``
from the program's ``kge/data.py``, kept here so that a change to the
program cannot change the traffic it is measured on. Two departures:

* every owner gets exactly its configured entity, relation and triple
  counts for every seed: triples that dedupe removes are topped up by
  further draws, so a cell's shapes depend on its configuration alone;
* the generated triples are written once per (configuration, seed, hash of
  this package) under ``bench/.cache/`` and read back by later runs, as a
  deployment reads its graph from disk.

Outputs are plain arrays per owner: ``num_entities``, ``num_relations``,
``universe_ids`` and the 90:5:5 ``train``/``valid``/``test`` split.
"""
from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path
from typing import Dict, List, Tuple

import numpy as np

CACHE_DIR = Path(__file__).resolve().parents[1] / ".cache" / "universe"

Owner = Dict[str, np.ndarray]


def source_hash() -> str:
    """Hash of this package's sources: a change to the generator misses the
    on-disk cache instead of reading graphs the old code made."""
    h = hashlib.sha256()
    for p in sorted(Path(__file__).resolve().parent.glob("*.py")):
        h.update(p.name.encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def _nearest_blocks():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(tgt, hid, ent_z):
        ent_sq = jnp.sum(ent_z**2, axis=1)

        def block(args):
            t, h = args
            d = (
                jnp.sum(t**2, axis=1)[:, None]
                - 2 * jnp.dot(t, ent_z.T, precision=jax.lax.Precision.HIGHEST)
                + ent_sq[None]
            )
            d = d.at[jnp.arange(t.shape[0]), h].set(jnp.inf)  # no self-loop
            return jnp.argmin(d, axis=1)

        return jax.lax.map(block, (tgt, hid))

    return run


def _nearest_tails(target, h_idx, ent_z, *, block_elems: int = 1 << 26):
    """argmin_e ||target_i - ent_z[e]||^2 over e != h_idx[i], blockwise on the
    default device (an exact scan over every (query, entity) pair)."""
    import jax.numpy as jnp

    n, e = len(target), len(ent_z)
    rows = max(1, min(n, block_elems // max(1, e)))
    nb = -(-n // rows)
    pad = nb * rows - n
    tgt = np.pad(target, ((0, pad), (0, 0))).reshape(nb, rows, -1)
    hid = np.pad(h_idx, (0, pad)).reshape(nb, rows).astype(np.int32)
    out = _nearest_blocks()(jnp.asarray(tgt), jnp.asarray(hid), jnp.asarray(ent_z))
    return np.asarray(out).reshape(-1)[:n].astype(np.int64)


def _split(triples: np.ndarray, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    n = len(triples)
    order = rng.permutation(n)
    tr, va = int(0.9 * n), int(0.95 * n)
    return {
        "train": triples[order[:tr]],
        "valid": triples[order[tr:va]],
        "test": triples[order[va:]],
    }


def synthesize(
    owners: List[Tuple[str, int, int, int]],
    alignments: List[Tuple[str, str, int]],
    *,
    seed: int,
    latent_dim: int = 12,
    noise: float = 0.05,
) -> Dict[str, Owner]:
    """A latent universe with translational structure (h + r ~ t), carved
    into owners of exactly ``(name, relations, entities, triples)`` each,
    every pair sharing exactly its listed number of aligned entities.

    Tails are the exact nearest latent entity to z_h + z_r + noise, so a
    TransX model can fit the graph, and aligned entities are one latent
    object in both owners."""
    rng = np.random.default_rng(seed)
    sizes = {n: (r, e, t) for n, r, e, t in owners}
    total_universe = sum(e for _, e, _ in sizes.values()) - sum(
        k for _, _, k in alignments
    )
    z = rng.normal(0, 1.0, (total_universe, latent_dim)).astype(np.float32)
    total_rel = sum(r for r, _, _ in sizes.values())
    rel_z = rng.normal(0, 0.6, (total_rel, latent_dim)).astype(np.float32)

    assigned: Dict[str, set] = {n: set() for n in sizes}
    pool = rng.permutation(total_universe)
    cursor = 0

    def take(k):
        nonlocal cursor
        out = pool[cursor : cursor + k]
        cursor += k
        return out

    for a, b, n_al in alignments:
        ids = take(n_al).tolist()
        assigned[a].update(ids)
        assigned[b].update(ids)

    rel_cursor = 0
    out: Dict[str, Owner] = {}
    for name, (n_rel, n_ent, n_tri) in sizes.items():
        ids = list(assigned[name])
        ids.extend(take(n_ent - len(ids)).tolist())
        ids = np.array(sorted(ids), dtype=np.int64)
        rel_ids = np.arange(rel_cursor, rel_cursor + n_rel)
        rel_cursor += n_rel
        # draw rounds of n_tri candidate triples (one compiled shape) until
        # n_tri distinct ones exist; keep them in order of first draw
        got = np.zeros((0, 3), np.int32)
        for _ in range(64):
            if len(got) >= n_tri:
                break
            h_idx = rng.integers(0, n_ent, n_tri)
            r_idx = rng.integers(0, n_rel, n_tri)
            target = z[ids[h_idx]] + rel_z[rel_ids[r_idx]]
            target += rng.normal(0, noise, target.shape).astype(np.float32)
            t_idx = _nearest_tails(target, h_idx, z[ids])
            cand = np.stack([h_idx, r_idx, t_idx], axis=1).astype(np.int32)
            allc = np.concatenate([got, cand])
            _, first = np.unique(allc, axis=0, return_index=True)
            got = allc[np.sort(first)]
        if len(got) < n_tri:
            raise ValueError(f"{name}: {n_tri} distinct triples out of reach "
                             f"({len(got)} after 64 rounds)")
        triples = got[:n_tri]
        out[name] = {
            "num_entities": np.int64(n_ent),
            "num_relations": np.int64(n_rel),
            "universe_ids": ids,
            **_split(triples, rng),
        }
    return out


def equal_shape(
    n_owners: int, *, entities: int, relations: int, triples: int,
    shared: int, seed: int,
) -> Dict[str, Owner]:
    """N owners of identical extents; every pair shares the same ``shared``
    aligned entities, in the same local slots of every owner."""
    private = entities - shared
    if private < 0:
        raise ValueError("shared aligned block exceeds the entity count")
    out: Dict[str, Owner] = {}
    for i in range(n_owners):
        rng = np.random.default_rng(seed + 7919 * i)
        h = rng.integers(0, entities, triples)
        r = rng.integers(0, relations, triples)
        t = (h + 1 + rng.integers(0, entities - 1, triples)) % entities
        tri = np.stack([h, r, t], axis=1).astype(np.int32)
        ids = np.concatenate(
            [np.arange(shared), shared + i * private + np.arange(private)]
        ).astype(np.int64)
        out[f"K{i}"] = {
            "num_entities": np.int64(entities),
            "num_relations": np.int64(relations),
            "universe_ids": ids,
            **_split(tri, rng),
        }
    return out


def build(universe: dict, seed: int) -> Dict[str, Owner]:
    """Generate the owners that a configuration's ``universe`` block names."""
    kind = universe["kind"]
    if kind == "synthesize":
        return synthesize(
            [tuple(o) for o in universe["owners"]],
            [tuple(a) for a in universe.get("alignments", [])],
            seed=seed,
        )
    if kind == "equal_shape":
        return equal_shape(
            universe["owners"], entities=universe["entities"],
            relations=universe["relations"], triples=universe["triples"],
            shared=universe["shared"], seed=seed,
        )
    raise ValueError(f"unknown universe kind {kind!r}")


def load(universe: dict, seed: int, *, cache_dir: Path = CACHE_DIR) -> Tuple[Dict[str, Owner], float]:
    """The owners of ``universe`` at ``seed``, read from the on-disk cache.
    On a miss the graphs are generated and written first, then read back as
    on a hit. Returns (owners, seconds spent generating and writing, 0.0 on
    a hit): that is data preparation, which a run's set-up leaves out."""
    key = hashlib.sha256(
        json.dumps(universe, sort_keys=True).encode()
    ).hexdigest()[:12]
    path = cache_dir / f"{key}-{seed}-{source_hash()}.npz"
    built_s = 0.0
    if not path.exists():
        t = time.perf_counter()
        owners = build(universe, seed)
        cache_dir.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f".tmp{os.getpid()}.npz")
        np.savez(tmp, **{f"{n}/{k}": v for n, o in owners.items() for k, v in o.items()})
        os.replace(tmp, path)
        del owners
        built_s = time.perf_counter() - t
    with np.load(path) as f:
        owners: Dict[str, Owner] = {}
        for k in f.files:
            name, field = k.split("/", 1)
            owners.setdefault(name, {})[field] = f[k]
    return owners, built_s
