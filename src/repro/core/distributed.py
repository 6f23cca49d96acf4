"""Mesh-mapped FKGE — the paper's process topology on a TPU mesh.

The paper runs each KG owner as a GPU process and ships (batch, d) adversarial
samples / gradients over OS pipes. On a pod we map each owner to a slice of
the mesh along a ``party`` axis and the exchange becomes a
``jax.lax.ppermute`` (collective-permute over ICI/DCI):

    client slice:  adv = X_batch @ W          ──ppermute──►  host slice
    host slice:    teachers/PATE/student step, ∂L_G/∂adv  ──ppermute──► client
    client slice:  W ← W − lr·Xᵀ·∂L_G/∂adv

Privacy boundary: the only tensors crossing slices are the generated samples
and their gradients — exactly the paper's interface. Raw X and Y never leave
their slice; this is verifiable in the lowered HLO (the collective-permute
operands are (batch, d) and (batch, d), nothing else).

Also provides a sharded KGE train step: the entity table is sharded over the
``model`` axis (LOD-scale tables don't fit one device) and triple batches over
``data``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core.pate import pate_vote, teacher_votes
from repro.core.ppat import PPATConfig, _disc_prob, _init_disc, _sgd_momentum


def make_party_mesh(n_parties: int = 2) -> Mesh:
    devs = jax.devices()[:n_parties]
    return jax.make_mesh(
        (n_parties,), ("party",), devices=devs, axis_types=(AxisType.Auto,)
    )


# --------------------------------------------------------------- owner mesh
#: meshes are cached per extent so repeated tick dispatches hand jit the SAME
#: mesh object (equal-but-distinct meshes would still hit the pjit cache, but
#: the cache keeps sharding construction off the per-tick hot path)
_OWNER_MESHES: dict = {}


def make_owner_mesh(n_owners: int) -> Mesh:
    """A 1-D ``("owners",)`` mesh over the first ``n_owners`` devices — the
    federation tick engine's unit of spatial parallelism: each KG owner's
    tick-plan entry subgraph runs on its own device (the paper's
    one-process-per-KG topology, minus the OS pipes)."""
    mesh = _OWNER_MESHES.get(n_owners)
    if mesh is None:
        if n_owners > len(jax.devices()):
            raise ValueError(
                f"owner mesh of {n_owners} exceeds {len(jax.devices())} devices"
            )
        mesh = owner_mesh_over(jax.devices()[:n_owners])
        _OWNER_MESHES[n_owners] = mesh
    return mesh


def owner_mesh_over(devices: Sequence) -> Mesh:
    """The ``("owners",)`` mesh over ``devices`` in exactly their order:
    group assembly puts entry ``k`` on ``devices[k]`` and reads position
    ``k`` back from mesh slot ``k``. ``jax.make_mesh`` would reorder TPU
    chips into a physical ring (``[0, 1, 3, 2]`` on a v5e 2x2), which
    silently hands each entry another owner's result."""
    return Mesh(np.asarray(devices), ("owners",), axis_types=(AxisType.Auto,))


def owner_shard_map(fn, n_owners: int):
    """SPMD-map ``fn`` over a stacked-leading-owner-axis pytree: each owner's
    slice executes on its own mesh device, with no collectives — ``fn`` is
    traced ONCE, so N equal-shaped owners cost one trace + one compile
    instead of N (the tick engine's trace-time dedup lever). The body sees
    local shards of extent 1 and must keep the leading axis."""
    mesh = make_owner_mesh(n_owners)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(P("owners"),), out_specs=P("owners"),
        check_vma=False,
    )


def owner_sharding(n_owners: int) -> NamedSharding:
    """Input sharding for ``owner_shard_map`` operands (leading owner axis)."""
    return NamedSharding(make_owner_mesh(n_owners), P("owners"))


# ---------------------------------------------------- owner-sticky placement
class OwnerPlacement:
    """Sticky owner → device registry: each owner is assigned a home device
    (round-robin, in first-seen order) the first time it is looked up, and
    the assignment NEVER changes afterwards — plan recomposition (drained
    queues, mixed handshake/self-train ticks, owners joining late) cannot
    re-place an owner. This is what lets the federation tick engine keep an
    owner's state (embedding tables, padded triple stores, CSR filters, pair
    caches) resident on one chip across ticks instead of re-staging it from
    the default device every dispatch."""

    def __init__(self, devices: Optional[Sequence] = None):
        self.devices: Tuple = tuple(
            devices if devices is not None else jax.devices()
        )
        self._slot: Dict[str, int] = {}
        #: latest published view version resident on each owner's home —
        #: streamed federation keys residency by VERSION, not tick: once
        #: owners desynchronize the global tick no longer says whether the
        #: state on a device is current, the owner's version counter does
        self._version: Dict[str, int] = {}

    def slot(self, owner: str) -> int:
        """The owner's sticky device index (== its preferred position in an
        owner-mesh chunk)."""
        s = self._slot.get(owner)
        if s is None:
            s = len(self._slot) % len(self.devices)
            self._slot[owner] = s
        return s

    def device(self, owner: str):
        return self.devices[self.slot(owner)]

    def note_version(self, owner: str, version: int) -> None:
        """Record that ``owner``'s sticky home now holds its ``version``-th
        accepted publish (called from every scheduler accept path)."""
        self._version[owner] = int(version)

    def version(self, owner: str) -> int:
        """The owner's latest published view version resident on its home
        (0 before any accept)."""
        return self._version.get(owner, 0)

    def assignments(self) -> Dict[str, int]:
        return dict(self._slot)

    def restore_assignments(self, slots: Dict[str, int]) -> None:
        """Adopt checkpointed sticky assignments (crash-consistent resume):
        a resumed scheduler sees owners in *resume-plan* order, not the
        original first-sight order, so without this the homes — and hence
        which device each owner's resident caches repopulate on — could
        differ from the interrupted run. Slots beyond this process's device
        count wrap (the mesh may have shrunk across the restart)."""
        for owner, slot in slots.items():
            self._slot[owner] = int(slot) % len(self.devices)


def replica_devices(home_slot: int, n: int, devices: Optional[Sequence] = None) -> List:
    """The serving tier's replica ring for an owner homed at ``home_slot``:
    ``n`` consecutive mesh devices starting at the home, wrapping, and
    clamped to the mesh size (asking for 4 replicas on a 2-device mesh
    yields 2). Replica 0 IS the owner's sticky home device — the device
    owner-sticky federation keeps the accepted tables resident on — so a
    version publish's first replica copy is zero-copy by construction."""
    devices = tuple(devices if devices is not None else jax.devices())
    if n < 1:
        raise ValueError(f"replica count must be >= 1, got {n}")
    n = min(int(n), len(devices))
    return [devices[(int(home_slot) + i) % len(devices)] for i in range(n)]


def committed_device(tree) -> Optional[jax.Device]:
    """The single device a pytree is committed to, or ``None`` when its
    leaves are uncommitted (free to follow any computation). Used by
    non-sharded consumers (the serial federation path, trainer handoff) to
    co-locate their own operands with owner-resident state."""
    for leaf in jax.tree.leaves(tree):
        if getattr(leaf, "committed", False):
            devs = leaf.devices()
            if len(devs) == 1:
                return next(iter(devs))
    return None


def chunk_extents(n: int, n_devices: int) -> List[Tuple[int, int]]:
    """Decompose a signature bucket of ``n`` entries into ``(real, extent)``
    chunks: greedy full-mesh chunks of ``n_devices`` entries, then ONE
    remainder chunk whose extent is the next power of two (capped at the
    device count) — the ``extent - real`` tail positions are filled with
    masked dummy entries (replicas of a real entry whose outputs are
    discarded).

    Restricting extents to ``{n_devices} ∪ {2^k < n_devices}`` caps group
    programs per signature at ~log₂(devices): a bucket shrinking by one
    owner (an owner draining its queue mid-federation) re-pads into an
    already-compiled extent instead of compiling one program per exact
    bucket size.
    """
    if n_devices < 1:
        raise ValueError("chunk_extents needs at least one device")
    out: List[Tuple[int, int]] = []
    pos = 0
    while n - pos >= n_devices:
        out.append((n_devices, n_devices))
        pos += n_devices
    r = n - pos
    if r:
        extent = min(1 << (r - 1).bit_length(), n_devices)
        out.append((r, extent))
    return out


def assemble_group(entries: List[Dict], extent: int) -> Dict:
    """Zero-copy stacking of per-owner inputs into shard_map group operands.

    ``entries`` are ``extent`` structurally-identical pytrees whose leaves
    are committed single-device arrays, entry ``k`` on mesh device ``k``.
    Each leaf is stacked along a leading owner axis via
    ``jax.make_array_from_single_device_arrays`` — a metadata-only view of
    the resident per-device shards, NOT a gather-to-one-device + re-shard
    (the ``jnp.stack`` + ``device_put`` this replaces paid 2·extent array
    movements per leaf per tick). The only per-leaf device work is the
    ``expand_dims`` reshape producing the (1, ...) shard view."""
    sharding = owner_sharding(extent)
    flats = [jax.tree.flatten(e) for e in entries]
    treedef = flats[0][1]
    stacked = []
    for leaves in zip(*(f[0] for f in flats)):
        shards = [jnp.expand_dims(x, 0) for x in leaves]
        stacked.append(
            jax.make_array_from_single_device_arrays(
                (extent,) + tuple(leaves[0].shape), sharding, shards
            )
        )
    return jax.tree.unflatten(treedef, stacked)


def disassemble_group(out, extent: int) -> List:
    """Split a shard_map group output back into per-owner pytrees WITHOUT
    moving data: position ``k``'s result is mesh device ``k``'s shard,
    squeezed back to the unstacked shape and still committed to that device
    — group outputs stay owner-resident across ticks."""
    leaves, treedef = jax.tree.flatten(out)
    per_pos = [[] for _ in range(extent)]
    for leaf in leaves:
        shards = sorted(leaf.addressable_shards, key=lambda s: s.index[0].start)
        for k in range(extent):
            per_pos[k].append(jnp.squeeze(shards[k].data, axis=0))
    return [jax.tree.unflatten(treedef, p) for p in per_pos]


def init_distributed_ppat(key, dim: int, cfg: PPATConfig):
    """Host discriminator params + client W, replicated pytree."""
    kt, ks = jax.random.split(key)
    teachers = jax.vmap(lambda k: _init_disc(k, dim, cfg.hidden))(
        jax.random.split(kt, cfg.num_teachers)
    )
    student = _init_disc(ks, dim, cfg.hidden)
    return {
        "teachers": teachers,
        "teachers_vel": jax.tree.map(jnp.zeros_like, teachers),
        "student": student,
        "student_vel": jax.tree.map(jnp.zeros_like, student),
        "w": jnp.eye(dim, dtype=jnp.float32),
        "w_vel": jnp.zeros((dim, dim), jnp.float32),
    }


def ppat_exchange_step(mesh: Mesh, cfg: PPATConfig):
    """Build the SPMD one-round function.

    Layout: party 0 = client (holds x batches), party 1 = host (holds y
    batches). SPMD means both slices execute the same program on their local
    shard; role-irrelevant results are masked out. The two ppermutes in the
    lowered HLO are the paper's pipe sends.
    """

    def step(state, xb, yb, key):
        # xb: (2, B, d) party-sharded — party 0's slice is the real X batch.
        # yb: (2, B, d) — party 1's slice is the real Y batch.
        def spmd(state, xb, yb, key):
            party = jax.lax.axis_index("party")
            xb = xb[0]  # local shard (1, B, d) → (B, d)
            yb = yb[0]
            key = key[0]

            # --- client role: generate adversarial samples ----------------
            adv_local = xb @ state["w"]
            # pipe: client → host (0 → 1)
            adv = jax.lax.ppermute(adv_local, "party", [(0, 1), (1, 0)])
            # on party 1, ``adv`` now holds the client's generated batch

            # --- host role: teachers + PATE + student ---------------------
            t = cfg.num_teachers
            b, d = adv.shape
            per = b // t
            adv_parts = adv[: per * t].reshape(t, per, d)
            real_parts = yb[: per * t].reshape(t, per, d)

            def teacher_loss(tp, fake, re):
                pf = _disc_prob(tp, fake)
                pr = _disc_prob(tp, re)
                return -(jnp.mean(jnp.log(1 - pf + 1e-8)) + jnp.mean(jnp.log(pr + 1e-8)))

            t_losses, t_grads = jax.vmap(jax.value_and_grad(teacher_loss))(
                state["teachers"], adv_parts, real_parts
            )
            is_host = (party == 1).astype(jnp.float32)
            t_grads = jax.tree.map(lambda g: g * is_host, t_grads)
            new_teachers, new_tvel = _sgd_momentum(
                state["teachers"], t_grads, state["teachers_vel"], cfg.lr, cfg.momentum
            )

            probs = jax.vmap(lambda tp: _disc_prob(tp, adv))(new_teachers)
            labels, n0, n1 = pate_vote(key, teacher_votes(probs), cfg.lam)

            def student_loss(sp):
                ps = _disc_prob(sp, adv)
                return -jnp.mean(
                    labels * jnp.log(ps + 1e-8) + (1 - labels) * jnp.log(1 - ps + 1e-8)
                )

            s_loss, s_grads = jax.value_and_grad(student_loss)(state["student"])
            s_grads = jax.tree.map(lambda g: g * is_host, s_grads)
            new_student, new_svel = _sgd_momentum(
                state["student"], s_grads, state["student_vel"], cfg.lr, cfg.momentum
            )

            def gen_loss(a):
                ps = _disc_prob(new_student, a)
                if cfg.saturating:
                    return jnp.mean(jnp.log(1 - ps + 1e-8))
                return -jnp.mean(jnp.log(ps + 1e-8))

            g_loss, grad_adv = jax.value_and_grad(gen_loss)(adv)
            # pipe: host → client (1 → 0)
            grad_back = jax.lax.ppermute(grad_adv, "party", [(1, 0), (0, 1)])

            # --- client role: apply chain rule to W -----------------------
            is_client = (party == 0).astype(jnp.float32)
            gw = (xb.T @ grad_back) * is_client
            new_wvel = cfg.momentum * state["w_vel"] + gw
            new_w = state["w"] - cfg.lr * new_wvel
            if cfg.ortho_beta:
                bta = cfg.ortho_beta
                new_w = (1 + bta) * new_w - bta * (new_w @ new_w.T) @ new_w
            new_w = jnp.where(is_client > 0, new_w, state["w"])

            new_state = {
                "teachers": new_teachers,
                "teachers_vel": new_tvel,
                "student": new_student,
                "student_vel": new_svel,
                "w": new_w,
                "w_vel": jnp.where(is_client > 0, new_wvel, state["w_vel"]),
            }
            # replicate role-owned state across parties so the pytree stays
            # consistent: host owns discriminators, client owns W.
            sync = lambda v, owner: jax.lax.ppermute(
                v, "party", [(owner, 1 - owner)]
            ) * (1 - _mine(party, owner)) + v * _mine(party, owner)

            def _mine(p, owner):
                return (p == owner).astype(jnp.float32)

            for k in ("teachers", "teachers_vel", "student", "student_vel"):
                new_state[k] = jax.tree.map(lambda v: sync(v, 1), new_state[k])
            for k in ("w", "w_vel"):
                new_state[k] = sync(new_state[k], 0)
            # metrics get a leading local axis so out_specs can concatenate
            # them over parties; row 1 (the host) is the authoritative one.
            metrics = {
                "gen_loss": g_loss[None],
                "student_loss": s_loss[None],
                "teacher_loss": jnp.mean(t_losses)[None],
            }
            return new_state, metrics, (n0, n1)

        fn = jax.shard_map(
            spmd,
            mesh=mesh,
            in_specs=(P(), P("party"), P("party"), P("party")),
            out_specs=(P(), P("party"), P("party")),
            check_vma=False,
        )
        return fn(state, xb, yb, key)

    return jax.jit(step)


# ---------------------------------------------------------------- sharded KGE
def make_sharded_kge_step(mesh: Mesh, model, *, lr: float):
    """Data-parallel margin-loss step with the entity table sharded over
    'model' and triple batches over 'data' — the substrate FKGE rides on for
    LOD-scale KGs (1.4M × d tables)."""
    from repro.kge.models import margin_loss, score_triples

    ent_spec = P("model", None)
    rel_spec = P(None, None)

    def step(params, pos, neg):
        def loss_fn(p):
            sp = score_triples(p, model, pos[:, 0], pos[:, 1], pos[:, 2])
            sn = score_triples(p, model, neg[:, 0], neg[:, 1], neg[:, 2])
            return margin_loss(sp, sn, model.margin)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params = jax.tree.map(lambda x, g: x - lr * g, params, grads)
        return params, loss

    in_shardings = (
        {"ent": NamedSharding(mesh, ent_spec), "rel": NamedSharding(mesh, rel_spec)},
        NamedSharding(mesh, P("data", None)),
        NamedSharding(mesh, P("data", None)),
    )
    out_shardings = (
        {"ent": NamedSharding(mesh, ent_spec), "rel": NamedSharding(mesh, rel_spec)},
        NamedSharding(mesh, P()),
    )
    return jax.jit(step, in_shardings=in_shardings, out_shardings=out_shardings)
