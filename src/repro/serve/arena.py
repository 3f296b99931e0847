"""Fixed-shape device memory arena for per-session serving state.

Every session's state (``OnlineState`` for ingest/query sessions,
``StreamState`` for streaming ones) is one *row* of a set of preallocated
slabs: each pytree leaf of the single-session template (inner batch dim
1) becomes a slab with a leading row axis.  Slot ids are handed out from
a free-list; nothing is ever reallocated per session.

REFCOUNTED ROWS: a live row is held by one or more logical references —
a resident session, a forked child sharing its parent's state
copy-on-write, a prefix-cache entry pinning a compressed shared prefix.
``alloc`` hands a row out at refcount 1, ``incref`` adds a holder, and
``free`` DROPS ONE REFERENCE — the row only returns to its shard's
free-list when the count hits zero.  Shared rows are read-only by
contract: every scatter entry point (``unpack`` / ``mark_dirty`` /
``reset_slots``) refuses target rows with refcount > 1, because a write
through one holder would silently corrupt every sibling — writers must
break sharing first (clone the row into a fresh slot and decref the
shared one; `SessionManager.activate_batch` does this with one jitted
clone per shard, `launch.serve.cow_clone_slots`).  The consistency
probe asserts the refcount bookkeeping (every live row counted >= 1,
refs tracked only for live rows) and reports any recorded write-guard
violation.

SHARDING (session-axis partitioning): the arena is split into
``n_shards`` equal contiguous row blocks along the leading axis — one
block per device when the engine runs mesh-native.  Shard ``s`` owns
rows ``[s * (slots_per_shard + 1), (s + 1) * (slots_per_shard + 1))``:
``slots_per_shard`` data rows handed out by the shard's OWN free-list,
plus one reserved *scratch* row at the block's end (``pad_slot_of(s)``).
Slot ids stay GLOBAL row indices, so every jitted gather/scatter —
``pack``/``unpack`` here, the engine's fused step, the pressure
controller's recompression — works verbatim on a sharded arena; when the
slabs carry a `NamedSharding` over the row axis the block boundaries
coincide with device boundaries and shard-local batches never touch
another device's rows.  ``n_shards=1`` reproduces the original layout
exactly (``n_slots + 1`` rows, scratch at ``n_slots``).

``pack`` gathers any set of active slot ids into a contiguous batch for
the vmapped session ops (`launch.serve.session_vmap`), and ``unpack``
scatters the updated batch back — both one jitted XLA gather/scatter
over donated buffers.  The engine's hot path fuses all three into one
program via `launch.serve.make_arena_step` (or, sharded, one
`shard_map` program via `make_sharded_arena_step`); pack/unpack here
serve the offload/restore and single-slot paths.

The scheduler pads a short batch up to its bucket size with the owning
shard's scratch row, so padding lanes gather scratch, compute garbage,
and scatter the garbage back to scratch — shapes stay bucketed with no
semantic effect and pad traffic stays shard-local.
"""
from __future__ import annotations

import functools
from collections import deque
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import inference as I
from repro.core import streaming as STR
from repro.kernels import ref
from repro.models.config import ModelConfig


class ArenaFull(RuntimeError):
    """No free session slots (caller should offload or shed load).

    Internal to the serve package: `ServeEngine` admission control
    guarantees this never escapes `submit`/`run` (batches are capped at
    evictable capacity — see `serve.admission`); it can still surface
    from direct `SessionArena`/`SessionManager` misuse."""


# Shared across every arena instance: jax.jit caches by function
# identity, so per-instance `jax.jit(...)` wrappers would recompile the
# same gather/scatter for every arena built (one per fuzzed trace in
# tests/simulation.py, one per engine elsewhere).
# XLA's own gather/scatter: it works on the slab in its native device
# layout (no relayout copy) and partitions over a mesh-placed arena.
@jax.jit
def _pack_slabs(slabs, ids):
    return jax.tree.map(lambda slab: ref.session_gather_ref(slab, ids), slabs)


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_slabs(slabs, ids, state):
    return jax.tree.map(
        lambda slab, rows: ref.session_scatter_ref(slab, ids, rows),
        slabs, state)


def online_template(cfg: ModelConfig, cache_len: int,
                    mem_slots: Optional[int] = None):
    """Single-session (inner batch 1) OnlineState shape tree."""
    return jax.eval_shape(
        functools.partial(I.init_online_state, cfg, 1, cache_len, mem_slots))


def stream_template(cfg: ModelConfig):
    """Single-session (inner batch 1) StreamState shape tree."""
    return jax.eval_shape(functools.partial(STR.init_stream_state, cfg, 1))


class SessionArena:
    """Slab allocator + jitted pack/unpack for one state template.

    ``n_shards``: partition the slots into equal contiguous row blocks,
    each with its own free-list and scratch row (see module docstring).
    ``place``: optional callable applied to the freshly-zeroed slabs
    (e.g. ``lambda t: jax.device_put(t, NamedSharding(mesh, P("shards")))``
    to pin one row block per device)."""

    def __init__(self, template: Any, n_slots: int, n_shards: int = 1,
                 place: Optional[Callable] = None):
        if n_slots < 1:
            raise ValueError("arena needs at least one slot")
        if n_shards < 1:
            raise ValueError("arena needs at least one shard")
        if n_slots % n_shards:
            raise ValueError(
                f"n_slots ({n_slots}) must divide evenly into n_shards "
                f"({n_shards}) so every device owns an equal block")
        self.template = template
        self.n_slots = n_slots
        self.n_shards = n_shards
        self.slots_per_shard = n_slots // n_shards
        self._stride = self.slots_per_shard + 1   # rows per shard block
        self.n_rows = n_shards * self._stride
        self.slabs = jax.tree.map(
            lambda s: jnp.zeros((self.n_rows,) + s.shape, s.dtype), template)
        # placed (mesh-sharded) slabs span several devices: callers that
        # stage data for pack/unpack must NOT commit it to one device
        # (committed single-device operands conflict with the sharded
        # slab inside the jitted gather/scatter) — see
        # `SessionManager._restore_batch`
        self.placed = place is not None
        if place is not None:
            self.slabs = place(self.slabs)
        self._free = [deque(self.shard_slots(s)) for s in range(n_shards)]
        self._live = set()
        self._refs = {}               # slot -> reference count (live only)
        self._dirty = set()           # slots that have ever been written
        self._violations = []         # recorded shared-row write attempts
        self._pack = _pack_slabs
        self._scatter = _scatter_slabs

    # -- allocation ----------------------------------------------------
    @classmethod
    def for_online(cls, cfg: ModelConfig, n_slots: int, cache_len: int,
                   mem_slots: Optional[int] = None, n_shards: int = 1,
                   place: Optional[Callable] = None) -> "SessionArena":
        return cls(online_template(cfg, cache_len, mem_slots), n_slots,
                   n_shards, place)

    @classmethod
    def for_stream(cls, cfg: ModelConfig, n_slots: int, n_shards: int = 1,
                   place: Optional[Callable] = None) -> "SessionArena":
        return cls(stream_template(cfg), n_slots, n_shards, place)

    # -- shard geometry ------------------------------------------------
    def shard_slots(self, shard: int) -> range:
        """The data rows shard ``shard`` owns (its scratch row excluded)."""
        base = shard * self._stride
        return range(base, base + self.slots_per_shard)

    def pad_slot_of(self, shard: int) -> int:
        """The shard's reserved scratch row (batch padding lanes)."""
        return shard * self._stride + self.slots_per_shard

    @property
    def pad_slot(self) -> int:
        """Shard 0's scratch row — with ``n_shards == 1`` this is row
        ``n_slots``, the original single-arena scratch slot."""
        return self.pad_slot_of(0)

    def shard_of(self, slot: int) -> int:
        """Owning shard of a global slot/row id."""
        return slot // self._stride

    def local_row(self, slot: int) -> int:
        """Row index within the owning shard's block (what a device sees
        under `shard_map`: ``slots_per_shard`` is every shard's local
        scratch row)."""
        return slot % self._stride

    @property
    def n_free(self) -> int:
        return sum(len(f) for f in self._free)

    def shard_free(self, shard: int) -> int:
        return len(self._free[shard])

    @property
    def occupancy(self) -> float:
        return 1.0 - self.n_free / self.n_slots

    def alloc(self, shard: int = 0) -> int:
        if not 0 <= shard < self.n_shards:
            raise ValueError(f"shard {shard} out of range "
                             f"[0, {self.n_shards})")
        if not self._free[shard]:
            raise ArenaFull(
                f"all {self.slots_per_shard} slots of shard {shard} in use")
        slot = self._free[shard].popleft()
        self._live.add(slot)
        self._refs[slot] = 1
        return slot

    def incref(self, slot: int) -> int:
        """Add one logical reference to a live row (fork / prefix-cache
        attach); returns the new count.  The row will survive ``free``
        calls until every holder has released it."""
        if slot not in self._live:
            raise ValueError(f"slot {slot} is not allocated")
        self._refs[slot] += 1
        return self._refs[slot]

    def refcount(self, slot: int) -> int:
        """Current reference count (0 for rows not allocated)."""
        return self._refs.get(slot, 0)

    def shared(self, slot: int) -> bool:
        """Whether the row has more than one holder (writes forbidden
        until sharing is broken)."""
        return self._refs.get(slot, 0) > 1

    def shared_slots(self) -> List[int]:
        """Live rows currently held by more than one reference."""
        return sorted(s for s, n in self._refs.items() if n > 1)

    def free(self, slot: int) -> int:
        """Drop ONE reference; the row returns to its shard's free-list
        only when no holder remains.  Returns the remaining count."""
        if slot not in self._live:
            raise ValueError(f"slot {slot} is not allocated")
        self._refs[slot] -= 1
        left = self._refs[slot]
        if left == 0:
            del self._refs[slot]
            self._live.remove(slot)
            self._free[self.shard_of(slot)].append(slot)
        return left

    def _guard_writes(self, slot_ids) -> None:
        """Reject any scatter targeting a shared row: one holder writing
        through a row with refcount > 1 would corrupt every sibling.
        Violations are recorded (surfaced by `consistency_errors`) and
        raised — callers must COW-break first."""
        bad = sorted({int(s) for s in slot_ids
                      if self._refs.get(int(s), 0) > 1})
        if bad:
            msg = (f"write targets shared rows {bad} (refcount > 1): "
                   "break sharing (cow_clone_slots) before any scatter")
            self._violations.append(msg)
            raise RuntimeError(msg)

    def metrics_sample(self) -> dict:
        """Point-in-time occupancy sample for gauge export (the engine's
        ``_sample_gauges`` reads this on every metrics snapshot).  The
        ``shards`` list carries the same sample per shard block."""
        return {"n_slots": self.n_slots, "live": self.n_slots - self.n_free,
                "free": self.n_free, "occupancy": self.occupancy,
                "shared": len(self.shared_slots()),
                "shards": [
                    {"n_slots": self.slots_per_shard,
                     "live": self.slots_per_shard - len(self._free[s]),
                     "free": len(self._free[s]),
                     "occupancy": 1.0 - (len(self._free[s])
                                         / self.slots_per_shard)}
                    for s in range(self.n_shards)]}

    def consistency_errors(self) -> list:
        """Free-list / live-set invariant violations (empty = healthy):
        no slot both free and live, no duplicates in any shard's free
        list, every data row of every shard accounted exactly once, and
        no slot parked on the wrong shard's free-list.  The serve
        property suite asserts this after every simulated event
        (double-free / leak / cross-shard corruption detection)."""
        errs = []
        all_free = []
        for shard in range(self.n_shards):
            free = list(self._free[shard])
            owned = set(self.shard_slots(shard))
            stray = [s for s in free if s not in owned]
            if stray:
                errs.append(f"shard {shard} free list holds foreign "
                            f"slots: {sorted(stray)}")
            all_free.extend(free)
        if len(all_free) != len(set(all_free)):
            errs.append(f"duplicate slots in free lists: "
                        f"{sorted(all_free)}")
        overlap = set(all_free) & self._live
        if overlap:
            errs.append(f"slots both free and live: {sorted(overlap)}")
        data_rows = set()
        for shard in range(self.n_shards):
            data_rows.update(self.shard_slots(shard))
        missing = data_rows - set(all_free) - self._live
        if missing:
            errs.append(f"slots leaked (neither free nor live): "
                        f"{sorted(missing)}")
        bogus = (set(all_free) | self._live) - data_rows
        if bogus:
            errs.append(f"out-of-range slots tracked: {sorted(bogus)}")
        unref = self._live - set(self._refs)
        if unref:
            errs.append(f"live slots with no refcount: {sorted(unref)}")
        ghost = set(self._refs) - self._live
        if ghost:
            errs.append(f"refcounts tracked for dead slots: "
                        f"{sorted(ghost)}")
        nonpos = sorted(s for s, n in self._refs.items() if n < 1)
        if nonpos:
            errs.append(f"non-positive refcounts: {nonpos}")
        errs.extend(f"shared-row write attempted: {v}"
                    for v in self._violations)
        return errs

    # -- batched pack/unpack -------------------------------------------
    def pack(self, slot_ids: Sequence[int]):
        """Gather slots into a batch: leaves (B,) + template shape."""
        ids = jnp.asarray(slot_ids, jnp.int32)
        return self._pack(self.slabs, ids)

    def unpack(self, slot_ids: Sequence[int], state) -> None:
        """Scatter an updated batch back (donates slabs + batch)."""
        self._guard_writes(slot_ids)
        ids = jnp.asarray(slot_ids, jnp.int32)
        self._dirty.update(int(i) for i in slot_ids)
        self.slabs = self._scatter(self.slabs, ids, state)

    def mark_dirty(self, slot_ids: Sequence[int]) -> None:
        """Record external writes (the engine's fused step updates
        ``slabs`` directly without going through ``unpack``)."""
        self._guard_writes(slot_ids)
        self._dirty.update(int(i) for i in slot_ids)

    # -- single-slot access (offload/restore path) ---------------------
    def read_slot(self, slot: int):
        """One session's state (template shape, no batch axis)."""
        return jax.tree.map(lambda slab: slab[slot], self.slabs)

    def write_slot(self, slot: int, state) -> None:
        """Write one session's state (template shape) into a slot."""
        batched = jax.tree.map(lambda x: jnp.asarray(x)[None], state)
        self.unpack([slot], batched)

    def reset_slots(self, slot_ids: Sequence[int]) -> None:
        """Zero slots (fresh sessions) — never-written slots are already
        zero from construction and are skipped; the rest are cleared with
        one batched scatter, padded to a bucketed size (extra lanes hit
        the scratch row) so the scatter only ever compiles per bucket."""
        from repro.launch.specs import batch_bucket
        stale = [s for s in slot_ids if s in self._dirty]
        if not stale:
            return
        # bucket for the common case; fall back to the exact count when
        # it exceeds the largest bucket (pad_slot may repeat — harmless)
        n = max(batch_bucket(len(stale)), len(stale))
        ids = stale + [self.pad_slot] * (n - len(stale))
        zeros = jax.tree.map(
            lambda s: jnp.zeros((n,) + s.shape, s.dtype), self.template)
        self.unpack(ids, zeros)
        self._dirty.difference_update(stale)

    def reset_slot(self, slot: int) -> None:
        """Zero a slot (fresh session without a host-side init tree)."""
        self.reset_slots([slot])
