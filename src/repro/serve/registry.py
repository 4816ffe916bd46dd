"""Multi-tenant ACAM template-bank registry (the serving super-bank).

The wearable scenario (per-device calibrated templates — see PAPERS.md) puts
one small `TemplateBank` per tenant on the server. Serving them one kernel
launch per tenant would waste the fused classify kernel's batching, so the
registry **pads and stacks** every tenant's bank into ONE device-resident
super-bank:

  * tenant classes occupy a contiguous row range ``[offset, offset + C)``
    of a shared ``(C_cap, K_max, N)`` bank — the scheduler restricts each
    request's Eq. 12 decision to its tenant's range via the class windows
    of the serve kernel (`repro.kernels.acam_match.ops.serve_classify`);
  * per-tenant binarisation thresholds live in a ``(T_cap, N)`` table; the
    scheduler gathers each slot's row and *shifts the query features* so one
    shared zero-threshold binarisation serves every tenant in the batch;
  * **bucketed shapes**: class ranges are allocated in ``class_bucket``
    units and capacities (``C_cap``, ``T_cap``) only ever grow by doubling,
    so hot register / update / evict leave the device arrays' shapes — and
    therefore every jitted caller's trace cache — untouched in the steady
    state. A capacity grow is the only (rare) retrace event.

Host-side numpy mirrors hold the authoritative state; device arrays are
rebuilt lazily (`device_bank`, `thresholds_table`) and cached per
`generation` and mesh generation, so an unchanged registry never re-uploads
and the scheduler's "one bank gather per tick" stays a gather, not a
transfer. Under a sharded plan the scheduler hands over the plan's
`Placement`, and each array goes from host memory straight onto its
sharding (class rows over the model axis), once: the counters
``bank_placements`` / ``bank_bytes_placed`` (`stats`) count them.

The serve kernel keeps all ``K_max * padded_classes(C_cap)`` template rows
VMEM-resident up to `repro.match.MAX_FUSED_ROWS` and walks the class
dimension in chunks past it — same semantics, still one dispatch per tick.
The scheduler's dispatch routes through `repro.match.MatchEngine`, so the
same super-bank also serves the `reference` and `device` (RRAM-physics)
backends and executes under the engine's 2D `PartitionPlan` when a mesh is
installed: the batch shards over the data-parallel axes and the
super-bank's class rows shard over the model axis.

Bank sharding is why the registry is **shard-aligned**: constructed with
``bank_shards=S`` (the spec path passes `ServiceSpec.mesh.bank_shards`
explicitly; the legacy service shim infers it from the installed mesh via
`repro.match.bank_shards_in_mesh`), capacity stays divisible by S and the
allocator never places a tenant's bucket run across a shard boundary —
every tenant's Eq. 12 class window lives on ONE device, so a request's
scores come from a single shard and only the tiny (max, argmax) reduce
crosses devices. Per-shard padding rows keep ``valid = False`` and are
driven to -inf before the WTA, exactly like bucket padding. Capacity grows
by doubling, which doubles the shard row count: old shard boundaries are a
superset of the new ones, so existing placements stay aligned. `reshard`
re-packs every bucket run to NEW shard boundaries in place (live
resharding, driven by `HybridService.reconfigure`) — tenants keep their
ids, slots, thresholds and template rows; only offsets move.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from repro.core.templates import TemplateBank
from repro.match.plan import Placement


class RegistryError(ValueError):
    """Raised for invalid register/update/evict operations."""


@dataclasses.dataclass(frozen=True)
class TenantEntry:
    """Immutable snapshot of a tenant's placement in the super-bank."""

    tenant_id: str
    slot: int  # row in the thresholds (and service head) tables
    offset: int  # first class row in the super-bank
    num_classes: int  # true class count
    c_bucket: int  # allocated (bucketed) class rows
    k: int  # true templates-per-class
    valid_rows: int  # programmed template rows (ACAM energy, Eq. 14)
    generation: int  # registry generation at (re)registration

    @property
    def window(self) -> tuple[int, int]:
        """The tenant's Eq. 12 class window [lo, hi) in the super-bank."""
        return self.offset, self.offset + self.num_classes


@dataclasses.dataclass
class PreparedBank:
    """A shadow super-bank built alongside the live one (`prepare_reshard`):
    fresh host arrays re-packed to new shard boundaries plus the tenant
    placements that go with them. `adopt_prepared` flips the registry to
    this buffer between scheduler ticks; `source_generation` pins the
    registry state it was built from (any mutation in between makes the
    buffer stale and the flip refuses)."""

    bank_shards: int
    capacity: int
    source_generation: int
    arrays: dict  # _templates/_lower/_upper/_valid replacement arrays
    bucket_used: "np.ndarray"
    placements: list  # [(tenant_id, new_offset)]
    moved: int  # tenants whose offset changed


class TemplateBankRegistry:
    """Registry of per-tenant `TemplateBank`s stacked into one super-bank."""

    def __init__(self, num_features: int, *, k_max: int = 2,
                 class_bucket: int = 16, initial_classes: int = 128,
                 initial_tenants: int = 8, bank_shards: int = 1):
        if initial_classes % class_bucket:
            raise ValueError("initial_classes must be a class_bucket multiple")
        if bank_shards < 1:
            raise ValueError("bank_shards must be >= 1")
        self.num_features = num_features
        self.k_max = k_max
        self.class_bucket = class_bucket
        self.bank_shards = bank_shards
        # capacity must cut into bank_shards equal shards of whole buckets
        # (the engine's PartitionPlan shards class rows in C_cap/S chunks)
        align = bank_shards * class_bucket
        initial_classes = -(-initial_classes // align) * align
        self._c_cap = initial_classes
        self._t_cap = initial_tenants
        n = num_features
        self._templates = np.zeros((self._c_cap, k_max, n), np.float32)
        self._lower = np.zeros((self._c_cap, k_max, n), np.float32)
        self._upper = np.zeros((self._c_cap, k_max, n), np.float32)
        self._valid = np.zeros((self._c_cap, k_max), bool)
        self._thr = np.zeros((self._t_cap, n), np.float32)
        self._bucket_used = np.zeros(self._c_cap // class_bucket, bool)
        self._slot_used = np.zeros(self._t_cap, bool)
        self._tenants: dict[str, TenantEntry] = {}
        self.generation = 0
        #: (placement key, bank, thresholds table): `device_bank`
        self._device_cache: tuple | None = None
        #: placements made (one per generation that served) and their bytes
        self.bank_placements = 0
        self.bank_bytes_placed = 0

    # -- introspection ------------------------------------------------------

    def __contains__(self, tenant_id: str) -> bool:
        return tenant_id in self._tenants

    def __len__(self) -> int:
        return len(self._tenants)

    def get(self, tenant_id: str) -> TenantEntry:
        try:
            return self._tenants[tenant_id]
        except KeyError:
            raise RegistryError(f"unknown tenant {tenant_id!r}") from None

    def lookup(self, tenant_id: str) -> TenantEntry | None:
        """Non-raising `get` — the scheduler re-resolves entries at tick
        time so queued requests always see the tenant's *current* placement
        (hot update may relocate it; evict removes it)."""
        return self._tenants.get(tenant_id)

    def bank_of(self, tenant_id: str) -> TemplateBank:
        """The tenant's CURRENT bank, read back out of the packed host
        arrays as (num_classes, k, N) host copies — byte-identical to what
        `device_bank()` serves for this tenant's window. This is how
        restore paths (e.g. the semantic cache's template slots) rebuild
        per-tenant state from a loaded registry without re-deriving it."""
        e = self.get(tenant_id)
        sl = slice(e.offset, e.offset + e.num_classes)
        return TemplateBank(
            templates=self._templates[sl, :e.k].copy(),
            lower=self._lower[sl, :e.k].copy(),
            upper=self._upper[sl, :e.k].copy(),
            valid=self._valid[sl, :e.k].copy(),
            thresholds=self._thr[e.slot].copy())

    @property
    def capacity_classes(self) -> int:
        return self._c_cap

    @property
    def capacity_tenants(self) -> int:
        return self._t_cap

    def stats(self) -> dict:
        return {
            "tenants": len(self._tenants),
            "generation": self.generation,
            "capacity_classes": self._c_cap,
            "capacity_tenants": self._t_cap,
            "used_class_buckets": int(self._bucket_used.sum()),
            "programmed_rows": int(self._valid.sum()),
            "bank_shards": self.bank_shards,
            "rows_per_shard": self.rows_per_shard,
            "bank_placements": self.bank_placements,
            "bank_bytes_placed": self.bank_bytes_placed,
        }

    def shard_rows_used(self) -> list[int]:
        """Allocated class rows per bank shard (bucket granularity) — the
        autoscaling policy's primary signal: when the fullest shard
        approaches `rows_per_shard`, the next registration may force a
        capacity grow (device-shape change + retrace), so the policy
        escalates `bank_shards` *before* that happens."""
        per_shard = self.rows_per_shard // self.class_bucket
        return [int(self._bucket_used[s * per_shard:(s + 1) * per_shard]
                    .sum()) * self.class_bucket
                for s in range(self.bank_shards)]

    # -- allocation ---------------------------------------------------------

    @property
    def rows_per_shard(self) -> int:
        """Class rows per bank shard (== C_cap when unsharded)."""
        return self._c_cap // self.bank_shards

    def _alloc_classes(self, n_buckets: int) -> int:
        """First-fit contiguous bucket run that never straddles a shard
        boundary; grows capacity (doubling) when fragmented/full — the only
        event that changes device shapes. Growth doubles the shard size, so
        new boundaries are a subset of old ones and placements stay legal."""
        while True:
            shard_buckets = self.rows_per_shard // self.class_bucket
            run = 0
            for i, used in enumerate(self._bucket_used):
                if i % shard_buckets == 0:
                    run = 0  # runs restart at every shard boundary
                run = 0 if used else run + 1
                if run == n_buckets:
                    start = i - n_buckets + 1
                    self._bucket_used[start:i + 1] = True
                    return start * self.class_bucket
            self._grow_classes()

    def _grow_classes(self) -> None:
        old = self._c_cap
        self._c_cap *= 2
        for name in ("_templates", "_lower", "_upper"):
            arr = getattr(self, name)
            grown = np.zeros((self._c_cap,) + arr.shape[1:], arr.dtype)
            grown[:old] = arr
            setattr(self, name, grown)
        valid = np.zeros((self._c_cap, self.k_max), bool)
        valid[:old] = self._valid
        self._valid = valid
        used = np.zeros(self._c_cap // self.class_bucket, bool)
        used[:old // self.class_bucket] = self._bucket_used
        self._bucket_used = used

    def _alloc_slot(self) -> int:
        free = np.flatnonzero(~self._slot_used)
        if free.size == 0:
            old = self._t_cap
            self._t_cap *= 2
            thr = np.zeros((self._t_cap, self.num_features), np.float32)
            thr[:old] = self._thr
            self._thr = thr
            used = np.zeros(self._t_cap, bool)
            used[:old] = self._slot_used
            self._slot_used = used
            free = np.flatnonzero(~self._slot_used)
        slot = int(free[0])
        self._slot_used[slot] = True
        return slot

    # -- mutation -----------------------------------------------------------

    def _check_bank(self, bank: TemplateBank) -> tuple[int, int]:
        c, k, n = bank.templates.shape
        if n != self.num_features:
            raise RegistryError(
                f"bank has {n} features, registry serves {self.num_features}")
        if k > self.k_max:
            raise RegistryError(f"bank k={k} exceeds registry k_max={self.k_max}")
        return c, k

    def _write(self, offset: int, c_bucket: int, bank: TemplateBank) -> int:
        c, k = bank.templates.shape[0], bank.templates.shape[1]
        end = offset + c_bucket
        self._templates[offset:end] = 0.0
        self._lower[offset:end] = 0.0
        self._upper[offset:end] = 0.0
        self._valid[offset:end] = False
        self._templates[offset:offset + c, :k] = np.asarray(bank.templates)
        self._lower[offset:offset + c, :k] = np.asarray(bank.lower)
        self._upper[offset:offset + c, :k] = np.asarray(bank.upper)
        valid = np.asarray(bank.valid, bool)
        self._valid[offset:offset + c, :k] = valid
        return int(valid.sum())

    def _bump(self) -> None:
        self.generation += 1
        self._device_cache = None

    def register(self, tenant_id: str, bank: TemplateBank) -> TenantEntry:
        """Hot-register a tenant's bank: allocate a bucketed class range,
        write templates + thresholds, no device-shape change (steady state)."""
        if tenant_id in self._tenants:
            raise RegistryError(f"tenant {tenant_id!r} already registered; "
                                "use update()")
        c, k = self._check_bank(bank)
        n_buckets = -(-c // self.class_bucket)
        offset = self._alloc_classes(n_buckets)
        slot = self._alloc_slot()
        rows = self._write(offset, n_buckets * self.class_bucket, bank)
        self._thr[slot] = np.asarray(bank.thresholds)
        self._bump()
        entry = TenantEntry(tenant_id, slot, offset, c,
                            n_buckets * self.class_bucket, k, rows,
                            self.generation)
        self._tenants[tenant_id] = entry
        return entry

    def update(self, tenant_id: str, bank: TemplateBank) -> TenantEntry:
        """Hot-update a tenant's bank in place (per-user recalibration).

        Re-uses the allocated class range when the new bank fits its bucket;
        otherwise relocates (evict + register semantics, same tenant slot)."""
        old = self.get(tenant_id)
        c, k = self._check_bank(bank)
        if c <= old.c_bucket:
            rows = self._write(old.offset, old.c_bucket, bank)
            self._thr[old.slot] = np.asarray(bank.thresholds)
            self._bump()
            entry = dataclasses.replace(old, num_classes=c, k=k,
                                        valid_rows=rows,
                                        generation=self.generation)
        else:
            # relocate: invalidate + free the old range before reallocating
            self._valid[old.offset:old.offset + old.c_bucket] = False
            self._templates[old.offset:old.offset + old.c_bucket] = 0.0
            start = old.offset // self.class_bucket
            self._bucket_used[start:start + old.c_bucket // self.class_bucket] \
                = False
            n_buckets = -(-c // self.class_bucket)
            offset = self._alloc_classes(n_buckets)
            rows = self._write(offset, n_buckets * self.class_bucket, bank)
            self._thr[old.slot] = np.asarray(bank.thresholds)
            self._bump()
            entry = TenantEntry(tenant_id, old.slot, offset, c,
                                n_buckets * self.class_bucket, k, rows,
                                self.generation)
        self._tenants[tenant_id] = entry
        return entry

    # -- live resharding ----------------------------------------------------

    def _pack(self, entries, cap: int, bank_shards: int):
        """First-fit placement of existing bucket runs into a fresh bank of
        ``cap`` rows cut into ``bank_shards`` shards (runs restart at shard
        boundaries, exactly like `_alloc_classes`). Returns
        [(entry, new_offset)] or None when the capacity cannot hold them."""
        shard_buckets = (cap // bank_shards) // self.class_bucket
        used = np.zeros(cap // self.class_bucket, bool)
        out = []
        for e in entries:
            n_buckets = e.c_bucket // self.class_bucket
            if n_buckets > shard_buckets:
                return None
            placed = None
            run = 0
            for i in range(len(used)):
                if i % shard_buckets == 0:
                    run = 0
                run = 0 if used[i] else run + 1
                if run == n_buckets:
                    start = i - n_buckets + 1
                    used[start:i + 1] = True
                    placed = start * self.class_bucket
                    break
            if placed is None:
                return None
            out.append((e, placed))
        return out

    def _build_shadow(self, cap: int, bank_shards: int) -> "PreparedBank":
        """Copy every tenant's bucket run into FRESH arrays of ``cap`` rows
        cut into ``bank_shards`` shards (first-fit via `_pack`, growing
        ``cap`` by doubling until everyone fits). Pure read of the live
        bank: nothing this registry serves changes until `adopt_prepared`."""
        order = sorted(self._tenants.values(), key=lambda e: e.offset)
        while (placement := self._pack(order, cap, bank_shards)) is None:
            cap *= 2  # doubling keeps future growth boundary-compatible
        arrays = {name: np.zeros((cap,) + getattr(self, name).shape[1:],
                                 getattr(self, name).dtype)
                  for name in ("_templates", "_lower", "_upper", "_valid")}
        bucket_used = np.zeros(cap // self.class_bucket, bool)
        moved = 0
        placements = []
        for entry, offset in placement:
            lo, hi = entry.offset, entry.offset + entry.c_bucket
            for name, arr in arrays.items():
                arr[offset:offset + entry.c_bucket] = \
                    getattr(self, name)[lo:hi]
            start = offset // self.class_bucket
            bucket_used[start:start + entry.c_bucket
                        // self.class_bucket] = True
            moved += offset != entry.offset
            placements.append((entry.tenant_id, offset))
        return PreparedBank(bank_shards=bank_shards, capacity=cap,
                            source_generation=self.generation,
                            arrays=arrays, bucket_used=bucket_used,
                            placements=placements, moved=moved)

    def prepare_reshard(self, bank_shards: int) -> "PreparedBank":
        """Build the re-packed super-bank ALONGSIDE the live one (the
        double-buffered reshard's prepare half — `repro.fleet.reshard`).
        The live bank keeps serving while this copies; `adopt_prepared`
        flips to the shadow between ticks. The prepared buffer records the
        source generation, so a registry mutation after prepare (register/
        update/evict) makes it stale and adopt refuses it."""
        if bank_shards < 1:
            raise ValueError("bank_shards must be >= 1")
        align = bank_shards * self.class_bucket
        cap = -(-self._c_cap // align) * align
        return self._build_shadow(cap, bank_shards)

    def adopt_prepared(self, prepared: "PreparedBank") -> int:
        """Flip to a shadow bank built by `prepare_reshard`: swap the host
        arrays + allocation map, move tenant offsets, bump the generation
        (device caches drop; the next `device_bank()` uploads the new
        buffer and the old one is garbage). O(tenants) pointer work — the
        O(rows) copy already happened in prepare, while serving continued.
        Raises `RegistryError` when the registry mutated since prepare."""
        if prepared.source_generation != self.generation:
            raise RegistryError(
                f"prepared bank is stale: built at generation "
                f"{prepared.source_generation}, registry is now at "
                f"{self.generation}; re-prepare")
        for name, arr in prepared.arrays.items():
            setattr(self, name, arr)
        self._bucket_used = prepared.bucket_used
        for tenant_id, offset in prepared.placements:
            entry = self._tenants[tenant_id]
            self._tenants[tenant_id] = dataclasses.replace(
                entry, offset=offset, generation=self.generation + 1)
        self._c_cap = prepared.capacity
        self.bank_shards = prepared.bank_shards
        self._bump()
        return prepared.moved

    def reshard(self, bank_shards: int) -> int:
        """Re-pack every tenant's bucket run to new shard boundaries
        WITHOUT re-registering anyone: tenant ids, slots, thresholds, head
        tables (slot-indexed, service-side), template rows and `valid_rows`
        all survive — only class-row offsets move (and capacity grows when
        the new alignment needs more rows; growth keeps doubling from
        there, so later boundaries remain a superset). Returns the number
        of tenants whose offset changed.

        The caller (the control plane) drains the scheduler first; queued
        work is safe regardless because placements are resolved at tick
        time (`lookup`), never at submit time. (`prepare_reshard` +
        `adopt_prepared` is the no-drain double-buffered variant.)
        """
        if bank_shards == self.bank_shards:
            return 0
        return self.adopt_prepared(self.prepare_reshard(bank_shards))

    def compact(self) -> int:
        """Shrink capacity back down after evictions: re-pack every tenant
        into the SMALLEST shard-aligned capacity that holds them.

        `_grow_classes` only ever doubles and `evict` only frees buckets,
        so a registry that once held many tenants serves a mostly-empty
        super-bank forever — every `device_bank()` upload, fused-kernel
        row budget and shard copy pays for rows nobody owns. This is the
        reclaim hook the fleet policy triggers when occupancy drops below
        its threshold (`repro.fleet.policy.should_compact`).

        Placement-invariant per tenant: `bank_of(t)` returns the same
        bytes before and after (only offsets move). Changes the device
        array shapes (the one retrace event, same as a capacity grow).
        Returns the number of class rows freed (0 = already minimal)."""
        align = self.bank_shards * self.class_bucket
        used = int(self._bucket_used.sum()) * self.class_bucket
        cap = max(align, -(-used // align) * align)
        if cap >= self._c_cap:
            return 0
        prepared = self._build_shadow(cap, self.bank_shards)
        if prepared.capacity >= self._c_cap:
            return 0  # fragmentation kept the pack from shrinking
        freed = self._c_cap - prepared.capacity
        self.adopt_prepared(prepared)
        return freed

    def evict(self, tenant_id: str) -> None:
        """Drop a tenant: invalidate its rows, free its bucket range + slot."""
        entry = self.get(tenant_id)
        end = entry.offset + entry.c_bucket
        self._valid[entry.offset:end] = False
        self._templates[entry.offset:end] = 0.0
        start = entry.offset // self.class_bucket
        self._bucket_used[start:start + entry.c_bucket // self.class_bucket] \
            = False
        self._slot_used[entry.slot] = False
        del self._tenants[tenant_id]
        self._bump()

    # -- durable state (service snapshot/restore) ---------------------------

    def snapshot_state(self) -> tuple[dict, dict]:
        """The registry's full durable state as ``(arrays, meta)``.

        ``arrays`` is a flat dict of host numpy copies (copies, so an async
        checkpoint writer never races a hot register/update), ``meta`` a
        JSON-serialisable dict of the scalars + tenant placements. Together
        they are everything `load_state` needs to rebuild this registry
        bit-identically — the super-bank a restored service gathers is the
        same bytes, so served predictions/margins are the same bits
        (`repro.serve.snapshot`).
        """
        arrays = {
            "templates": self._templates.copy(),
            "lower": self._lower.copy(),
            "upper": self._upper.copy(),
            "valid": self._valid.copy(),
            "thresholds": self._thr.copy(),
            "bucket_used": self._bucket_used.copy(),
            "slot_used": self._slot_used.copy(),
        }
        meta = {
            "num_features": self.num_features,
            "k_max": self.k_max,
            "class_bucket": self.class_bucket,
            "bank_shards": self.bank_shards,
            "capacity_classes": self._c_cap,
            "capacity_tenants": self._t_cap,
            "generation": self.generation,
            "tenants": [dataclasses.asdict(e)
                        for e in self._tenants.values()],
        }
        return arrays, meta

    def load_state(self, arrays: dict, meta: dict) -> None:
        """Adopt a `snapshot_state` payload wholesale: capacities, bank
        arrays, allocation maps and tenant placements — zero re-registrations
        (`register` is never called; `TenantEntry`s are reconstructed as
        snapshotted). The bank-shape fields must match this registry's
        construction parameters; everything else is overwritten."""
        for field in ("num_features", "k_max", "class_bucket"):
            if meta[field] != getattr(self, field):
                raise RegistryError(
                    f"snapshot {field}={meta[field]} does not match this "
                    f"registry's {field}={getattr(self, field)}; restore "
                    "through a spec built from the snapshot")
        self._c_cap = int(meta["capacity_classes"])
        self._t_cap = int(meta["capacity_tenants"])
        self.bank_shards = int(meta["bank_shards"])
        self._templates = np.array(arrays["templates"], np.float32)
        self._lower = np.array(arrays["lower"], np.float32)
        self._upper = np.array(arrays["upper"], np.float32)
        self._valid = np.array(arrays["valid"], bool)
        self._thr = np.array(arrays["thresholds"], np.float32)
        self._bucket_used = np.array(arrays["bucket_used"], bool)
        self._slot_used = np.array(arrays["slot_used"], bool)
        self._tenants = {d["tenant_id"]: TenantEntry(**d)
                         for d in meta["tenants"]}
        self.generation = int(meta["generation"])
        self._bump()  # drop caches; device views rebuild from the new bytes

    # -- device views -------------------------------------------------------

    def device_bank(self, placement: Placement | None = None
                    ) -> TemplateBank:
        """The (C_cap, K_max, N) super-bank as a device-resident
        `TemplateBank`, placed once per (generation, mesh generation,
        placement).

        `thresholds` is the shared zero vector: per-tenant thresholds are
        applied by *shifting the query features* (scheduler), which keeps
        the fused kernel's binarisation tenant-agnostic.

        ``placement`` (`repro.match.placement_for`, the scheduler's) puts
        each array from host memory straight onto the plan's sharding:
        class rows over the model axis, the thresholds whole on every
        device, so a tick under a sharded plan moves none of it. None (no
        sharded plan) leaves the arrays uncommitted on the default device.
        """
        return self._placed(placement)[0]

    def thresholds_table(self, placement: Placement | None = None
                         ) -> jax.Array:
        """(T_cap, N) per-tenant binarisation thresholds, placed with the
        super-bank (`device_bank`)."""
        return self._placed(placement)[1]

    def is_placed(self, placement: Placement | None = None) -> bool:
        """Whether `device_bank` / `thresholds_table` under ``placement``
        are cached: False after a registry or mesh generation change."""
        return self._device_cache is not None and \
            self._device_cache[0] == self._placement_key(placement)

    def placement_bytes(self) -> int:
        """Bytes a placement moves: the bank's four arrays, its shared
        thresholds vector and the thresholds table (one copy of each)."""
        return sum(a.nbytes for a in (self._templates, self._lower,
                                      self._upper, self._valid, self._thr)) \
            + 4 * self.num_features

    def _placement_key(self, placement):
        from repro.distributed import context

        return self.generation, context.generation(), placement

    def _placed(self, placement):
        key = self._placement_key(placement)
        if self._device_cache is None or self._device_cache[0] != key:
            row = table = None  # uncommitted, on the default device
            if placement is not None:
                row, table = placement.bank, placement.table
            bank = TemplateBank(
                templates=jax.device_put(self._templates, row),
                lower=jax.device_put(self._lower, row),
                upper=jax.device_put(self._upper, row),
                valid=jax.device_put(self._valid, row),
                thresholds=jax.device_put(
                    np.zeros((self.num_features,), np.float32), table),
            )
            self._device_cache = (key, bank, jax.device_put(self._thr, table))
            self.bank_placements += 1
            self.bank_bytes_placed += self.placement_bytes()
        return self._device_cache[1:]
