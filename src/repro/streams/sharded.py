"""Multi-device sharded streaming: the dense device ring over a JAX mesh.

``KeyedStage(state_backend="sharded")`` runs :mod:`repro.streams.device`'s
dense key-indexed state ring across ``n_shards`` devices of a 1-D
``("shard",)`` mesh (built with :func:`repro.launch.mesh.make_mesh`), with
the whole interval still ONE jitted step — now a ``shard_map`` whose only
cross-device traffic is a single masked ``all_to_all``.

Placement: key-block sharding
-----------------------------
The global dense domain ``D`` (power-of-two high-water mark, as on one
device) is split into ``S`` contiguous key blocks of ``B = ceil(D / S)``
rows; key ``k`` lives on shard ``k // B`` at local row ``k % B`` forever.
Each shard appends its own padding-sink row (local index ``B``), so the
global state arrays are ``(window+1, S * (B+1))`` with
``NamedSharding(mesh, P(None, "shard"))`` and every shard-local scatter can
dump masked/padded lanes harmlessly, exactly like the single-device layout.

State placement is a function of the KEY, not of the assignment — F(k)
moves keys between *tasks*, never between *shards*. That is why rebalance
migration stays relabel-only per shard (the host ``task`` mirror is the only
thing that changes, same as the single-device backend) and why the paper's
protocol cost model is preserved bit-for-bit: migrated bytes still come
from the closed-form ``mem`` mirror.

Dataflow: replicated table, one all_to_all per interval
-------------------------------------------------------
The *stream* enters the mesh sliced by position: the interval's tuple batch
is split into ``S`` contiguous chunks (padded to a power-of-two cap with
key ``-1``), one per device — the moral equivalent of ``S`` upstream
sources. Each device then ships its tuples' contributions to the shard that
owns each key inside the jitted step:

* "add" mode never moves tuples at all: each device builds an ``(S, B+1)``
  partial histogram of its chunk (rows = destination shard) and ONE tiled
  ``all_to_all`` transposes partials across the mesh; the receiving shard
  sums its ``S`` incoming rows. Traffic is ``S * (B+1)`` ints per device
  regardless of tuple count.
* "max" mode needs the raw values for the scatter-max fold, so each device
  builds masked ``(S, cap)`` send matrices (key ``-1`` / value
  ``INT32_MIN`` in lanes that target other shards) and the same tiled
  ``all_to_all`` delivers every tuple to its owner, which folds locally.

The routing table stays host-replicated: the controller's small mixed
table (the paper's core "small Delta" property is exactly what makes
replication cheap) is broadcast to every device on ``assignment_version``
bumps, and each shard rebuilds the ``F(k)`` column for ITS key block only
(``axis_index * B + arange``), so the dense route refresh parallelizes
S-ways and no dense table is ever shipped.

Everything downstream of the step — float64 closed forms, ownership/mem
mirrors, stats, emits — is shared verbatim with the single-device backend:
:class:`ShardedStateFleet` returns *host-dense* ``(D+1,)`` views (the
per-shard blocks de-interleaved) so :class:`~repro.streams.backends.
DeviceBackend`'s host logic cannot tell the difference, which is what makes
``tests/test_engine_sharded.py``'s bit-parity against the object oracle a
structural property rather than a numerical accident.

CPU CI runs this with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(virtual devices; architecture demonstration); on a TPU host every chip is
one shard. The route is the jnp twin of the routing kernel — the Pallas
kernel does not run inside the shard_map, so ``substrate="pallas"`` is
rejected by :meth:`ShardedDeviceBackend.check` rather than silently
ignored. Every input is placed on the mesh with a ``NamedSharding``
(tuple chunks split over ``"shard"``, step and table operands
replicated), so nothing is staged on one device and resharded.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.kernels.routing_lookup import _fmix32
from repro.launch.mesh import make_mesh

from .backends import DeviceBackend, register_backend
from .device import DeviceStateFleet
from .state import ColumnarSpec

_INT32_MIN = np.iinfo(np.int32).min

#: python-side-effect trace counters (same pattern as streams/device.py):
#: increments run at TRACE time only, so tests can assert the sharded step
#: compiles once across intervals and once per route refresh shape.
TRACE_COUNTS = {"interval_step": 0, "route_dense": 0}


def _build_step_add(mesh, S: int, B: int):
    """Jitted shard_map for one "add"-mode interval on an S-device mesh."""
    L = B + 1

    def body(vals, pres, kchunk, cur_col, keep_cols):
        TRACE_COUNTS["interval_step"] += 1
        k = kchunk[0]                                  # this device's chunk
        valid = k >= 0
        t = jnp.where(valid, k // B, 0)
        r = jnp.where(valid, k % B, B)
        # partial histogram: row s = my chunk's counts for shard s's block
        partial = jnp.zeros((S, L), jnp.int32).at[t, r] \
            .add(valid.astype(jnp.int32))
        # transpose partials across the mesh: after the tiled all_to_all,
        # row i holds device i's partial for MY block — sum and fold
        recv = jax.lax.all_to_all(partial, "shard", 0, 0, tiled=True)
        counts = recv.sum(axis=0).at[B].set(0)
        win0 = vals.sum(axis=0)
        slot0 = (vals * cur_col[:, None]).sum(axis=0)
        seen = (counts > 0).astype(jnp.int32)
        vals = vals + cur_col[:, None] * counts[None, :]
        pres = jnp.maximum(pres, cur_col[:, None] * seen[None, :])
        vals = vals * keep_cols[:, None]
        pres = pres * keep_cols[:, None]
        return (vals, pres, counts, win0, slot0,
                pres.sum(axis=0), vals.sum(axis=0))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "shard"), P(None, "shard"), P("shard", None),
                  P(None), P(None)),
        out_specs=(P(None, "shard"), P(None, "shard"), P("shard"), P("shard"),
                   P("shard"), P("shard"), P("shard"))))


def _build_step_max(mesh, S: int, B: int):
    """Jitted shard_map for one "max"-mode interval: tuples travel."""
    L = B + 1

    def body(vals, pres, kchunk, vchunk, cur_col, keep_cols):
        TRACE_COUNTS["interval_step"] += 1
        k = kchunk[0]
        v = vchunk[0]
        valid = k >= 0
        t = jnp.where(valid, k // B, 0)
        # masked send matrices: row s carries only my lanes that target
        # shard s; every other lane is the padding identity
        hit = valid[None, :] & (t[None, :] == jnp.arange(S,
                                                         dtype=k.dtype)[:, None])
        send_k = jnp.where(hit, k[None, :], -1)
        send_v = jnp.where(hit, v[None, :], _INT32_MIN)
        rk = jax.lax.all_to_all(send_k, "shard", 0, 0, tiled=True).reshape(-1)
        rv = jax.lax.all_to_all(send_v, "shard", 0, 0, tiled=True).reshape(-1)
        rvalid = rk >= 0
        r = jnp.where(rvalid, rk % B, B)
        counts = jnp.zeros((L,), jnp.int32).at[r] \
            .add(rvalid.astype(jnp.int32)).at[B].set(0)
        gmax = jnp.full((L,), _INT32_MIN, jnp.int32).at[r].max(rv)
        win0 = vals.sum(axis=0)
        slot0 = (vals * cur_col[:, None]).sum(axis=0)
        seen = (counts > 0).astype(jnp.int32)
        newslot = jnp.where(seen > 0, jnp.maximum(slot0, gmax), slot0)
        vals = vals + cur_col[:, None] * (newslot - slot0)[None, :]
        pres = jnp.maximum(pres, cur_col[:, None] * seen[None, :])
        vals = vals * keep_cols[:, None]
        pres = pres * keep_cols[:, None]
        return (vals, pres, counts, win0, slot0,
                pres.sum(axis=0), vals.sum(axis=0))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, "shard"), P(None, "shard"), P("shard", None),
                  P("shard", None), P(None), P(None)),
        out_specs=(P(None, "shard"), P(None, "shard"), P("shard"), P("shard"),
                   P("shard"), P("shard"), P("shard"))))


def _build_route(mesh, S: int, B: int, n_dest: int, seed: int):
    """Jitted shard_map route refresh: each shard computes F(k) for its own
    key block from the replicated (tkeys, tdests) table — the jnp twin of
    the routing kernel's mix + table-override semantics."""
    L = B + 1

    def body(tk, td):
        TRACE_COUNTS["route_dense"] += 1
        me = jax.lax.axis_index("shard").astype(jnp.int32)
        kid = me * B + jnp.arange(L, dtype=jnp.int32)
        h = _fmix32(kid.astype(jnp.uint32) ^ jnp.uint32(seed & 0xFFFFFFFF))
        base = (h % jnp.uint32(n_dest)).astype(jnp.int32)
        ok = (tk >= 0) & (tk < S * B) & (tk // B == me)
        slot = jnp.where(ok, tk % B, B)
        # non-local / empty table slots write base[B] onto the sink row — a
        # no-op (same trick as device._route_dense's padding row)
        return base.at[slot].set(jnp.where(ok, td, base[B]))

    return jax.jit(jax.shard_map(body, mesh=mesh,
                                 in_specs=(P(None), P(None)),
                                 out_specs=P("shard")))


class ShardedStateFleet(DeviceStateFleet):
    """The dense state ring block-sharded across an S-device mesh.

    Drop-in for :class:`~repro.streams.device.DeviceStateFleet`: the same
    surface, but ``vals``/``pres`` are global ``(W1, S*(B+1))`` arrays
    sharded over the mesh's ``"shard"`` axis, and every host-facing output
    (step observables, route tables, ``host_state``) is de-interleaved back
    to the key-dense ``(D+1,)`` layout so the engine-side closed forms are
    shared verbatim with the single-device backend.
    """

    def __init__(self, window: int, spec: ColumnarSpec,
                 n_shards: Optional[int] = None, min_domain: int = 512):
        n_avail = jax.device_count()
        if n_shards is None:
            n_shards = n_avail
        if not 1 <= n_shards <= n_avail:
            raise ValueError(
                f"n_shards={n_shards} outside [1, {n_avail}] available jax "
                "devices (set XLA_FLAGS=--xla_force_host_platform_device_"
                "count=N for virtual CPU devices)")
        self.n_shards = int(n_shards)
        self.mesh = make_mesh((self.n_shards,), ("shard",))
        self._sharding = NamedSharding(self.mesh, P(None, "shard"))
        self._chunks = NamedSharding(self.mesh, P("shard", None))
        self._replicated = NamedSharding(self.mesh, P())
        self._block = 0            # B: keys per shard; local sink row is B
        self._chunk_cap = 0        # per-shard tuple-chunk pad bucket (pow2 HWM)
        self._step_fns = {}        # (mode, B) -> jitted shard_map
        self._route_fns = {}       # (B, n_dest, seed) -> jitted shard_map
        super().__init__(window, spec, min_domain)

    # -- layout helpers ---------------------------------------------------------
    def _gcols(self, rows: np.ndarray) -> np.ndarray:
        """Global key ids -> columns of the interleaved sharded layout."""
        B = self._block
        return ((rows // B) * (B + 1) + rows % B).astype(np.int64)

    def _to_dense_1d(self, garr) -> np.ndarray:
        """(S*(B+1),) global output -> host key-dense (domain+1,)."""
        a = np.asarray(garr)
        if self._block == 0:           # domain never grown: nothing held yet
            return np.zeros(self.domain + 1, a.dtype)
        L = self._block + 1
        dense = a.reshape(self.n_shards, L)[:, :self._block] \
            .reshape(-1)[:self.domain]
        out = np.zeros(self.domain + 1, a.dtype)
        out[:self.domain] = dense
        return out

    def _to_dense_2d(self, a: np.ndarray) -> np.ndarray:
        if self._block == 0:           # domain never grown: nothing held yet
            return np.zeros((a.shape[0], self.domain + 1), a.dtype)
        L = self._block + 1
        dense = a.reshape(a.shape[0], self.n_shards, L)[:, :, :self._block] \
            .reshape(a.shape[0], -1)[:, :self.domain]
        out = np.zeros((a.shape[0], self.domain + 1), a.dtype)
        out[:, :self.domain] = dense
        return out

    # -- shape management -------------------------------------------------------
    def ensure_domain(self, needed: int) -> bool:
        if needed <= self.domain:
            return False
        old_dom = self.domain
        if old_dom:
            old_vals, old_pres = self.host_state()    # key-dense (W1, D+1)
        dom = max(self._min_domain, 1 << (int(needed) - 1).bit_length())
        S = self.n_shards
        B = -(-dom // S)          # ceil: rows in [dom, S*B) are dead padding
        G = S * (B + 1)
        vals = np.zeros((self._ncols, G), np.int32)
        pres = np.zeros((self._ncols, G), np.int32)
        task = np.full(dom + 1, -1, dtype=np.int32)
        mem = np.zeros(dom + 1, dtype=np.float64)
        self._block = B
        if old_dom:
            gcol = self._gcols(np.arange(old_dom))
            vals[:, gcol] = old_vals[:, :old_dom]
            pres[:, gcol] = old_pres[:, :old_dom]
            task[:old_dom] = self.task[:old_dom]
            mem[:old_dom] = self.mem[:old_dom]
        self.domain = dom
        self.vals = jax.device_put(vals, self._sharding)
        self.pres = jax.device_put(pres, self._sharding)
        self.task, self.mem = task, mem
        self._all_keys = None
        self._host_dirty = True
        return True

    # -- the fused hot path -----------------------------------------------------
    def _chunk(self, arr: Optional[np.ndarray], n: int, pad,
               cap: int) -> jnp.ndarray:
        flat = np.full(self.n_shards * cap, pad, dtype=np.int32)
        if n:
            flat[:n] = arr
        return jax.device_put(flat.reshape(self.n_shards, cap), self._chunks)

    def interval_step(self, keys: np.ndarray, tuple_vals: Optional[np.ndarray],
                      dest_dense, n_tasks: int, keep_cols: np.ndarray,
                      cur_col: np.ndarray, mode: str):
        """Same contract as the parent, all-host-dense outputs; the final
        ``task_counts`` slot is always None (no built-in operator is
        max-mode AND unit-cost, so the engine derives per-task loads from
        counts + the host dest mirror — see backends.DeviceBackend)."""
        S = self.n_shards
        n = int(keys.shape[0])
        per = -(-n // S) if n else 1
        if per > self._chunk_cap:
            self._chunk_cap = max(256, 1 << (per - 1).bit_length())
        cap = self._chunk_cap
        kchunk = self._chunk(keys, n, -1, cap)
        fn_key = (mode, self._block)
        fn = self._step_fns.get(fn_key)
        if fn is None:
            build = _build_step_add if mode == "add" else _build_step_max
            fn = build(self.mesh, S, self._block)
            self._step_fns[fn_key] = fn
        cur = jax.device_put(cur_col, self._replicated)
        keep = jax.device_put(keep_cols, self._replicated)
        if mode == "add":
            out = fn(self.vals, self.pres, kchunk, cur, keep)
        else:
            vchunk = self._chunk(tuple_vals, n, _INT32_MIN, cap)
            out = fn(self.vals, self.pres, kchunk, vchunk, cur, keep)
        self.vals, self.pres = out[0], out[1]
        self._host_dirty = True
        return (self._to_dense_1d(out[2]), self._to_dense_1d(out[3]),
                self._to_dense_1d(out[4]), self._to_dense_1d(out[5]),
                self._to_dense_1d(out[6]), None)

    def evict(self, keep_cols: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        cnt, tot = super().evict(keep_cols)     # global (S*(B+1),) outputs
        return self._to_dense_1d(cnt), self._to_dense_1d(tot)

    def route_dense(self, tkeys: np.ndarray, tdests: np.ndarray, n_dest: int,
                    seed: int, use_kernel: bool, interpret: Optional[bool]):
        """S-way parallel dense route refresh from the replicated table.

        Always the jnp twin: ``use_kernel`` is never True here, because
        :meth:`ShardedDeviceBackend.check` rejects ``substrate="pallas"``.
        """
        fn_key = (self._block, int(n_dest), int(seed))
        fn = self._route_fns.get(fn_key)
        if fn is None:
            fn = _build_route(self.mesh, self.n_shards, self._block,
                              int(n_dest), int(seed))
            self._route_fns[fn_key] = fn
        return fn(jax.device_put(tkeys.astype(np.int32), self._replicated),
                  jax.device_put(tdests.astype(np.int32), self._replicated))

    def dest_host_dense(self, dev) -> np.ndarray:
        return self._to_dense_1d(dev).astype(np.int64)

    # -- host snapshots (pack contract + introspection) -------------------------
    def host_state(self) -> Tuple[np.ndarray, np.ndarray]:
        if self._host_dirty:
            self._host_vals = self._to_dense_2d(np.asarray(self.vals))
            self._host_pres = self._to_dense_2d(np.asarray(self.pres))
            self._host_dirty = False
        return self._host_vals, self._host_pres

    def _cols(self, rows: np.ndarray) -> Tuple[np.ndarray, int]:
        """Interleaved global columns; shard 0's local sink is column B."""
        return self._gcols(rows), self._block


@register_backend
class ShardedDeviceBackend(DeviceBackend):
    """The device backend over a :class:`ShardedStateFleet`.

    Everything above the fleet — closed forms, mirrors, stats, emits, the
    relabel-only migration — is inherited from
    :class:`~repro.streams.backends.DeviceBackend` untouched; the sharding
    is invisible outside the fused step. Explicit-only: ``auto`` never
    selects it (on CPU the virtual devices are an architecture
    demonstration, and on accelerators the choice of S belongs to the
    launcher).
    """

    name = "sharded"

    def _make_fleet(self):
        stage = self.stage
        return ShardedStateFleet(stage.window, stage.operator.columnar_spec,
                                 n_shards=stage.n_shards)

    @classmethod
    def check(cls, operator, controller, vectorized, substrate="numpy"):
        super().check(operator, controller, vectorized, substrate)
        if substrate == "pallas":
            raise ValueError(
                "state_backend='sharded' does not run the Pallas routing "
                "kernel inside its shard_map (the per-shard route is the jnp "
                "twin); use substrate='numpy', or state_backend='device' for "
                "the kernel route on one device")

    @classmethod
    def auto_eligible(cls, operator, controller, vectorized):
        return False
